"""Training batches of fixed-width bags made on the device from ``(seed,
step)``, MLPerf DLRMv2's multi-hot form: feature ``f`` gives every
example ``bag_lengths[f]`` ids, every slot live, each ``floor(u**skew *
S_f)`` as in ``device_batches`` (spread evenly over the ids float32
skips above ``2**24``); labels from the same planted logistic model,
each bag's mean of ``sin(id * c_f)`` standing in for the single id.
``sparse`` is the list form: ``F`` arrays ``(batch, L_f)``, no
``mask``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.generator import bag_lengths, planted

ENTRY = "train"


def batch_fn(mix: dict, model: dict, seed: int):
    """A jitted ``step -> {dense, sparse, label}`` for this seed."""
    from bench.reference.common import key_from_seed
    lens = bag_lengths(mix, model)
    sizes = jnp.asarray(np.repeat(model["table_sizes"], lens), jnp.int32)
    splits = np.cumsum(lens)[:-1]
    b, f = mix["batch"], len(lens)
    base = key_from_seed(seed)
    w_dense = planted(seed, "wd", (model["dense_dim"],))
    a = planted(seed, "a", (f,))
    c = planted(seed, "c", (f,)) * 5.0

    @jax.jit
    def make_batch(step):
        kd, ks, kj, kl = jax.random.split(jax.random.fold_in(base, step), 4)
        dense = jax.random.normal(kd, (b, model["dense_dim"]))
        u = jax.random.uniform(ks, (b, int(lens.sum())))
        x = u ** mix["skew"] * sizes
        # above 2**24 float32 holds every 2nd, 4th ... id: each id from
        # the drawn value up to the next float32 is equally likely
        gap = jnp.nextafter(x, jnp.inf) - x
        jitter = jnp.floor(jax.random.uniform(kj, x.shape) * gap)
        ids = jnp.minimum(jnp.floor(x).astype(jnp.int32)
                          + jitter.astype(jnp.int32), sizes - 1)
        sparse = jnp.split(ids, splits, axis=1)
        bags = jnp.stack([jnp.mean(jnp.sin(s * c[i]), axis=1)
                          for i, s in enumerate(sparse)], axis=1)
        score = dense @ w_dense + (bags * a).sum(-1)
        noise = mix["label_noise"] * jax.random.normal(kl, (b,))
        return {"dense": dense, "sparse": sparse,
                "label": (score + noise > 0).astype(jnp.float32)}

    return make_batch
