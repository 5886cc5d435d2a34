"""Training batches of ``batch`` examples made on the device from ``(seed,
step)``: ids ``floor(u**skew * S_f)`` and labels from a planted logistic
model over the dense features and the ids, as ``data/criteo.py``'s
``batch_at``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.generator import planted

ENTRY = "train"


def batch_fn(mix: dict, model: dict, seed: int):
    """A jitted ``step -> {dense, sparse, label}`` for this seed."""
    from bench.reference.common import key_from_seed
    sizes = jnp.asarray(model["table_sizes"], jnp.int32)
    b, f = mix["batch"], len(model["table_sizes"])
    base = key_from_seed(seed)
    w_dense = planted(seed, "wd", (model["dense_dim"],))
    a = planted(seed, "a", (f,))
    c = planted(seed, "c", (f,)) * 5.0

    @jax.jit
    def make_batch(step):
        kd, ks, kl = jax.random.split(jax.random.fold_in(base, step), 3)
        dense = jax.random.normal(kd, (b, model["dense_dim"]))
        u = jax.random.uniform(ks, (b, f))
        sparse = jnp.minimum(jnp.floor(u ** mix["skew"] * sizes).astype(jnp.int32),
                             sizes - 1)
        score = dense @ w_dense + (jnp.sin(sparse * c) * a).sum(-1)
        noise = mix["label_noise"] * jax.random.normal(kl, (b,))
        return {"dense": dense, "sparse": sparse,
                "label": (score + noise > 0).astype(jnp.float32)}

    return make_batch
