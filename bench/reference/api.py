"""What the harness asks of a reference model: seeded weights, quantised
tables, served scores in blocks, and the first three training steps."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from .common import (bce_with_logits, key_from_seed, lookup, opt_init,
                     opt_update, quantize_rows)


def family(model: dict):
    return importlib.import_module(f"{__package__}.{model['family']}")


def make_params(seed: int, model: dict):
    """All weights from the seed, on the device, in one jitted call, in
    the layout the program consumes (f32, QR tables as
    ``{"table_0", "table_1"}`` per feature)."""
    fam = family(model)
    return jax.jit(lambda key: fam.init(key, model))(key_from_seed(seed))


def quantize_tables(params, bits: int = 8):
    tables = jax.jit(lambda ts: [{k: quantize_rows(v, bits) for k, v in t.items()}
                                 for t in ts])(params["tables"])
    return dict(params, tables=tables)


def serve_logits(qparams, dense, idx, mask, model: dict, dtype=jnp.float32,
                 block: int = 128) -> np.ndarray:
    """Scores of requests ``dense (N, 13)``, ``idx``/``mask`` ``(N, F, L)``,
    ``block`` requests at a time so the reference fits beside nothing
    else.  ``dtype`` float32 with ``highest`` matmuls is the reference;
    bfloat16 is the control's dense half."""
    fam = family(model)

    @jax.jit
    def run(p, d, i, m):
        with jax.default_matmul_precision("highest"):
            feats = lookup(p["tables"], i, model, dtype, mask=m)
            return fam.dense_forward(p, d, feats, model).astype(jnp.float32)

    n = len(dense)
    out = np.empty(n, np.float32)
    for s in range(0, n, block):
        e = min(n, s + block)
        pad = block - (e - s)
        args = [np.concatenate([a[s:e], np.zeros((pad,) + a.shape[1:], a.dtype)])
                for a in (dense, idx, mask)]
        out[s:e] = np.asarray(run(qparams, *args))[: e - s]
    return out


def train_steps(params0, batches, model: dict, opt: dict, dtype=jnp.float32):
    """The configuration's optimizer over ``batches`` from ``params0``.
    Returns each step's loss, the per-leaf norm of the first gradient and
    the per-leaf norm of the parameters' change after the first and after
    the last step."""
    fam = family(model)
    cast = lambda t: jax.tree.map(lambda x: x.astype(dtype), t)  # noqa: E731

    def loss_fn(p, b):
        feats = lookup(p["tables"], b["sparse"], model, dtype)
        logits = fam.dense_forward(p, b["dense"], feats, model)
        return bce_with_logits(logits, b["label"].astype(dtype))

    @jax.jit
    def step(p, s, b, t):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
            gnorm = jnp.stack([jnp.linalg.norm(x.astype(jnp.float32))
                               for x in jax.tree.leaves(g)])
            p, s = opt_update(opt, g, s, p, t)
        return p, s, loss.astype(jnp.float32), gnorm

    change = jax.jit(lambda a, b: jnp.stack(
        [jnp.linalg.norm(x.astype(jnp.float32) - y)
         for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]))
    p = cast(params0)
    s = opt_init(opt, p, dtype)
    losses, first = [], None
    for t, b in enumerate(batches):
        p, s, loss, gnorm = step(p, s, b, jnp.int32(t))
        losses.append(float(loss))
        if first is None:
            first = np.asarray(gnorm)
            change1 = np.asarray(change(p, params0))
    return {"losses": losses, "first_grad": first, "change1": change1,
            "change": np.asarray(change(p, params0))}
