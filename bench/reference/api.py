"""What the harness asks of a reference model: seeded weights, quantised
tables, served scores in blocks, and the first three training steps."""

from __future__ import annotations

import importlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import (bce_with_logits, int_exchange, key_from_seed, lookup,
                     opt_init, opt_update, quantize_rows)


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


def train_steps(params0, batches, model: dict, opt: dict, dtype=jnp.float32,
                replicas: int = 1, bits: Optional[int] = None):
    """The configuration's optimizer over ``batches`` from ``params0``.
    A batch's ``sparse`` is ``(B, F)``, or ``(B, F, L)`` bags with their
    ``mask``.  Where the configuration states a gradient exchange
    (``opt["compress"]``, "int8"), the batch is split into ``replicas``
    shards of consecutive rows, each takes its own mean gradient, and the
    optimizer gets their mean through ``common.int_exchange``, its errors
    carried from step to step; ``bits`` gives the code another width.
    Matmuls run at ``opt["matmul_precision"]``, the precision the
    configuration states, or ``highest`` where it states none.  Returns each step's loss, the per-leaf norm of the first gradient as
    the optimizer gets it and the per-leaf norm of the parameters' change
    after the first and after the last step."""
    fam = family(model)
    cast = lambda t: jax.tree.map(lambda x: x.astype(dtype), t)  # noqa: E731
    if opt.get("compress") is not None:
        bits = bits or {"int8": 8}[opt["compress"]]
    precision = opt.get("matmul_precision", "highest")

    def loss_fn(p, b):
        feats = lookup(p["tables"], b["sparse"], model, dtype,
                       mask=b.get("mask"))
        logits = fam.dense_forward(p, b["dense"], feats, model)
        return bce_with_logits(logits, b["label"].astype(dtype))

    @jax.jit
    def step(p, s, e, b, t):
        with jax.default_matmul_precision(precision):
            if bits is None:
                loss, g = jax.value_and_grad(loss_fn)(p, b)
            else:
                shards = jax.tree.map(lambda x: x.reshape(
                    (replicas, x.shape[0] // replicas) + x.shape[1:]), b)
                loss, g = jax.vmap(jax.value_and_grad(loss_fn),
                                   in_axes=(None, 0))(p, shards)
                loss = jnp.mean(loss)
                g, e = int_exchange(g, e, bits)
                g = jax.tree.map(lambda x: x.astype(dtype), g)
            gnorm = jnp.stack([jnp.linalg.norm(x.astype(jnp.float32))
                               for x in jax.tree.leaves(g)])
            p, s = opt_update(opt, g, s, p, t)
        return p, s, e, loss.astype(jnp.float32), gnorm

    change = jax.jit(lambda a, b: jnp.stack(
        [jnp.linalg.norm(x.astype(jnp.float32) - y)
         for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]))
    p = cast(params0)
    s = opt_init(opt, p, dtype)
    e = None if bits is None else [jnp.zeros((replicas,) + x.shape, jnp.float32)
                                   for x in jax.tree.leaves(p)]
    losses, first = [], None
    for t, b in enumerate(batches):
        p, s, e, loss, gnorm = step(p, s, e, b, jnp.int32(t))
        losses.append(float(loss))
        if first is None:
            first = np.asarray(gnorm)
            change1 = np.asarray(change(p, params0))
    return {"losses": losses, "first_grad": first, "change1": change1,
            "change": np.asarray(change(p, params0))}
