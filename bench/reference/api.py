"""What the harness asks of a reference model: seeded weights, quantised
tables, served scores in blocks, and the first three training steps."""

from __future__ import annotations

import importlib
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import (bce_with_logits, feature_ids, int_exchange,
                     key_from_seed, lookup, opt_init, opt_update, pool,
                     qr_ids, quantize_rows)


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


BLOCK = 2048     # examples at most in one block of the reference's gradient


def train_steps(params0, batches, model: dict, opt: dict, dtype=jnp.float32,
                replicas: int = 1, bits: Optional[int] = None,
                block: Optional[int] = None):
    """The configuration's optimizer over ``batches`` from ``params0``.
    A batch's ``sparse`` is ``(B, F)``, ``(B, F, L)`` bags with their
    ``mask``, or the list form, ``F`` arrays ``(B, L_f)`` with every slot
    live.  Where the configuration states a gradient exchange
    (``opt["compress"]``, "int8"), the batch is split into ``replicas``
    shards of consecutive rows, each takes its own mean gradient, and the
    optimizer gets their mean through ``common.int_exchange``, its errors
    carried from step to step; ``bits`` gives the code another width.
    A shard (the batch, without an exchange) of more than ``block``
    (``BLOCK``) examples takes its gradient and loss as the
    example-weighted mean over blocks of at most ``block`` consecutive
    examples, so the peak holds the parameters, ``params0``, the gradient
    and one block; such a step donates its parameters and optimizer state.
    A shard of at most ``block`` is one block, taken by one program that
    donates nothing: donation changes the compiled program, and with it
    the rounding.  Matmuls run at ``opt["matmul_precision"]``, the
    precision the configuration states, or ``highest`` where it states
    none.  Returns each step's loss, the per-leaf norm of the first
    gradient as the optimizer gets it and the per-leaf norm of the
    parameters' change after the first and after the last step."""
    fam = family(model)
    block = BLOCK if block is None else block
    if opt.get("compress") is not None:
        bits = bits or {"int8": 8}[opt["compress"]]
    precision = opt.get("matmul_precision", "highest")

    def loss_fn(p, b):
        feats = lookup(p["tables"], b["sparse"], model, dtype,
                       mask=b.get("mask"))
        logits = fam.dense_forward(p, b["dense"], feats, model)
        return bce_with_logits(logits, b["label"].astype(dtype))

    def apply(p, s, e, g, t):
        """The exchange, where there is one, and the optimizer's step."""
        if bits is not None:
            g, e = int_exchange(g, e, bits)
            g = jax.tree.map(lambda x: x.astype(dtype), g)
        gnorm = jnp.stack([jnp.linalg.norm(x.astype(jnp.float32))
                           for x in jax.tree.leaves(g)])
        p, s = opt_update(opt, g, s, p, t)
        return p, s, e, gnorm

    @jax.jit
    def step(p, s, e, b, t):
        """One step, each shard's gradient in one block."""
        with jax.default_matmul_precision(precision):
            if bits is None:
                loss, g = jax.value_and_grad(loss_fn)(p, b)
            else:
                shards = jax.tree.map(lambda x: x.reshape(
                    (replicas, x.shape[0] // replicas) + x.shape[1:]), b)
                loss, g = jax.vmap(jax.value_and_grad(loss_fn),
                                   in_axes=(None, 0))(p, shards)
                loss = jnp.mean(loss)
            p, s, e, gnorm = apply(p, s, e, g, t)
        return p, s, e, loss.astype(jnp.float32), gnorm

    @partial(jax.jit, donate_argnums=0)
    def add_block(g, p, b, r, w):
        """``g`` (each leaf ``(replicas, *shape)``) plus ``w`` times the
        gradient of block ``b``'s mean loss in replica ``r``'s row, and
        ``w`` times that loss.  The gradient is taken with respect to the
        rows the block reads and added into the tables' rows, so no table
        copy is made for a block."""
        with jax.default_matmul_precision(precision):
            dense = {k: v for k, v in p.items() if k != "tables"}
            ids = [qr_ids(model, f, feature_ids(b["sparse"], f))
                   for f in range(len(p["tables"]))]
            rows = [(jnp.take(t["table_0"], i0, axis=0),
                     jnp.take(t["table_1"], i1, axis=0))
                    for t, (i0, i1) in zip(p["tables"], ids)]

            def rows_loss(dense, rows):
                feats = jnp.stack([pool(e0 * e1, b["sparse"], b.get("mask"),
                                        f, dtype)
                                   for f, (e0, e1) in enumerate(rows)], axis=1)
                logits = fam.dense_forward(dense, b["dense"], feats, model)
                return bce_with_logits(logits, b["label"].astype(dtype))

            loss, (gd, gr) = jax.value_and_grad(rows_loss, (0, 1))(dense, rows)
        out = {k: jax.tree.map(lambda a, x: a.at[r].add(w * x), g[k], gd[k])
               for k in dense}
        out["tables"] = [
            {k: a[k].at[r, i.reshape(-1)].add(w * x.reshape(-1, x.shape[-1]))
             for k, i, x in (("table_0", i0, e0), ("table_1", i1, e1))}
            for a, (i0, i1), (e0, e1) in zip(g["tables"], ids, gr)]
        return out, w * loss.astype(jnp.float32)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, s, e, g, t):
        if bits is None:
            g = jax.tree.map(lambda x: x[0], g)
        return apply(p, s, e, g, t)

    zeros = jax.jit(lambda p: jax.tree.map(
        lambda x: jnp.zeros((replicas,) + x.shape, dtype), p))

    def blocked(p, s, e, b, t):
        """One step, each shard's gradient as the mean over its blocks."""
        n = len(b["label"]) // replicas
        g, loss = zeros(p), 0.0
        for r in range(replicas):
            for lo in range(r * n, (r + 1) * n, block):
                hi = min(lo + block, (r + 1) * n)
                part = jax.tree.map(lambda x: x[lo:hi], b)
                g, part_loss = add_block(g, p, part, r, (hi - lo) / n)
                loss = loss + part_loss / replicas
        p, s, e, gnorm = update(p, s, e, g, t)
        return p, s, e, loss, gnorm

    change = jax.jit(lambda a, b: jnp.stack(
        [jnp.linalg.norm(x.astype(jnp.float32) - y)
         for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]))
    one = [len(b["label"]) // replicas <= block for b in batches]
    if all(one):
        p = jax.tree.map(lambda x: x.astype(dtype), params0)
    else:   # a blocked step donates a copy, so params0 stays readable
        p = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.copy(x).astype(dtype), t))(params0)
    s = opt_init(opt, p, dtype)
    e = None if bits is None else [jnp.zeros((replicas,) + x.shape, jnp.float32)
                                   for x in jax.tree.leaves(p)]
    losses, first = [], None
    for t, b in enumerate(batches):
        p, s, e, loss, gnorm = (step if one[t] else blocked)(p, s, e, b,
                                                             jnp.int32(t))
        losses.append(float(loss))
        if first is None:
            first = np.asarray(gnorm)
            change1 = np.asarray(change(p, params0))
    return {"losses": losses, "first_grad": first, "change1": change1,
            "change": np.asarray(change(p, params0))}
