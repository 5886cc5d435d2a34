"""Error-feedback compressed gradient reduction (bf16 / int8).

Data-parallel training all-reduces one full gradient copy per step; at
production scale that is the wire-dominant collective.  Compressing the
reduction to bf16 (2 B/elem) or int8 (1 B/elem + one f32 scale per leaf)
cuts that 2–4×, and **error feedback** (Karimireddy et al., 2019) keeps
the *time-averaged* update unbiased: the residual each compression step
throws away is carried forward and added to the next gradient, so the sum
of emitted gradients telescopes to the sum of true gradients.

``mode`` may be a single string or a **per-leaf pytree / flat list** of
strings (see ``repro.dist.policy`` for the rule engine that produces
one), and ``init_error_state`` allocates residual state only for leaves
that actually compress (a 0-d placeholder otherwise).

Wire formats (what actually crosses the links, per ``shard_map`` axis).
Both compressed modes use a **two-phase exchange** instead of a plain
``psum`` of the narrow dtype — a ``psum`` of int8 must widen to int32 to
sum without overflow (4 B/elem: no saving), and backends without native
narrow-dtype arithmetic (XLA CPU) silently upcast a bf16 all-reduce to
f32.  Pure data movement (``all_to_all`` / ``all_gather``) keeps the
compressed dtype on every backend:

* Phase 1: compress locally (bf16 cast, or int8 with a ``pmax``-shared
  scale) and ``all_to_all`` the payload so each device owns one shard of
  every peer's compressed gradient; sum it **in f32** (int32 for int8 —
  exact: ≤ 127·n), in a fixed order, so the reduction is deterministic
  and never accumulates in bf16.
* Phase 2: re-compress the shard mean and ``all_gather`` it.

Each phase moves (n−1)/n · payload bytes → 2(n−1)/n · {2 B, 1 B}/elem vs
2(n−1)/n · 4 B for an f32 all-reduce: **2× / 4× less wire**.  All inputs
to phase 2 are bitwise identical across replicas, so every replica emits
the same reduced gradient and the per-replica optimizer updates stay in
lock-step without a re-broadcast.  Phase 1's compression error is
telescoped by error feedback; phase 2's (one compression step of the
*mean* gradient — bf16 ulp ≈ 0.2%, int8 ≤ 0.4%, shared by all replicas)
is, for int8, *also* telescoped: **two-level error feedback** charges
each device ``n×`` its own shard's requantization residual (it computed
that shard's mean exactly), so the residual re-enters the next step's
mean exactly once and the emitted-gradient sum telescopes over both
levels (``two_level=True``, the default).

``ef_psum_scatter_grads``-style building blocks for the FSDP path live
in ``_reduce_scatter_leaf`` (used by ``train.loop.make_fsdp_train_step``):
same compression, but the reduction lands as a shard (reduce-scatter /
int8 ``all_to_all``), skipping phase 2 entirely.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["quantize_int8", "init_error_state", "ef_psum_grads", "MODES",
           "resolve_modes"]

MODES = ("none", "bf16", "int8")


def quantize_int8(x):
    """Symmetric per-tensor int8 quantisation.

    Returns ``(q, scale)`` with ``q`` int8 in [-127, 127] and
    ``x ≈ q * scale``; round-to-nearest bounds the error by ``scale / 2``.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(amax / 127.0, jnp.finfo(jnp.float32).tiny)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def resolve_modes(tree_like, mode) -> list[str]:
    """Per-leaf mode list for ``tree_like``: accepts a single mode string, a
    flat list, a pytree of strings, or a policy object with ``.modes()``."""
    n_leaves = len(jax.tree.leaves(tree_like))
    if hasattr(mode, "modes"):  # CompressionPolicy (duck-typed: no import cycle)
        flat = mode.modes(tree_like)
    elif isinstance(mode, str):
        flat = [mode] * n_leaves
    else:
        flat = jax.tree.leaves(mode, is_leaf=lambda x: isinstance(x, str))
    if len(flat) != n_leaves:
        raise ValueError("mode tree does not match gradient tree "
                         f"({len(flat)} vs {n_leaves} leaves)")
    for m in flat:
        if m not in MODES:
            raise ValueError(f"unknown compression mode {m!r}; "
                             f"expected one of {MODES}")
    return flat


def init_error_state(grads_like, mode=None):
    """Zero residual per gradient leaf (f32 regardless of grad dtype).

    With ``mode`` (string / pytree / policy), residual state is allocated
    **only for compressed leaves**; ``"none"`` leaves get a 0-d placeholder —
    on a billion-parameter model whose large leaves are the only compressed
    ones, that is the difference between doubling gradient memory and not.
    """
    leaves, treedef = jax.tree.flatten(grads_like)
    modes = (["__full__"] * len(leaves) if mode is None
             else resolve_modes(grads_like, mode))
    out = [jnp.zeros(() if m == "none" else jnp.shape(g), jnp.float32)
           for g, m in zip(leaves, modes)]
    return jax.tree.unflatten(treedef, out)


def _bf16_to_wire(x):
    """bf16 values → uint16 bit pattern.  Collectives carry the integer
    payload: backends without native bf16 collectives (XLA CPU float
    normalization) would otherwise silently retype them to f32 — 2× the
    wire bytes this mode exists to save.  Bitcast is free; integer data
    movement is supported everywhere."""
    return lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16)


def _bf16_from_wire(u):
    return lax.bitcast_convert_type(u, jnp.bfloat16).astype(jnp.float32)


def _shared_scale(v, axis_name):
    """Quantisation scale agreed across the axis (pmax) so integer partial
    sums are exact and bitwise identical on every replica."""
    amax = jnp.max(jnp.abs(v))
    if axis_name:
        amax = lax.pmax(amax, axis_name)
    return jnp.maximum(amax / 127.0, jnp.finfo(jnp.float32).tiny)


def _quant(v, scale):
    return jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)


def _compressed_allreduce_mean(v, axis_name, mode, two_level=True):
    """Two-phase compressed-on-the-wire mean-all-reduce (module docstring).

    Returns ``(mean, charged)``: the replicated mean estimate and what
    error feedback charges this device for — its decompressed phase-1
    contribution, minus (with ``two_level``, int8 only) the phase-2
    requantization residual of its own shard scaled by ``n``.

    Two-level error feedback: phase 2 re-quantizes the already-reduced
    shard mean ``y`` to ``out = q2·scale2``, losing ``r2 = y - out`` — an
    error *outside* plain EF (which only telescopes phase-1 loss), so it
    used to bias every step by one int8 step of the mean.  Each device
    knows ``r2`` exactly for its own shard (it computed ``y`` there), so
    it charges ``n·r2`` at its shard's positions: summed over the axis
    each shard's residual enters the next step's mean exactly once, and
    the emitted-gradient sum telescopes over *both* compression levels.
    """
    n = lax.psum(1, axis_name)
    if mode == "bf16":
        payload = _bf16_to_wire(v)  # uint16 bits on the wire
        deq = _bf16_from_wire(payload)
    else:  # int8
        scale = _shared_scale(v, axis_name)
        payload = _quant(v, scale)
        deq = payload.astype(jnp.float32) * scale
    if n == 1:
        return deq, deq
    # Shards are blocks of leading-dim rows (padded to a multiple of n), so
    # the leaf keeps its own layout: flattening a narrow (rows, D) leaf
    # forces a relayout that pads D to 128 lanes on TPU.
    x = _rows_padded(payload, n)
    k = x.shape[0] // n
    # phase 1: each device ends up holding every peer's copy of its shard
    mine = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=True).reshape((n, k) + x.shape[1:])
    if mode == "bf16":
        y = jnp.sum(_bf16_from_wire(mine), axis=0) / n
        gathered = lax.all_gather(_bf16_to_wire(y), axis_name, tiled=True)
        out = _bf16_from_wire(gathered)
        charged = deq
    else:
        shard_sum = jnp.sum(mine.astype(jnp.int32), axis=0)  # exact: ≤ 127·n
        y = shard_sum.astype(jnp.float32) * (scale / n)
        scale2 = _shared_scale(y, axis_name)
        q2 = _quant(y, scale2)
        gathered = lax.all_gather(q2, axis_name, tiled=True)
        out = gathered.astype(jnp.float32) * scale2
        charged = deq
        if two_level:
            r2 = y - q2.astype(jnp.float32) * scale2  # this shard's phase-2 loss
            d = _rows_padded(deq, n)
            at = lax.axis_index(axis_name) * k
            own = lax.dynamic_slice_in_dim(d, at, k, axis=0)
            charged = lax.dynamic_update_slice_in_dim(
                d, own - n * r2, at, axis=0)
            charged = charged[:_rows(v)].reshape(v.shape)
    return out[:_rows(v)].reshape(v.shape), charged


def _rows(x) -> int:
    return x.shape[0] if x.ndim else 1


def _rows_padded(x, n):
    """``x`` as at least 1-d, its leading dim zero-padded to a multiple of n."""
    x = x.reshape(1) if x.ndim == 0 else x
    pad = (-x.shape[0]) % n
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x


def _reduce_leaf(g, e, axis_name, mode, two_level=True):
    """Compressed mean-all-reduce of one leaf → (reduced_full, new_err)."""
    v = g.astype(jnp.float32) + e
    if mode == "none":
        out = lax.pmean(v, axis_name) if axis_name else v
        return out.astype(g.dtype), jnp.zeros_like(e)
    if mode == "bf16":
        if axis_name:
            out, deq = _compressed_allreduce_mean(v, axis_name, mode)
        else:
            out = deq = v.astype(jnp.bfloat16).astype(jnp.float32)
        return out.astype(g.dtype), v - deq
    if mode == "int8":
        if axis_name:
            out, deq = _compressed_allreduce_mean(v, axis_name, mode,
                                                  two_level=two_level)
        else:
            q, scale = quantize_int8(v)
            deq = q.astype(jnp.float32) * scale
            out = deq
        return out.astype(g.dtype), v - deq
    raise ValueError(f"unknown compression mode {mode!r}; expected one of {MODES}")


def _reduce_scatter_leaf(g, e, axis_name, mode, dim):
    """Compressed mean-reduce-scatter of one leaf along concrete ``dim``.

    Returns ``(shard, new_err)``: this device's shard of the mean gradient
    (``shape[dim] / n`` along ``dim``) and the full-shape residual.  The
    compressed paths stop after phase 1 of the two-phase exchange — the
    shard sum *is* the reduce-scatter, so only (n−1)/n · {2, 1} B/elem
    crosses the wire (2× / 4× less than an f32 reduce-scatter).
    """
    n = lax.psum(1, axis_name)
    v = g.astype(jnp.float32) + e
    if n == 1:
        red, new_e = _reduce_leaf(g, e, None, mode)
        return red.astype(jnp.float32), new_e
    if mode == "none":
        shard = lax.psum_scatter(v, axis_name, scatter_dimension=dim,
                                 tiled=True) / n
        return shard, jnp.zeros_like(e)
    if mode == "bf16":
        c = _bf16_to_wire(v)
        mine = lax.all_to_all(c, axis_name, split_axis=dim, concat_axis=dim,
                              tiled=True)
        # dim is now n consecutive blocks of shape[dim]//n, one per peer
        split = mine.shape[:dim] + (n, mine.shape[dim] // n) + mine.shape[dim + 1:]
        shard = jnp.sum(_bf16_from_wire(mine.reshape(split)), axis=dim) / n
        return shard, v - _bf16_from_wire(c)
    if mode == "int8":
        scale = _shared_scale(v, axis_name)
        q = _quant(v, scale)
        mine = lax.all_to_all(q, axis_name, split_axis=dim, concat_axis=dim,
                              tiled=True)
        split = mine.shape[:dim] + (n, mine.shape[dim] // n) + mine.shape[dim + 1:]
        shard_sum = jnp.sum(mine.reshape(split).astype(jnp.int32), axis=dim)
        shard = shard_sum.astype(jnp.float32) * (scale / n)
        return shard, v - q.astype(jnp.float32) * scale
    raise ValueError(f"unknown compression mode {mode!r}; expected one of {MODES}")


def ef_psum_grads(grads, err, *, axis_name=None, mode="bf16",
                  two_level=True):
    """Compressed (mean-)reduction of a gradient tree with error feedback.

    Args:
      grads: gradient pytree.
      err: residual pytree from the previous step (``init_error_state`` to
        start); same treedef as ``grads``.
      axis_name: mapped axis to reduce over (``shard_map``/``pmap`` body),
        or ``None`` for local compression only.
      mode: ``"none" | "bf16" | "int8"``, a per-leaf pytree / flat list of
        those, or a ``policy.CompressionPolicy``.
      two_level: carry the int8 phase-2 requantization residual into the
        error state as well (``_compressed_allreduce_mean`` docstring), so
        the time-averaged update telescopes over both compression levels.
        On by default; off reproduces the single-level behaviour (one int8
        step of the mean per step of standing bias).

    Returns ``(reduced_grads, new_err)``.  The reduction is a *mean* over
    the axis, matching a per-shard-mean loss.
    """
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err)
    if len(flat_e) != len(flat_g):
        raise ValueError("error state does not match gradient tree "
                         f"({len(flat_e)} vs {len(flat_g)} leaves)")
    modes = resolve_modes(grads, mode)
    out = [_reduce_leaf(g, e, axis_name, m, two_level=two_level)
           for g, e, m in zip(flat_g, flat_e, modes)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))
