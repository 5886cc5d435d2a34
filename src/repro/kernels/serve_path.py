"""Fused serving hot-path kernel (Pallas TPU): gather → dequant → pool → project.

The serving data path for one categorical feature is

    rows   = dequant(gather(tables, idx))        # int8 rows widen in VMEM
    pooled = sum_l mask[b, l] * combine(rows)    # multi-hot bag pooling
    feat   = pooled @ proj                       # mixed-dim width projection

Unfused that is up to six HBM gathers per row (q/scale/zp per table), a
``(B, L, D)`` f32 intermediate, a reduction, and a separate projection
matmul.  This kernel does the whole thing in one VMEM pass, and it is the
one row-gather kernel of the repo: ``kernels.ops`` runs a plain QR lookup
(``qr_lookup``) as one-slot bags of it, with no mask.

* **Row fetch.**  Per-row table indices (and the pool weights, when
  given) are **scalar-prefetch** operands.  Each table's
  ``BlockSpec.index_map`` fetches the sublane-aligned ``(ROW_BLOCK, d)``
  block holding the wanted row (block ``r // ROW_BLOCK``), double-buffered
  across grid steps, and the kernel selects row ``r % ROW_BLOCK`` in VMEM
  (``_take_row``).  The TPU refuses a ``(1, d)`` block: the last two block
  dims must be multiples of ``(8, 128)`` or whole array dims.  A table
  shorter than a block is fetched whole.
* **Bounded prefetch.**  Scalar prefetch lives in SMEM (1 MiB on v5e), so
  a batch of more than ``MAX_SLOTS`` bag slots runs as several calls of at
  most ``MAX_SLOTS`` slots each (at least one output block per call).
* dequantization (``(q - zp) * scale``) and the mult/add combine happen in
  VMEM, in f32 (accumulation-audit convention: a bf16 running sum rounds
  every one of the L adds);
* the ``(1, d)`` bag accumulator lives in VMEM scratch across the L inner
  grid steps, and on the last step is projected through the resident
  ``(d, D)`` projection and written into its row of the ``(ROW_BLOCK, D)``
  output block, which stays in VMEM while its rows are produced.

Shapes are degrees of freedom, not special cases: one table (full /
hashing-trick, the caller pre-folds ``idx mod m``) or a QR pair, dense
f32/bf16 or row-quantized int8 tables, projection present (mixed-dimension
plans) or absent (uniform widths).  Empty bags (all-zero mask rows) pool
to the exact zero vector; the wrapper pads ``L=0`` waves to one masked
slot, mirroring the engine's ``Lb >= 1`` floor, and pads the batch to a
whole number of output blocks (and calls) with rows it then cuts off.

``tests/test_tpu_compile.py`` compiles it for a v5e chip at D ∈ {16, 128};
on the CPU backend it runs in interpret mode (``kernels.ops``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_serve_pool"]

ROW_BLOCK = 8   # f32 sublane tile: rows per fetched table block / out block
# bag slots per call: three int32/f32 prefetch arrays of this length take
# 384 KiB of SMEM (v5e's 1 MiB held 3 x 65536 slots, not 3 x 262144)
MAX_SLOTS = 1 << 15


def _row_spec(rows: int, width: int, pick):
    """BlockSpec of the aligned block holding row ``pick(i, j, *prefetch)``."""
    s = min(ROW_BLOCK, rows)
    return pl.BlockSpec((s, width),
                        lambda i, j, *pf: (pick(i, j, *pf) // s, 0))


def _take_row(ref, r):
    """Row ``r`` of the table, as ``(1, width)`` f32, out of the aligned
    block ``ref`` that ``_row_spec`` fetched.  A select, not a multiply:
    rows of a partial edge block past the table end hold stale VMEM."""
    blk = ref[...].astype(jnp.float32)
    hit = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) == r % blk.shape[0]
    return jnp.sum(jnp.where(hit, blk, 0.0), axis=0, keepdims=True)


def _kernel(*refs, op, has_b, has_mask, quant, project, l_steps, pool_dtype):
    """Ref layout (flags select which slots exist):

    ``[idx_a, (idx_b), (mask)] + [w_a, (meta_a), (w_b), (meta_b), (proj)]
    + [out] + [acc]``
    """
    it = iter(refs)
    ia_ref = next(it)
    ib_ref = next(it) if has_b else None
    mask_ref = next(it) if has_mask else None
    wa_ref = next(it)
    ma_ref = next(it) if quant else None
    wb_ref = mb_ref = None
    if has_b:
        wb_ref = next(it)
        mb_ref = next(it) if quant else None
    proj_ref = next(it) if project else None
    out_ref = next(it)
    acc_ref = next(it)

    i = pl.program_id(0)
    l = pl.program_id(1)
    k = i * l_steps + l

    def fetch(w_ref, m_ref, r):
        row = _take_row(w_ref, r)
        if quant:
            meta = _take_row(m_ref, r)                 # (1, 2): scale, zp
            row = (row - meta[:, 1:2]) * meta[:, 0:1]
        return row

    row = fetch(wa_ref, ma_ref, ia_ref[k])
    if has_b:
        rb = fetch(wb_ref, mb_ref, ib_ref[k])
        row = row * rb if op == "mult" else row + rb
    contrib = row * mask_ref[k] if has_mask else row

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = contrib

    @pl.when(l > 0)
    def _acc():
        acc_ref[...] = acc_ref[...] + contrib

    @pl.when(l == l_steps - 1)
    def _emit():
        # One rounding to the pool dtype (table dtype for dense tables, f32
        # for dequantized rows) *before* the projection — bit-parity with
        # the unfused pool-then-project path the models ship today.
        out = acc_ref[...].astype(pool_dtype).astype(jnp.float32)
        if project:
            out = jnp.dot(out, proj_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        here = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0) \
            == i % ROW_BLOCK
        out_ref[...] = jnp.where(here, out, out_ref[...])


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def fused_serve_pool(idx_a, mask, w_a, idx_b=None, w_b=None, meta_a=None,
                     meta_b=None, proj=None, *, op: str = "mult",
                     interpret: bool = False):
    """Fused bag lookup: gather (+dequant) → masked sum-pool → project.

    Args:
      idx_a: int32 ``(B, L)`` row indices into ``w_a`` (pre-folded: the
        remainder ``i % m`` for QR pairs, ``i mod m`` for hash tables).
      mask: ``(B, L)`` pool weights (0 drops the slot; an all-zero row —
        an empty bag — pools to the exact zero vector), or None when every
        slot counts with weight 1.  ``L=0`` is legal and padded to one
        masked slot.
      w_a: ``(m, d)`` table — f32/bf16 dense, or int8 with ``meta_a``.
      idx_b, w_b: optional quotient side of a QR pair (``op`` combines).
      meta_a, meta_b: f32 ``(rows, 2)`` per-row ``(scale, zp)`` when the
        matching table is int8 (both tables of a pair quantize together).
      proj: optional ``(d, D)`` mixed-dimension projection applied to the
        pooled bag (pooling and projection are both linear, so
        pool-then-project equals the unfused path).
      interpret: run the kernel body through the Pallas interpreter (the
        CPU backend's only mode; ``kernels.ops`` decides).
    Returns: ``(B, D)`` features — ``D = proj.shape[1]`` when projecting,
      else ``d``; dtype f32 for quantized/projected paths, the table dtype
      otherwise.
    """
    quant = meta_a is not None
    has_b = idx_b is not None
    project = proj is not None
    if has_b != (w_b is not None) or (quant and has_b) != (meta_b is not None):
        raise ValueError("QR pair / quant meta operands must come in pairs")
    b_live = idx_a.shape[0]
    if idx_a.shape[1] == 0:                    # all-empty wave: Lb floors at 1
        mask = jnp.zeros((b_live, 1), jnp.float32)
        idx_a = jnp.zeros((b_live, 1), jnp.int32)
        idx_b = jnp.zeros((b_live, 1), jnp.int32) if has_b else None
    l = idx_a.shape[1]
    # whole output blocks, and whole calls of at most MAX_SLOTS slots
    rows = max(ROW_BLOCK, MAX_SLOTS // l // ROW_BLOCK * ROW_BLOCK)
    b = -(-b_live // ROW_BLOCK) * ROW_BLOCK
    rows = min(rows, b)
    b = -(-b // rows) * rows
    pad = b - b_live
    if pad:                                    # padded rows are cut off

        def grow(x):
            return jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:],
                                                 x.dtype)])
        idx_a = grow(idx_a)
        idx_b = grow(idx_b) if has_b else None
        mask = grow(mask) if mask is not None else None
    d = w_a.shape[1]
    pool_dtype = jnp.float32 if quant else w_a.dtype
    out_dtype = jnp.float32 if (quant or project) else w_a.dtype
    d_out = proj.shape[1] if project else d

    def row_a(i, j, ia, *_):
        return ia[i * l + j]

    def row_b(i, j, ia, ib, *_):
        return ib[i * l + j]

    def pinned(i, j, *_):
        return (0, 0)

    in_specs = [_row_spec(w_a.shape[0], d, row_a)]
    operands = [w_a]
    if quant:
        in_specs.append(_row_spec(meta_a.shape[0], 2, row_a))
        operands.append(meta_a.astype(jnp.float32))
    if has_b:
        in_specs.append(_row_spec(w_b.shape[0], d, row_b))
        operands.append(w_b)
        if quant:
            in_specs.append(_row_spec(meta_b.shape[0], 2, row_b))
            operands.append(meta_b.astype(jnp.float32))
    if project:
        in_specs.append(pl.BlockSpec(proj.shape, pinned))  # stays resident
        operands.append(proj)

    n_prefetch = 1 + has_b + (mask is not None)
    call = pl.pallas_call(
        functools.partial(_kernel, op=op, has_b=has_b,
                          has_mask=mask is not None, quant=quant,
                          project=project, l_steps=l, pool_dtype=pool_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(rows, l),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((ROW_BLOCK, d_out),
                                   lambda i, j, *_: (i // ROW_BLOCK, 0)),
            scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d_out), jnp.float32),
        interpret=interpret,
    )
    outs = []
    for c in range(0, b, rows):
        prefetch = [idx_a[c:c + rows].reshape(-1).astype(jnp.int32)]
        if has_b:
            prefetch.append(idx_b[c:c + rows].reshape(-1).astype(jnp.int32))
        if mask is not None:
            prefetch.append(mask[c:c + rows].reshape(-1).astype(jnp.float32))
        outs.append(call(*prefetch, *operands))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return out[:b_live].astype(out_dtype)
