"""Jit'd public wrappers around the Pallas kernels.

These are what models call.  Responsibilities:
  * compute quotient/remainder bucket indices (cheap vector ops XLA fuses);
  * choose execution path: the compiled Pallas kernel, or the jnp
    reference for configs the kernels don't cover (op="concat", k>2
    partitions).  Interpret mode is chosen here and only here
    (``interpret_mode``): on the CPU backend, and nowhere else;
  * handle padding so callers never see blocking constraints.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.compositional import is_quantized_table as _is_quant
from ..core.compositional import masked_bag_sum, table_rows
from . import ref
from .dot_interaction import dot_interaction as _dot_kernel
from .serve_path import fused_serve_pool as _serve_kernel

__all__ = ["interpret_mode", "qr_lookup", "serve_bag_pool", "dlrm_interact"]


def interpret_mode() -> bool:
    """Pallas interpret mode runs the kernel bodies on the CPU backend (its
    only way to run them); every other backend compiles them."""
    return jax.default_backend() == "cpu"


def _split_idx(idx, m):
    idx = jnp.asarray(idx, jnp.int32)
    return idx % m, idx // m


def _rows(table) -> int:
    return (table["q"] if _is_quant(table) else table).shape[0]


def _meta(table):
    """(rows, 2) f32 per-row (scale, zp) — the fused kernel's meta operand."""
    return jnp.concatenate([table["scale"].astype(jnp.float32),
                            table["zp"].astype(jnp.float32)], axis=1)


def qr_lookup(idx, w_rem, w_quo, *, op: str = "mult", use_kernel: bool = True):
    """QR-trick embedding lookup for arbitrary-rank ``idx``.

    Tables may be dense arrays or row-quantized dicts (``serve.quantize``).
    On the kernel path each lookup is a one-slot bag of the fused kernel:
    both rows fetched, dequantized (int8) and combined in VMEM in f32.
    """
    m = _rows(w_rem)
    rem, quo = _split_idx(idx, m)
    quant = _is_quant(w_rem)
    if use_kernel and op in ("mult", "add") and quant == _is_quant(w_quo):
        out = _serve_kernel(
            rem.reshape(-1, 1), None, w_rem["q"] if quant else w_rem,
            idx_b=quo.reshape(-1, 1), w_b=w_quo["q"] if quant else w_quo,
            meta_a=_meta(w_rem) if quant else None,
            meta_b=_meta(w_quo) if quant else None,
            op=op, interpret=interpret_mode())
        return out.reshape(*rem.shape, out.shape[-1])
    a, b = table_rows(w_rem, rem), table_rows(w_quo, quo)
    if op == "concat":
        return jnp.concatenate([a, b], axis=-1)
    return a * b if op == "mult" else a + b


def serve_bag_pool(idx, mask, w_a, w_b=None, *, op: str = "mult", proj=None,
                   use_kernel: bool = True):
    """Serving hot-path pooled lookup: gather (+dequant) → pool → project.

    The single entry point the serving stack routes through.  ``w_a`` (and
    the optional quotient table ``w_b``) may be dense arrays or
    row-quantized dicts (``serve.quantize``).  With ``w_b`` given, ``idx``
    is raw and split ``(i % m, i // m)`` here; single-table callers
    (full / hash / the engine's device-resident row slab) pass pre-folded
    indices.  ``proj`` is the mixed-dimension ``(d, D)`` projection —
    pooling and projection fuse into the same VMEM pass on the kernel
    path, and the jnp fallback (op="concat"/mixed-quant pairs
    the kernel doesn't cover) computes the identical math via the
    ``kernels.ref`` oracle.
    """
    quant_a = _is_quant(w_a)
    quant_b = _is_quant(w_b) if w_b is not None else quant_a
    if w_b is not None:
        m = _rows(w_a)
        idx_a, idx_b = _split_idx(idx, m)
    else:
        idx_a, idx_b = jnp.asarray(idx, jnp.int32), None
    fusable = (w_b is None or op in ("mult", "add")) and quant_a == quant_b
    qa = w_a["q"] if quant_a else w_a
    qb = (w_b["q"] if quant_b else w_b) if w_b is not None else None
    ma = _meta(w_a) if quant_a else None
    mb = _meta(w_b) if (w_b is not None and quant_b) else None
    if use_kernel and fusable:
        return _serve_kernel(idx_a, mask, qa, idx_b=idx_b, w_b=qb,
                             meta_a=ma, meta_b=mb, proj=proj, op=op,
                             interpret=interpret_mode())
    if not fusable:
        # op="concat" / mixed dense+quant pair: gather per table, combine,
        # pool in f32, project — same contract, jnp all the way
        a = table_rows(w_a, idx_a)
        b = table_rows(w_b, idx_b)
        rows = (jnp.concatenate([a, b], axis=-1) if op == "concat"
                else (a * b if op == "mult" else a + b))
        quant = quant_a or quant_b
        pooled = masked_bag_sum(rows, mask).astype(
            jnp.float32 if quant else a.dtype)
        return pooled if proj is None \
            else pooled.astype(jnp.float32) @ proj.astype(jnp.float32)
    return ref.fused_serve_pool_ref(idx_a, mask, qa, idx_b=idx_b, w_b=qb,
                                    meta_a=ma, meta_b=mb, proj=proj, op=op)


def dlrm_interact(x, *, use_kernel: bool = True, block_b: int = 8):
    """DLRM pairwise-dot interaction, padding batch to the kernel block."""
    if not use_kernel:
        return ref.dot_interaction_ref(x)
    b = x.shape[0]
    pad = (-b) % block_b
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    out = _dot_kernel(x, block_b=block_b, interpret=interpret_mode())
    return out[:b]
