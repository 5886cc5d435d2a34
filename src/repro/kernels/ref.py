"""Pure-jnp oracles for every Pallas kernel (ground truth for tests/benches)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core.compositional import masked_bag_sum

__all__ = ["qr_gather_ref", "qr_gather_quant_ref", "qr_embedding_bag_ref",
           "fused_serve_pool_ref", "dot_interaction_ref"]


def qr_gather_ref(rem_idx, quo_idx, w_rem, w_quo, *, op: str = "mult"):
    a = jnp.take(w_rem, rem_idx, axis=0)
    b = jnp.take(w_quo, quo_idx, axis=0)
    return a * b if op == "mult" else a + b


def _dequant_rows_ref(w, meta, idx):
    """f32 rows from an int8 table + per-row (scale, zp) meta."""
    rows = jnp.take(w, idx, axis=0).astype(jnp.float32)
    m = jnp.take(meta.astype(jnp.float32), idx, axis=0)
    return (rows - m[..., 1:2]) * m[..., 0:1]


def qr_gather_quant_ref(rem_idx, quo_idx, w_rem, w_quo, rem_meta, quo_meta,
                        *, op: str = "mult"):
    a = _dequant_rows_ref(w_rem, rem_meta, rem_idx)
    b = _dequant_rows_ref(w_quo, quo_meta, quo_idx)
    return a * b if op == "mult" else a + b


def qr_embedding_bag_ref(rem_idx, quo_idx, mask, w_rem, w_quo, *, op: str = "mult"):
    # Accumulate the bag sum in f32 (accumulation-audit convention): the
    # oracle must not inherit the bf16 running-sum rounding it exists to
    # catch in the kernels.  Result is cast back to the table dtype.
    rows = qr_gather_ref(rem_idx, quo_idx, w_rem, w_quo, op=op)  # (B, L, D)
    return masked_bag_sum(rows, mask).astype(w_rem.dtype)


def fused_serve_pool_ref(idx_a, mask, w_a, idx_b=None, w_b=None, meta_a=None,
                         meta_b=None, proj=None, *, op: str = "mult"):
    """Oracle for ``serve_path.fused_serve_pool``: gather (+dequant) →
    combine → masked f32 sum-pool → one rounding to the pool dtype →
    projection.  The combine happens in f32 even for dense bf16 tables
    (bf16 rows are exact in f32), matching the kernel's accumulation-audit
    convention, so the only dtype-dependent rounding is the single cast of
    the pooled bag."""
    quant = meta_a is not None
    if mask.shape[1] == 0:                     # all-empty wave: Lb floors at 1
        b_ = mask.shape[0]
        mask = jnp.zeros((b_, 1), mask.dtype)
        idx_a = jnp.zeros((b_, 1), jnp.int32)
        idx_b = jnp.zeros((b_, 1), jnp.int32) if idx_b is not None else None

    def rows(w, meta, idx):
        r = jnp.take(w, idx, axis=0).astype(jnp.float32)
        if meta is not None:
            m = jnp.take(meta.astype(jnp.float32), idx, axis=0)
            r = (r - m[..., 1:2]) * m[..., 0:1]
        return r

    row = rows(w_a, meta_a, idx_a)
    if idx_b is not None:
        rb = rows(w_b, meta_b, idx_b)
        row = row * rb if op == "mult" else row + rb
    pooled = masked_bag_sum(row, mask).astype(
        jnp.float32 if quant else w_a.dtype)
    if proj is None:
        return pooled
    return pooled.astype(jnp.float32) @ proj.astype(jnp.float32)


def dot_interaction_ref(x):
    # f32 MXU accumulation, matching the kernel's preferred_element_type
    scores = jnp.einsum("bfd,bgd->bfg", x, x,
                        preferred_element_type=jnp.float32)
    i, j = np.tril_indices(x.shape[1], k=-1)
    return scores[:, i, j].astype(x.dtype)
