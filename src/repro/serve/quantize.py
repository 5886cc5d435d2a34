"""Post-training row-wise quantization of compositional embedding tables.

The paper shrinks tables at *training* time (QR / complementary
partitions); this module multiplies that win at *serve* time with
post-training row-wise quantization ("Learning Compressed Embeddings for
On-Device Inference"-style): each table row gets its own affine int8 code

    w ≈ scale * (q - zp)        q int8 in [-127, 127], zp int8, scale bf16

so a ``(rows, D)`` f32 table becomes ``D + 3`` bytes per row instead of
``4·D`` (D=64: 0.262x; the serve bench's acceptance bar is 0.27x).  Design
choices that matter:

* **per-row** scale/zp — embedding rows differ in magnitude by orders of
  magnitude under Zipfian training (hot rows grow), so a per-tensor scale
  would burn the int8 budget on the hottest row;
* the row range is widened to include 0 (``lo = min(row, 0)``, ``hi =
  max(row, 0)``), which pins the zero-point into int8 range and makes
  padding rows exact;
* the scale is **rounded to bf16 before quantizing**, so dequantization
  with the stored scale reproduces exactly the grid the encoder used and
  the end-to-end error keeps the textbook round-to-nearest bound
  ``|dequant(w) - w| <= scale / 2`` per row (pinned by tests and by
  ``benchmarks/serve_bench.py``'s built-in check);
* integer zero-point (TFLite convention) — ``zp`` contributes no rounding
  error of its own.

A quantized table is a plain pytree: ``{"q": int8 (rows, D), "scale":
bf16 (rows, 1), "zp": int8 (rows, 1)}`` — it jits, shards (the rule
engine's ``table_\\d+`` pattern matches the parent path), and
checkpoints like any other params.  Lookups dequantize only the gathered
rows (``core.compositional.table_rows``); the fused Pallas kernel
(``kernels.serve_path.fused_serve_pool``) does the dequant in VMEM during
the combine.

``mode="bf16"`` is the cheap alternative: matching leaves are cast to
bf16 arrays (0.5x bytes, ~3-decimal-digit rows) with no layout change.
"""

from __future__ import annotations

import math
import re
from typing import Sequence

import jax
import jax.numpy as jnp

from ..core.compositional import is_quantized_table, table_rows
from ..optim.optimizers import leaf_paths

__all__ = ["MODES", "TABLE_PATTERN", "quantize_table", "dequantize_rows",
           "dequantize_table", "is_quantized_table", "quantize_params",
           "table_bytes", "table_shapes", "memory_report",
           "paths_and_leaves", "row_bytes"]

MODES = ("f32", "bf16", "int8")

# Same path idiom as sharding.RULES / policy.POLICY_RULES: embedding and
# hash tables are the memory-dominant leaves quantization exists for.
TABLE_PATTERN = r"(^|/)(embed\w*|wte|tok_emb|tables?)(/|$)|(^|/)table_\d+($|/)"

# q and zp live in [-QMAX, QMAX]; the grid spans 2*QMAX - 2 steps so that
# rounding the zero-point to an integer can never push a code out of range.
_QMAX = 127
_STEPS = 2 * _QMAX - 2  # 252


def row_bytes(dim: int, mode: str = "int8") -> int:
    """Bytes per stored table row of width ``dim`` under ``mode``.

    The single bytes/row model shared by the serving stack (cache byte
    budgets, ``table_bytes``) and the memory planner's serve-cost domain:
    int8 rows carry ``dim`` q bytes + 2 (bf16 scale) + 1 (int8 zp).
    """
    if mode not in MODES:
        raise ValueError(f"unknown quantization mode {mode!r}; "
                         f"expected one of {MODES}")
    return {"f32": 4 * dim, "bf16": 2 * dim, "int8": dim + 3}[mode]


def quantize_table(w) -> dict:
    """Row-wise affine int8 quantization of a ``(rows, D)`` table.

    Returns ``{"q", "scale", "zp"}`` (see module docstring for the wire
    format and the ``scale/2`` per-row error bound).
    """
    if w.ndim != 2:
        raise ValueError(f"quantize_table expects (rows, D), got {w.shape}")
    w32 = w.astype(jnp.float32)
    lo = jnp.minimum(w32.min(axis=1, keepdims=True), 0.0)
    hi = jnp.maximum(w32.max(axis=1, keepdims=True), 0.0)
    scale = jnp.maximum((hi - lo) / _STEPS, jnp.finfo(jnp.float32).tiny)
    # round-trip through bf16 FIRST: the encoder and decoder must agree on
    # the grid, otherwise the stored-scale mismatch adds |w| * 2^-9 error
    scale = scale.astype(jnp.bfloat16)
    s32 = scale.astype(jnp.float32)
    zp = jnp.round(-(_QMAX - 1) - lo / s32)  # in [-(QMAX-1), QMAX-1]
    q = jnp.clip(jnp.round(w32 / s32 + zp), -_QMAX, _QMAX)
    return {"q": q.astype(jnp.int8), "scale": scale,
            "zp": zp.astype(jnp.int8)}


def dequantize_rows(qt: dict, idx):
    """Gather + dequantize rows ``idx`` from a quantized table (f32 out).

    Only the gathered rows are ever widened — the f32 table never
    materialises (the point of serving quantized).
    """
    return table_rows(qt, idx)


def dequantize_table(qt: dict):
    """Full-table dequantization (tests / error-bound checks only)."""
    return ((qt["q"].astype(jnp.float32) - qt["zp"].astype(jnp.float32))
            * qt["scale"].astype(jnp.float32))


def _match(path: str, patterns: Sequence[str]) -> bool:
    return any(re.search(p, path) for p in patterns)


def quantize_params(params, mode: str = "int8",
                    patterns: Sequence[str] = (TABLE_PATTERN,)):
    """Quantize every rank-2 table leaf of a param tree for serving.

    Leaves whose path matches ``patterns`` (default: the shared table
    pattern) are replaced by quantized-table dicts (``int8``) or cast to
    bf16 (``bf16``); everything else — MLPs, norms, biases — is returned
    untouched.  ``mode="f32"`` is the identity (so benches can treat the
    three modes uniformly).
    """
    if mode not in MODES:
        raise ValueError(f"unknown quantization mode {mode!r}; "
                         f"expected one of {MODES}")
    if mode == "f32":
        return params
    leaves, treedef = jax.tree.flatten(params)
    paths = leaf_paths(params)
    out = []
    for path, leaf in zip(paths, leaves):
        if getattr(leaf, "ndim", 0) == 2 and _match(path, patterns):
            out.append(quantize_table(leaf) if mode == "int8"
                       else leaf.astype(jnp.bfloat16))
        else:
            out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def _leaf_bytes(leaf) -> int:
    if is_quantized_table(leaf):
        return sum(_leaf_bytes(v) for v in leaf.values())
    n = int(math.prod(leaf.shape)) if leaf.shape else 1
    return n * jnp.dtype(leaf.dtype).itemsize


def paths_and_leaves(params):
    """(path, leaf) pairs treating quantized-table dicts as single leaves —
    a quantized leaf keeps the path of the f32 leaf it replaced, so zipping
    the two trees by path pairs original and quantized tables exactly."""
    return list(zip(leaf_paths(params, is_leaf=is_quantized_table),
                    jax.tree.leaves(params, is_leaf=is_quantized_table)))


def table_bytes(params, patterns: Sequence[str] = (TABLE_PATTERN,)) -> int:
    """Total bytes of the table leaves (quantized dicts count q+scale+zp)."""
    return sum(_leaf_bytes(leaf) for path, leaf in paths_and_leaves(params)
               if is_quantized_table(leaf) or _match(path, patterns))


def table_shapes(params, patterns: Sequence[str] = (TABLE_PATTERN,)
                 ) -> list[tuple[str, int, int]]:
    """``(path, rows, width)`` per table leaf — mixed-dimension plans give
    every feature its own row width, and this is the report that makes the
    per-table layout auditable (quantized dicts report their ``q`` shape)."""
    out = []
    for path, leaf in paths_and_leaves(params):
        if is_quantized_table(leaf):
            out.append((path, int(leaf["q"].shape[0]),
                        int(leaf["q"].shape[1])))
        elif getattr(leaf, "ndim", 0) == 2 and _match(path, patterns):
            out.append((path, int(leaf.shape[0]), int(leaf.shape[1])))
    return out


def memory_report(params, qparams, placement=None) -> dict:
    """Bytes vs f32 for the table leaves: the number the paper + serving
    stack exist to shrink.  ``ratio`` is what the serve bench gates on;
    ``table_dims`` is the distinct-row-width set (singleton for uniform
    models, several entries under a mixed-dimension plan).

    With a ``placement`` (``dist.serve_placement.ServePlacement``) the
    report adds the sharded-serving view: per-device table bytes under
    that placement (replicated sub-tables count in full, row-sharded
    ones contribute their padded 1/N slice) and the per-device ratio
    against an even f32 split — the memory argument for serving a plan
    on N devices."""
    base = table_bytes(params)
    quant = table_bytes(qparams)
    report = {"f32_table_bytes": base, "quant_table_bytes": quant,
              "ratio": quant / base if base else 1.0,
              "table_dims": sorted({w for _, _, w in table_shapes(params)}),
              "model_bytes_f32": sum(_leaf_bytes(l) for l in
                                     jax.tree.leaves(params)),
              "model_bytes_quant": sum(
                  _leaf_bytes(l) for l in
                  jax.tree.leaves(qparams, is_leaf=is_quantized_table))}
    if placement is not None:
        n = placement.n_devices
        per_dev = placement.bytes_per_device()
        report["placement"] = {
            "n_devices": n,
            "table_bytes_per_device": per_dev,
            "replicated_bytes": placement.replicated_bytes(),
            "pad_bytes": placement.pad_bytes(),
            "ratio_per_device": (per_dev / (base / n)) if base else 1.0,
        }
    return report
