"""Operation-based compositional embeddings (paper §2, §4).

Functional modules: frozen-dataclass configs with ``init(key) -> params``
(a dict of jnp arrays) and ``apply(params, idx) -> embeddings``.  All
``apply`` methods accept arbitrary-rank integer index arrays and return
``idx.shape + (dim,)`` activations, and are jit/vmap/pjit friendly.

Pooled ("bag") lookups for multi-hot features sum masked rows; the fused
Pallas TPU kernels in ``repro.kernels`` implement the same contracts (their
``ref.py`` oracles call into this module).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .partitions import Partition, qr_partitions

__all__ = [
    "FullEmbedding",
    "HashEmbedding",
    "CompositionalEmbedding",
    "qr_embedding",
    "bag_pool",
    "masked_bag_sum",
    "table_rows",
    "is_quantized_table",
]

OPS = ("mult", "add", "concat")


def _uniform(key, shape, scale, dtype):
    return jax.random.uniform(key, shape, minval=-scale, maxval=scale, dtype=dtype)


def is_quantized_table(leaf) -> bool:
    """The serving stack's row-quantized table wire format (the single
    predicate every consumer — gathers, kernels, byte accounting — uses)."""
    return isinstance(leaf, dict) and "q" in leaf and "scale" in leaf


def table_rows(table, idx):
    """Gather rows from a dense *or* row-quantized table.

    The serving stack (``repro.serve.quantize``) replaces table leaves with
    ``{"q": int8 (rows, D), "scale": bf16 (rows, 1), "zp": int8 (rows, 1)}``
    pytrees; every ``apply`` path below funnels through here, so the same
    model code serves f32, bf16, and int8 tables.  Only the gathered rows
    are dequantized (``scale * (q - zp)``, f32) — the full-precision table
    never materialises, which is the serve-time memory win.
    """
    if is_quantized_table(table):
        q = jnp.take(table["q"], idx, axis=0).astype(jnp.float32)
        zp = jnp.take(table["zp"], idx, axis=0).astype(jnp.float32)
        scale = jnp.take(table["scale"], idx, axis=0).astype(jnp.float32)
        return (q - zp) * scale
    return jnp.take(table, idx, axis=0)


def _gather(gather, table, idx, key):
    """Route one sub-table lookup through ``gather`` when given.

    ``gather(table_leaf, row_ids, sub_key) -> rows`` replaces the local
    ``table_rows`` take — the hook the sharded serve path uses to fetch
    remotely-resident rows over the mesh (``dist.serve_placement``)
    through the *same* ``apply``/``bag_pool`` combine code as the local
    path, so the two are bit-identical by construction.
    """
    if gather is None:
        return table_rows(table, idx)
    return gather(table, idx, key)


@dataclasses.dataclass(frozen=True)
class FullEmbedding:
    """The baseline |S| x D table (paper Fig. 1 / 'Full')."""

    num_categories: int
    dim: int
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        scale = (1.0 / self.num_categories) ** 0.5
        return {"table": _uniform(key, (self.num_categories, self.dim), scale, self.param_dtype)}

    def apply(self, params, idx, gather=None):
        return _gather(gather, params["table"], idx, "table")

    @property
    def num_params(self) -> int:
        return self.num_categories * self.dim

    @property
    def out_dim(self) -> int:
        return self.dim


@dataclasses.dataclass(frozen=True)
class HashEmbedding:
    """Hashing trick (paper Alg. 1): ``x -> table[x mod m]`` — lossy baseline."""

    num_categories: int
    dim: int
    m: int = 1
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        scale = (1.0 / self.num_categories) ** 0.5
        return {"table": _uniform(key, (self.m, self.dim), scale, self.param_dtype)}

    def apply(self, params, idx, gather=None):
        return _gather(gather, params["table"], jnp.asarray(idx) % self.m,
                       "table")

    @property
    def num_params(self) -> int:
        return self.m * self.dim

    @property
    def out_dim(self) -> int:
        return self.dim


@dataclasses.dataclass(frozen=True)
class CompositionalEmbedding:
    """Operation-based compositional embedding over complementary partitions.

    One table per partition (rows = that partition's bucket count); per-index
    rows are combined with ``op`` in {mult, add, concat} (paper eq. 6).  With
    the QR pair this is exactly Algorithm 2.  ``dims`` gives each table's
    embedding width: for mult/add all must equal ``dim``; for concat they
    must sum to ``dim`` (defaults to an even split).
    """

    num_categories: int
    dim: int
    partitions: tuple[Partition, ...] = ()
    op: str = "mult"
    dims: tuple[int, ...] = ()
    param_dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        if self.op not in OPS:
            raise ValueError(f"op={self.op!r} not in {OPS}")
        if not self.partitions:
            raise ValueError("need at least one partition")
        k = len(self.partitions)
        if not self.dims:
            if self.op == "concat":
                base = self.dim // k
                dims = [base] * k
                dims[-1] += self.dim - base * k
            else:
                dims = [self.dim] * k
            object.__setattr__(self, "dims", tuple(dims))
        if self.op == "concat":
            if sum(self.dims) != self.dim:
                raise ValueError(f"concat dims {self.dims} must sum to {self.dim}")
        elif any(d != self.dim for d in self.dims):
            raise ValueError(f"{self.op} requires all dims == {self.dim}, got {self.dims}")

    def init(self, key):
        # Matches the reference DLRM QR implementation: every table is drawn
        # uniform(-sqrt(1/|S|), sqrt(1/|S|)).  For `mult` the product of k
        # such rows has scale |S|^{-k/2}; we compensate so the *combined*
        # embedding matches the full table's scale (important for training
        # parity — confirmed by the Fig.4-style benchmark).
        keys = jax.random.split(key, len(self.partitions))
        scale = (1.0 / self.num_categories) ** 0.5
        if self.op == "mult":
            scale = scale ** (1.0 / len(self.partitions))
        return {
            f"table_{j}": _uniform(k, (p.num_buckets, d), scale, self.param_dtype)
            for j, (p, d, k) in enumerate(zip(self.partitions, self.dims, keys))
        }

    def partition_embeddings(self, params, idx, gather=None):
        """Per-partition rows (the 'feature generation' mode, paper §4)."""
        idx = jnp.asarray(idx)
        return [
            _gather(gather, params[f"table_{j}"], p.bucket(idx), f"table_{j}")
            for j, p in enumerate(self.partitions)
        ]

    def apply(self, params, idx, gather=None):
        zs = self.partition_embeddings(params, idx, gather=gather)
        if self.op == "concat":
            return jnp.concatenate(zs, axis=-1)
        if self.op == "add":
            return sum(zs[1:], zs[0])
        out = zs[0]
        for z in zs[1:]:
            out = out * z
        return out

    @property
    def num_params(self) -> int:
        return sum(p.num_buckets * d for p, d in zip(self.partitions, self.dims))

    @property
    def out_dim(self) -> int:
        return self.dim


def qr_embedding(
    num_categories: int,
    dim: int,
    num_collisions: int = 4,
    op: str = "mult",
    param_dtype: jnp.dtype = jnp.float32,
) -> CompositionalEmbedding:
    """Quotient–remainder trick (paper Alg. 2) with the paper's knob.

    ``num_collisions`` c enforces ~c categories per remainder bucket, i.e.
    remainder table of ``m = ceil(|S|/c)`` rows and quotient table of ``c``
    rows — an ~c× parameter reduction (paper §5.3 "4 hash collisions").
    """
    m = max(1, -(-num_categories // max(1, num_collisions)))
    return CompositionalEmbedding(
        num_categories=num_categories,
        dim=dim,
        partitions=tuple(qr_partitions(num_categories, m)),
        op=op,
        param_dtype=param_dtype,
    )


def bag_pool(module, params, idx, mask=None, gather=None):
    """Sum-pooled multi-hot lookup: ``sum_l emb(idx[..., l]) * mask[..., l]``.

    ``idx``: int array ``(..., L)``; ``mask``: optional ``(..., L)`` (1 keeps
    the row).  Returns ``(..., dim)``.  This is the contract the fused
    Pallas kernel (``kernels.serve_path``) implements.  ``gather`` substitutes
    the row fetch (see ``_gather``) — the sharded serve path's hook.
    """
    emb = module.apply(params, idx, gather=gather)  # (..., L, D)
    # pool in f32, round once (accumulation-audit convention): a bf16
    # running sum would round every one of the L adds
    return masked_bag_sum(emb, mask).astype(emb.dtype)


def masked_bag_sum(rows, mask=None):
    """``sum_l rows[..., l, :] * mask[..., l]`` in f32, in a fixed order.

    Every XLA pooling path (``bag_pool``, the engine's cache and sharded
    programs, the kernel oracles) sums through here.  A ``reduce`` leaves
    the order of the L adds to the compiler, which picks it per fusion, so
    two programs pooling the same rows could disagree in the last bit.
    Halving the bag explicitly — slot ``l`` plus slot ``l + P/2`` of the
    bag zero-padded to a power of two ``P`` — fixes one order for all of
    them in ``log2 P`` vector adds (L unrolled adds cost 42% at L=64 on
    v5e, see PERF.md).
    """
    x = rows.astype(jnp.float32)
    if mask is not None:
        x = x * mask[..., None].astype(jnp.float32)
    l = x.shape[-2]
    if l == 0:
        return jnp.zeros(x.shape[:-2] + x.shape[-1:], jnp.float32)
    p = 1 << (l - 1).bit_length()
    if p != l:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, p - l), (0, 0)])
    while x.shape[-2] > 1:
        h = x.shape[-2] // 2
        x = x[..., :h, :] + x[..., h:, :]
    return x[..., 0, :]
