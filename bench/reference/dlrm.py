"""DLRM (Naumov et al. 2019) as the paper's section 5 runs it: bottom MLP
over the dense features, pairwise dot products of the bottom output and
the 26 pooled embeddings, top MLP on ``[bottom, dots]``.  Beside the
model, the counts the metrics divide by."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import init_mlp, init_tables, mlp, mlp_flops, mlp_param_count


def _mlps(model: dict) -> tuple[list, list]:
    d, f = model["emb_dim"], len(model["table_sizes"]) + 1
    return ([model["dense_dim"], *model["bottom_mlp"], d],
            [f * (f - 1) // 2 + d, *model["top_mlp"], 1])


def init(key, model: dict) -> dict:
    kb, kt, ke = jax.random.split(key, 3)
    bottom, top = _mlps(model)
    return {"bottom": init_mlp(kb, bottom), "top": init_mlp(kt, top),
            "tables": init_tables(ke, model)}


def dense_forward(params, dense, feats, model: dict):
    """Logits ``(B,)`` from dense inputs ``(B, 13)`` and pooled features
    ``(B, 26, D)``, in the dtype of ``feats``.  The dots are the strict
    lower triangle of ``X Xᵀ``, a matmul like the MLPs' (so each runs at
    the matmul precision in force)."""
    z = mlp(params["bottom"], dense.astype(feats.dtype))
    x = jnp.concatenate([z[:, None, :], feats], axis=1)
    i, j = np.tril_indices(x.shape[1], k=-1)
    dots = jnp.einsum("bfd,bgd->bfg", x, x)[:, i, j]
    return mlp(params["top"], jnp.concatenate([z, dots], axis=-1),
               final_linear=True)[:, 0]


def forward_flops(model: dict) -> int:
    """One example's forward FLOPs: the two MLPs and the ``n(n-1)/2``
    pairwise dots of the ``n = F + 1`` vectors (pooling adds not counted)."""
    n = len(model["table_sizes"]) + 1
    return (sum(mlp_flops(dims) for dims in _mlps(model))
            + n * (n - 1) // 2 * 2 * model["emb_dim"])


def dense_param_count(model: dict) -> int:
    return sum(mlp_param_count(dims) for dims in _mlps(model))
