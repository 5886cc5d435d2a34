"""DLRM (Naumov et al. 2019) as the paper's section 5 runs it: bottom MLP
over the dense features, pairwise dot products of the bottom output and
the 26 pooled embeddings, top MLP on ``[bottom, dots]``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import init_mlp, init_tables, mlp


def init(key, model: dict) -> dict:
    kb, kt, ke = jax.random.split(key, 3)
    d, f = model["emb_dim"], len(model["table_sizes"]) + 1
    return {"bottom": init_mlp(kb, [model["dense_dim"], *model["bottom_mlp"], d]),
            "top": init_mlp(kt, [f * (f - 1) // 2 + d, *model["top_mlp"], 1]),
            "tables": init_tables(ke, model)}


def dense_forward(params, dense, feats, model: dict):
    """Logits ``(B,)`` from dense inputs ``(B, 13)`` and pooled features
    ``(B, 26, D)``, in the dtype of ``feats``."""
    z = mlp(params["bottom"], dense.astype(feats.dtype))
    x = jnp.concatenate([z[:, None, :], feats], axis=1)
    i, j = np.tril_indices(x.shape[1], k=-1)
    dots = jnp.sum(x[:, i, :] * x[:, j, :], axis=-1)
    return mlp(params["top"], jnp.concatenate([z, dots], axis=-1),
               final_linear=True)[:, 0]
