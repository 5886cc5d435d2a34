"""Deep & Cross Network (Wang et al. 2017) as the paper's section 5 runs
it: ``x0 = [dense, embeddings]``, six cross layers
``x_{l+1} = x0 (x_l . w_l) + b_l + x_l`` beside a deep MLP, and a linear
output over both.  Beside the model, the counts the metrics divide by."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import init_mlp, init_tables, mlp, mlp_flops, mlp_param_count


def _x0_dim(model: dict) -> int:
    return model["dense_dim"] + model["emb_dim"] * len(model["table_sizes"])


def init(key, model: dict) -> dict:
    kc, kd, ke, ko = jax.random.split(key, 4)
    d0 = _x0_dim(model)
    cross = [{"w": jax.random.normal(k, (d0,), jnp.float32) * d0 ** -0.5,
              "b": jnp.zeros((d0,), jnp.float32)}
             for k in jax.random.split(kc, model["cross_layers"])]
    return {"tables": init_tables(ke, model), "cross": cross,
            "deep": init_mlp(kd, [d0, *model["deep_mlp"]]),
            "out": init_mlp(ko, [d0 + model["deep_mlp"][-1], 1])}


def dense_forward(params, dense, feats, model: dict):
    dt = feats.dtype
    x0 = jnp.concatenate([dense.astype(dt), feats.reshape(feats.shape[0], -1)],
                         axis=-1)
    x = x0
    for l in params["cross"]:
        x = x0 * (x @ l["w"].astype(dt))[:, None] + l["b"].astype(dt) + x
    deep = mlp(params["deep"], x0)
    return mlp(params["out"], jnp.concatenate([x, deep], axis=-1),
               final_linear=True)[:, 0]


def forward_flops(model: dict) -> int:
    """One example's forward FLOPs: each cross layer's ``x . w``,
    ``x0 * s`` and ``+ b + x`` (5 per element of x0), the deep MLP and the
    output layer (pooling adds not counted)."""
    d0 = _x0_dim(model)
    return (model["cross_layers"] * 5 * d0 + mlp_flops([d0, *model["deep_mlp"]])
            + mlp_flops([d0 + model["deep_mlp"][-1], 1]))


def dense_param_count(model: dict) -> int:
    d0 = _x0_dim(model)
    return (2 * d0 * model["cross_layers"]
            + mlp_param_count([d0, *model["deep_mlp"]])
            + mlp_param_count([d0 + model["deep_mlp"][-1], 1]))
