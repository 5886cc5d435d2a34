"""Operations and minimum bytes, counted from the configuration's shapes
and the ids a batch or wave really holds.  Nothing here depends on how
the program implements a layer, so a count is the same for every PR."""

from __future__ import annotations

import numpy as np


def _mlp_flops(dims) -> int:
    return sum(2 * i * o for i, o in zip(dims[:-1], dims[1:]))


def forward_flops(model: dict) -> int:
    """Multiply-adds (as 2 FLOPs) of one example's forward pass through
    the MLPs and the interaction (DLRM: the F(F-1)/2 pairwise dots) or the
    cross layers (DCN: ``x . w``, ``x0 * s``, ``+ b + x``); the pooling
    adds of the embeddings are not counted."""
    d, f = model["emb_dim"], len(model["table_sizes"])
    if model["family"] == "dlrm":
        n = f + 1
        return (_mlp_flops([model["dense_dim"], *model["bottom_mlp"], d])
                + n * (n - 1) // 2 * 2 * d
                + _mlp_flops([n * (n - 1) // 2 + d, *model["top_mlp"], 1]))
    d0 = model["dense_dim"] + d * f
    return (model["cross_layers"] * 5 * d0
            + _mlp_flops([d0, *model["deep_mlp"]])
            + _mlp_flops([d0 + model["deep_mlp"][-1], 1]))


def _qr_m(size: int, collisions: int) -> int:
    return max(1, -(-size // max(1, collisions)))


def touched_rows(model: dict, feature: int, ids: np.ndarray) -> int:
    """Distinct rows of feature ``feature``'s two QR tables that ``ids``
    read: remainder rows ``id % m`` plus quotient rows ``id // m``."""
    m = _qr_m(model["table_sizes"][feature], model["num_collisions"])
    ids = np.asarray(ids, np.int64).ravel()
    return len(np.unique(ids % m)) + len(np.unique(ids // m))


def embed_min_bytes(model: dict, ids: np.ndarray, lens) -> int:
    """Least bytes one served wave's embed must move: every distinct int8
    sub-row it reads once (``D + 3`` bytes: codes, bf16 scale, int8 zero
    point) and its pooled f32 features written once.  ``ids`` is
    ``(requests, sum(lens))``, the bags flat in feature order."""
    d = model["emb_dim"]
    total, start = 0, 0
    for f, n in enumerate(lens):
        total += touched_rows(model, f, ids[:, start:start + n]) * (d + 3)
        start += n
    return total + ids.shape[0] * len(lens) * d * 4


OPT_STATE_ARRAYS = {"adagrad": 1, "amsgrad": 3}


def dense_param_count(model: dict) -> int:
    d, f = model["emb_dim"], len(model["table_sizes"])
    bias = lambda dims: sum(o for o in dims[1:])  # noqa: E731
    if model["family"] == "dlrm":
        n = f + 1
        dims = [[model["dense_dim"], *model["bottom_mlp"], d],
                [n * (n - 1) // 2 + d, *model["top_mlp"], 1]]
        return sum(_mlp_flops(x) // 2 + bias(x) for x in dims)
    d0 = model["dense_dim"] + d * f
    dims = [[d0, *model["deep_mlp"]], [d0 + model["deep_mlp"][-1], 1]]
    return (2 * d0 * model["cross_layers"]
            + sum(_mlp_flops(x) // 2 + bias(x) for x in dims))


def train_step_min_bytes(model: dict, optimizer: str, sparse: np.ndarray) -> int:
    """Least bytes one training step must move: each table row the batch
    touches and every dense parameter is read and written, with its
    optimizer state, and its gradient is written and read, all f32.  Rows
    the batch does not touch need not move, so a sparse update can reach
    this and a dense one cannot."""
    per = 4 * (2 + 2 * OPT_STATE_ARRAYS[optimizer] + 2)
    rows = sum(touched_rows(model, f, sparse[:, f])
               for f in range(sparse.shape[1]))
    return per * (rows * model["emb_dim"] + dense_param_count(model))
