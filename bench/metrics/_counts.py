"""Operations and minimum bytes, counted from the configuration's shapes
and the ids a batch or wave really holds.  Nothing here depends on how
the program implements a layer, so a count is the same for every PR.
What differs by model family (its FLOPs and dense parameters) lives in
the family's reference module, ``bench/reference/<family>.py``."""

from __future__ import annotations

from typing import Optional

import numpy as np

from bench.reference.api import family
from bench.reference.common import qr_rows, rowwise, table_shapes


def forward_flops(model: dict) -> int:
    """Multiply-adds (as 2 FLOPs) of one example's forward pass, as the
    model's family counts them (``<family>.forward_flops``)."""
    return family(model).forward_flops(model)


def dense_param_count(model: dict) -> int:
    return family(model).dense_param_count(model)


def touched_rows(model: dict, feature: int, ids: np.ndarray,
                 mask: Optional[np.ndarray] = None) -> int:
    """Distinct rows of feature ``feature``'s two QR tables that ``ids``
    read: remainder rows ``id % m`` plus quotient rows ``id // m``.  With
    a bag ``mask`` of ``ids``' shape, only slots whose mask is non-zero
    count."""
    m, _ = qr_rows(model["table_sizes"][feature], model["num_collisions"])
    ids = np.asarray(ids, np.int64)
    ids = ids.ravel() if mask is None else ids[np.asarray(mask) != 0]
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


def train_step_min_bytes(model: dict, train: dict, sparse,
                         mask: Optional[np.ndarray] = None) -> int:
    """Least bytes one training step must move: each table row the batch
    touches and every dense parameter is read and written, with its
    optimizer state, and its gradient is written and read, all f32.
    ``train`` is the configuration's: its optimizer's state arrays, one
    value an element, or one a table row where its tables are row-wise.
    Rows the batch does not touch need not move, so a sparse update can
    reach this and a dense one cannot.  ``sparse`` is ``(B, F)``, ``(B,
    F, L)`` bags with their ``mask`` (padded slots touch nothing), or the
    list form, ``F`` arrays ``(B, L_f)`` with every slot live."""
    per = 4 * (2 + 2 * OPT_STATE_ARRAYS[train["name"]] + 2)
    if isinstance(sparse, (list, tuple)):
        bags = [(np.asarray(ids), None) for ids in sparse]
    else:
        sparse = np.asarray(sparse)
        mask = None if mask is None else np.asarray(mask)
        bags = [(sparse[:, f], None if mask is None else mask[:, f])
                for f in range(sparse.shape[1])]
    rows = sum(touched_rows(model, f, ids, m) for f, (ids, m) in enumerate(bags))
    d = model["emb_dim"]
    table = rows * (4 * 4 * d + 4 * 2) if rowwise(train) else rows * d * per
    return table + per * dense_param_count(model)


def grad_elements(model: dict) -> int:
    """Elements of the whole gradient: every QR table and dense parameter."""
    return (sum(r0 * d0 + r1 * d1 for (r0, d0), (r1, d1) in table_shapes(model))
            + dense_param_count(model))


def error_state_min_bytes(model: dict) -> int:
    """Least bytes a step of an exchange with error feedback moves for it:
    each replica's f32 residual of every gradient element, read and
    written (a residual outlives the step that made it, so rows the batch
    does not touch carry one too)."""
    return 2 * 4 * grad_elements(model)


WIRE_BYTES = {"int8": 1}


def exchange_min_bytes(model: dict, compress: str, chips: int) -> int:
    """Least bytes one chip must send in a data-parallel step whose
    gradients are mean-all-reduced over ``chips``, every leaf in the
    uniform mode ``compress``: ``2(n-1)/n`` of the gradient payload, 1 B
    an element in int8; scales left out."""
    return 2 * (chips - 1) * grad_elements(model) * WIRE_BYTES[compress] // chips
