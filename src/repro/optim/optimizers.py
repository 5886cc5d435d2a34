"""Optimizers from scratch (no optax in the container).

The paper trains with Adagrad and AMSGrad "with default hyperparameters"
(§5.2); production DLRM uses *row-wise* Adagrad on embedding tables (one
accumulator per row instead of per element — 1/D the optimizer memory for
tables, the same memory-trick family as the paper's).  All are provided,
plus Adam and Adafactor (factored second moment — what lets arctic-480b's
optimizer state fit HBM), global-norm clipping, and LR schedules.

Design: every optimizer is defined by *leaf-level* ``init_leaf(p)`` /
``update_leaf(g, s, p, step)`` functions; tree-level ``init``/``update``
flatten the param tree once and map over leaves.  That makes the
``partitioned`` combinator (different rules for different subtrees — e.g.
row-wise Adagrad on embedding tables, Adam elsewhere) a per-leaf dispatch
instead of a pytree surgery problem, and the optimizer state a flat list
that checkpoints/reshards like any other pytree.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = ["sgd", "adagrad", "rowwise_adagrad", "adam", "adafactor",
           "partitioned", "clip_by_global_norm", "cosine_schedule",
           "constant_schedule", "global_norm", "Optimizer", "leaf_paths",
           "state_structs"]


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init_leaf: Callable    # p -> leaf_state (dict of arrays)
    update_leaf: Callable  # (g, s, p, step) -> (new_p, new_s)

    def init(self, params):
        return [self.init_leaf(p) for p in jax.tree.leaves(params)]

    def update(self, grads, state, params, step):
        leaves_g, treedef = jax.tree.flatten(grads)
        leaves_p = jax.tree.leaves(params)
        out = [self.update_leaf(g, s, p, step)
               for g, s, p in zip(leaves_g, state, leaves_p)]
        new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
        return new_params, [o[1] for o in out]


def constant_schedule(lr: float):
    return lambda step: lr


def cosine_schedule(lr: float, warmup: int, total: int, floor: float = 0.1):
    def fn(step):
        warm = lr * jnp.minimum(1.0, (step + 1) / max(warmup, 1))
        t = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * t))
        return jnp.where(step < warmup, warm, lr * cos)
    return fn


def global_norm(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        grads), norm


def _sched(lr):
    return lr if callable(lr) else constant_schedule(lr)


def _step_p(p, u):
    return (p.astype(jnp.float32) + u).astype(p.dtype)


def sgd(lr, momentum: float = 0.0, weight_decay: float = 0.0):
    sched = _sched(lr)

    def init_leaf(p):
        return {"m": jnp.zeros(p.shape, jnp.float32)} if momentum else {}

    def update_leaf(g, s, p, step):
        g32 = g.astype(jnp.float32) + weight_decay * p.astype(jnp.float32)
        if momentum:
            m = momentum * s["m"] + g32
            return _step_p(p, -sched(step) * m), {"m": m}
        return _step_p(p, -sched(step) * g32), s

    return Optimizer(init_leaf, update_leaf)


def adagrad(lr=1e-2, eps: float = 1e-10):
    """Duchi et al. 2011 — the paper's default optimizer."""
    sched = _sched(lr)

    def init_leaf(p):
        return {"acc": jnp.zeros(p.shape, jnp.float32)}

    def update_leaf(g, s, p, step):
        g32 = g.astype(jnp.float32)
        acc = s["acc"] + jnp.square(g32)
        return _step_p(p, -sched(step) * g32 / (jnp.sqrt(acc) + eps)), {"acc": acc}

    return Optimizer(init_leaf, update_leaf)


def rowwise_adagrad(lr=1e-2, eps: float = 1e-10):
    """Adagrad with one accumulator per table row (production-DLRM trick).

    For a (rows, D) table the state is (rows, 1) — 1/D the optimizer
    memory.  Non-2D leaves fall back to element-wise Adagrad.
    """
    sched = _sched(lr)

    def init_leaf(p):
        shape = (p.shape[0], 1) if p.ndim == 2 else p.shape
        return {"acc": jnp.zeros(shape, jnp.float32)}

    def update_leaf(g, s, p, step):
        g32 = g.astype(jnp.float32)
        if g.ndim == 2:
            acc = s["acc"] + jnp.mean(jnp.square(g32), axis=1, keepdims=True)
        else:
            acc = s["acc"] + jnp.square(g32)
        return _step_p(p, -sched(step) * g32 / (jnp.sqrt(acc) + eps)), {"acc": acc}

    return Optimizer(init_leaf, update_leaf)


def adam(lr=1e-3, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
         amsgrad: bool = False, weight_decay: float = 0.0):
    """Adam / AMSGrad (Reddi et al. 2019) — the paper's second optimizer."""
    sched = _sched(lr)

    def init_leaf(p):
        # one buffer per entry: a jit that donates the state cannot take
        # the same buffer twice
        names = ("m", "v", "vmax") if amsgrad else ("m", "v")
        return {k: jnp.zeros(p.shape, jnp.float32) for k in names}

    def update_leaf(g, s, p, step):
        t = step + 1
        g32 = g.astype(jnp.float32)
        m = b1 * s["m"] + (1 - b1) * g32
        v = b2 * s["v"] + (1 - b2) * jnp.square(g32)
        ns = {"m": m, "v": v}
        if amsgrad:
            vmax = jnp.maximum(s["vmax"], v)
            ns["vmax"] = vmax
            vhat = vmax
        else:
            vhat = v
        mhat = m / (1 - b1 ** t)
        vhat = vhat / (1 - b2 ** t)
        u = -sched(step) * mhat / (jnp.sqrt(vhat) + eps)
        if weight_decay:
            u = u - sched(step) * weight_decay * p.astype(jnp.float32)
        return _step_p(p, u), ns

    return Optimizer(init_leaf, update_leaf)


def adafactor(lr=1e-2, eps: float = 1e-30, clip_threshold: float = 1.0,
              decay: float = 0.8):
    """Factored second moment: O(rows+cols) state for ≥2-D leaves."""
    sched = _sched(lr)

    def init_leaf(p):
        if p.ndim >= 2:
            return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}
        return {"v": jnp.zeros(p.shape, jnp.float32)}

    def update_leaf(g, s, p, step):
        t = step + 1
        beta = 1.0 - (t.astype(jnp.float32) if hasattr(t, "astype") else float(t)) ** (-decay)
        g32 = g.astype(jnp.float32)
        g2 = jnp.square(g32) + eps
        if g.ndim >= 2:
            vr = beta * s["vr"] + (1 - beta) * g2.mean(axis=-1)
            vc = beta * s["vc"] + (1 - beta) * g2.mean(axis=-2)
            ns = {"vr": vr, "vc": vc}
            denom = (vr[..., None] * vc[..., None, :]
                     / jnp.maximum(vr.mean(axis=-1, keepdims=True), eps)[..., None])
            u = g32 / jnp.sqrt(jnp.maximum(denom, eps))
        else:
            v = beta * s["v"] + (1 - beta) * g2
            ns = {"v": v}
            u = g32 / jnp.sqrt(jnp.maximum(v, eps))
        rms = jnp.sqrt(jnp.mean(jnp.square(u)) + 1e-12)
        u = u / jnp.maximum(1.0, rms / clip_threshold)
        return _step_p(p, -sched(step) * u), ns

    return Optimizer(init_leaf, update_leaf)


def leaf_paths(tree, is_leaf=None) -> list[str]:
    """'/'-joined string path per leaf, in ``jax.tree.leaves`` order.
    ``is_leaf`` matches the ``jax.tree`` parameter (e.g. to treat the
    serving stack's quantized-table dicts as single leaves)."""
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    def keystr(k):
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k)
    return ["/".join(keystr(k) for k in path) for path, _ in flat]


def state_structs(optimizer: Optimizer, params_like):
    """Optimizer-state ShapeDtypeStructs without materialising the state.

    One per-param entry, in ``jax.tree.leaves`` order.  This is what the
    FSDP planner consults to pick a scatter dim each state leaf can be
    sliced along (row-wise Adagrad's ``(rows, 1)`` accumulator admits dim
    0 only; Adafactor's factored stats admit none) — keeping "what shape
    is the state" knowledge here rather than in the train loop.
    """
    return jax.eval_shape(optimizer.init, params_like)


def partitioned(rules, default: Optimizer):
    """Per-leaf optimizer dispatch by path predicate.

    ``rules``: [(predicate(path) -> bool, Optimizer)]; first match wins,
    ``default`` otherwise.  E.g. row-wise Adagrad on ``.*table.*`` leaves
    (embedding tables), AMSGrad elsewhere — the paper's configuration.
    """
    def pick(path):
        for pred, opt in rules:
            if pred(path):
                return opt
        return default

    class _Partitioned(Optimizer):
        def __init__(self):
            super().__init__(init_leaf=None, update_leaf=None)

        def init(self, params):
            paths = leaf_paths(params)
            return [pick(path).init_leaf(p)
                    for path, p in zip(paths, jax.tree.leaves(params))]

        def update(self, grads, state, params, step):
            paths = leaf_paths(params)
            leaves_g, treedef = jax.tree.flatten(grads)
            leaves_p = jax.tree.leaves(params)
            out = [pick(path).update_leaf(g, s, p, step)
                   for path, g, s, p in zip(paths, leaves_g, state, leaves_p)]
            new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
            return new_params, [o[1] for o in out]

    return _Partitioned()
