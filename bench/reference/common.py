"""Parts the two reference models share: QR tables, row-wise quantisation,
pooled lookups, MLPs, the loss and the optimizers.

Everything is plain ``jax.numpy``.  ``dtype`` is the compute type:
``float32`` for the reference, ``bfloat16`` for the control that runs one
precision below what the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def qr_rows(size: int, collisions: int) -> tuple[int, int]:
    """Rows of the remainder and quotient tables of one QR feature (paper
    Alg. 2): ``m = ceil(S / c)`` remainder rows, ``ceil(S / m)`` quotient
    rows."""
    if size <= 1:
        raise ValueError("a feature of one category has no QR tables")
    m = max(1, -(-size // max(1, collisions)))
    return m, -(-size // m)


def qr_ids(model: dict, f: int, ids):
    """Remainder and quotient rows ``(id % m, id // m)`` of feature
    ``f``'s ids."""
    m, _ = qr_rows(model["table_sizes"][f], model["num_collisions"])
    return ids % m, ids // m


def table_shapes(model: dict) -> list[tuple[tuple[int, int], tuple[int, int]]]:
    d = model["emb_dim"]
    return [((m, d), (q, d)) for m, q in
            (qr_rows(s, model["num_collisions"]) for s in model["table_sizes"])]


def key_from_seed(seed: int):
    """A PRNG key for any non-negative whole seed (also above 2**32).  The
    ``rbg`` generator: the same draws for the same seed on one device, and
    its program lowers in well under a second where threefry's took 8 s
    for the 540 MB of tables."""
    seed = int(seed) % (1 << 63)
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 32)


def init_tables(key, model: dict) -> list[dict]:
    """QR tables drawn uniform(-s, s) with ``s = S**-1/4``, so the product
    of the two rows has the full table's scale ``S**-1/2``."""
    out = []
    for f, (s0, s1) in enumerate(table_shapes(model)):
        k0, k1 = jax.random.split(jax.random.fold_in(key, f))
        s = model["table_sizes"][f] ** -0.25
        out.append({"table_0": jax.random.uniform(k0, s0, jnp.float32, -s, s),
                    "table_1": jax.random.uniform(k1, s1, jnp.float32, -s, s)})
    return out


def init_mlp(key, dims) -> list[dict]:
    keys = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (i, o), jnp.float32) * (2.0 / i) ** 0.5,
             "b": jnp.zeros((o,), jnp.float32)}
            for k, i, o in zip(keys, dims[:-1], dims[1:])]


# ---------------------------------------------------------------- quantise

def quantize_rows(w, bits: int = 8) -> dict:
    """Row-wise affine code ``w ~ scale * (q - zp)``: the configuration's
    int8 format (bf16 scale, integer zero-point, grid of ``2*QMAX - 2``
    steps with the row range widened to hold 0); ``bits=4`` is the same
    code on a 4-bit grid (the control)."""
    qmax = (1 << (bits - 1)) - 1
    steps = 2 * qmax - 2
    lo = jnp.minimum(w.min(axis=1, keepdims=True), 0.0)
    hi = jnp.maximum(w.max(axis=1, keepdims=True), 0.0)
    scale = jnp.maximum((hi - lo) / steps, jnp.finfo(jnp.float32).tiny)
    scale = scale.astype(jnp.bfloat16).astype(jnp.float32)
    zp = jnp.round(-(qmax - 1) - lo / scale)
    q = jnp.clip(jnp.round(w / scale + zp), -qmax, qmax)
    return {"q": q.astype(jnp.int8), "scale": scale, "zp": zp.astype(jnp.int8)}


def dequant(t: dict, rows, dtype):
    q = jnp.take(t["q"], rows, axis=0).astype(jnp.float32)
    zp = jnp.take(t["zp"], rows, axis=0).astype(jnp.float32)
    sc = jnp.take(t["scale"], rows, axis=0)
    return ((q - zp) * sc).astype(dtype)


def feature_ids(idx, f: int):
    """Feature ``f``'s ids in a batch's ``sparse``: ``(B,)`` of ``(B, F)``,
    ``(B, L)`` of ``(B, F, L)`` bags, or the ``f``-th array ``(B, L_f)``
    of the list form."""
    return idx[f] if isinstance(idx, (list, tuple)) else idx[:, f]


def pool(e, idx, mask, f: int, dtype):
    """Feature ``f``'s rows ``e`` pooled: one id's row ``(B, D)`` as it is;
    a bag's ``(B, L, D)`` summed over its slots, those under a zero
    ``mask`` adding nothing (``(B, F, L)`` bags) or every slot live (the
    list form)."""
    if isinstance(idx, (list, tuple)):
        return jnp.sum(e, axis=1)
    if idx.ndim == 3:
        return jnp.sum(e * mask[:, f, :, None].astype(dtype), axis=1)
    return e


def lookup(tables: list[dict], idx, model: dict, dtype, mask=None):
    """Pooled QR rows ``(B, F, D)``.  ``idx`` is ``(B, F)`` (one id per
    feature), ``(B, F, L)`` with ``mask`` (bags padded to the widest;
    masked slots add nothing), or the list form: ``F`` arrays ``(B,
    L_f)``, bags of fixed widths with every slot live.  Quantised tables
    (dicts with ``q``) are dequantised row by row; float tables are read
    as they are."""
    feats = []
    for f, t in enumerate(tables):
        r, q = qr_ids(model, f, feature_ids(idx, f))
        if isinstance(t["table_0"], dict):
            e = dequant(t["table_0"], r, dtype) * dequant(t["table_1"], q, dtype)
        else:
            e = (jnp.take(t["table_0"].astype(dtype), r, axis=0)
                 * jnp.take(t["table_1"].astype(dtype), q, axis=0))
        feats.append(pool(e, idx, mask, f, dtype))
    return jnp.stack(feats, axis=1)


def mlp_flops(dims) -> int:
    """Multiply-adds (as 2 FLOPs) of one example through an MLP ``dims``."""
    return sum(2 * i * o for i, o in zip(dims[:-1], dims[1:]))


def mlp_param_count(dims) -> int:
    return sum(i * o + o for i, o in zip(dims[:-1], dims[1:]))


def mlp(layers, x, final_linear: bool = False):
    for i, l in enumerate(layers):
        x = x @ l["w"].astype(x.dtype) + l["b"].astype(x.dtype)
        if not (final_linear and i == len(layers) - 1):
            x = jnp.maximum(x, 0)
    return x


def bce_with_logits(logits, labels):
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


# ---------------------------------------------------------------- optimizers

def rowwise(opt: dict) -> bool:
    """Whether the configuration's optimizer keeps one accumulator per
    table row (``"tables": "rowwise"``, Adagrad only) rather than one per
    element.  Any other ``tables`` raises."""
    mode = opt.get("tables")
    if mode not in (None, "rowwise") or (mode and opt["name"] != "adagrad"):
        raise ValueError(f"tables {mode!r} under optimizer {opt['name']!r}: "
                         "only Adagrad has a row-wise form")
    return mode == "rowwise"


def table_leaves(params) -> list[bool]:
    """For each leaf of ``params``, in leaf order, whether it is a QR
    table (a leaf under ``"tables"``)."""
    return [getattr(path[0], "key", None) == "tables"
            for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]]


def opt_init(opt: dict, params, dtype):
    names = {"adagrad": ("acc",), "amsgrad": ("m", "v", "vmax")}[opt["name"]]
    rows = rowwise(opt)
    return [{n: jnp.zeros((p.shape[0], 1) if rows and tab else p.shape, dtype)
             for n in names}
            for p, tab in zip(jax.tree.leaves(params), table_leaves(params))]


def opt_update(opt: dict, grads, state, params, step: int):
    """One step of the configuration's optimizer: Adagrad (Duchi et al.
    2011), its tables row-wise where the configuration says so (one
    accumulator a row, ``acc += mean(g**2)`` over the row), or AMSGrad
    (Reddi et al. 2018) with Adam's bias correction."""
    gl, treedef = jax.tree.flatten(grads)
    pl = jax.tree.leaves(params)
    rows = [rowwise(opt) and tab for tab in table_leaves(params)]
    lr = opt["lr"]
    new_p, new_s = [], []
    for g, s, p, row in zip(gl, state, pl, rows):
        if opt["name"] == "adagrad":
            sq = jnp.mean(g * g, axis=1, keepdims=True) if row else g * g
            acc = s["acc"] + sq
            new_p.append(p - lr * g / (jnp.sqrt(acc) + opt["eps"]))
            new_s.append({"acc": acc})
        else:
            b1, b2, t = opt["b1"], opt["b2"], step + 1
            m = b1 * s["m"] + (1 - b1) * g
            v = b2 * s["v"] + (1 - b2) * g * g
            vmax = jnp.maximum(s["vmax"], v)
            mhat = m / (1 - b1 ** t)
            vhat = vmax / (1 - b2 ** t)
            new_p.append(p - lr * mhat / (jnp.sqrt(vhat) + opt["eps"]))
            new_s.append({"m": m, "v": v, "vmax": vmax})
    return jax.tree.unflatten(treedef, new_p), new_s


def int_exchange(grads, err, bits: int):
    """The mean over ``n`` replicas of their gradients (each leaf ``(n,
    *shape)``) as a data-parallel exchange in a ``bits``-wide integer code
    gives it: each replica's gradient plus the error it carries is coded
    on one scale all share (``max |v| / qmax``, rounded to nearest), the
    codes are summed exactly, and their mean is coded again on a scale of
    its own.  Error feedback at both levels: each replica carries what
    its first code lost, and replica ``r`` also carries ``n`` times what
    the second code lost on its block ``r`` of the leaf's rows (``n``
    blocks of ``ceil(rows / n)``).  Returns the mean, replicated, and the
    new errors."""
    qmax = 2 ** (bits - 1) - 1
    tiny = jnp.finfo(jnp.float32).tiny

    def leaf(g, e):
        n = g.shape[0]
        v = g.astype(jnp.float32) + e
        scale = jnp.maximum(jnp.max(jnp.abs(v)) / qmax, tiny)
        q = jnp.clip(jnp.round(v / scale), -qmax, qmax)
        y = jnp.sum(q, axis=0) * (scale / n)
        scale2 = jnp.maximum(jnp.max(jnp.abs(y)) / qmax, tiny)
        out = jnp.clip(jnp.round(y / scale2), -qmax, qmax) * scale2
        rows = y.shape[0] if y.ndim else 1
        block = jnp.arange(rows) // -(-rows // n)
        own = (block[None, :] == jnp.arange(n)[:, None]).astype(jnp.float32)
        own = own.reshape((n,) + y.shape[:1] + (1,) * (y.ndim - 1))
        charged = q * scale - n * own * (y - out)
        return out, v - charged

    pairs = [leaf(g, e) for g, e in zip(jax.tree.leaves(grads), err)]
    treedef = jax.tree.structure(grads)
    return (jax.tree.unflatten(treedef, [o for o, _ in pairs]),
            [e for _, e in pairs])


def first_grad_norms(opt: dict, state, params) -> np.ndarray:
    """Per-leaf norm of the first gradient, read back from an optimizer
    state after exactly one step from zero state: Adagrad's accumulator is
    the mean of ``g**2`` over the elements each entry stands for (one, or
    a table row's ``D`` where it is row-wise: ``params`` give the leaves'
    sizes), AMSGrad's first moment is ``(1 - b1) g``."""
    if opt["name"] == "adagrad":
        return np.asarray([
            float(jnp.sqrt(jnp.sum(s["acc"].astype(jnp.float32))
                           * (p.size // s["acc"].size)))
            for s, p in zip(state, jax.tree.leaves(params))])
    return np.asarray([float(jnp.linalg.norm(s["m"].astype(jnp.float32)))
                       / (1 - opt["b1"]) for s in state])


def leaf_norms(tree) -> np.ndarray:
    return np.asarray([float(jnp.linalg.norm(x.astype(jnp.float32)))
                       for x in jax.tree.leaves(tree)])
