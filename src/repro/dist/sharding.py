"""Path+shape sharding rule engine.

One place decides how every tensor in the system is laid out on a mesh:

* ``spec_for(path, shape, mesh)`` — parameter path (``"/"``-joined, see
  ``repro.optim.optimizers.leaf_paths``) + shape → ``PartitionSpec``.
  Rules are a small ordered table of ``(path regex, template)`` pairs;
  the first matching rule wins, then the template is *fitted* to the
  concrete shape: an axis group whose size does not divide a dim is moved
  to the first free dim it does divide, or dropped.  The engine therefore
  **never emits an invalid spec** — GSPMD would reject (or silently pad)
  an axis that does not divide its dim.

* ``tree_shardings(structs, mesh, overrides)`` — whole-pytree version,
  returning ``NamedSharding``s in tree order.

* ``constrain`` / ``constrain_batch`` — in-model activation pinning
  (``with_sharding_constraint``) that degrades to a no-op when there is
  no ambient mesh (plain jit / eager tests) or when the named axes are
  manual (inside ``shard_map``), so model code never has to branch on the
  execution context.

Mesh axis conventions (see ``repro.launch.mesh``): ``model`` is the
tensor-parallel axis; every other axis (``data``, and ``pod`` on
multi-pod meshes) is data-parallel.  The symbol ``"dp"`` in templates and
``constrain`` calls expands to the data-parallel axis group.

Rule table (first match wins; see README "Sharding rules"):

====================================  ==========================  =============
path pattern                          template                    example leaf
====================================  ==========================  =============
embed* / wte / tok_emb / table(s)     ("model", None)             embedding rows
lm_head / head / logits / unembed     ("model", "dp")             output head
moe / expert(s)                       ("model", "dp", None)       (E, D, F) stack
1-D / scalar leaves                   ()                          norm gains
default rank-N dense                  (None, …, "dp", "model")    mlp wi/wo
====================================  ==========================  =============
"""

from __future__ import annotations

import re
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "RULES", "INFERENCE_OVERRIDES", "spec_for", "tree_shardings",
    "fit_template", "batch_axes", "constrain", "constrain_batch",
    "set_batch_shard_axes", "model_divides", "scatter_dims",
    "placement_overrides", "placement_specs",
]


# ------------------------------------------------------------------ rule table


RULES: tuple[tuple[str, tuple], ...] = (
    # Embedding / hash tables: row-sharded over `model` — the paper's
    # memory-dominant tensors; each chip holds a slice of the rows.
    (r"(^|/)(embed\w*|wte|tok_emb|tables?)(/|$)|(^|/)table_\d+($|/)",
     ("model", None)),
    # Output head: 2-D ("model", data-group) — TP on d_model, FSDP on vocab.
    (r"(^|/)(lm_head|head|logits|unembed|out_head)(/|$)",
     ("model", "dp")),
    # Stacked expert weights (E, d_in, d_out): expert-parallel over `model`,
    # FSDP over the data group on d_in.
    (r"(^|/)(moe|experts?)(/|$)",
     ("model", "dp", None)),
)


def _default_template(rank: int) -> tuple:
    """Generic dense leaf: TP on the last dim, FSDP on the one before."""
    if rank < 2:
        return ()
    return (None,) * (rank - 2) + ("dp", "model")


# "Same rules, minus FSDP": at inference weights are read-only, so
# gathering them over the data group every step buys nothing — keep only
# the tensor-parallel placements.  Passed as ``overrides`` to
# ``tree_shardings`` / ``param_structs`` by the dry-run machinery.
NO_FSDP = "no_fsdp"
INFERENCE_OVERRIDES: tuple[tuple[str, object], ...] = ((r".*", NO_FSDP),)


def placement_overrides(placement) -> tuple[tuple[str, tuple], ...]:
    """Override rules for plan-aware *serving* placement.

    Each row-sharded sub-table of a ``dist.serve_placement.ServePlacement``
    gets a path-exact rule splitting its rows over the ``data`` axis; a
    trailing catch-all replicates everything else (serving weights are
    read-only — the same no-FSDP rationale as ``INFERENCE_OVERRIDES``,
    and the dense stage runs per-device on its batch slice with full
    weights).  Feed to ``spec_for``/``tree_shardings`` like any override
    table; first match wins, so the sharded-table rules lead.
    """
    rules = [(rf"^{re.escape(e.path)}($|/)", ("data", None))
             for e in placement.entries if e.strategy == "row_shard"]
    rules.append((r".*", ()))
    return tuple(rules)


def placement_specs(params, placement):
    """``PartitionSpec`` pytree for serve-time placement — the
    ``shard_map`` in_specs of the sharded wave program.  Row-sharded
    sub-table leaves (rows pre-padded to a multiple of N, so the fitter
    never relocates the axis) get ``P("data", None)``; every other leaf
    replicates."""
    from ..optim.optimizers import leaf_paths
    overrides = placement_overrides(placement)
    sizes = {"data": placement.n_devices}
    leaves, treedef = jax.tree.flatten(params)
    paths = leaf_paths(params)
    specs = [fit_template(_template_for(p, len(l.shape), overrides),
                          l.shape, sizes, batch=("data",))
             if getattr(l, "ndim", 0) > 1 else P()
             for p, l in zip(paths, leaves)]
    return jax.tree.unflatten(treedef, specs)


# ------------------------------------------------------ batch-axes module state

# What the symbol "dp" means for in-model `constrain` calls, and the size of
# the model axis for `model_divides`.  `lowerables` (configs/common.py) sets
# these from the target mesh before tracing; the defaults match a plain
# ("data", "model") mesh so direct model calls under `jax.set_mesh(mesh)`
# also work.
_BATCH_AXES: tuple[str, ...] = ("data",)
_MODEL_SIZE: int = 1


def set_batch_shard_axes(axes: Sequence[str], model_size: int = 1) -> None:
    """Configure the data-parallel axis group (and model size) used by
    ``constrain``/``constrain_batch``/``model_divides`` during tracing."""
    global _BATCH_AXES, _MODEL_SIZE
    _BATCH_AXES = tuple(axes) or ("data",)
    _MODEL_SIZE = max(int(model_size), 1)


def batch_axes(mesh) -> tuple[str, ...]:
    """The mesh's data-parallel axis group: every axis except ``model``."""
    return tuple(a for a in mesh.axis_names if a != "model")


def model_divides(n: int) -> bool:
    """True when ``n`` can be evenly sharded over the model axis."""
    return n % _MODEL_SIZE == 0


# ------------------------------------------------------------------ the engine


def _group_size(group: tuple[str, ...], sizes: dict[str, int]) -> int:
    return int(np.prod([sizes[a] for a in group], dtype=np.int64)) if group else 1


def fit_template(template: Sequence, shape: Sequence[int],
                 sizes: dict[str, int],
                 batch: tuple[str, ...] = ("data",)) -> P:
    """Fit a rule template to a concrete shape given mesh axis sizes.

    Template entries per leading dim: ``None``, ``"model"``, ``"dp"`` (the
    data-parallel group), an axis name, or a tuple of axis names.  Axes not
    present in ``sizes`` are dropped.  A group whose size does not divide
    its dim is relocated to the first free dim it does divide (left to
    right), else dropped — the returned spec is always valid for ``shape``.
    """
    rank = len(shape)
    if rank <= 1:
        return P()
    resolved: list[tuple[str, ...]] = []
    for ent in list(template)[:rank]:
        if ent is None:
            resolved.append(())
            continue
        group = batch if ent == "dp" else (tuple(ent) if isinstance(ent, (tuple, list))
                                           else (ent,))
        resolved.append(tuple(a for a in group if a in sizes))
    resolved += [()] * (rank - len(resolved))

    spec: list[tuple[str, ...]] = [()] * rank
    used: set[str] = set()
    homeless: list[tuple[str, ...]] = []
    for i, group in enumerate(resolved):
        group = tuple(a for a in group if a not in used)
        if not group:
            continue
        n = _group_size(group, sizes)
        if shape[i] > 0 and shape[i] % n == 0:
            spec[i] = group
            used.update(group)
        else:
            homeless.append(group)
    for group in homeless:
        group = tuple(a for a in group if a not in used)
        if not group:
            continue
        n = _group_size(group, sizes)
        for i in range(rank):
            if not spec[i] and shape[i] > 0 and shape[i] % n == 0:
                spec[i] = group
                used.update(group)
                break

    def ent(g: tuple[str, ...]):
        if not g:
            return None
        return g[0] if len(g) == 1 else g

    return P(*[ent(g) for g in spec])


def _template_for(path: str, rank: int,
                  overrides: Optional[Sequence[tuple[str, object]]] = None):
    for pattern, template in tuple(overrides or ()) + RULES:
        if re.search(pattern, path):
            if template == NO_FSDP:
                base = _template_for(path, rank, overrides=None)
                return tuple(None if e == "dp" else e for e in base)
            return template
    return _default_template(rank)


def spec_for(path: str, shape: Sequence[int], mesh,
             overrides: Optional[Sequence[tuple[str, object]]] = None) -> P:
    """PartitionSpec for one parameter leaf.  1-D/scalar leaves replicate;
    everything else goes through the rule table + shape fitting."""
    if len(shape) <= 1:
        return P()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return fit_template(_template_for(path, len(shape), overrides), shape,
                        sizes, batch=batch_axes(mesh))


def scatter_dims(path: str, shape: Sequence[int], mesh,
                 axis: str = "data") -> tuple[int, ...]:
    """Candidate reduce-scatter dims for one leaf, best first.

    The dim the rule engine (``spec_for``) assigns to ``axis`` leads — the
    gradient shard then has the same layout the FSDP param shard would —
    followed by every other dim the axis size divides (left to right).
    Dims the axis size does not divide are never returned, so the caller
    can reduce-scatter any returned dim without padding.
    """
    shape = tuple(shape)
    n = dict(mesh.shape).get(axis, 1)
    spec = spec_for(path, shape, mesh)
    preferred = [i for i, ent in enumerate(spec)
                 if ent is not None
                 and axis in (ent if isinstance(ent, tuple) else (ent,))]
    order = preferred + [i for i in range(len(shape)) if i not in preferred]
    return tuple(i for i in order if shape[i] > 0 and shape[i] % n == 0)


def tree_shardings(structs, mesh, overrides=None):
    """``NamedSharding`` per leaf of ``structs`` (tree order preserved)."""
    from ..optim.optimizers import leaf_paths
    leaves, treedef = jax.tree.flatten(structs)
    paths = leaf_paths(structs)
    out = [NamedSharding(mesh, spec_for(p, l.shape, mesh, overrides))
           for p, l in zip(paths, leaves)]
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------- activation pinning


def _auto_axis_sizes() -> dict[str, int]:
    """Sizes of the ambient mesh's ``Auto`` axes (``jax.set_mesh``).  Empty
    outside a mesh; axes bound manually (``shard_map`` bodies) or typed
    ``Explicit`` are left out — specs on them would make
    ``with_sharding_constraint`` fail at lowering time."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return {}
    return {a: n for a, n, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types) if t == AxisType.Auto}


def constrain(x, *axes):
    """``with_sharding_constraint`` with one entry per leading dim.

    Entries: ``None``, ``"model"``, ``"dp"`` (expands to the configured
    data-parallel axis group), an axis name, or a tuple of names.  Missing
    trailing entries replicate.  Degrades to identity when there is no
    ambient mesh, inside ``shard_map`` (manual axes), or when a dim cannot
    divide the requested axis group — model code calls this unconditionally.
    """
    sizes = _auto_axis_sizes()
    if not sizes or not hasattr(x, "shape"):
        return x
    shape = x.shape
    spec: list = [None] * len(shape)
    nontrivial = False
    for i, ent in enumerate(axes[:len(shape)]):
        if ent is None:
            continue
        group = _BATCH_AXES if ent == "dp" else (tuple(ent) if isinstance(ent, (tuple, list))
                                                 else (ent,))
        group = tuple(a for a in group if a in sizes)
        if not group:
            continue
        n = _group_size(group, sizes)
        if shape[i] % n != 0 or shape[i] == 0:
            continue
        spec[i] = group[0] if len(group) == 1 else group
        nontrivial = True
    if not nontrivial:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_batch(x):
    """Pin dim 0 (the batch dim) to the data-parallel axis group.  No-op
    outside a mesh context and for scalars."""
    ndim = getattr(x, "ndim", 0)
    if not ndim:
        return x
    return constrain(x, "dp", *([None] * (ndim - 1)))
