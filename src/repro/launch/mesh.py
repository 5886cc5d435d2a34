"""Mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state (smoke tests must keep seeing 1 CPU device; only
``dryrun.py`` forces 512 host devices via XLA_FLAGS before any jax import).

Every mesh in the repo comes from ``make_mesh``: its axes are ``Auto``, so
``with_sharding_constraint`` and the rule-table placements keep working
(``jax.make_mesh`` defaults to ``Explicit`` axes since jax 0.8).

Production target: TPU v5e pods, 256 chips each.
  single pod : (data=16, model=16)
  multi-pod  : (pod=2, data=16, model=16)  — 512 chips
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests with forced host devices)."""
    return make_mesh((data, model), ("data", "model"))
