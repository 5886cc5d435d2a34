"""DLRM pairwise dot-interaction kernel (Pallas TPU).

DLRM concatenates the bottom-MLP output with all sparse embeddings into
``X ∈ (B, F, D)`` and feeds the strictly-lower-triangular entries of
``X·Xᵀ`` to the top MLP.  Per batch block this is a small MXU matmul
(``F×D @ D×F``); the kernel writes the ``(F, F)`` scores with f32
accumulation and the wrapper takes the triangle with static indices, a
gather XLA fuses into the next op (the TPU refuses an in-kernel
``jnp.take``).

Blocking: grid over batch; each step owns a ``(Bb, F, D)`` VMEM tile.  For
Criteo-scale DLRM (F=27, D=16..64) a whole batch block is a few KB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["dot_interaction"]


def _kernel(x_ref, out_ref):
    x = x_ref[...]  # (Bb, F, D)
    out_ref[...] = jax.lax.dot_general(
        x, x,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (Bb, F, F)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def dot_interaction(x, *, block_b: int = 8, interpret: bool = False):
    """Packed strictly-lower-triangle of batched ``X·Xᵀ``.

    Args: x: ``(B, F, D)``.  Returns: ``(B, F*(F-1)//2)``.
    ``B`` must be divisible by ``block_b`` (ops.py pads).
    """
    b, f, d = x.shape
    scores = pl.pallas_call(
        _kernel,
        grid=(b // block_b,),
        in_specs=[pl.BlockSpec((block_b, f, d), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((block_b, f, f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, f, f), jnp.float32),
        interpret=interpret,
    )(x)
    tri_i, tri_j = np.tril_indices(f, k=-1)
    return scores[:, tri_i, tri_j].astype(x.dtype)
