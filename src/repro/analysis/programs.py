"""Registry of traceable exemplar programs for the layer-2 auditors.

Each entry builds ``(fn, args)`` pairs ready for ``jax.make_jaxpr`` (the
f32-accumulation audit) with *worst-case* low-precision operands: bf16
tables wherever the kernel accepts dense tables, int8 + meta on the
quantized paths.  If a kernel accumulates in its input dtype anywhere,
these programs — not a lucky f32 default — are what exposes it.

Programs trace only — nothing here runs to hardware.  The registry is
the extension point: a new kernel family registers its exemplar here and
is certified on every analyzer run from then on.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

__all__ = ["KernelProgram", "kernel_programs"]


@dataclasses.dataclass(frozen=True)
class KernelProgram:
    name: str
    build: Callable[[], tuple]   # () -> (fn, args tuple)
    notes: str = ""


def _bf16_qr_bag_kernel():
    import jax.numpy as jnp
    import numpy as np
    from ..kernels.serve_path import fused_serve_pool
    rng = np.random.default_rng(0)
    b, l, m, q, d = 4, 8, 16, 8, 32
    rem = jnp.asarray(rng.integers(0, m, (b, l)), jnp.int32)
    quo = jnp.asarray(rng.integers(0, q, (b, l)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (b, l)), jnp.float32)
    w_rem = jnp.asarray(rng.normal(size=(m, d)), jnp.bfloat16)
    w_quo = jnp.asarray(rng.normal(size=(q, d)), jnp.bfloat16)

    def fn(rem, quo, mask, w_rem, w_quo):
        return fused_serve_pool(rem, mask, w_rem, idx_b=quo, w_b=w_quo,
                                op="mult")
    return fn, (rem, quo, mask, w_rem, w_quo)


def _bf16_qr_gather_kernel():
    import jax.numpy as jnp
    import numpy as np
    from ..kernels.ops import qr_lookup
    rng = np.random.default_rng(1)
    n, m, q, d = 32, 16, 8, 32
    idx = jnp.asarray(rng.integers(0, m * q, (n,)), jnp.int32)
    w_rem = jnp.asarray(rng.normal(size=(m, d)), jnp.bfloat16)
    w_quo = jnp.asarray(rng.normal(size=(q, d)), jnp.bfloat16)

    def fn(idx, w_rem, w_quo):
        return qr_lookup(idx, w_rem, w_quo, op="add")
    return fn, (idx, w_rem, w_quo)


def _int8_qr_gather_kernel():
    import jax.numpy as jnp
    import numpy as np
    from ..kernels.ops import qr_lookup
    rng = np.random.default_rng(2)
    n, m, q, d = 32, 16, 8, 32

    def table(rows):
        return {"q": jnp.asarray(rng.integers(-127, 128, (rows, d)), jnp.int8),
                "scale": jnp.asarray(rng.uniform(0.01, 0.1, (rows, 1)),
                                     jnp.bfloat16),
                "zp": jnp.asarray(rng.integers(-8, 8, (rows, 1)), jnp.int8)}
    idx = jnp.asarray(rng.integers(0, m * q, (n,)), jnp.int32)

    def fn(idx, w_rem, w_quo):
        return qr_lookup(idx, w_rem, w_quo, op="mult")
    return fn, (idx, table(m), table(q))


def _bf16_fused_serve_kernel():
    import jax.numpy as jnp
    import numpy as np
    from ..kernels.serve_path import fused_serve_pool
    rng = np.random.default_rng(3)
    b, l, m, d, d_out = 4, 8, 16, 16, 32
    idx = jnp.asarray(rng.integers(0, m, (b, l)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (b, l)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(m, d)), jnp.bfloat16)
    proj = jnp.asarray(rng.normal(size=(d, d_out)), jnp.bfloat16)

    def fn(idx, mask, w, proj):
        return fused_serve_pool(idx, mask, w, proj=proj)
    return fn, (idx, mask, w, proj)


def _int8_fused_serve_kernel():
    import jax.numpy as jnp
    import numpy as np
    from ..kernels.serve_path import fused_serve_pool
    rng = np.random.default_rng(4)
    b, l, m, d = 4, 8, 16, 32
    idx_a = jnp.asarray(rng.integers(0, m, (b, l)), jnp.int32)
    idx_b = jnp.asarray(rng.integers(0, m, (b, l)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (b, l)), jnp.float32)
    w_a = jnp.asarray(rng.integers(-127, 128, (m, d)), jnp.int8)
    w_b = jnp.asarray(rng.integers(-127, 128, (m, d)), jnp.int8)
    meta = jnp.asarray(rng.uniform(0.01, 0.1, (m, 2)), jnp.float32)

    def fn(idx_a, mask, w_a, idx_b, w_b, meta):
        return fused_serve_pool(idx_a, mask, w_a, idx_b=idx_b, w_b=w_b,
                                meta_a=meta, meta_b=meta, op="mult")
    return fn, (idx_a, mask, w_a, idx_b, w_b, meta)


def _bf16_qr_bag_jnp():
    import jax.numpy as jnp
    import numpy as np
    from ..kernels.ops import serve_bag_pool
    rng = np.random.default_rng(5)
    b, l, m, q, d = 4, 8, 16, 8, 32
    idx = jnp.asarray(rng.integers(0, m * q, (b, l)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (b, l)), jnp.float32)
    w_rem = jnp.asarray(rng.normal(size=(m, d)), jnp.bfloat16)
    w_quo = jnp.asarray(rng.normal(size=(q, d)), jnp.bfloat16)

    def fn(idx, mask, w_rem, w_quo):
        return serve_bag_pool(idx, mask, w_rem, w_quo, op="concat",
                              use_kernel=False)
    return fn, (idx, mask, w_rem, w_quo)


def _bf16_bag_pool():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..core.compositional import bag_pool, qr_embedding
    rng = np.random.default_rng(6)
    size, d, b, l = 96, 32, 4, 8
    mod = qr_embedding(size, d, num_collisions=4, op="mult",
                       param_dtype=jnp.bfloat16)
    params = mod.init(jax.random.PRNGKey(0))
    idx = jnp.asarray(rng.integers(0, size, (b, l)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (b, l)), jnp.float32)

    def fn(params, idx, mask):
        return bag_pool(mod, params, idx, mask=mask)
    return fn, (params, idx, mask)


def _bf16_dot_interaction():
    import jax.numpy as jnp
    import numpy as np
    from ..kernels.dot_interaction import dot_interaction
    rng = np.random.default_rng(7)
    b, f, d = 8, 4, 16
    x = jnp.asarray(rng.normal(size=(b, f, d)), jnp.bfloat16)

    def fn(x):
        return dot_interaction(x)
    return fn, (x,)


def kernel_programs() -> list[KernelProgram]:
    """Every serve/train-kernel-reachable program the f32-accumulation
    audit certifies, with worst-case bf16/int8 operands."""
    return [
        KernelProgram("serve_path.fused_serve_pool[bf16 qr bag]",
                      _bf16_qr_bag_kernel,
                      "fused QR bag kernel, bf16 tables"),
        KernelProgram("ops.qr_lookup[bf16]", _bf16_qr_gather_kernel,
                      "QR gather as one-slot bags of the fused kernel, "
                      "bf16 tables"),
        KernelProgram("ops.qr_lookup[int8]", _int8_qr_gather_kernel,
                      "int8-dequant QR gather through the fused kernel"),
        KernelProgram("serve_path.fused_serve_pool[bf16+proj]",
                      _bf16_fused_serve_kernel,
                      "fused serve kernel, bf16 table + projection"),
        KernelProgram("serve_path.fused_serve_pool[int8 qr]",
                      _int8_fused_serve_kernel,
                      "fused serve kernel, quantized QR pair"),
        KernelProgram("ops.serve_bag_pool[bf16 jnp]", _bf16_qr_bag_jnp,
                      "jnp fallback bag path (concat op), bf16 tables"),
        KernelProgram("compositional.bag_pool[bf16 qr]", _bf16_bag_pool,
                      "model-side pooled lookup, bf16 QR module"),
        KernelProgram("dot_interaction.dot_interaction[bf16]",
                      _bf16_dot_interaction,
                      "DLRM pairwise-dot kernel, bf16 features"),
    ]
