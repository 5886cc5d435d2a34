"""Kernel microbenchmarks: wall-time per call (CPU interpret / jnp ref) plus
the derived HBM-traffic model that matters on the TPU target.

Wall times on this CPU container do NOT reflect TPU performance; the derived
column reports the analytic bytes-moved model (the quantity the fused
kernels improve): unfused QR bag = 3·L·D reads/writes per pooled row vs
fused = 2·L·D reads + D writes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.monotonic()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / iters * 1e6


def rows():
    from repro.kernels import ops, ref
    out = []
    m, q, d = 2048, 16, 128
    wr = jax.random.normal(jax.random.PRNGKey(0), (m, d), jnp.float32)
    wq = jax.random.normal(jax.random.PRNGKey(1), (q, d), jnp.float32)
    n = 512
    idx = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, m * q)

    ref_fn = jax.jit(lambda i: ref.qr_gather_ref(i % m, i // m, wr, wq))
    us = _time(ref_fn, idx)
    bytes_unfused = n * d * 4 * 3  # two gathered rows written + read + result
    out.append(("kernel/qr_gather/ref_jnp", round(us, 1),
                f"hbm_bytes_unfused={bytes_unfused}"))
    us = _time(lambda i: ops.qr_lookup(i, wr, wq), idx)
    bytes_fused = n * d * 4 * 2 + n * d * 4  # reads + single write
    out.append(("kernel/qr_gather/pallas_interpret", round(us, 1),
                f"hbm_bytes_fused={bytes_fused}"))

    b, l = 32, 8
    idx2 = jax.random.randint(jax.random.PRNGKey(3), (b, l), 0, m * q)
    mask = jnp.ones((b, l), jnp.float32)
    ref_bag = jax.jit(lambda i: ref.qr_embedding_bag_ref(i % m, i // m, mask, wr, wq))
    us = _time(ref_bag, idx2)
    out.append(("kernel/qr_bag/ref_jnp", round(us, 1),
                f"hbm_bytes_unfused={b * l * d * 4 * 3 + b * d * 4}"))
    us = _time(lambda i: ops.serve_bag_pool(i, mask, wr, wq), idx2)
    out.append(("kernel/qr_bag/pallas_interpret", round(us, 1),
                f"hbm_bytes_fused={b * l * d * 4 * 2 + b * d * 4}"))

    x = jax.random.normal(jax.random.PRNGKey(4), (256, 27, 16), jnp.float32)
    us = _time(jax.jit(ref.dot_interaction_ref), x)
    out.append(("kernel/dot_interact/ref_jnp", round(us, 1),
                "flops=%d" % (2 * 256 * 27 * 27 * 16)))
    us = _time(lambda x: ops.dlrm_interact(x), x)
    out.append(("kernel/dot_interact/pallas_interpret", round(us, 1),
                "vmem_tile=(8,27,16)"))
    return out
