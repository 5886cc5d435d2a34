"""Compile every Pallas kernel for a TPU v5e chip, without the chip.

The TPU compiler is installed with jax; it compiles for a described, not
attached, v5e and refuses what the chip would refuse (block tiling, VMEM),
which interpret mode cannot show.  Each case asserts the kernel survives as
a Mosaic ``tpu_custom_call``.  The topology is described inside a fixture,
never at import: only one process may load the TPU library, and every test
worker imports this file.
"""

import inspect

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dot_interaction import dot_interaction
from repro.kernels.serve_path import MAX_SLOTS, fused_serve_pool

# table rows not a multiple of the 8-row fetch block: edge blocks included
M, Q, N, B, L, F = 1003, 37, 100, 16, 4, 27
# 8x the slots one call takes: 3 prefetch arrays of this many slots would
# need 3 MiB of SMEM (v5e has 1 MiB), so this compiles only as 8 calls
BIG_B = 8 * MAX_SLOTS // L


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _qr_bag(idx, mask, wa, wb, ma=None, mb=None, proj=None):
    return fused_serve_pool(idx, mask, wa, idx_b=idx, w_b=wb, meta_a=ma,
                            meta_b=mb, proj=proj)


def _qr_gather(idx, wa, wb, ma=None, mb=None):
    """A plain QR lookup: one-slot bags with no mask (``ops.qr_lookup``)."""
    return fused_serve_pool(idx[:, None], None, wa, idx_b=idx[:, None],
                            w_b=wb, meta_a=ma, meta_b=mb)


def _cases(d):
    i32, f32, i8 = jnp.int32, jnp.float32, jnp.int8
    quant = [((M, d), i8), ((Q, d), i8), ((M, 2), f32), ((Q, 2), f32)]
    return {
        "fused_serve_pool": (
            _qr_bag, [((B, L), i32), ((B, L), f32)] + quant + [((d, d), f32)]),
        "fused_serve_pool_above_smem": (
            _qr_bag, [((BIG_B, L), i32), ((BIG_B, L), f32)] + quant),
        "qr_gather": (
            _qr_gather, [((N,), i32), ((M, d), jnp.bfloat16),
                         ((Q, d), jnp.bfloat16)]),
        "qr_gather_quant": (_qr_gather, [((N,), i32)] + quant),
        "qr_embedding_bag": (
            _qr_bag, [((B, L), i32), ((B, L), f32), ((M, d), f32),
                      ((Q, d), f32)]),
        "dot_interaction": (dot_interaction, [((256, F, d), f32)]),
    }


@pytest.mark.parametrize("d", [16, 128])
@pytest.mark.parametrize("kernel", ["fused_serve_pool",
                                    "fused_serve_pool_above_smem",
                                    "qr_gather", "qr_gather_quant",
                                    "qr_embedding_bag", "dot_interaction"])
def test_kernel_compiles_for_v5e(one_chip, kernel, d):
    fn, shapes = _cases(d)[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_no_kernel_defaults_to_interpret():
    for fn in (fused_serve_pool, dot_interaction):
        sig = inspect.signature(getattr(fn, "__wrapped__", fn))
        assert sig.parameters["interpret"].default is False, fn

