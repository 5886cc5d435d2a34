"""Launch plumbing: mesh axis types, the compile-cache location, and the
chip smoke script's refusal without a TPU and its phases on the CPU."""

import importlib.util
import os
import subprocess
import textwrap
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.launch import compile_cache
from repro.launch.mesh import make_local_mesh, make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("mesh_fn", [
    lambda: make_mesh((1,), ("data",)),
    lambda: make_mesh((1, 1, 1), ("pod", "data", "model")),
    make_local_mesh,
])
def test_meshes_have_auto_axes(mesh_fn):
    mesh = mesh_fn()
    assert mesh.axis_types == (AxisType.Auto,) * len(mesh.axis_names)


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    cache_dir_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable_compile_cache() == want   # stable across calls


def test_chip_smoke_refuses_without_tpu():
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


@pytest.fixture
def chip_smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "REDUCED", True)
    return mod


def test_chip_smoke_phases_at_reduced_size_on_cpu(chip_smoke):
    """The one-chip phases run end to end here (interpret-mode kernels);
    only the chip-only check, a compiled ``tpu_custom_call``, must fail."""
    reqs = chip_smoke.requests()
    chip_smoke.train_phase(chip_smoke.train_args())
    engine, scores = chip_smoke.serve_phase(reqs)
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.kernel_phase(engine, reqs, scores)


def test_chip_smoke_four_chip_phases_at_reduced_size_on_cpu():
    """``--chips 4``'s phases on four virtual CPU devices: the sharded
    engine bitwise equal to the single-device one, and the int8 DP step
    (replicated state placed once, so the step compiles once)."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import importlib.util, logging
        import jax
        jax.config.update("jax_log_compiles", True)
        compiles = []

        class Count(logging.Handler):
            def emit(self, record):
                if record.getMessage().startswith("Compiling jit(_step)"):
                    compiles.append(record)

        logging.getLogger("jax").addHandler(Count())
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.REDUCED = True
        mod.sharded_serve_phase(mod.requests())
        mod.dp_phase()
        assert len(compiles) == 1, compiles
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600,
                         env=dict(os.environ, PYTHONPATH=f"{REPO}/src",
                                  JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-3000:]
    assert "bitwise_parity=True" in res.stdout
    assert "shards_bitwise_identical=True" in res.stdout
