"""repro.dist unit + property tests.

The multi-device cases run in a subprocess with 8 forced host devices
(mirroring the dry-run idiom in test_sharding_and_dryrun.py) so the main
test process keeps its single-device view.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.dist.compress import ef_psum_grads, init_error_state, quantize_int8
from repro.dist.sharding import (batch_axes, constrain, constrain_batch,
                                 fit_template, spec_for)
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _axis_product(entry, sizes):
    if entry is None:
        return 1
    group = entry if isinstance(entry, tuple) else (entry,)
    return int(np.prod([sizes[a] for a in group]))


# ------------------------------------------------------------ rule engine


TEMPLATE_SYMBOLS = [None, "model", "dp", ("pod", "data"), "data", "pod"]


@settings(max_examples=200, deadline=None)
@given(st.data())
def test_fit_template_never_emits_indivisible_axis(data):
    """Property: every axis group in a fitted spec divides its dim, each
    mesh axis appears at most once, and the spec has full rank."""
    sizes = {"pod": data.draw(st.integers(1, 4)),
             "data": data.draw(st.integers(1, 8)),
             "model": data.draw(st.integers(1, 8))}
    rank = data.draw(st.integers(0, 4))
    shape = tuple(data.draw(st.integers(1, 400)) for _ in range(rank))
    template = tuple(data.draw(st.sampled_from(TEMPLATE_SYMBOLS))
                     for _ in range(data.draw(st.integers(0, 5))))
    spec = fit_template(template, shape, sizes, batch=("pod", "data"))
    if rank <= 1:
        assert spec == P()
        return
    assert len(spec) == rank
    seen = []
    for dim, entry in zip(shape, spec):
        assert dim % _axis_product(entry, sizes) == 0
        if entry is not None:
            seen.extend(entry if isinstance(entry, tuple) else (entry,))
    assert len(seen) == len(set(seen)), f"axis used twice: {spec}"


def test_fit_template_relocates_dropped_axis():
    sizes = {"data": 2, "model": 4}
    # 3 rows can't take model 4-ways; the 2048 column can
    assert fit_template(("model", None), (3, 2048), sizes) == P(None, "model")
    # nothing divides -> fully replicated, but still full-rank
    assert fit_template(("model", "dp"), (3, 5), sizes) == P(None, None)


def test_spec_for_single_device_mesh_and_1d():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert spec_for("layers/norm1/g", (2048,), mesh) == P()
    assert spec_for("anything/scalar", (), mesh) == P()
    # rank-2 leaves get full-rank specs on the trivial mesh
    assert len(spec_for("embed/table_0", (8000, 2048), mesh)) == 2


def test_batch_axes_excludes_model():
    mesh3 = make_mesh((1, 1, 1), ("pod", "data", "model"))
    assert batch_axes(mesh3) == ("pod", "data")
    mesh1 = make_mesh((1,), ("data",))
    assert batch_axes(mesh1) == ("data",)


def test_spec_engine_8dev_property_sweep():
    """On real 2-D/3-D meshes: every emitted axis divides its dim; inference
    overrides never introduce data-parallel weight sharding."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import itertools, json, random
        import numpy as np
        import jax
        from repro.launch.mesh import make_mesh
        from repro.dist.sharding import INFERENCE_OVERRIDES, batch_axes, spec_for

        random.seed(0)
        paths = ["embed/table_0", "embed/table_7", "lm_head/w", "layers/moe/wi",
                 "layers/moe/wo", "layers/mlp/wi/w", "layers/attn/wq/w",
                 "layers/norm1/g", "tables/3/q", "frontend_proj/w"]
        meshes = [((2, 4), ("data", "model")), ((8, 1), ("data", "model")),
                  ((1, 8), ("data", "model")), ((2, 2, 2), ("pod", "data", "model"))]
        checked = 0
        for shape_mesh, axes in meshes:
            mesh = make_mesh(shape_mesh, axes)
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            dp = batch_axes(mesh)
            for path in paths:
                for _ in range(30):
                    rank = random.randint(0, 3)
                    shape = tuple(random.randint(1, 600) for _ in range(rank))
                    for ov in (None, INFERENCE_OVERRIDES):
                        spec = spec_for(path, shape, mesh, overrides=ov)
                        assert len(spec) == (rank if rank > 1 else 0), (path, shape, spec)
                        for dim, ent in zip(shape, spec):
                            if ent is None:
                                continue
                            group = ent if isinstance(ent, tuple) else (ent,)
                            n = int(np.prod([sizes[a] for a in group]))
                            assert dim % n == 0, (path, shape, spec, mesh)
                            if ov is INFERENCE_OVERRIDES:
                                assert not (set(group) & set(dp)), \\
                                    ("inference spec uses dp axes", path, shape, spec)
                        checked += 1
        print(json.dumps({"checked": checked}))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=f"{REPO}/src"),
                         timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["checked"] >= 2000


# ------------------------------------------------------------ constrain


def test_constrain_batch_noop_outside_mesh():
    x = jnp.arange(12.0).reshape(4, 3)
    assert constrain_batch(x) is x
    assert constrain(x, "dp", "model") is x
    assert constrain_batch(jnp.float32(1.0)) is not None  # scalars pass through


def test_constrain_noop_under_jit_without_mesh():
    x = jnp.ones((8, 4))
    f = jax.jit(lambda a: constrain(a, "dp", "model"))
    out = f(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_constrain_is_identity_math_inside_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    x = jnp.arange(32.0).reshape(8, 4)
    with jax.set_mesh(mesh):
        f = jax.jit(lambda a: constrain(a, "dp", "model") * 2.0)
        out = f(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2.0)


def test_constrain_skips_manual_axes_in_shard_map():
    """Inside shard_map every mesh axis is manual: constrain must degrade to
    identity instead of failing at lowering time."""
    from jax import shard_map
    mesh = make_mesh((1,), ("data",))
    x = jnp.ones((4, 4))

    def body(a):
        return constrain_batch(a) + 1.0

    with jax.set_mesh(mesh):
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
        out = f(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) + 1.0)


# ------------------------------------------------------------ compression


def test_quantize_int8_zero_and_constant_inputs():
    q, s = quantize_int8(jnp.zeros((16,)))
    assert np.isfinite(float(s)) and float(s) > 0
    np.testing.assert_array_equal(np.asarray(q), 0)
    q, s = quantize_int8(jnp.full((16,), -2.5))
    np.testing.assert_allclose(np.asarray(q, np.float32) * float(s), -2.5, rtol=1e-6)


def test_ef_mode_none_is_exact():
    g = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((3, 3))}}
    err = init_error_state(g)
    out, new_err = ef_psum_grads(g, err, axis_name=None, mode="none")
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for e in jax.tree.leaves(new_err):
        np.testing.assert_array_equal(np.asarray(e), 0.0)


def test_ef_rejects_unknown_mode_and_mismatched_state():
    g = {"w": jnp.ones((4,))}
    with pytest.raises(ValueError):
        ef_psum_grads(g, init_error_state(g), axis_name=None, mode="fp4")
    with pytest.raises(ValueError):
        ef_psum_grads(g, [jnp.zeros((4,)), jnp.zeros((4,))], axis_name=None)


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_ef_residual_bounded(mode):
    """Error feedback never lets the residual grow: it stays within one
    quantisation step of zero under repeated compression."""
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (256,)) * 1e-3}
    err = init_error_state(g)
    for _ in range(100):
        out, err = ef_psum_grads(g, err, axis_name=None, mode=mode)
    e = np.abs(np.asarray(err["w"]))
    v = np.abs(np.asarray(g["w"])) + e.max()
    # one ulp of bf16 at |v|, or one int8 step of the tensor's scale
    bound = (2 ** -8) * v.max() if mode == "bf16" else (v.max() / 127) * 0.5
    assert e.max() <= bound + 1e-7


@pytest.mark.slow
def test_fsdp_matches_dp_8dev_shard_map():
    """On a real 8-device mesh: make_fsdp_train_step must track the
    replicated make_dp_train_step losses step for step under the same
    policy — exactly for mode 'none' (same math, different collectives),
    and within phase-2-compression noise for the auto policy (the DP path
    re-compresses the reduced mean for its gather; FSDP doesn't need to).
    The FSDP executable must actually contain scatter/gather collectives."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        from repro.launch.mesh import make_mesh
        import numpy as np
        from repro.core import EmbeddingSpec
        from repro.data.criteo import CriteoSpec, batch_at
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.models.dlrm import DLRMConfig, dlrm_init, dlrm_loss_fn
        from repro.optim.optimizers import adagrad
        from repro.train.loop import (init_dp_state, init_fsdp_state,
                                      make_dp_train_step, make_fsdp_train_step)

        SPEC = CriteoSpec(table_sizes=(100, 5000, 33))
        CFG = DLRMConfig(table_sizes=SPEC.table_sizes,
                         embedding=EmbeddingSpec(kind="qr", num_collisions=4,
                                                 threshold=50))
        loss_fn = lambda p, b: dlrm_loss_fn(p, b, CFG)
        mesh = make_mesh((8,), ("data",))
        opt = adagrad(1e-2)
        params = dlrm_init(jax.random.PRNGKey(0), CFG)

        s_dp = init_dp_state(params, opt, compress="none")
        st_dp = jax.jit(make_dp_train_step(loss_fn, opt, mesh, compress="none"))
        s_fs = init_fsdp_state(params, opt, mesh, policy="none")
        fsdp_none = make_fsdp_train_step(loss_fn, opt, mesh, params,
                                         policy="none")
        st_fs = jax.jit(fsdp_none)
        s_dpa = init_dp_state(params, opt, compress="auto")
        st_dpa = jax.jit(make_dp_train_step(loss_fn, opt, mesh,
                                            compress="auto"))
        s_au = init_fsdp_state(params, opt, mesh, policy="auto")
        st_au = jax.jit(make_fsdp_train_step(loss_fn, opt, mesh, params,
                                             policy="auto"))
        max_dloss = max_dauto = 0.0
        with jax.set_mesh(mesh):
            colls = analyze_hlo(jax.jit(fsdp_none)
                                .lower(s_fs, batch_at(0, 0, 64, SPEC))
                                .compile().as_text(), 8).collectives
            for i in range(8):
                batch = batch_at(0, i, 64, SPEC)
                s_dp, m_dp = st_dp(s_dp, batch)
                s_fs, m_fs = st_fs(s_fs, batch)
                s_dpa, m_dpa = st_dpa(s_dpa, batch)
                s_au, m_au = st_au(s_au, batch)
                max_dloss = max(max_dloss,
                                abs(float(m_dp["loss"]) - float(m_fs["loss"])))
                max_dauto = max(max_dauto,
                                abs(float(m_dpa["loss"]) - float(m_au["loss"]))
                                / max(1.0, float(m_dpa["loss"])))
        dparam = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                     for a, b in zip(jax.tree.leaves(s_dp["params"]),
                                     jax.tree.leaves(s_fs["params"])))
        print(json.dumps({"max_dloss": max_dloss, "max_dparam": dparam,
                          "max_dauto": max_dauto,
                          "collectives": sorted(colls)}))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=f"{REPO}/src"),
                         timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # 'none' paths differ only by f32 reduction order (psum vs psum_scatter)
    assert out["max_dloss"] <= 1e-4, out
    assert out["max_dparam"] <= 1e-4, out
    # same policy, different collective paths: only phase-2 re-compression
    # of the already-reduced mean separates them (≤ one bf16 ulp / int8
    # step of the mean per leaf per step)
    assert out["max_dauto"] <= 0.05, out
    # the FSDP executable genuinely reduce-scatters and gathers
    assert "all-gather" in out["collectives"], out
    assert ("reduce-scatter" in out["collectives"]
            or "all-to-all" in out["collectives"]), out


@pytest.mark.slow
def test_fsdp_bf16_param_gather_halves_wire_8dev():
    """FSDP with param_gather_dtype='bfloat16': the param all-gather rides
    as 2 B/elem (bitcast uint16 — pinned against the compiled HLO, which
    must agree with accounting within 10%), accounting reports exactly
    half the f32 gather bytes, and training still tracks the replicated
    DP step (own-shard f32 master precision; only remote shards round)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        # The random draws this comparison was set up with (jax < 0.5's
        # default).  With jax >= 0.5's partitionable draws the init sends
        # step 1 to a loss of ~19.5, after which a 1e-3 perturbation of
        # any kind grows by step 3: there int8-vs-uncompressed DP differ by
        # 0.027 relative, the bf16 gather from the DP step by 0.119.
        jax.config.update("jax_threefry_partitionable", False)
        from repro.launch.mesh import make_mesh
        from repro.core import EmbeddingSpec
        from repro.data.criteo import CriteoSpec, batch_at
        from repro.dist import accounting
        from repro.dist.policy import AUTO
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.models.dlrm import DLRMConfig, dlrm_init, dlrm_loss_fn
        from repro.optim.optimizers import adagrad
        from repro.train.loop import (init_dp_state, init_fsdp_state,
                                      make_dp_train_step, make_fsdp_train_step)

        SPEC = CriteoSpec(table_sizes=(100, 5000, 33))
        CFG = DLRMConfig(table_sizes=SPEC.table_sizes,
                         embedding=EmbeddingSpec(kind="qr", num_collisions=4,
                                                 threshold=50))
        loss_fn = lambda p, b: dlrm_loss_fn(p, b, CFG)
        mesh = make_mesh((8,), ("data",))
        opt = adagrad(1e-2)
        params = dlrm_init(jax.random.PRNGKey(0), CFG)

        acct_f32 = accounting.fsdp_step_wire_bytes(
            params, opt, mesh, AUTO, scalar_allreduces=3)
        acct_bf = accounting.fsdp_step_wire_bytes(
            params, opt, mesh, AUTO, scalar_allreduces=3,
            param_gather_dtype="bfloat16")
        step_bf = make_fsdp_train_step(loss_fn, opt, mesh, params,
                                       policy="auto",
                                       param_gather_dtype="bfloat16")
        s_bf = init_fsdp_state(params, opt, mesh, policy="auto")
        s_dp = init_dp_state(params, opt, compress="auto")
        st_dp = jax.jit(make_dp_train_step(loss_fn, opt, mesh,
                                           compress="auto"))
        st_bf = jax.jit(step_bf)
        with jax.set_mesh(mesh):
            hlo = analyze_hlo(jax.jit(step_bf)
                              .lower(s_bf, batch_at(0, 0, 64, SPEC))
                              .compile().as_text(), 8)
            max_d = 0.0
            for i in range(6):
                b = batch_at(0, i, 64, SPEC)
                s_dp, m1 = st_dp(s_dp, b)
                s_bf, m2 = st_bf(s_bf, b)
                max_d = max(max_d, abs(float(m1["loss"]) - float(m2["loss"]))
                            / max(1.0, float(m1["loss"])))
        print(json.dumps({
            "gather_f32": acct_f32["param_gather_bytes"],
            "gather_bf16": acct_bf["param_gather_bytes"],
            "acct_total": acct_bf["total_bytes"],
            "hlo_total": hlo.collective_bytes,
            "max_rel_dloss": max_d}))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=f"{REPO}/src"),
                         timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["gather_bf16"] == pytest.approx(out["gather_f32"] / 2)
    rel = abs(out["acct_total"] - out["hlo_total"]) / out["hlo_total"]
    assert rel <= 0.10, out
    # bf16-rounded remote shards perturb the forward by ~one bf16 ulp
    assert out["max_rel_dloss"] <= 0.05, out


@pytest.mark.slow
def test_dist_bench_acceptance_dp():
    """benchmarks/dist_bench.py end to end (dp path, 4 steps): exits 0,
    BENCH_dist.json reports int8 < 0.3× none on the HLO cross-check, and
    accounting matches HLO within 10% for every row."""
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "BENCH_dist.json")
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.dist_bench", "--steps", "4",
             "--paths", "dp", "--policies", "none,int8", "--out", out],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, PYTHONPATH=f"{REPO}/src",
                     XLA_FLAGS="--xla_force_host_platform_device_count=8"),
            timeout=900)
        assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-2000:])
        with open(out) as f:
            report = json.load(f)
    assert report["checks_failed"] == [], report["checks_failed"]
    assert report["int8_vs_none_ratio"] < 0.3, report["int8_vs_none_ratio"]
    for row in report["rows"]:
        rel = abs(row["wire_bytes"] - row["hlo_wire_bytes"]) \
            / row["hlo_wire_bytes"]
        assert rel <= 0.10, (row["path"], row["policy"], rel)


@pytest.mark.slow
def test_two_level_ef_tightens_int8_phase2_bias_8dev():
    """Two-level error feedback (phase-2 requant residual carried into the
    EF state) on the int8 two-phase exchange: with a *constant* per-device
    gradient, single-level EF converges to a standing bias of one int8
    step of the mean (phase 2 loses the same residual every step), while
    two-level telescopes it — the time-averaged output must land well
    inside the single-level floor, and replicas stay bitwise identical."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        import numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist.compress import ef_psum_grads

        mesh = make_mesh((8,), ("data",))
        D = 64
        g_all = (jax.random.normal(jax.random.PRNGKey(0), (8, D)) * 3e-3
                 + jnp.linspace(-1e-3, 1e-3, 8)[:, None])
        true_mean = np.asarray(g_all).mean(axis=0)

        def run(two_level, T=60):
            def step(g_shard, err_shard, total_shard):
                g = {"w": g_shard.reshape(D)}
                err = {"w": err_shard.reshape(D)}
                out, new_err = ef_psum_grads(g, err, axis_name="data",
                                             mode="int8",
                                             two_level=two_level)
                return (new_err["w"][None],
                        (total_shard.reshape(D) + out["w"])[None])
            sharded = shard_map(step, mesh=mesh, in_specs=(P("data"),) * 3,
                                out_specs=(P("data"),) * 2, check_vma=False)
            err = jnp.zeros((8, D)); total = jnp.zeros((8, D))
            with jax.set_mesh(mesh):
                fn = jax.jit(sharded)
                for _ in range(T):
                    err, total = fn(g_all, err, total)
            totals = np.asarray(total)
            for r in range(1, 8):
                np.testing.assert_array_equal(totals[r], totals[0])
            return float(np.abs(totals[0] / T - true_mean).max())

        print(json.dumps({"single": run(False), "two": run(True),
                          "scale": float(np.abs(true_mean).max())}))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=f"{REPO}/src"),
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # two-level telescopes phase 2: decisively under the single-level
    # standing bias, and within EF's O(residual / T) envelope of the truth
    assert out["two"] <= out["single"] / 3, out
    assert out["two"] <= 5e-4 * out["scale"] + 1e-7, out


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_ef_psum_unbiased_over_time_8dev_shard_map(mode):
    """Under a real 8-device shard_map psum with per-device-distinct
    gradients, the time-averaged EF-compressed reduction matches the true
    mean gradient, and every replica sees bitwise-identical output."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        import numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist.compress import ef_psum_grads, init_error_state

        mesh = make_mesh((8,), ("data",))
        D = 64
        # per-device gradient rows, deliberately tiny to stress quantisation
        g_all = (jax.random.normal(jax.random.PRNGKey(0), (8, D)) * 3e-3
                 + jnp.linspace(-1e-3, 1e-3, 8)[:, None])
        true_mean = np.asarray(g_all).mean(axis=0)

        def step(g_shard, err_shard, total_shard):
            g = {{"w": g_shard.reshape(D)}}
            err = {{"w": err_shard.reshape(D)}}
            out, new_err = ef_psum_grads(g, err, axis_name="data", mode="{mode}")
            return new_err["w"][None], (total_shard.reshape(D) + out["w"])[None]

        sharded = shard_map(step, mesh=mesh,
                            in_specs=(P("data"), P("data"), P("data")),
                            out_specs=(P("data"), P("data")), check_vma=False)
        err = jnp.zeros((8, D))
        total = jnp.zeros((8, D))
        T = 60
        with jax.set_mesh(mesh):
            fn = jax.jit(sharded)
            for _ in range(T):
                err, total = fn(g_all, err, total)
        totals = np.asarray(total)  # (8, D): per-replica accumulated output
        # every replica must hold the identical reduced gradient stream
        for r in range(1, 8):
            np.testing.assert_array_equal(totals[r], totals[0])
        avg = totals[0] / T
        err_abs = float(np.abs(avg - true_mean).max())
        # EF bound: |avg - true| <= max residual / T
        print(json.dumps({{"err_abs": err_abs,
                          "scale": float(np.abs(true_mean).max())}}))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=f"{REPO}/src"),
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["err_abs"] <= 0.02 * out["scale"] + 1e-5, out
