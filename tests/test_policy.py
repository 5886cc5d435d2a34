"""Compression policy engine + wire-bytes accounting + bench harness exit codes.

Multi-device behaviour (FSDP vs DP equivalence, HLO cross-checks) lives in
test_dist.py; these are the fast single-process properties.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import accounting
from repro.dist.compress import (ef_psum_grads, init_error_state,
                                 resolve_modes)
from repro.dist.policy import AUTO, CompressionPolicy, resolve_policy
from repro.launch.mesh import make_mesh
from repro.optim.optimizers import leaf_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _params():
    return {
        "embed": {"table_0": jnp.zeros((4096, 64)),      # big table → int8
                  "table_1": jnp.zeros((16, 16))},       # tiny table → none
        "mlp": {"w": jnp.zeros((512, 256)),              # dense matmul → bf16
                "b": jnp.zeros((256,))},                 # bias (1-D) → none
        "norm": {"g": jnp.zeros((70, 70))},              # 2-D but norm-named
        "head": {"w": jnp.zeros((64, 8))},               # under threshold
    }


# ------------------------------------------------------------- rule table


def test_mode_for_rules():
    p = AUTO
    assert p.mode_for("embed/table_0", (4096, 64)) == "int8"
    assert p.mode_for("tables/3/table_1", (8000, 16)) == "int8"
    assert p.mode_for("layers/mlp/w", (512, 256)) == "bf16"
    assert p.mode_for("layers/norm1/g", (2048,)) == "none"       # rank gate
    assert p.mode_for("layers/norm1/g", (70, 70)) == "none"      # name rule
    assert p.mode_for("mlp/b", (256,)) == "none"                 # rank gate
    assert p.mode_for("head/w", (8, 8)) == "none"                # size gate
    # size gate beats the table rule: a tiny table is not worth compressing
    assert p.mode_for("embed/table_9", (16, 16)) == "none"


def test_policy_tree_and_modes_align_with_leaves():
    params = _params()
    modes = AUTO.modes(params)
    paths_modes = dict(zip(leaf_paths(params), modes))
    assert paths_modes["embed/table_0"] == "int8"
    assert paths_modes["embed/table_1"] == "none"
    assert paths_modes["mlp/w"] == "bf16"
    assert paths_modes["mlp/b"] == "none"
    assert paths_modes["norm/g"] == "none"
    assert paths_modes["head/w"] == "none"
    # tree form round-trips through resolve_modes
    assert resolve_modes(params, AUTO.tree(params)) == modes
    assert resolve_modes(params, AUTO) == modes


def test_policy_validation():
    with pytest.raises(ValueError):
        CompressionPolicy(default="fp4")
    with pytest.raises(ValueError):
        CompressionPolicy(rules=((r".*", "int4"),))
    with pytest.raises(ValueError):
        resolve_policy("int4")
    assert resolve_policy("auto") is AUTO
    assert resolve_policy("bf16") == "bf16"
    custom = CompressionPolicy(min_compress_elems=1, default="int8")
    assert resolve_policy(custom) is custom
    assert custom.mode_for("mlp/w", (4, 4)) == "int8"


def test_custom_rules_first_match_wins():
    p = CompressionPolicy(rules=((r"special", "none"),) + AUTO.rules,
                          min_compress_elems=1)
    assert p.mode_for("special/table_0", (4096, 64)) == "none"
    assert p.mode_for("embed/table_0", (4096, 64)) == "int8"


# ----------------------------------------------- per-leaf error state + EF


def test_error_state_allocated_only_for_compressed_leaves():
    params = _params()
    err = init_error_state(params, AUTO)
    shapes = {p: e.shape for p, e in zip(leaf_paths(params),
                                         jax.tree.leaves(err))}
    assert shapes["embed/table_0"] == (4096, 64)   # int8 → full residual
    assert shapes["mlp/w"] == (512, 256)           # bf16 → full residual
    assert shapes["embed/table_1"] == ()           # none → placeholder
    assert shapes["mlp/b"] == ()
    assert shapes["norm/g"] == ()
    # default (no mode): full residual everywhere, as in PR 1
    full = init_error_state(params)
    assert all(e.shape == l.shape for e, l in
               zip(jax.tree.leaves(full), jax.tree.leaves(params)))


def test_ef_per_leaf_modes_local():
    key = jax.random.PRNGKey(0)
    g = {"table": jax.random.normal(key, (64, 64)),
         "b": jax.random.normal(jax.random.fold_in(key, 1), (32,))}
    modes = {"table": "int8", "b": "none"}
    err = init_error_state(g, modes)
    out, new_err = ef_psum_grads(g, err, axis_name=None, mode=modes)
    # 'none' leaf is exact with a placeholder residual
    np.testing.assert_array_equal(np.asarray(out["b"]), np.asarray(g["b"]))
    assert new_err["b"].shape == ()
    # int8 leaf is quantised within half a step, residual is the difference
    scale = float(np.abs(np.asarray(g["table"])).max()) / 127.0
    err_abs = np.abs(np.asarray(out["table"]) - np.asarray(g["table"]))
    assert err_abs.max() <= scale * 0.5 + 1e-7
    np.testing.assert_allclose(np.asarray(new_err["table"]),
                               np.asarray(g["table"]) - np.asarray(out["table"]),
                               atol=1e-6)


def test_ef_rejects_mismatched_mode_tree():
    g = {"a": jnp.ones((4,)), "b": jnp.ones((4,))}
    with pytest.raises(ValueError):
        ef_psum_grads(g, init_error_state(g), axis_name=None,
                      mode=["bf16"])  # 1 mode for 2 leaves
    with pytest.raises(ValueError):
        resolve_modes(g, ["bf16", "fp4"])


# ------------------------------------------------------------- accounting


def test_leaf_reduce_bytes_formulas():
    n, e = 8, 1024
    none_ar = accounting.leaf_reduce_bytes("none", e, n)
    bf16_ar = accounting.leaf_reduce_bytes("bf16", e, n)
    int8_ar = accounting.leaf_reduce_bytes("int8", e, n)
    assert none_ar == pytest.approx(2 * 7 / 8 * 4 * e)
    assert bf16_ar == pytest.approx(2 * 7 / 8 * 2 * e)
    # two-phase int8: ~0.25× of f32 + two scalar scale all-reduces
    assert int8_ar == pytest.approx(2 * 7 / 8 * e + 2 * 2 * 7 / 8 * 4)
    assert int8_ar < 0.3 * none_ar
    # reduce-scatter pattern is half the all-reduce (no gather phase)
    assert accounting.leaf_reduce_bytes("none", e, n, pattern="reduce_scatter") \
        == pytest.approx(7 / 8 * 4 * e)
    assert accounting.leaf_reduce_bytes("int8", e, n, pattern="reduce_scatter") \
        == pytest.approx(7 / 8 * e + 2 * 7 / 8 * 4)
    # single device: nothing crosses a wire
    assert accounting.leaf_reduce_bytes("int8", e, 1) == 0.0


def test_tree_accounting_int8_policy_under_0p3():
    """The PR acceptance ratio, at the accounting level, on a DLRM-shaped
    tree: uniform int8 < 0.3× of mode='none'."""
    params = _params()
    none = accounting.dp_step_wire_bytes(params, "none", 8)
    int8 = accounting.dp_step_wire_bytes(params, "int8", 8)
    auto = accounting.dp_step_wire_bytes(params, AUTO, 8)
    assert int8["total_bytes"] < 0.3 * none["total_bytes"]
    assert none["total_bytes"] > auto["total_bytes"] > int8["total_bytes"]
    assert set(auto["per_mode"]) == {"int8", "bf16", "none"}


def test_fsdp_accounting_reports_param_gather():
    from repro.optim.optimizers import adagrad
    params = _params()
    mesh = make_mesh((1,), ("data",))
    # trivial mesh: no wire at all
    acct = accounting.fsdp_step_wire_bytes(params, adagrad(1e-2), mesh, AUTO)
    assert acct["total_bytes"] == 0.0
    assert acct["n_leaves"] == len(jax.tree.leaves(params))


def test_fsdp_step_preserves_rank0_leaves():
    """Scalar params (learned temperature etc.) must come back rank-0: the
    'none'-mode residual placeholder is per-device 0-d, not (1,) — a (1,)
    residual would broadcast the whole update chain up a rank."""
    from repro.optim.optimizers import adagrad
    from repro.train.loop import init_fsdp_state, make_fsdp_train_step

    def loss_fn(p, b):
        pred = b["x"] @ p["w"] * p["temp"]
        loss = jnp.mean(jnp.square(pred - b["y"]))
        return loss, {"mse": loss}

    params = {"w": jnp.full((16, 8), 0.1), "temp": jnp.float32(1.0)}
    mesh = make_mesh((1,), ("data",))
    opt = adagrad(1e-2)
    state = init_fsdp_state(params, opt, mesh, policy="auto")
    step = jax.jit(make_fsdp_train_step(loss_fn, opt, mesh, params,
                                        policy="auto"))
    b = {"x": jnp.ones((4, 16)), "y": jnp.zeros((4, 8))}
    with jax.set_mesh(mesh):
        for _ in range(2):
            state, m = step(state, b)
    assert state["params"]["temp"].shape == ()
    assert state["params"]["w"].shape == (16, 8)
    assert np.isfinite(float(m["loss"]))


# ----------------------------------------------------- bench harness exits


def _run_bench(*args, env=None):
    e = dict(os.environ, PYTHONPATH=f"{REPO}/src")
    e.pop("REPRO_BENCH_INJECT_ERROR", None)
    if env:
        e.update(env)
    return subprocess.run([sys.executable, "-m", "benchmarks.run", *args],
                          capture_output=True, text=True, cwd=REPO, env=e,
                          timeout=600)


def test_benchmarks_run_exits_nonzero_on_error_row():
    """The CI bench lane can only gate on sections actually failing the
    process: inject an error, expect the /ERROR row AND exit code 1."""
    res = _run_bench("--only", "injected",
                     env={"REPRO_BENCH_INJECT_ERROR": "1"})
    assert res.returncode == 1, (res.stdout, res.stderr)
    assert "/ERROR" in res.stdout
    assert "injected benchmark failure" in res.stdout


def test_benchmarks_run_only_filter_green():
    """--only with no matching section runs nothing and exits 0 (the same
    path a fully-green run takes through the failure accounting)."""
    res = _run_bench("--only", "no_such_section")
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert res.stdout.strip().splitlines()[0] == "name,us_per_call,derived"
