"""Fault-tolerance integration: restart determinism, watchdog, compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EmbeddingSpec
from repro.data.criteo import CriteoSpec, batch_at
from repro.dist.compress import ef_psum_grads, init_error_state, quantize_int8
from repro.launch.mesh import make_mesh
from repro.models.dlrm import DLRMConfig, dlrm_init, dlrm_loss_fn
from repro.optim.optimizers import adam, adagrad, rowwise_adagrad, partitioned
from repro.train.loop import (SimulatedFailure, TrainConfig, Trainer,
                              init_state, make_train_step)

SPEC = CriteoSpec(table_sizes=(100, 5000, 33))
CFG = DLRMConfig(table_sizes=SPEC.table_sizes,
                 embedding=EmbeddingSpec(kind="qr", num_collisions=4, threshold=50))


def _loss_fn(p, b):
    return dlrm_loss_fn(p, b, CFG)


def _opt():
    return partitioned([(lambda p: "tables" in p, rowwise_adagrad(1e-2))],
                       adam(1e-3, amsgrad=True))


def test_kill_restart_bitwise_determinism(tmp_path):
    opt = _opt()

    def state0():   # a Trainer consumes its state: a fresh one each use
        return init_state(dlrm_init(jax.random.PRNGKey(1), CFG), opt)

    tc = TrainConfig(num_steps=20, ckpt_every=10, ckpt_dir=str(tmp_path), log_every=5)
    batcher = lambda s: batch_at(0, s, 64, SPEC)

    tr = Trainer(make_train_step(_loss_fn, opt), tc, batch_at=batcher)
    with pytest.raises(SimulatedFailure):
        tr.run(state0(), fail_at_step=15)
    # the step-10 checkpoint was issued 5 steps before the crash; let the
    # async writer finish (in real time-scales it completed long before)
    tr.checkpointer.wait()

    tr2 = Trainer(make_train_step(_loss_fn, opt), tc, batch_at=batcher)
    resumed = tr2.resume_or(state0())
    assert int(resumed["step"]) == 10
    final_resumed, _ = tr2.run(resumed)

    tr3 = Trainer(make_train_step(_loss_fn, opt),
                  TrainConfig(num_steps=20, ckpt_dir=None), batch_at=batcher)
    final_direct, _ = tr3.run(state0())
    for a, b in zip(jax.tree.leaves(final_resumed["params"]),
                    jax.tree.leaves(final_direct["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_training_reduces_loss():
    opt = adagrad(1e-2)
    state = init_state(dlrm_init(jax.random.PRNGKey(0), CFG), opt)
    step = jax.jit(make_train_step(_loss_fn, opt))
    losses = []
    for i in range(150):
        state, m = step(state, batch_at(0, i, 256, SPEC))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.05


def test_grad_accumulation_equivalent():
    """accum=4 must match accum=1 numerically (same global batch)."""
    opt = adagrad(1e-2)
    p0 = dlrm_init(jax.random.PRNGKey(2), CFG)
    batch = batch_at(0, 0, 64, SPEC)
    s1 = init_state(p0, opt)
    s4 = init_state(p0, opt)
    step1 = jax.jit(make_train_step(_loss_fn, opt, accum=1))
    step4 = jax.jit(make_train_step(_loss_fn, opt, accum=4))
    s1, m1 = step1(s1, batch)
    s4, m4 = step4(s4, batch)
    # losses are means over microbatches of per-microbatch means — equal for
    # equal-size microbatches.
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 5e-5
    for a, b in zip(jax.tree.leaves(s1["params"]), jax.tree.leaves(s4["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_watchdog_flags_straggler(monkeypatch):
    opt = adagrad(1e-2)
    state = init_state(dlrm_init(jax.random.PRNGKey(0), CFG), opt)
    tc = TrainConfig(num_steps=12, watchdog_factor=2.5)
    tr = Trainer(make_train_step(_loss_fn, opt), tc,
                 batch_at=lambda s: batch_at(0, s, 32, SPEC))
    import time as _time
    orig_step = tr.train_step

    def slow_step(state, batch):
        if int(state["step"]) == 9:
            _time.sleep(1.0)  # injected straggler
        return orig_step(state, batch)

    tr.train_step = slow_step
    tr.run(state)
    assert any(step == 9 for step, _ in tr.straggler_events)


def test_quantize_int8_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(x) - np.asarray(q, np.float32) * float(scale))
    assert err.max() <= float(scale) * 0.5 + 1e-6


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_error_feedback_is_unbiased_over_time(mode):
    """Sum of EF-compressed gradients converges to sum of true gradients."""
    g = {"w": jnp.full((64,), 0.003)}  # small values stress quantisation
    err = init_error_state(g)
    total = jnp.zeros((64,))
    for _ in range(50):
        out, err = ef_psum_grads(g, err, axis_name=None, mode=mode)
        total = total + out["w"]
    np.testing.assert_allclose(np.asarray(total), 0.003 * 50, rtol=0.02)


def test_dp_shard_map_compressed_training_runs():
    """shard_map DP path with bf16-compressed reduction on a 1-device mesh."""
    from repro.train.loop import init_dp_state, make_dp_train_step
    mesh = make_mesh((1,), ("data",))
    opt = adagrad(1e-2)
    state = init_dp_state(dlrm_init(jax.random.PRNGKey(0), CFG), opt)
    step = jax.jit(make_dp_train_step(_loss_fn, opt, mesh, compress="bf16"))
    with jax.set_mesh(mesh):
        for i in range(3):
            state, m = step(state, batch_at(0, i, 32, SPEC))
    assert np.isfinite(float(m["loss"]))
