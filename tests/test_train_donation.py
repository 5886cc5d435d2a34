"""The Trainer donates the train state to its jitted step: the step's new
params and optimizer state are written into the buffers of the state
passed in, so every state output aliases its input."""

import warnings

import jax
import numpy as np
import pytest

from repro.core import EmbeddingSpec
from repro.data.criteo import CriteoSpec, batch_at
from repro.launch.mesh import make_mesh
from repro.models.dcn import DCNConfig, dcn_init, dcn_loss_fn
from repro.models.dlrm import DLRMConfig, dlrm_init, dlrm_loss_fn
from repro.obs import Obs
from repro.optim.optimizers import (adafactor, adagrad, adam, partitioned,
                                    rowwise_adagrad, sgd)
from repro.train.loop import (TrainConfig, Trainer, init_dp_state,
                              init_fsdp_state, init_state, make_train_step)

SPEC = CriteoSpec(table_sizes=(100, 500, 33))
EMB = EmbeddingSpec(kind="qr", num_collisions=4, threshold=40)


def _model(family):
    """A tiny model and the optimizer its full-size benchmark trains with:
    DLRM with Adagrad, DCN with AMSGrad (three state arrays a leaf)."""
    if family == "dlrm":
        cfg = DLRMConfig(table_sizes=SPEC.table_sizes, emb_dim=16,
                         bottom_mlp=(32, 16), top_mlp=(32,), embedding=EMB)
        return (dlrm_init(jax.random.PRNGKey(0), cfg),
                lambda p, b: dlrm_loss_fn(p, b, cfg), adagrad(1e-2))
    cfg = DCNConfig(table_sizes=SPEC.table_sizes, emb_dim=16, cross_layers=2,
                    deep_mlp=(32, 16), embedding=EMB)
    return (dcn_init(jax.random.PRNGKey(0), cfg),
            lambda p, b: dcn_loss_fn(p, b, cfg), adam(1e-3, amsgrad=True))


def _buffers(tree):
    return [s.data.unsafe_buffer_pointer()
            for x in jax.tree.leaves(tree) for s in x.addressable_shards]


@pytest.mark.parametrize("family", ["dlrm", "dcn"])
def test_trainer_step_consumes_the_state_in_place(family):
    params, loss_fn, opt = _model(family)
    kept = [np.array(x) for x in jax.tree.leaves(params)]
    batches = [batch_at(0, s, 16, SPEC) for s in range(3)]
    obs = Obs()
    tr = Trainer(make_train_step(loss_fn, opt), TrainConfig(num_steps=3),
                 batch_at=batches.__getitem__, obs=obs)
    state = init_state(params, opt)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = tr.train_step.lower(state, batches[0]).compile()
    assert not [w for w in caught if "donated" in str(w.message)]
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    assert compiled.memory_analysis().alias_size_in_bytes == state_bytes

    old = jax.tree.leaves(state)
    state, _ = tr.step(state, batches[0])
    assert all(x.is_deleted() for x in old)
    assert obs.registry.gauge("train_state_aliased_share").value() >= 0.99
    for t in (1, 2):
        state, _ = tr.step(state, batches[t])
    assert int(state["step"]) == 3
    for x, want in zip(jax.tree.leaves(params), kept):
        assert not x.is_deleted()
        np.testing.assert_array_equal(np.asarray(x), want)


def test_trainer_without_obs_reads_no_aliasing(monkeypatch):
    """With obs off the loop does no new work: no lowering for the gauge."""
    monkeypatch.setattr(Trainer, "_read_aliasing",
                        lambda *a: pytest.fail("read with obs off"))
    params, loss_fn, opt = _model("dlrm")
    tr = Trainer(make_train_step(loss_fn, opt), TrainConfig(num_steps=2),
                 batch_at=lambda s: batch_at(0, s, 16, SPEC))
    state, _ = tr.run(init_state(params, opt))
    assert int(state["step"]) == 2


OPTIMIZERS = {
    "sgd_momentum": sgd(0.1, momentum=0.9),
    "adagrad": adagrad(1e-2),
    "rowwise_adagrad": rowwise_adagrad(1e-2),
    "adam": adam(1e-3),
    "amsgrad": adam(1e-3, amsgrad=True),
    "adafactor": adafactor(1e-2),
    "partitioned": partitioned([(lambda p: "tables" in p, rowwise_adagrad(1e-2))],
                               adam(1e-3, amsgrad=True)),
}


def _init(kind, params, opt):
    if kind == "init_state":
        return init_state(params, opt)
    if kind == "init_dp_state":
        return init_dp_state(params, opt, compress="auto")
    return init_fsdp_state(params, opt, make_mesh((1,), ("data",)),
                           policy="auto")


@pytest.mark.parametrize("kind", ["init_state", "init_dp_state",
                                  "init_fsdp_state"])
@pytest.mark.parametrize("name", list(OPTIMIZERS))
def test_state_leaves_are_distinct_buffers(name, kind):
    """A donated state may not hold one buffer twice."""
    params, _, _ = _model("dlrm")
    state = _init(kind, params, OPTIMIZERS[name])
    bufs = _buffers(state)
    assert len(bufs) == len(set(bufs))
    if kind == "init_state":   # and none of the caller's params
        assert not set(bufs) & set(_buffers(params))
