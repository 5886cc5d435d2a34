"""The benchmark's plain references against the program, on the CPU at the
program's reduced sizes, and the counts its metrics divide by."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_fixture as bf
from bench import generator, harness, spec
from bench import serve as sv
from bench.metrics import _counts
from bench.reference import api as ref

SEED = 2 ** 31 + 77


def config(name: str) -> dict:
    cfg = json.loads((bf.REPO / "bench" / "configs" / f"{name}.json").read_text())
    cfg["program"]["reduced_sizes"] = True
    cfg["model"]["table_sizes"] = bf.REDUCED_SIZES
    cfg["serve"]["max_batch"] = 8
    cfg["program_cfg"], cfg["program_api"] = harness.program_config(cfg)
    return cfg


def served(cfg, mix, n=24):
    """Scores the engine gives ``n`` requests of ``mix``, and the requests."""
    params = ref.make_params(SEED, cfg["model"])
    engine = sv.build_engine(cfg, params)
    reqs = spec.process("open_poisson").requests(mix, cfg["model"], SEED,
                                                 generator.WINDOW, 1.0, rate=n)
    uids = [engine.submit(reqs.dense[i], reqs.bags(i)) for i in range(n)]
    done = engine.run_until_drained()
    return np.asarray([done[u].score for u in uids]), reqs, params


MIXES = {"multihot": {"bag_lengths": bf.MULTIHOT, "skew": 1.5},
         "onehot": {"bag_lengths": None, "skew": 1.5}}


@pytest.mark.parametrize("name,mix", [("dlrm-criteo-kaggle", "multihot"),
                                      ("dcn-criteo-kaggle", "onehot")])
def test_engine_scores_match_reference_and_control_fails(name, mix):
    cfg = config(name)
    got, reqs, params = served(cfg, MIXES[mix])
    rows = np.arange(len(got))
    want = ref.serve_logits(ref.quantize_tables(params), *reqs.padded(rows),
                            cfg["model"])
    assert harness.gap_ratio(got, want) < 1e-5
    # the control, int4 tables and a bfloat16 dense half, reads thousands
    # of times further off.  (Against the committed limit it is read on the
    # chip at the cell's size, where it reads 0.33-0.56: PERF.md; the
    # reduced tables here read lower.)
    ctl = ref.serve_logits(ref.quantize_tables(params, bits=4),
                           *reqs.padded(rows), cfg["model"], dtype=jnp.bfloat16)
    assert harness.gap_ratio(ctl, want) > 0.03
    # an answer altered where it is produced fails the committed limit
    limit = json.loads((bf.REPO / "bench" / "configs" / f"{name}.json")
                       .read_text())["limits"]["score_gap"]
    bad = got.copy()
    bad[3] = got[np.argmax(np.abs(got - got[3]))]   # another request's score
    assert harness.gap_ratio(bad, want) > limit


@pytest.mark.parametrize("name", ["dlrm-criteo-kaggle", "dcn-criteo-kaggle"])
def test_trainer_steps_match_reference_and_bf16_fails(name):
    from repro.train.loop import TrainConfig, Trainer, init_state, make_train_step
    cfg = config(name)
    model, opt, api = cfg["model"], cfg["train"], cfg["program_api"]
    make_batch = spec.process("device_batches").batch_fn(
        {"batch": 64, "skew": 1.5, "label_noise": 0.5}, model, SEED)
    params0 = ref.make_params(SEED, model)
    state = init_state(params0, api.optimizer)
    trainer = Trainer(make_train_step(api.loss_fn, api.optimizer),
                      TrainConfig(num_steps=0), batch_at=make_batch)
    from bench.reference.common import first_grad_norms, leaf_norms
    losses = []
    for t in range(3):
        state, met = trainer.train_step(state, make_batch(t))
        losses.append(float(met["loss"]))
        if t == 0:
            first = first_grad_norms(opt, state["opt"])
            change1 = leaf_norms(jax.tree.map(lambda a, b: a - b,
                                              state["params"], params0))
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, state["params"], params0))
    batches = [make_batch(t) for t in range(3)]
    want = ref.train_steps(params0, batches, model, opt)
    limits = json.loads((bf.REPO / "bench" / "configs" / f"{name}.json")
                        .read_text())["limits"]
    got = harness.train_numbers({"losses": losses, "first_grad": first,
                                 "change1": change1, "change": change}, want)
    assert all(v < 1e-4 for v in got.values()), got
    ctl = harness.compared(harness.train_numbers(
        ref.train_steps(params0, batches, model, opt, dtype=jnp.bfloat16),
        want), limits)
    assert any(v > lim for v, lim in ctl.values()), ctl


def test_counts_by_hand():
    model = {"family": "dlrm", "dense_dim": 2, "table_sizes": [5, 9],
             "emb_dim": 2, "num_collisions": 4, "bottom_mlp": [3],
             "top_mlp": [4]}
    # bottom 2-3-2: 2*(6+6)=24; 3 vectors, 3 pairs of 2-wide dots: 12;
    # top (3+2)-4-1: 2*(20+4)=48
    assert _counts.forward_flops(model) == 84
    dcn = {"family": "dcn", "dense_dim": 2, "table_sizes": [5, 9],
           "emb_dim": 2, "cross_layers": 2, "deep_mlp": [3]}
    # x0 = 2 + 2*2 = 6; cross 2*5*6 = 60; deep 6-3: 36; out (6+3)-1: 18
    assert _counts.forward_flops(dcn) == 114
    # S=9, c=4: m=3 remainder rows; ids 0,1,4,8 -> remainders {0,1,2},
    # quotients {0,1,2}: 6 rows
    assert _counts.touched_rows(model, 1, np.array([0, 1, 4, 8, 4])) == 6
    # S=5: m=2; ids {0,3}: remainders {0,1}, quotients {0,1}: 4 rows
    ids = np.array([[0, 0, 4], [3, 8, 8]])    # per request: f0 1 id, f1 2
    # f0 ids {0,3}: 4 rows; f1 ids {0,4,8}: remainders {0,1,2}, quotients
    # {0,1,2}: 6 rows; 10 rows * (2+3) B + 2 requests * 2 feats * 2 * 4 B
    assert _counts.embed_min_bytes(model, ids, [1, 2]) == 10 * 5 + 32
    # dense params: bottom 2*3+3 + 3*2+2 = 17; top 5*4+4 + 4*1+1 = 29
    assert _counts.dense_param_count(model) == 46
    sparse = np.array([[0, 0], [3, 4]])       # f0 {0,3}: 4 rows; f1 {0,4}: 4
    # Adagrad: 4 B * (2 + 2 + 2) per value; (8 rows * 2 + 46) values
    assert _counts.train_step_min_bytes(model, "adagrad", sparse) == 24 * 62
    assert _counts.train_step_min_bytes(model, "amsgrad", sparse) == 40 * 62
