"""The harness end to end on the CPU at reduced sizes: every cell's loop
for about a second, the traced path, loading by name, the refusal without
a TPU, and the contract's rules on names and units."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import bench_fixture as bf
from bench import harness, spec, tracing

SEED = 2 ** 31 + 5
BENCH = json.loads((bf.REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
# cells on several chips run on forced host devices: test_bench_dp.py
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
SMALL_TRACE = Path(__file__).parent / "data" / "train_step.xplane.pb"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bf.reduced_copy(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_cell_runs_at_reduced_size(root, cell):
    run, line = harness.execute(cell, SEED, 1.0, False, root=root,
                                require_chip=False)
    assert line["correct"], line
    assert list(line)[-1] == "checks"
    want = {m["name"] for m in spec.metrics_for(BENCH, cell, False)}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert run.notes["compiles_in_window"] >= 0


@pytest.mark.parametrize("cell", ["dlrm-criteo-kaggle.serve-multihot-tail",
                                  "dcn-criteo-kaggle.train-b2048"])
def test_traced_run_reads_per_layer_metrics(root, cell, monkeypatch):
    # the CPU has no device plane: the traced window reads the small trace
    # recorded on the chip
    monkeypatch.setattr(tracing, "reduce_dir",
                        lambda d: tracing.reduce_profile(_small_trace()))
    run, line = harness.execute(cell, SEED, 1.0, True, root=root,
                                require_chip=False)
    assert line["correct"], line
    want = {m["name"] for m in spec.metrics_for(BENCH, cell, True)}
    got = set(line["metrics"])
    device_only = {"embed_roofline.tail", "train_step_roofline"}
    assert want - device_only <= got <= want
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert line["breakdown"]["device_ops"] and line["breakdown"]["idle_gaps"]


def _small_trace():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(SMALL_TRACE))


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=bf.REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    for d in BENCH["paths"]:
        shutil.copytree(bf.REPO / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bf.REPO / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_new_config_mix_and_metric_load_by_name_alone(tmp_path):
    """A later PR adds a cell by adding files and entries: nothing that is
    there changes."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "toy.json").write_text(
        json.dumps({"model": {"table_sizes": [7, 9]}}))
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(
        json.dumps({"kind": "serve", "rate_rps": 3.0}))
    (tmp_path / "bench" / "metrics" / "toy_metric.py").write_text(
        "def read(run):\n    return 2.0 * run.setup_s\n")
    bench = {"configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
             "workloads": [{"name": "toy.burst", "config": "toy",
                            "traffic": "burst", "chips": 1}],
             "end_to_end": [{"name": "setup_s"}],
             "per_layer": [{"name": "toy_metric.burst", "workloads": ["toy.burst"]},
                           {"name": "other.x", "workloads": ["elsewhere"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = spec.load_benchmark(tmp_path)
    cell = spec.workload(b, "toy.burst")
    assert spec.load_config(b, cell["config"], tmp_path)["model"]["table_sizes"] == [7, 9]
    assert spec.load_mix(cell["traffic"], tmp_path)["rate_rps"] == 3.0
    names = [m["name"] for m in spec.metrics_for(b, "toy.burst", True)]
    assert names == ["toy_metric.burst"]
    read = spec.reader(names[0], tmp_path)
    assert read(harness.Run({}, {}, {}, {}, setup_s=1.5)) == 3.0


BURSTY = '''"""On/off arrivals: the mix's rate for ``on_s``, then ``off_s`` of nothing."""
import numpy as np

from bench import generator
from bench.serve import OpenLoop

ENTRY = "serve"
Loop = OpenLoop


def requests(mix, model, seed, stream, seconds, rate=None):
    rng = generator.rng_for(seed, stream)
    on, off = mix["on_s"], mix["off_s"]
    live = seconds * on / (on + off)
    n = max(1, int(round((rate or mix["rate_rps"]) * live)))
    t = np.sort(rng.uniform(0.0, live, n))
    return generator.draw_requests(mix, model, rng, t + np.floor(t / on) * off)
'''


def test_new_traffic_process_runs_by_name_alone(tmp_path):
    """A mix whose arrival process is new code: its module and its mix are
    new files, the cell a new entry, and the harness drives it without an
    edit to any file that is there."""
    root = bf.reduced_copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "traffic" / "bursty.py").write_text(BURSTY)
    (root / "bench" / "traffic" / "serve-bursty.json").write_text(json.dumps(
        {"process": "bursty", "rate_rps": 200.0, "on_s": 0.1, "off_s": 0.15,
         "bag_lengths": bf.MULTIHOT, "skew": 1.5, "warm_s": 0.2}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "dlrm-criteo-kaggle.serve-bursty",
                               "config": "dlrm-criteo-kaggle",
                               "traffic": "serve-bursty", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    run, line = harness.execute("dlrm-criteo-kaggle.serve-bursty", SEED, 1.0,
                                False, root=root, require_chip=False)
    assert line["correct"], line
    assert line["attempted"] == 80 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s"}
    due = np.diff(run.serve["due"])
    assert due.max() > 0.15          # the off spans are there
    assert all(p.read_bytes() == b for p, b in before.items())


BAG_FAMILY = '''"""A throwaway reference family: DLRM's equations under a name of
its own."""
from bench.reference.dlrm import (dense_forward, dense_param_count,  # noqa: F401
                                  forward_flops, init)
'''

BAG_BATCHES = '''"""Training batches of bags: ``bag`` slots a feature, the first
``1 + floor(u * bag)`` of them live."""
import jax
import jax.numpy as jnp

ENTRY = "train"


def batch_fn(mix, model, seed):
    from bench.reference.common import key_from_seed
    sizes = jnp.asarray(model["table_sizes"], jnp.int32)[:, None]
    b, f, bag = mix["batch"], len(model["table_sizes"]), mix["bag"]
    base = key_from_seed(seed)

    @jax.jit
    def make_batch(step):
        kd, ks, kn, kl = jax.random.split(jax.random.fold_in(base, step), 4)
        u = jax.random.uniform(ks, (b, f, bag))
        live = 1 + jnp.floor(jax.random.uniform(kn, (b, f, 1)) * bag)
        return {"dense": jax.random.normal(kd, (b, model["dense_dim"])),
                "sparse": jnp.minimum(jnp.floor(u ** mix["skew"] * sizes)
                                      .astype(jnp.int32), sizes - 1),
                "mask": (jnp.arange(bag) < live).astype(jnp.float32),
                "label": jax.random.bernoulli(kl, 0.3, (b,)).astype(jnp.float32)}
    return make_batch
'''


def test_new_family_with_bag_batches_runs_by_new_files_alone(tmp_path,
                                                             monkeypatch):
    """A multi-hot training configuration of a new family: its reference
    module, traffic process, mix and configuration are new files, its cell
    a new entry, and the program a new arch whose loss reads the bags'
    mask.  The harness takes it with no file of ``bench/`` edited."""
    import dataclasses
    import types
    from repro import configs
    from repro.configs import dlrm_criteo
    from repro.models.dlrm import dlrm_forward
    from bench.reference.common import bce_with_logits

    root = bf.reduced_copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    fam = types.ModuleType("bench.reference.dlrm_bags")
    exec(BAG_FAMILY, fam.__dict__)
    monkeypatch.setitem(sys.modules, "bench.reference.dlrm_bags", fam)

    def api(cfg):
        def loss_fn(p, b):
            logits = dlrm_forward(p, b["dense"], b["sparse"], cfg,
                                  mask=b["mask"])
            loss = bce_with_logits(logits, b["label"])
            return loss, {"bce": loss}
        return dataclasses.replace(dlrm_criteo.api(cfg), loss_fn=loss_fn)
    monkeypatch.setitem(configs.ARCHS, "dlrm-bags", types.SimpleNamespace(
        config=dlrm_criteo.config, api=api))

    (root / "bench" / "traffic" / "bag_batches.py").write_text(BAG_BATCHES)
    (root / "bench" / "traffic" / "train-bags.json").write_text(json.dumps(
        {"process": "bag_batches", "batch": 32, "bag": 4, "skew": 1.5}))
    cfg = json.loads((root / "bench" / "configs" / "dlrm-criteo-kaggle.json")
                     .read_text())
    cfg["model"]["family"], cfg["program"]["arch"] = "dlrm_bags", "dlrm-bags"
    (root / "bench" / "configs" / "dlrm-bags.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dlrm-bags",
                             "file": "bench/configs/dlrm-bags.json"})
    bench["workloads"].append({"name": "dlrm-bags.train-bags",
                               "config": "dlrm-bags", "traffic": "train-bags",
                               "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    run, line = harness.execute("dlrm-bags.train-bags", SEED, 1.0, False,
                                root=root, require_chip=False)
    assert line["correct"], line
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    batch = spec.process("bag_batches", root).batch_fn(
        json.loads((root / "bench" / "traffic" / "train-bags.json").read_text()),
        cfg["model"], SEED)(0)
    assert batch["sparse"].ndim == 3 and 0 < float(batch["mask"].mean()) < 1
    assert all(p.read_bytes() == b for p, b in before.items())


def test_list_form_rowwise_cell_runs_by_new_files_alone(tmp_path,
                                                       monkeypatch):
    """A multi-hot configuration in MLPerf's form: fixed-width bags in the
    list form (``device_bags``), row-wise Adagrad on the tables, and a
    reference in blocks.  Its mix and configuration are new files, its
    cell a new entry, and the program a new arch whose loss pads the list
    form and whose optimizer is the program's ``partitioned`` row-wise
    one.  The harness takes it with no file of ``bench/`` edited."""
    import dataclasses
    import types
    import jax.numpy as jnp
    from repro import configs
    from repro.configs import dlrm_criteo
    from repro.models.dlrm import dlrm_forward
    from repro.optim.optimizers import adagrad, partitioned, rowwise_adagrad
    from bench.reference import api as ref
    from bench.reference.common import bce_with_logits

    root = bf.reduced_copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    monkeypatch.setattr(ref, "BLOCK", 16)     # the batch of 64 in 4 blocks

    def api(cfg):
        def loss_fn(p, b):
            widths = [x.shape[1] for x in b["sparse"]]
            idx = jnp.stack([jnp.pad(x, ((0, 0), (0, max(widths) - w)))
                             for x, w in zip(b["sparse"], widths)], axis=1)
            mask = (np.arange(max(widths)) < np.asarray(widths)[:, None])
            logits = dlrm_forward(p, b["dense"], idx, cfg,
                                  mask=jnp.asarray(mask, jnp.float32)[None])
            loss = bce_with_logits(logits, b["label"])
            return loss, {"bce": loss}
        opt = partitioned([(lambda path: "tables" in path,
                            rowwise_adagrad(1e-2))], adagrad(1e-2))
        return dataclasses.replace(dlrm_criteo.api(cfg), loss_fn=loss_fn,
                                   optimizer=opt)
    monkeypatch.setitem(configs.ARCHS, "dlrm-list", types.SimpleNamespace(
        config=dlrm_criteo.config, api=api))

    (root / "bench" / "traffic" / "train-list.json").write_text(json.dumps(
        {"process": "device_bags", "batch": 64, "bag_lengths": bf.MULTIHOT,
         "skew": 1.5, "label_noise": 0.5}))
    cfg = json.loads((root / "bench" / "configs" / "dlrm-criteo-kaggle.json")
                     .read_text())
    cfg["program"]["arch"] = "dlrm-list"
    cfg["train"] = {"name": "adagrad", "tables": "rowwise", "lr": 1e-2,
                    "eps": 1e-10}
    (root / "bench" / "configs" / "dlrm-list.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dlrm-list",
                             "file": "bench/configs/dlrm-list.json"})
    bench["workloads"].append({"name": "dlrm-list.train-list",
                               "config": "dlrm-list", "traffic": "train-list",
                               "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    run, line = harness.execute("dlrm-list.train-list", SEED, 1.0, False,
                                root=root, require_chip=False)
    assert line["correct"], line
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert all(v["value"] < 1e-4 for v in line["checks"].values()), line
    assert all(p.read_bytes() == b for p, b in before.items())


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contract():
    b = BENCH
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (bf.REPO / "bench" / "metrics"
                / f"{m['name'].split('.')[0]}.py").is_file()
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for c in b["configs"]:
        assert NAME.match(c["name"]) and (bf.REPO / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (bf.REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        reported = [m for m in b["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
        assert spec.metrics_for(b, w["name"], True)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_trace_reduction_by_hand():
    """The small trace is a cut of a traced DLRM training window on one
    v5e (``data/train_step.xplane.pbtxt``, made by
    ``bench/tools/cut_trace.py``): the end of one ``jit_step``, the next
    ``jit_make_batch``, twelve ops and the host spans over them.  In ns:

    * window: 569512837 .. 572744736, 3231899 long;
    * ops 2, 270+3, 2+3, 268, 95, 98+89, 237, 6+313 long (joined where one
      ends as the next starts): busy 1386;
    * idle 3230513: under ``bench.train_step`` (.. 571570936) 2056713,
      under ``bench.batch`` (571580066 .. 572743736) 1163670, the 9130
      between them and the 1000 after the last span under no span;
    * ``jit_step`` ends at 569519001, 6164 inside the window, and began
      before it; ``jit_make_batch`` runs 13206, all inside.
    """
    s = tracing.reduce_profile(_small_trace())
    assert s.devices == 1
    assert s.window_s == pytest.approx(3231899e-9, rel=1e-12)
    assert s.busy_s == pytest.approx(1386e-9, rel=1e-9)
    assert s.module_s == pytest.approx({"jit_step": 6164e-9,
                                        "jit_make_batch": 13206e-9}, rel=1e-9)
    assert s.module_calls == {"jit_make_batch": 1}
    assert dict(s.idle_gaps) == pytest.approx(
        {"bench.train_step": 2056713e-9, "bench.batch": 1163670e-9,
         "no bench span": 10130e-9}, rel=1e-9)
    # the ten longest of the twelve ops: the two 2 ns ones are left out
    assert len(s.device_ops) == 10
    assert sum(v for _, v in s.device_ops) == pytest.approx(1382e-9, rel=1e-9)
    assert s.device_ops[0][0] == "slice.26"       # the longest op, 313 ns


def test_exchange_and_mfu_readers_by_hand():
    """The four-chip readers on a made-up trace summary: the collectives'
    device time a step, the exchange's roofline share, and ``mfu`` over
    the cell's chips (one chip reads as it always did)."""
    assert [tracing.is_collective(op) for op in (
        "all-to-all.3", "all_to_all.7", "all_gather", "all-gather-start",
        "all-gather-done.12", "all-reduce.1", "reduce-scatter.4",
        "collective-permute-done.2", "fusion.3", "all-reduce-fusion.1",
        "copy-start.2")] == [True] * 8 + [False] * 3
    model = {"family": "dlrm", "dense_dim": 2, "table_sizes": [5, 9],
             "emb_dim": 2, "num_collisions": 4, "bottom_mlp": [3],
             "top_mlp": [4]}
    summary = tracing.TraceSummary(
        window_s=1.0, busy_s=0.5, devices=4, module_s={}, module_calls={},
        op_s={"fusion.1": 0.3, "all-to-all.2": 0.1, "all-gather-start.3": 0.05,
              "all-gather-done.3": 0.05}, device_ops=[], idle_gaps=[])
    peaks = {"ici_bytes_per_s": 1000.0, "bf16_flops_per_s": 1e6}
    run = harness.Run({"chips": 4}, {"model": model,
                                     "train": {"compress": "int8"}},
                      {}, peaks, trace=summary, window_s=2.0,
                      train={"traced_steps": [7, 8], "examples": 10})
    read = lambda n: spec.reader(n)(run)  # noqa: E731
    # 0.2 s of collectives a chip over 2 steps; fusion.1 is no collective
    assert read("collective_ms_per_step.dp4") == pytest.approx(100.0)
    # 102 B a step (test_exchange_bytes_by_hand), 2 steps, 0.2 s, 1000 B/s
    assert read("exchange_roofline.dp4") == pytest.approx(100 * 204 / 0.2 / 1000)
    # 84 FLOPs forward (test_counts_by_hand), x3, 5 examples/s, 4 chips
    assert read("mfu.dp4") == pytest.approx(100 * 5 * 252 / 4e6)
    run.cell = {"chips": 1}
    assert read("mfu.train") == pytest.approx(100 * 5 * 252 / 1e6)
    assert read("collective_ms_per_step.dp4") is None


@pytest.mark.parametrize("chips,module,least", [(1, "jit_step", 1440),
                                                (4, "jit__step", 1440 + 544)])
def test_train_step_roofline_by_hand(chips, module, least):
    """Two steps of a batch of two examples on the small model of
    ``test_counts_by_hand``: feature 0's ids 0, 1 touch 2 remainder rows
    and 1 quotient row, feature 1's ids 4, 8 touch 2 and 2; 7 rows of 2
    and 46 dense parameters, each read and written with its Adagrad
    accumulator and gradient, 24 B: 1440 B.  The data-parallel step (its
    module ``jit__step``) also reads and writes the f32 residual of all
    68 gradient elements: 544 B more."""
    model = {"family": "dlrm", "dense_dim": 2, "table_sizes": [5, 9],
             "emb_dim": 2, "num_collisions": 4, "bottom_mlp": [3],
             "top_mlp": [4]}
    train = {"name": "adagrad"}
    if chips > 1:
        train["compress"] = "int8"
    summary = tracing.TraceSummary(
        window_s=1.0, busy_s=0.5, devices=chips, module_s={module: 0.5},
        module_calls={module: 2}, op_s={}, device_ops=[], idle_gaps=[])
    batch = {"sparse": np.array([[0, 4], [1, 8]])}
    run = harness.Run({"chips": chips}, {"model": model, "train": train}, {},
                      {"hbm_bytes_per_s": 1000.0}, trace=summary,
                      train={"traced_steps": [5, 6], "step_module": module,
                             "batch_at": lambda s: batch})
    got = spec.reader("train_step_roofline")(run)
    assert got == pytest.approx(100 * least * 2 / 0.5 / 1000)
    run.train["step_module"] = "jit_other"
    assert spec.reader("train_step_roofline")(run) is None


def test_train_step_roofline_reads_list_form_and_rowwise_by_hand():
    """The reader takes a batch in the list form and a row-wise optimizer:
    feature 0's ids 0, 3 (one a bag) touch 4 rows, feature 1's 0, 4, 8
    (two a bag) 6, of 2 values each; a table row moves its values and
    gradients (16 B each) and one accumulator (8 B), the 46 dense
    parameters 24 B each: 400 + 1104 B a step."""
    model = {"family": "dlrm", "dense_dim": 2, "table_sizes": [5, 9],
             "emb_dim": 2, "num_collisions": 4, "bottom_mlp": [3],
             "top_mlp": [4]}
    train = {"name": "adagrad", "tables": "rowwise"}
    summary = tracing.TraceSummary(
        window_s=1.0, busy_s=0.5, devices=1, module_s={"jit_step": 0.5},
        module_calls={"jit_step": 2}, op_s={}, device_ops=[], idle_gaps=[])
    batch = {"sparse": [np.array([[0], [3]]), np.array([[0, 4], [8, 4]])]}
    run = harness.Run({"chips": 1}, {"model": model, "train": train}, {},
                      {"hbm_bytes_per_s": 1000.0}, trace=summary,
                      train={"traced_steps": [5, 6], "step_module": "jit_step",
                             "batch_at": lambda s: batch})
    got = spec.reader("train_step_roofline")(run)
    assert got == pytest.approx(100 * (400 + 1104) * 2 / 0.5 / 1000)
