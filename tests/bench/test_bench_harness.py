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
SMALL_TRACE = Path(__file__).parent / "data" / "train_step.xplane.pb"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bf.reduced_copy(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", CELLS)
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
