"""The benchmark's four-chip training cell on the CPU, at reduced size, on
four forced host devices in a process of its own (the test process keeps
its one device): the program's data-parallel step reaches ``correct``,
each fault planted in its timed path does not, and a four-chip cell
whose configuration names no compression policy is refused."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import bench_fixture as bf
from bench import harness
from bench.faults import FAULTS

HERE = Path(__file__).resolve().parent
BENCH = json.loads((bf.REPO / "BENCHMARK.json").read_text())
DP_CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] > 1]


def committed_limits(cell: str) -> dict:
    """The limits the cell's configuration file states (the reduced copy
    the runs use has the fixture's tight ones instead)."""
    config = next(w["config"] for w in BENCH["workloads"] if w["name"] == cell)
    path = next(c["file"] for c in BENCH["configs"] if c["name"] == config)
    return json.loads((bf.REPO / path).read_text())["limits"]


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(HERE), str(bf.REPO),
                                           str(bf.REPO / "src")]))
    p = subprocess.run([sys.executable, str(HERE / "dp_cpu_runs.py")],
                       cwd=bf.REPO, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", DP_CELLS)
def test_dp_cell_runs_at_reduced_size(runs, cell):
    line = runs[cell]["sound"]
    assert line["correct"], line
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["notes"]["compiles_in_window"] == 0


@pytest.mark.parametrize("cell,fault", [(c, f) for c in DP_CELLS
                                        for f in FAULTS])
def test_broken_dp_step_is_not_correct(runs, cell, fault):
    line = runs[cell][fault]
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", DP_CELLS)
def test_dp_cell_keeps_the_committed_limits(runs, cell):
    got = harness.compared(runs[cell]["sound"]["numbers"],
                           committed_limits(cell))
    assert got and all(v <= lim for v, lim in got.values()), got


@pytest.mark.parametrize("cell,fault", [(c, f) for c in DP_CELLS
                                        for f in FAULTS])
def test_broken_dp_step_fails_the_committed_limits(runs, cell, fault):
    limits = committed_limits(cell)
    got = harness.compared(runs[cell][fault]["numbers"], limits)
    assert set(got) == set(limits), got
    assert any(v > lim for v, lim in got.values()), got


@pytest.mark.parametrize("cell", DP_CELLS)
def test_dp_cell_without_compression_is_refused(runs, cell):
    assert runs[cell]["without_compress"].startswith("ValueError"), runs[cell]
