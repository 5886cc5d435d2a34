"""A copy of the benchmark at the program's reduced sizes, in a temp dir,
for the CPU tests: the same harness, readers and references, with the
configurations cut to ``REDUCED_SIZES`` and short warm-ups."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

REDUCED_SIZES = [1000, 200, 50000, 12000, 31, 24, 12517, 633, 3, 931]
MULTIHOT = [3, 2, 1, 2, 6, 1, 12, 27, 3, 1]
LIMITS = {"score_gap": 1e-3, "loss_gap": 1e-3, "grad_gap": 1e-3,
          "change_gap": 1e-3}


def reduced_copy(dst: Path, rates=(100.0, 400.0), batch: int = 64) -> Path:
    """``dst`` gets BENCHMARK.json and bench/ with every configuration at
    reduced sizes; returns ``dst``."""
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        f = dst / c["file"]
        cfg = json.loads(f.read_text())
        cfg["program"]["reduced_sizes"] = True
        cfg["model"]["table_sizes"] = REDUCED_SIZES
        cfg["limits"] = dict(LIMITS)
        if "serve" in cfg:
            cfg["serve"]["max_batch"] = 8
        f.write_text(json.dumps(cfg))
    tdir = dst / "bench" / "traffic"
    for p in tdir.glob("*.json"):
        mix = json.loads(p.read_text())
        if mix["process"] == "open_poisson":
            mix["warm_s"] = 0.2
            if mix.get("bag_lengths"):
                mix["bag_lengths"], mix["rate_rps"] = MULTIHOT, rates[0]
            else:
                mix["rate_rps"] = rates[1]
        else:
            mix["batch"] = batch
        p.write_text(json.dumps(mix))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst
