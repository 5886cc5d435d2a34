"""Runs of the benchmark's four-chip training cells at reduced size on four
forced host devices, for ``test_bench_dp.py``: each cell as it is, with
each fault planted in its timed path, and with its configuration's
compression policy taken away.  Run as a script in a process of its own
(``XLA_FLAGS`` must force the devices before JAX starts); prints one JSON
object, the last line of its output."""

import json
import sys
import tempfile
from pathlib import Path

import bench_fixture as bf
from bench import faults, harness

SEED = 2 ** 31 + 13


def line_of(root, cell, traced=False):
    """The cell's result line, with every training number the run read
    (``numbers``, compared or not) and the window's compile and step
    counts."""
    run, line = harness.execute(cell, SEED, 0.5, traced, root=root,
                                require_chip=False)
    numbers = dict(run.notes["not_compared"],
                   **{k: v for k, (v, _) in run.checks.items()})
    return dict(line, numbers=numbers,
                notes={k: run.notes[k] for k in
                       ("compiles_in_window", "steps_in_window")})


def main() -> int:
    root = bf.reduced_copy(Path(tempfile.mkdtemp(prefix="bench_dp_")))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = [w for w in bench["workloads"] if w["chips"] > 1]
    out = {}
    for w in cells:
        name = w["name"]
        out[name] = {"sound": line_of(root, name)}
        for fault in faults.FAULTS:
            with faults.plant(fault):
                out[name][fault] = line_of(root, name)
        cfg_file = root / next(c["file"] for c in bench["configs"]
                               if c["name"] == w["config"])
        cfg = json.loads(cfg_file.read_text())
        del cfg["train"]["compress"]
        cfg_file.write_text(json.dumps(cfg))
        try:
            line_of(root, name)
            out[name]["without_compress"] = "ran"
        except ValueError as e:
            out[name]["without_compress"] = f"ValueError: {e}"
    print(json.dumps(out, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
