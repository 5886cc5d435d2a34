"""Find a cell's pieces by name: ``BENCHMARK.json`` names the cells,
configurations and metrics; the files are looked up from those names.

* a configuration: the JSON file its ``file`` entry names;
* a traffic mix: ``bench/traffic/<traffic>.json``, whose ``"process"``
  names its traffic process ``bench/traffic/<process>.py`` (what a
  process module holds: ``bench/generator.py``);
* a metric: ``bench/metrics/<base>.py``, where ``<base>`` is the metric's
  name up to its first ``.`` (``host_ms_per_wave.tail`` and
  ``host_ms_per_wave.overload`` share one reader), with a function
  ``read(run) -> float | None``.

Adding a cell, a configuration, a mix or a metric is adding files and
entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = _named(bench["configs"], name, "config")
    return json.loads((Path(root) / entry["file"]).read_text())


def load_mix(name: str, root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: the end-to-end ones without
    tracing, the per-layer ones with it; a metric with a ``workloads`` key
    belongs to the cells it lists, one without it to every cell."""
    group = bench["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def _module(path: Path, name: str, what: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path} for {what}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT):
    base = name.split(".", 1)[0]
    return _module(Path(root) / "bench" / "metrics" / f"{base}.py",
                   f"bench_metric_{base}", f"metric {name!r}").read


def process(name: str, root: Path = ROOT):
    """The traffic process module ``bench/traffic/<name>.py``."""
    return _module(Path(root) / "bench" / "traffic" / f"{name}.py",
                   f"bench_traffic_{name}", f"traffic process {name!r}")
