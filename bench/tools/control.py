#!/usr/bin/env python3
"""Readings of a serving cell's control and of a planted fault, at the
cell's own size, on several seeds, for setting the limit of ``correct``.

    python3 bench/tools/control.py --workload <serving cell> --seeds 1,2,3

The control is the plain reference put in the program's place one
precision below what the configuration states: int4 tables and a
bfloat16 dense half (with each half alone, for reading).  The fault is
one answer swapped with another's.  The requests are those of a window
(the mix at its rate for ``run_seconds``), sampled as a run samples them.
Prints one JSON line per seed.  Training cells: ``train_readings.py``.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import generator, harness, spec  # noqa: E402
from bench.reference import api as ref  # noqa: E402


def serve_readings(cfg, mix, seed, seconds):
    import jax.numpy as jnp
    model = cfg["model"]
    proc = spec.process(mix["process"])
    reqs = proc.requests(mix, model, seed, generator.WINDOW, seconds)
    rng = np.random.default_rng([seed % (1 << 63), 7])
    rows = np.sort(rng.choice(len(reqs), min(harness.SAMPLE, len(reqs)),
                              replace=False))
    args = reqs.padded(rows)
    params = ref.make_params(seed, model)
    q8, q4 = ref.quantize_tables(params), ref.quantize_tables(params, bits=4)
    want = ref.serve_logits(q8, *args, model)
    out = {}
    for name, qp, dt in (("control", q4, jnp.bfloat16),
                         ("int4_only", q4, jnp.float32),
                         ("bf16_dense_only", q8, jnp.bfloat16)):
        out[name] = harness.gap_ratio(ref.serve_logits(qp, *args, model, dtype=dt),
                                      want)
    swapped = want.copy()
    swapped[[0, 1]] = want[[1, 0]]
    out["swap_fault"] = harness.gap_ratio(swapped, want)
    return {"score_gap": out}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    harness.check_devices(cell["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_mix(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        r = serve_readings(cfg, mix, seed, bench["run_seconds"])
        print(json.dumps({"seed": seed, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
