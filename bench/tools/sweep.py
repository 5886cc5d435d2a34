#!/usr/bin/env python3
"""Find a serving cell's knee: one process, one set-up, then the cell's mix
at each offered rate for a span, draining between rates.

    python3 bench/tools/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 5,10,20

One JSON line per rate: offered and served requests per second, requests
still queued when the span closed, latency percentiles from the due time,
waves and their mean size.  The knee is the highest rate whose backlog
does not grow through the span.  Needs the chip, like ``bench/run.py``.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness, spec  # noqa: E402
from bench import serve as sv  # noqa: E402
from bench.reference import api as ref  # noqa: E402

DRAIN_LIMIT_S = 40.0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--cache-rows", type=int, default=None,
                    help="serve with this many cache rows instead (0: none)")
    ap.add_argument("--warm-s", type=float, default=None,
                    help="warm at the mix's rate this long instead")
    args = ap.parse_args()
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    t_start = time.monotonic()
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    harness.check_devices(cell["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_mix(cell["traffic"])
    proc = spec.process(mix["process"])
    cfg["program_cfg"], cfg["program_api"] = harness.program_config(cfg)
    if args.cache_rows is not None:
        cfg["serve"]["cache_rows"] = args.cache_rows
    if args.warm_s is not None:
        mix["warm_s"] = args.warm_s
    engine = sv.build_engine(cfg, ref.make_params(args.seed, cfg["model"]))
    sv.warm(engine, cfg, mix, proc, args.seed)
    print(json.dumps({"setup_s": time.monotonic() - t_start}), flush=True)
    counter = harness.CompileCounter()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = proc.requests(mix, cfg["model"], args.seed, 10 + k,
                             args.seconds, rate=rate)
        engine.reset_metrics()
        counter.count, counter.on = 0, True
        loop = proc.Loop(engine, reqs, time.monotonic())
        t1 = loop.t0 + args.seconds
        loop.run(t1)
        counter.on = False
        queued = loop.pending + (len(reqs) - loop.next)
        m = engine.metrics()
        loop.run(time.monotonic() + DRAIN_LIMIT_S, drain=True)
        lat = (loop.done - loop.due) * 1e3
        served = int(np.sum(loop.done <= t1))
        fin = lat[np.isfinite(lat)]
        print(json.dumps({
            "rate": rate, "served_rps": served / args.seconds,
            "queued_at_close": int(queued), "undrained": loop.pending,
            "p50_ms": float(np.percentile(fin, 50)) if len(fin) else None,
            "p99_ms": float(np.percentile(fin, 99)) if len(fin) else None,
            "waves": m["waves"], "mean_wave": float(np.mean(engine.wave_sizes or [0])),
            "wave_ms_p50": m["p50_ms"], "paths": m["paths"],
            "compiles": counter.count}), flush=True)
        if loop.pending or loop.next < len(reqs):
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
