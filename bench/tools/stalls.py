#!/usr/bin/env python3
"""What runs during a serving cell's long engine steps: one set-up, then
the cell's mix at its rate under the profiler (Python tracer off) for a
few spans; for every ``bench.engine_step`` host span longer than
``--slow-ms``, the host events that overlap it most, on every host thread,
and the device time inside it.

    python3 bench/tools/stalls.py --workload <serving cell> --seed <n> \
        --seconds 20 --spans 2

One JSON line per slow step.  Needs the chip, like ``bench/run.py``.
"""

import argparse
import json
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec, tracing  # noqa: E402
from bench import serve as sv  # noqa: E402
from bench.reference import api as ref  # noqa: E402

TOP = 15


def slow_steps(pd, slow_ns: int) -> list[dict]:
    host, device = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((line.name, ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns) for ev in line.events)
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                                  for ev in line.events)
    out = []
    for _, name, s, e in host:
        if name != "bench.engine_step" or e - s < slow_ns:
            continue
        seen = defaultdict(int)
        for line, other, hs, he in host:
            c = min(e, he) - max(s, hs)
            if c > 0 and other != "bench.engine_step":
                seen[f"{line} | {other}"] += c
        busy = sum(min(e, de) - max(s, ds) for ds, de in device
                   if min(e, de) > max(s, ds))
        out.append({"step_ms": (e - s) * 1e-6, "device_busy_ms": busy * 1e-6,
                    "host": [[k, v * 1e-6] for k, v in sorted(
                        seen.items(), key=lambda kv: -kv[1])[:TOP]]})
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, default=1)
    ap.add_argument("--slow-ms", type=float, default=50.0)
    args = ap.parse_args()
    import jax
    from jax.profiler import ProfileData
    from repro.launch.compile_cache import enable_compile_cache
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    harness.check_devices(cell["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_mix(cell["traffic"])
    proc = spec.process(mix["process"])
    cfg["program_cfg"], cfg["program_api"] = harness.program_config(cfg)
    engine = sv.build_engine(cfg, ref.make_params(args.seed, cfg["model"]))
    sv.warm(engine, cfg, mix, proc, args.seed)
    for k in range(args.spans):
        reqs = proc.requests(mix, cfg["model"], args.seed, 20 + k, args.seconds)
        trace_dir = tempfile.mkdtemp(prefix="bench_stalls_")
        harness.start_profiler(trace_dir)
        loop = proc.Loop(engine, reqs, time.monotonic(), annotate=True)
        loop.run(loop.t0 + args.seconds)
        jax.profiler.stop_trace()
        loop.run(time.monotonic() + sv.DRAIN_S, drain=True)
        steps = slow_steps(ProfileData.from_file(tracing.find_xplane(trace_dir)),
                           int(args.slow_ms * 1e6))
        print(json.dumps({"span": k, "slow_steps": len(steps),
                          "client_ms": [round(x * 1e3, 1) for x in loop.slow_steps]}),
              flush=True)
        for st in steps:
            print(json.dumps({"span": k, **st}), flush=True)
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
