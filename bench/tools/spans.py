#!/usr/bin/env python3
"""Where a cell's device idle time goes, by the program's own spans, and
what recording those spans costs.

    python3 bench/tools/spans.py --workload <cell> --seed <n> \
        [--seconds 20] [--pairs 3] [--stall-spans 0]

One set-up as ``bench/run.py`` makes it, then:

1. cost: ``--pairs`` rounds of ``--seconds`` windows; each window's
   end-to-end metric comes from the benchmark's own reader.  Serving: a
   pair a round on the same traffic (its own seed), one with the engine's
   spans recording (``Obs(trace=True)``) and one without, alternating
   which goes first.  Training: three windows a round, in an order that
   rotates: ``bare`` (``Trainer.train_step``, then waiting on the loss, as
   ``bench/run.py --trace 0`` runs it), ``step`` (``Trainer.step`` with
   the tracer off: the same calls, the caller holding the old state until
   the loss is ready) and ``spans`` (``Trainer.step`` recording its
   spans); ``step`` against ``spans`` is the spans' cost, ``bare`` against
   ``step`` what no span changes;
2. join: a traced window as ``bench/run.py --trace 1`` makes it (settle,
   then ``bench.window``, the same ``bench.*`` host spans), with
   ``Tracer.anchor()`` right after the profiler starts, reduced by
   ``bench/program_trace.py``: the device's idle time by the innermost
   program or ``bench.*`` span, per wave or per step, and for training the
   step's device time by named scope;
3. stalls (serving, ``--stall-spans`` > 0): traced windows of
   ``--seconds``; for every ``bench.engine_step`` longer than 50 ms, the
   program spans that overlap it most and the device time inside it.

One JSON line per reading on standard output.  Needs the chip, like
``bench/run.py``.
"""

import argparse
import gc
import json
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generator, harness, program_trace, spec, tracing  # noqa: E402
from bench import serve as sv  # noqa: E402
from bench.reference import api as ref  # noqa: E402

SLOW_NS = 50_000_000
FLUSH = "serve.flush"
TRAIN_MODES = ("bare", "step", "spans")
TOP = 6


def emit(**kw) -> None:
    print(json.dumps(kw, default=float), flush=True)


def read_metric(name: str, **run) -> float:
    return spec.reader(name)(SimpleNamespace(**run))


class GcPauses:
    """Start (monotonic), seconds and generation of every collection from
    its making to ``stop``."""

    def __init__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._seen)

    def stop(self) -> None:
        gc.callbacks.remove(self._seen)

    def _seen(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.pauses.append((self._t, time.monotonic() - self._t,
                                info["generation"]))
            self._t = None


def slow_spans(events, t0_tracer: float, pauses) -> list:
    """``[span, ms, ms of collections inside it, oldest generation]`` of
    every program host span longer than ``SLOW_NS``."""
    out = []
    for e in events:
        if e.get("cat") != "host" or e["dur"] * 1e3 < SLOW_NS:
            continue
        s = t0_tracer + e["ts"] * 1e-6
        t = s + e["dur"] * 1e-6
        inside = [(min(t, ps + pd) - max(s, ps), g) for ps, pd, g in pauses
                  if min(t, ps + pd) > max(s, ps)]
        out.append([e["name"], round(e["dur"] * 1e-3, 1),
                    round(sum(c for c, _ in inside) * 1e3, 1),
                    max((g for _, g in inside), default=None)])
    return out


def profile(trace_dir: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(tracing.find_xplane(trace_dir))


# ---------------------------------------------------------------- serving

def serve_window(engine, proc, cfg, mix, seed, seconds, on, obs, metrics,
                 counter) -> dict:
    """One untraced window of ``seconds`` on stream ``WINDOW`` of ``seed``,
    with the engine's spans recording or not; the cell's end-to-end
    ``metrics`` and the client's lag.  Where the cell's metric counts the
    drain (``serve_p50_ms``) every answer is waited for, as ``bench/run.py``
    does; else the backlog is dropped after the window."""
    engine._obs = obs if on else None
    engine.reset_metrics()
    obs.registry.reset(prefix="serve_")
    obs.tracer.drain()
    reqs = proc.requests(mix, cfg["model"], seed, generator.WINDOW, seconds)
    gcp, counter.on, counter.count = GcPauses(), True, 0
    t0 = time.monotonic()
    loop = proc.Loop(engine, reqs, t0)
    loop.run(t0 + seconds)
    t1 = t0 + seconds
    counter.on = False
    gcp.stop()
    events = obs.tracer.drain()
    if "serve_p50_ms" in metrics:
        loop.run(t1 + sv.DRAIN_S, drain=True)
    else:
        engine._queue.clear()
        engine.run_until_drained()
        engine.completed.clear()
    run = dict(serve={"due": loop.due, "submitted": loop.submitted,
                      "done": loop.done, "t0": t0, "t1": t1},
               window_s=seconds)
    out = {"spans": on, "seed": seed,
           "waves_traced": sum(e["name"] == "wave" for e in events),
           "slow_steps_ms": [round(x * 1e3, 1) for x in loop.slow_steps],
           "slow_spans": slow_spans(events, obs.tracer._t0, gcp.pauses),
           "gc_full": sum(g == 2 for _, _, g in gcp.pauses),
           "gc_s": sum(d for _, d, _ in gcp.pauses),
           "gc_over_50ms": [[round(d * 1e3, 1), g] for _, d, g in gcp.pauses
                            if d * 1e9 >= SLOW_NS],
           "compiles": counter.count,
           "unsubmitted_at_close": len(reqs) - loop.next}
    for m in metrics + ["client_lag_p99_ms"]:
        out[m] = read_metric(m, **run)
    engine._obs = obs
    return out


def serve_traced(engine, proc, cfg, mix, seed, seconds, obs, settle):
    """A traced window (profiler on, anchor, ``settle`` s, then
    ``bench.window`` for ``seconds``); the profile and the tracer's
    events of it, and the registry's stage sums."""
    import jax
    reqs = proc.requests(mix, cfg["model"], seed, generator.TRACED,
                         settle + seconds)
    trace_dir = tempfile.mkdtemp(prefix="bench_spans_")
    engine.reset_metrics()
    obs.tracer.drain()
    harness.start_profiler(trace_dir)
    obs.tracer.anchor()
    loop = proc.Loop(engine, reqs, time.monotonic(), annotate=True)
    loop.run(loop.t0 + settle)
    obs.registry.reset(prefix="serve_")
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        loop.run(loop.t0 + settle + seconds)
    events = obs.tracer.drain()
    stages = {s["labels"]["stage"]: (s["sum"], s["count"]) for s in
              obs.registry.snapshot()["serve_stage_seconds"]["series"]}
    jax.profiler.stop_trace()
    loop.run(time.monotonic() + sv.DRAIN_S, drain=True)
    pd = profile(trace_dir)
    import shutil
    shutil.rmtree(trace_dir, ignore_errors=True)
    return pd, events, stages, loop


def serve_join(engine, proc, cfg, mix, seed, obs) -> None:
    from repro.serve.recsys import HOST_SPANS
    pd, events, stages, _ = serve_traced(engine, proc, cfg, mix, seed,
                                         harness.TRACE_WINDOW_S, obs,
                                         harness.TRACE_SETTLE_S)
    j = program_trace.reduce_joined(pd, events)
    waves = j.ends_in_window.get("wave", 0)
    exposed = sum(v for k, v in j.program_idle.items()
                  if k in HOST_SPANS and k != FLUSH)
    per_wave = defaultdict(float)
    for e in events:
        if e.get("cat") == "host":
            per_wave[e["name"]] += e["dur"] * 1e-3
    n = max(1, sum(e["name"] == "wave" for e in events))
    host = sum(stages[s][0] for s in ("pad", "probe", "dense", "miss_gather"))
    emit(reading="join", window_s=j.window_s, busy_s=j.busy_s,
         idle_share=100 * (1 - j.busy_s / j.window_s), waves=waves,
         exposed_host_ms_per_wave=1e3 * exposed / max(1, waves),
         host_ms_per_wave=1e3 * host / max(1, stages["pad"][1]),
         span_ms_per_wave={k: v / n for k, v in per_wave.items()},
         program_idle_s=j.program_idle, idle_gaps=j.idle_gaps)


def stalls(engine, proc, cfg, mix, seed, seconds, obs, k) -> None:
    """Slow engine steps of one traced window, and what the program was
    doing through them."""
    pd, events, _, loop = serve_traced(engine, proc, cfg, mix, seed + 1 + k,
                                       seconds, obs, 0.0)
    host = program_trace.host_events(pd)
    offset = program_trace.anchor_offset_ns(host, events)
    prog = program_trace.joined_spans(events, offset)
    ops = [(ev.start_ns, ev.end_ns) for plane in pd.planes
           if plane.name.startswith("/device:") and "CPU" not in plane.name
           for line in plane.lines if line.name == "XLA Ops"
           for ev in line.events]
    slow = [(s, e) for n, s, e in host
            if n == "bench.engine_step" and e - s >= SLOW_NS]
    emit(reading="stalls", span=k, seconds=seconds, slow_steps=len(slow),
         client_ms=[round(x * 1e3, 1) for x in loop.slow_steps])
    for s, e in slow:
        over = defaultdict(float)
        for n, ps, pe in prog:
            c = min(e, pe) - max(s, ps)
            if c > 0:
                over[n] += c * 1e-6
        runtime = defaultdict(float)
        for n, hs, he in host:
            c = min(e, he) - max(s, hs)
            if c > 0 and not n.startswith(("bench.", "obs.")):
                runtime[n] += c * 1e-6
        busy = sum(min(e, de) - max(s, ds) for ds, de in ops
                   if min(e, de) > max(s, ds))
        emit(reading="stall", span=k, step_ms=(e - s) * 1e-6,
             device_busy_ms=busy * 1e-6,
             program_ms=sorted(over.items(), key=lambda kv: -kv[1])[:TOP],
             runtime_ms=sorted(runtime.items(), key=lambda kv: -kv[1])[:TOP])


def serve_main(args, cfg, mix, proc, metrics) -> None:
    from repro.obs import Obs
    obs = Obs(trace=True)
    counter = harness.CompileCounter()
    engine = sv.build_engine(cfg, ref.make_params(args.seed, cfg["model"]),
                             obs=obs)
    sv.warm(engine, cfg, mix, proc, args.seed)
    emit(reading="setup_done")
    for p in range(args.pairs):
        seed = args.seed + 100 + p
        for on in ((False, True) if p % 2 == 0 else (True, False)):
            emit(reading="cost", **serve_window(engine, proc, cfg, mix, seed,
                                                args.seconds, on, obs,
                                                metrics, counter))
    serve_join(engine, proc, cfg, mix, args.seed, obs)
    for k in range(args.stall_spans):
        stalls(engine, proc, cfg, mix, args.seed, args.seconds, obs, k)


# ---------------------------------------------------------------- training

def train_main(args, cfg, mix, proc, metrics) -> None:
    import jax
    from repro.obs import Obs
    from repro.train.loop import TrainConfig, Trainer, init_state, make_train_step
    api = cfg["program_api"]
    make_batch = proc.batch_fn(mix, cfg["model"], args.seed)
    state = init_state(ref.make_params(args.seed, cfg["model"]), api.optimizer)
    obs = Obs(trace=True)
    trainer = Trainer(make_train_step(api.loss_fn, api.optimizer),
                      TrainConfig(num_steps=0, log_every=1),
                      batch_at=make_batch, obs=obs)
    step = 0
    for _ in range(3):
        state, met = trainer.train_step(state, trainer.batch_at(step))
        step += 1
    jax.block_until_ready(met["loss"])
    emit(reading="setup_done")
    for p in range(args.pairs):
        for mode in TRAIN_MODES[p % 3:] + TRAIN_MODES[:p % 3]:
            trainer._obs = obs if mode == "spans" else None
            obs.tracer.drain()
            n, t0 = 0, time.monotonic()
            while time.monotonic() < t0 + args.seconds:
                batch = trainer.batch_at(step)
                if mode == "bare":
                    state, met = trainer.train_step(state, batch)
                    jax.block_until_ready(met["loss"])
                else:
                    state, met = trainer.step(state, batch)
                step += 1
                n += 1
            t1 = time.monotonic()
            emit(reading="cost", mode=mode, round=p, steps=n,
                 events=len(obs.tracer.drain()),
                 **{m: read_metric(m, train={"examples": n * mix["batch"]},
                                   window_s=t1 - t0) for m in metrics})
    trainer._obs = obs

    trace_dir = tempfile.mkdtemp(prefix="bench_spans_")

    def steps_until(t_end):
        nonlocal state, step
        while time.monotonic() < t_end:
            with jax.profiler.TraceAnnotation("bench.batch"):
                batch = trainer.batch_at(step)
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, _ = trainer.step(state, batch)
            step += 1

    obs.tracer.drain()
    harness.start_profiler(trace_dir)
    obs.tracer.anchor()
    steps_until(time.monotonic() + harness.TRACE_SETTLE_S)
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        steps_until(time.monotonic() + harness.TRACE_WINDOW_S)
    jax.profiler.stop_trace()
    events = obs.tracer.drain()
    hlo = trainer.train_step.lower(state, trainer.batch_at(step)).compile().as_text()
    pd = profile(trace_dir)
    j = program_trace.reduce_joined(pd, events, program_trace.hlo_op_names(hlo))
    steps = j.ends_in_window.get("train.wait", 0)
    total = sum(j.step_scope_s.values())
    per_step = defaultdict(list)
    for e in events:
        per_step[e["name"]].append(e["dur"] * 1e-3)
    emit(reading="join", window_s=j.window_s, busy_s=j.busy_s,
         idle_share=100 * (1 - j.busy_s / j.window_s), steps=steps,
         exposed_dispatch_ms_per_step=1e3 * j.program_idle.get(
             "train.dispatch", 0.0) / max(1, steps),
         optimizer_device_share=100 * j.step_scope_s.get("optimizer", 0.0)
         / total if total else None,
         step_phase_s=j.step_scope_s,
         span_ms_median={k: statistics.median(v) for k, v in per_step.items()},
         program_idle_s=j.program_idle, idle_gaps=j.idle_gaps)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--stall-spans", type=int, default=0)
    args = ap.parse_args()
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    harness.check_devices(cell["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the step's scopes are read from its compiled HLO's metadata, which a
    # cache keyed without metadata may hand back from another version of
    # the program (one compiled without the scopes)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_mix(cell["traffic"])
    proc = spec.process(mix["process"])
    cfg["program_cfg"], cfg["program_api"] = harness.program_config(cfg)
    metrics = [m["name"] for m in spec.metrics_for(bench, args.workload, False)
               if m["name"] != "setup_s"]
    emit(reading="cell", workload=args.workload, seed=args.seed)
    (serve_main if proc.ENTRY == "serve" else train_main)(args, cfg, mix, proc,
                                                          metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())
