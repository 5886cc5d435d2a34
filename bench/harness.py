"""Run one cell once and make its result line.

The flow of every run: check the chips, set the compile cache, build the
cell's program from the seed and warm it (all of that is ``setup_s``),
measure for ``seconds``, wait for late answers, read the memory peak, with
``--trace 1`` trace a further steady window, free the program, run the
plain reference and compare.  Metrics come from the readers that
``spec.reader`` finds by name; the numbers compared for ``correct``, each
beside its limit, go last in the result line and on standard error.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Optional

import numpy as np

from . import generator, spec

TRACE_SETTLE_S = 0.5     # traced run: profiler on, before the window
TRACE_WINDOW_S = 2.0     # traced run: the window the device metrics read
SAMPLE = 512             # served answers compared with the reference
STAND_IN_KIND = "TPU v5 lite"   # peaks a run without the chip reads (tests)


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """What a run measured, for the metric readers."""
    cell: dict
    cfg: dict
    mix: dict
    peaks: dict
    devices: list = dataclasses.field(default_factory=list)
    setup_s: float = math.nan
    window_s: float = math.nan
    serve: Optional[dict] = None      # see serve_cell
    train: Optional[dict] = None      # see train_cell
    trace: Any = None                 # tracing.TraceSummary of the traced window
    checks: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    notes: dict = dataclasses.field(default_factory=dict)


class Phases:
    """Seconds of each set-up phase, from the process's start."""

    def __init__(self, t_start: float):
        self.last = time.monotonic()
        self.s = {"start_up": self.last - t_start}

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.s[name] = now - self.last
        self.last = now

    def done(self) -> dict:
        self.mark("rest")
        return {k: round(v, 3) for k, v in self.s.items()}


class GcWatch:
    """Collections of the oldest generation, and seconds spent in any
    collection, from its making to ``stop``."""

    def __init__(self):
        self.on, self.full, self.seconds, self._t = True, 0, 0.0, None
        gc.callbacks.append(self._seen)

    def stop(self) -> None:
        self.on = False
        gc.callbacks.remove(self._seen)

    def _seen(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.seconds += time.monotonic() - self._t
            self.full += info["generation"] == 2


class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache while
    ``on`` (``jax.monitoring``'s backend-compile event fires for both)."""

    def __init__(self):
        from jax._src import dispatch
        from jax import monitoring
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.on, self.count = False, 0
        monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.on and event == self.event:
            self.count += 1


def check_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked, {len(devs)} found")
    return devs[:chips]


def program_config(cfg: dict):
    """The program's own config for this configuration, checked against
    the sizes the configuration file states: every key of its ``model``
    that the program's config also has (lists as tuples), and the QR
    embedding."""
    from repro.configs import get_arch
    mod = get_arch(cfg["program"]["arch"])
    pcfg = mod.config(reduced=cfg["program"]["reduced_sizes"])
    m = cfg["model"]
    want = {k: tuple(v) if isinstance(v, list) else v for k, v in m.items()
            if hasattr(pcfg, k)}
    got = {k: getattr(pcfg, k) for k in want}
    emb = pcfg.embedding
    if (got != want or emb.kind != "qr" or emb.op != m["op"]
            or emb.num_collisions != m["num_collisions"]):
        raise ValueError(f"program config {got} {emb} differs from {want}")
    return pcfg, mod.api(pcfg)


def dp_compress(cell: dict, cfg: dict) -> Optional[str]:
    """The gradient compression policy a training cell runs under: a cell
    on several chips runs the program's data-parallel step, which needs
    the configuration's ``train.compress``; a one-chip cell runs the plain
    step, and a policy there would be ignored.  Either mismatch raises."""
    compress = cfg["train"].get("compress")
    if (cell["chips"] > 1) != (compress is not None):
        raise ValueError(
            f"cell {cell['name']!r} asks for {cell['chips']} chip(s) and its "
            f"configuration states train.compress {compress!r}: a "
            "data-parallel cell needs a policy, a one-chip cell none")
    return compress


def gap_ratio(prog: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap between the program's and the reference's values, over
    the reference's root mean square."""
    if not np.all(np.isfinite(prog)):
        return math.inf
    rms = float(np.sqrt(np.mean(np.square(ref.astype(np.float64)))))
    return float(np.max(np.abs(prog - ref))) / max(rms, 1e-30)


def leaf_gap(prog, ref, keep: np.ndarray, pick=np.max) -> float:
    """Gap between two per-leaf norms, per leaf against the larger of that
    leaf's reference norm and the median leaf's; ``pick`` takes the worst
    leaf (``np.max``) or the median one (``np.median``)."""
    prog, ref = np.asarray(prog), np.asarray(ref)
    if not np.all(np.isfinite(prog)):
        return math.inf
    med = float(np.median(ref[keep]))
    return float(pick(np.abs(prog - ref)[keep] / np.maximum(ref[keep], med)))


def kept_leaves(want: dict) -> np.ndarray:
    """Leaves whose first gradient in the reference is above a thousandth
    of the median leaf's; the others move under the optimizer by rounding
    alone and are left out of the gradient and change comparisons."""
    g = want["first_grad"]
    return g >= 1e-3 * np.median(g)


def loss_gaps(got: dict, want: dict) -> np.ndarray:
    """Relative gap of each step's loss."""
    lp, lr = np.asarray(got["losses"]), np.asarray(want["losses"])
    return np.where(np.isfinite(lp), np.abs(lp - lr) / np.abs(lr), math.inf)


def train_numbers(got: dict, want: dict) -> dict:
    """Every training number the program and the reference are compared
    by: the first step's relative loss gap; the worst leaf's gap in the
    norm of the first gradient; in the norm of the parameters' change after
    the first step; and after the third, by the worst leaf and by the
    median one.  A configuration's ``limits`` say which are compared
    (PERF.md gives the readings each was chosen from)."""
    keep = kept_leaves(want)
    return {"loss_gap": float(loss_gaps(got, want)[0]),
            "grad_gap": leaf_gap(got["first_grad"], want["first_grad"], keep),
            "change1_gap": leaf_gap(got["change1"], want["change1"], keep),
            "change_gap": leaf_gap(got["change"], want["change"], keep),
            "change_median_gap": leaf_gap(got["change"], want["change"], keep,
                                          np.median)}


def compared(numbers: dict, limits: dict) -> dict:
    """``{name: (value, limit)}`` for the numbers the limits name."""
    return {k: (numbers[k], lim) for k, lim in limits.items() if k in numbers}


# ---------------------------------------------------------------- serving

def serve_cell(run: Run, proc, seed: int, seconds: float, traced: bool,
               counter, t_start: float, keep_trace: Optional[str]) -> None:
    import jax
    from .reference import api as ref
    from . import serve as sv

    cfg, mix, model = run.cfg, run.mix, run.cfg["model"]
    obs = None
    if traced:
        from repro.obs import Obs
        obs = Obs(trace=True, collisions=False)
    phases = Phases(t_start)
    params = jax.block_until_ready(ref.make_params(seed, model))
    phases.mark("weights")
    engine = sv.build_engine(cfg, params, obs=obs)
    del params
    jax.block_until_ready(engine.params)
    phases.mark("quantize_and_engine")
    sv.warm(engine, cfg, mix, proc, seed, phases)
    reqs = proc.requests(mix, model, seed, generator.WINDOW, seconds)
    if obs is not None:
        obs.tracer.drain()
    gcw = GcWatch()
    counter.on = True
    t0 = time.monotonic()
    run.setup_s = t0 - t_start
    run.notes["setup_phases_s"] = phases.done()
    loop = proc.Loop(engine, reqs, t0)
    loop.run(t0 + seconds)
    t1 = t0 + seconds
    counter.on = False
    gcw.stop()
    unsubmitted = len(reqs) - loop.next
    m = engine.metrics()
    waves = [e["args"] for e in obs.tracer.drain() if e["name"] == "wave"] \
        if obs is not None else []
    stages = {}
    if obs is not None:
        for s in obs.registry.snapshot()["serve_stage_seconds"]["series"]:
            stages[s["labels"]["stage"]] = (s["sum"], s["count"])
    # every request of the schedule fell due inside the window: the drain
    # submits what the window did not and waits for every answer
    loop.run(t1 + sv.DRAIN_S, drain=True)
    due, done = loop.due, loop.done
    lat_ms = (done - due)[np.isfinite(done)] * 1e3
    run.window_s = seconds
    run.serve = {"due": due, "submitted": loop.submitted, "done": done,
                 "t0": t0, "t1": t1, "stages": stages, "waves": waves,
                 "live_slots": int(generator.bag_lengths(mix, model).sum())}
    run.attempted = len(reqs)
    run.failed = int(np.sum(np.isnan(done)))
    run.notes.update(paths=m["paths"], waves=m["waves"], buckets=m["buckets"],
                     compiles_in_window=counter.count,
                     unsubmitted_at_close=unsubmitted,
                     queued_at_close=int(np.sum(~(done <= t1))),
                     offered_rps=mix["rate_rps"],
                     latency_p99_max_ms=[round(float(np.percentile(lat_ms, q)), 3)
                                         for q in (99, 100)] if len(lat_ms) else None,
                     slow_steps_ms=[round(x * 1e3, 1) for x in loop.slow_steps],
                     gc_full_in_window=gcw.full,
                     gc_s_in_window=round(gcw.seconds, 4))
    if traced:
        serve_traced(run, engine, obs, proc, seed, keep_trace)
    run.notes["memory_peak_bytes"] = memory_peak(run.devices)

    rng = np.random.default_rng([seed % (1 << 63), 7])
    rows = np.sort(rng.choice(len(reqs), min(SAMPLE, len(reqs)), replace=False))
    got = loop.score[rows].astype(np.float64)
    del engine, loop
    gc.collect()
    want = ref.serve_logits(ref.quantize_tables(ref.make_params(seed, model)),
                            *reqs.padded(rows), model)
    run.checks.update(compared({"score_gap": gap_ratio(got, want)},
                               cfg["limits"]))


def serve_traced(run: Run, engine, obs, proc, seed: int, keep_trace) -> None:
    import jax
    from . import serve as sv
    from . import tracing
    span = TRACE_SETTLE_S + TRACE_WINDOW_S
    reqs = proc.requests(run.mix, run.cfg["model"], seed, generator.TRACED, span)
    trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
    obs.tracer.drain()
    obs.registry.reset(prefix="serve_")
    start_profiler(trace_dir)
    loop = proc.Loop(engine, reqs, time.monotonic(), annotate=True)
    loop.run(loop.t0 + TRACE_SETTLE_S)
    before = [e["args"] for e in obs.tracer.drain() if e["name"] == "wave"]
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        loop.run(loop.t0 + span)
    inside = [e["args"] for e in obs.tracer.drain() if e["name"] == "wave"]
    jax.profiler.stop_trace()
    loop.run(time.monotonic() + sv.DRAIN_S, drain=True)
    run.trace = reduce_trace(trace_dir, keep_trace, run.notes)
    first = sum(w["requests"] for w in before)
    run.serve["traced"] = {"reqs": reqs, "first_row": first, "waves": inside}


# ---------------------------------------------------------------- training

def train_program(run: Run, make_batch):
    """``(trainer, start)``: the program's step in a ``Trainer`` (which
    donates the state) driven by ``make_batch``, and ``start(params0) ->
    (state, params0)``, its first state with ``params0`` placed as the
    state's params are and left readable.  On one chip the plain step; on
    several, as ``repro.launch.train`` builds it for ``--compress-policy``:
    the data-parallel step over a ``data`` mesh of the cell's chips, the
    state replicated, each batch split over ``data`` where it is made."""
    import jax
    import jax.numpy as jnp
    from repro.train.loop import (TrainConfig, Trainer, init_dp_state,
                                  init_state, make_dp_train_step,
                                  make_train_step)
    api = run.cfg["program_api"]
    compress = dp_compress(run.cell, run.cfg)
    if compress is None:
        step = make_train_step(api.loss_fn, api.optimizer)

        def start(params0):
            return init_state(params0, api.optimizer), params0
    else:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((len(run.devices),), ("data",), devices=run.devices)
        rep = NamedSharding(mesh, PartitionSpec())
        step = make_dp_train_step(api.loss_fn, api.optimizer, mesh,
                                  compress=compress)
        # the state's params are copies, so the donated state owns them
        init = jax.jit(lambda p: init_dp_state(
            jax.tree.map(jnp.copy, p), api.optimizer, compress=compress),
            out_shardings=rep)

        def start(params0):
            params0 = jax.device_put(params0, rep)
            return init(params0), params0
        make_batch = jax.jit(make_batch, out_shardings=NamedSharding(
            mesh, PartitionSpec("data")))
    trainer = Trainer(step, TrainConfig(num_steps=0, log_every=1),
                      batch_at=make_batch)
    return trainer, start


def train_cell(run: Run, proc, seed: int, seconds: float, traced: bool,
               counter, t_start: float, keep_trace: Optional[str]) -> None:
    import jax
    import jax.numpy as jnp
    from .reference import api as ref
    from .reference.common import first_grad_norms

    cfg, mix, model, opt = run.cfg, run.mix, run.cfg["model"], run.cfg["train"]
    make_batch = proc.batch_fn(mix, model, seed)
    phases = Phases(t_start)
    params0 = jax.block_until_ready(ref.make_params(seed, model))
    phases.mark("weights")
    trainer, start = train_program(run, make_batch)
    state, params0 = start(params0)
    norms = jax.jit(lambda a, b: jnp.stack(
        [jnp.linalg.norm(x - y) for x, y in
         zip(jax.tree.leaves(a), jax.tree.leaves(b))]))
    losses = []
    for t in range(3):                       # set-up: the reference's steps
        state, met = trainer.train_step(state, trainer.batch_at(t))
        losses.append(float(met["loss"]))
        if t == 0:
            first = first_grad_norms(opt, state["opt"])
            change1 = np.asarray(norms(state["params"], params0))
    change = np.asarray(norms(state["params"], params0))
    del params0
    phases.mark("compile_and_first_3_steps")
    gcw = GcWatch()
    counter.on = True
    step, t0 = 3, time.monotonic()
    run.setup_s = t0 - t_start
    run.notes["setup_phases_s"] = phases.done()
    while time.monotonic() < t0 + seconds:
        state, met = trainer.train_step(state, trainer.batch_at(step))
        jax.block_until_ready(met["loss"])
        step += 1
    t1 = time.monotonic()
    counter.on = False
    gcw.stop()
    run.window_s = t1 - t0
    n = step - 3
    run.train = {"steps": n, "examples": n * mix["batch"]}
    run.attempted = n * mix["batch"]
    run.notes.update(steps_in_window=n, compiles_in_window=counter.count,
                     last_loss=float(met["loss"]), gc_full_in_window=gcw.full,
                     gc_s_in_window=round(gcw.seconds, 4))
    if traced:
        state, step = train_traced(run, trainer, state, step, keep_trace)
    run.notes["memory_peak_bytes"] = memory_peak(run.devices)
    batch_at = trainer.batch_at
    del state, met, trainer
    gc.collect()

    # the batches the program stepped on, whole, on the reference's device
    t_ref = time.monotonic()
    batches = [jax.device_get(batch_at(t)) for t in range(3)]
    want = ref.train_steps(ref.make_params(seed, model), batches, model, opt,
                           replicas=run.cell["chips"])
    run.notes["reference_s"] = round(time.monotonic() - t_ref, 3)
    got = {"losses": losses, "first_grad": first, "change1": change1,
           "change": change}
    numbers = train_numbers(got, want)
    run.checks.update(compared(numbers, cfg["limits"]))
    run.notes["not_compared"] = {k: v for k, v in numbers.items()
                                 if k not in run.checks}
    run.notes["loss_gap_per_step"] = [float(x) for x in loss_gaps(got, want)]
    run.notes["leaves_left_out"] = int(np.sum(~kept_leaves(want)))


def train_traced(run: Run, trainer, state, step: int, keep_trace):
    import jax
    from . import tracing
    trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")

    def steps_until(t_end, annotate):
        nonlocal state, step
        first = step
        while time.monotonic() < t_end:
            with jax.profiler.TraceAnnotation("bench.batch"):
                batch = trainer.batch_at(step)
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, met = trainer.train_step(state, batch)
                jax.block_until_ready(met["loss"])
            step += 1
        return list(range(first, step))

    start_profiler(trace_dir)
    steps_until(time.monotonic() + TRACE_SETTLE_S, True)
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        inside = steps_until(time.monotonic() + TRACE_WINDOW_S, True)
    jax.profiler.stop_trace()
    run.trace = reduce_trace(trace_dir, keep_trace, run.notes)
    run.train["traced_steps"] = inside
    run.train["batch_at"] = trainer.batch_at
    # the step's XLA module: jit_step on one chip, jit__step data-parallel
    run.train["step_module"] = f"jit_{trainer.train_step.__name__}"
    return state, step


# ---------------------------------------------------------------- result

def start_profiler(trace_dir: str) -> None:
    """The profiler without its Python function tracer, which slows every
    Python call of the host loop many times over; the ``bench.*`` spans and
    the runtime's own host events stay."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def reduce_trace(trace_dir: str, keep_trace: Optional[str], notes: dict):
    """The trace's summary; the device seconds of its busiest programs go
    into the notes.  The trace is deleted unless ``keep_trace`` asked for
    it to be kept there."""
    from . import tracing
    summary = tracing.reduce_dir(trace_dir)

    def top(d):
        return dict(sorted(d.items(), key=lambda kv: -kv[1])[:tracing.TOP])
    notes["module_s"] = top(summary.module_s)
    if tracing.collective_ops(summary):
        notes["collective_ops_s"] = top(tracing.collective_ops(summary))
    if keep_trace is None:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    return summary


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def peaks_for(kind: str, root: Path) -> dict:
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


def execute(name: str, seed: int, seconds: float, traced: bool, *,
            root: Path = spec.ROOT, t_start: Optional[float] = None,
            require_chip: bool = True,
            keep_trace: Optional[str] = None) -> tuple[Run, dict]:
    """One run of cell ``name``; returns the ``Run`` and its result line.
    With ``keep_trace`` a traced run keeps its profiler trace there."""
    t_start = time.monotonic() if t_start is None else t_start
    bench = spec.load_benchmark(root)
    cell = spec.workload(bench, name)
    import jax
    devices = check_devices(cell["chips"]) if require_chip \
        else jax.devices()[:cell["chips"]]
    if len(devices) < cell["chips"]:
        raise NoChip(f"{cell['chips']} devices asked, {len(devices)} found")
    cfg = spec.load_config(bench, cell["config"], root)
    mix = spec.load_mix(cell["traffic"], root)
    proc = spec.process(mix["process"], root)
    cfg["program_cfg"], cfg["program_api"] = program_config(cfg)
    kind = devices[0].device_kind if require_chip else STAND_IN_KIND
    run = Run(cell, cfg, mix, peaks_for(kind, root), devices)
    counter = CompileCounter()
    BODIES[proc.ENTRY](run, proc, seed, seconds, traced, counter, t_start,
                       keep_trace)

    metrics = {}
    for m in spec.metrics_for(bench, name, traced):
        value = spec.reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.notes.get("memory_peak_bytes", 0)}
    line = {"correct": correct(run), "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.device_ops,
                             "idle_gaps": run.trace.idle_gaps}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in run.checks.items()}
    return run, line


BODIES = {"serve": serve_cell, "train": train_cell}


def correct(run: Run) -> bool:
    return (run.failed == 0 and bool(run.checks)
            and all(math.isfinite(v) and v <= lim
                    for v, lim in run.checks.values()))


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="with --trace 1, keep the profiler trace in DIR")
    args = ap.parse_args(argv)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()          # <checkout>/.jax_cache unless set outside
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        run, line = execute(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_start=t_start,
                            keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print("bench: " + json.dumps(run.notes, default=str), flush=True)
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
