"""Serving cells: ``RecsysEngine`` driven by a traffic process's client
(``OpenLoop``, the open-loop client, is here).

Set-up builds the engine as ``launch/serve.py`` does for the configuration's
flags, from weights the harness makes from the seed; warms every batch
bucket of the cell's bag bucket, then runs the mix at its rate for
``warm_s`` so the cache and the compiled shapes reach their steady state.
The window submits each request when it is due and steps the engine while
any request is outstanding; a request's latency runs from when it was due
to when its score is on the host.  After the window the client drains:
what the window left unsubmitted (a long step can hold the client past
the close) is submitted, and every answer is waited for.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from . import generator

DRAIN_S = 60.0          # an answer may come this long after the window
SLOW_STEP_S = 0.05      # engine steps longer than this are listed in notes


def _span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def build_engine(cfg: dict, params, *, obs=None):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.serve.cache import DeviceHotRowCache, HotRowCache
    from repro.serve.quantize import quantize_params
    from repro.serve.recsys import RecsysEngine

    s = cfg["serve"]
    qparams = quantize_params(params, mode=s["quantize"])
    cls = DeviceHotRowCache if s["cache_impl"] == "device" else HotRowCache
    cache = cls(capacity_rows=s["cache_rows"]) if s["cache_rows"] else None
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    return RecsysEngine(cfg["program_cfg"], qparams, max_batch=s["max_batch"],
                        cache=cache, mesh=mesh, batching=s["batching"],
                        max_inflight=s["max_inflight"], obs=obs)


class OpenLoop:
    """One client that submits ``reqs`` on their schedule and steps the
    engine; it records, per request, when it was submitted and when its
    score arrived (``nan`` until then), on the host's monotonic clock."""

    def __init__(self, engine, reqs: generator.Requests, t0: float,
                 annotate: bool = False):
        self.engine, self.reqs, self.t0 = engine, reqs, t0
        self.due = t0 + reqs.due
        self.submitted = np.full(len(reqs), np.nan)
        self.done = np.full(len(reqs), np.nan)
        self.score = np.full(len(reqs), np.nan, np.float32)
        self.row_of: dict[int, int] = {}
        self.next = 0
        self.annotate = annotate
        self.slow_steps: list[float] = []     # engine steps over SLOW_STEP_S

    @property
    def pending(self) -> int:
        return len(self.row_of)

    def _submit_due(self, now: float) -> None:
        eng, reqs = self.engine, self.reqs
        if self.next >= len(reqs) or self.due[self.next] > now:
            return
        with _span(self.annotate, "bench.submit"):      # one span a batch
            while self.next < len(reqs) and self.due[self.next] <= now:
                i = self.next
                uid = eng.submit(reqs.dense[i], reqs.bags(i))
                self.submitted[i] = time.monotonic()
                self.row_of[uid] = i
                self.next += 1

    def _step(self) -> None:
        t = time.monotonic()
        with _span(self.annotate, "bench.engine_step"):
            finished = self.engine.step()
        now = time.monotonic()
        if now - t > SLOW_STEP_S:
            self.slow_steps.append(now - t)
        for r in finished:
            # the client takes its answer: the engine keeps every finished
            # request in ``completed`` until someone removes it
            self.engine.completed.pop(r.uid, None)
            i = self.row_of.pop(r.uid, None)
            if i is not None:
                self.done[i] = now
                self.score[i] = r.score

    def run(self, until: float, drain: bool = False) -> None:
        """Serve until ``until`` (monotonic).  With ``drain``, submit what
        the schedule still holds as it falls due, and return as soon as
        every request has been submitted and answered."""
        while True:
            now = time.monotonic()
            if now >= until:
                return
            self._submit_due(now)
            if self.pending:
                self._step()
            elif self.next >= len(self.reqs):
                if drain:
                    return
                time.sleep(max(0.0, min(until - now, 1e-3)))
            else:
                wait = min(self.due[self.next], until) - now
                if wait > 0:
                    with _span(self.annotate, "bench.wait"):
                        time.sleep(wait)


def warm(engine, cfg: dict, mix: dict, proc, seed: int, phases=None) -> None:
    """Every pow2 batch bucket up to ``max_batch`` with the cell's own bags,
    then ``warm_s`` seconds of the mix at its rate, through its process
    ``proc``."""
    model, mb = cfg["model"], cfg["serve"]["max_batch"]
    sizes = [1 << k for k in range(mb.bit_length()) if 1 << k <= mb]
    reqs = generator.draw_requests(mix, model,
                                   generator.rng_for(seed, generator.WARM_BUCKETS),
                                   np.zeros(sum(sizes)))
    i = 0
    for b in sizes:
        for j in range(i, i + b):
            engine.submit(reqs.dense[j], reqs.bags(j))
        engine.run_until_drained()
        engine.completed.clear()
        i += b
    if phases is not None:
        phases.mark("warm_buckets")
    steady = proc.requests(mix, model, seed, generator.WARM, mix["warm_s"])
    loop = proc.Loop(engine, steady, time.monotonic())
    loop.run(loop.t0 + mix["warm_s"] + DRAIN_S, drain=True)
    engine.reset_metrics()
    if phases is not None:
        phases.mark("warm_steady")
