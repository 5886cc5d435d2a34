"""Span tracer with Chrome-trace / Perfetto JSON export.

``Tracer`` records *complete* events (name, begin, duration) on a
monotonic clock, either through the ``span("stage")`` context manager
(nesting tracked per thread) or through ``complete(name, t0, dur)`` when
the caller already owns the boundary timestamps (the serving engine's
usage: its stage timers double as the trace events, so tracing adds no
clock read per stage).

Every event carries a Chrome-trace ``cat``:

* ``"host"`` — an interval in which the recording thread was doing this
  work (the engine's ``serve.*`` stages, the trainer's ``train.*`` spans);
* ``"interval"`` — a span that overlaps other work (a whole serving
  ``wave``, a request's ``queue_wait``, a wave ``inflight`` on the device).

Export is the Chrome Trace Event JSON format (``{"traceEvents": [...]}``
with ``ph: "X"`` complete events, microsecond timestamps), which
``chrome://tracing`` and https://ui.perfetto.dev both load directly.

**Joining a profiler trace.**  The events are on the tracer's own clock,
which is not the clock of a ``jax.profiler`` trace.  ``anchor()``, called
while the profiler runs, enters a ``TraceAnnotation`` named
``ANCHOR`` and records an ``ANCHOR`` event at a clock read made inside it;
the offset between the two timestamps of that one moment maps every event
onto the profiler's timeline (both clocks run at the same rate, so one
anchor is enough).

The tracer is append-only and bounded (``max_events``, oldest dropped);
``drain()`` hands the events over and clears, so a long-running engine
can stream trace chunks without unbounded growth.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time

__all__ = ["Tracer", "ANCHOR"]

ANCHOR = "obs.clock_anchor"


class Tracer:
    def __init__(self, *, max_events: int = 200_000, pid: int = 0):
        self.max_events = max_events
        self.pid = pid
        self.events: list[dict] = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self._t0 = time.monotonic()

    # ------------------------------------------------------------- recording

    def _depth(self) -> int:
        return getattr(self._local, "depth", 0)

    def complete(self, name: str, t0: float, dur_s: float, *,
                 cat: str = "host", **args) -> None:
        """Record one complete event from caller-owned monotonic
        timestamps (``t0`` from ``time.monotonic()``, duration in
        seconds).  The hot-path entry point: no clock reads here."""
        ev = {"name": name, "cat": cat, "ph": "X", "pid": self.pid,
              "tid": threading.get_ident() & 0xFFFF,
              "ts": (t0 - self._t0) * 1e6, "dur": dur_s * 1e6}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)
            if len(self.events) > self.max_events:
                del self.events[0]

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Context manager form: times the block as a ``host`` event and
        tracks nesting depth per thread (depth rides in ``args.depth`` so
        malformed nesting is assertable)."""
        depth = self._depth()
        self._local.depth = depth + 1
        t0 = time.monotonic()
        try:
            yield self
        finally:
            dur = time.monotonic() - t0
            self._local.depth = depth
            self.complete(name, t0, dur, depth=depth, **args)

    def anchor(self) -> None:
        """Mark one moment on both clocks: a ``jax.profiler``
        ``TraceAnnotation`` named ``ANCHOR`` in the running profiler
        trace, and an ``ANCHOR`` event of zero length at a clock read
        made inside it.  Call it while the profiler runs."""
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(ANCHOR):
            t = time.monotonic()
        self.complete(ANCHOR, t, 0.0, cat="interval")

    # ------------------------------------------------------------- export

    def chrome_trace(self) -> dict:
        """The Chrome Trace Event payload (Perfetto-loadable)."""
        with self._lock:
            events = [dict(e) for e in self.events]
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def to_json(self) -> str:
        return json.dumps(self.chrome_trace())

    def save(self, path: str) -> str:
        import os
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            f.write(self.to_json())
        return path

    def drain(self) -> list[dict]:
        """Hand over and clear the event buffer (streaming export)."""
        with self._lock:
            events, self.events = self.events, []
        return events

    def __len__(self) -> int:
        with self._lock:
            return len(self.events)
