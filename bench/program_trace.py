"""Join the program's own spans to a profiler trace, and split the train
step's device time by its named scopes.

The program records its spans with ``repro.obs.Tracer`` on the tracer's
own clock.  A traced window calls ``Tracer.anchor()`` while the profiler
runs, which leaves one moment on both clocks: a host event named
``ANCHOR`` in the ``.xplane.pb`` and an ``ANCHOR`` event among the
tracer's.  Their offset maps every tracer event onto the trace's timeline.
Within the ``bench.window`` host span (``bench/tracing.py``),
``reduce_joined`` gives

* ``program_idle``: the device's idle seconds under each program span, the
  innermost one where spans nest.  Only ``cat`` "host" events are read
  (the thread doing that work); "interval" events overlap other work;
* ``idle_gaps``: the device's idle seconds by the innermost span over them,
  the program's or the harness's: a program span wins over the ``bench.*``
  span around it, and a ``bench.*`` span keeps what no program span covers
  (``tracing.TraceSummary.idle_gaps`` with the program's spans added);
* ``step_scope_s``: the device seconds of the train step's program
  (``jit_step``) by the named scope of each op (``repro.train.loop.
  make_train_step``): ``forward``, ``backward`` (ops under
  ``transpose(jvp(forward))``), ``optimizer`` and ``other``.  v5e's op
  events carry no ``op_name``, so an op's comes from the compiled
  program's HLO metadata, keyed by instruction name (``hlo_op_names``); a
  fusion has its root's;
* ``ends_in_window``: per program event name, the events that end inside
  the window (a serving ``wave`` ends when it is reaped).
"""

from __future__ import annotations

import bisect
import dataclasses
import itertools
import re
from collections import defaultdict

from bench import tracing
from repro.obs.trace import ANCHOR

STEP_MODULE = "jit_step"
SCOPES = ("forward", "backward", "optimizer", "other")
NO_SPAN = "no bench span"


@dataclasses.dataclass
class Joined:
    window_s: float
    busy_s: float
    program_idle: dict        # program span -> device-idle seconds under it
    idle_gaps: list           # [[span, seconds]], most first (program or bench)
    step_scope_s: dict        # scope -> seconds of jit_step's ops
    ends_in_window: dict      # program event name -> events ending in window


def scope_of(op_name: str) -> str:
    """The named scope of an op of ``make_train_step`` by its ``op_name``
    (``jit(step)/transpose(jvp(forward))/dot_general`` is ``backward``)."""
    parts = op_name.split("/")
    if "transpose(jvp(forward))" in parts:
        return "backward"
    if "jvp(forward)" in parts or "forward" in parts:
        return "forward"
    if "optimizer" in parts:
        return "optimizer"
    return "other"


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?'
                    r'op_name="([^"]*)"', re.M)


def hlo_op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` from a compiled program's HLO text
    (``jitted.lower(...).compile().as_text()``)."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def host_events(pd) -> list:
    """``(name, start_ns, end_ns)`` of every event on the host planes."""
    return [(ev.name, ev.start_ns, ev.end_ns) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def anchor_offset_ns(host, events) -> float:
    """Trace time minus tracer time (ns) of the one moment ``anchor``
    marked on both clocks; ``host`` is ``host_events`` of the trace."""
    trace_ns = next((s for n, s, _ in host if n == ANCHOR), None)
    mine_us = next((e["ts"] for e in events if e["name"] == ANCHOR), None)
    if trace_ns is None or mine_us is None:
        raise ValueError(f"no {ANCHOR!r} in the trace and the program's events")
    return trace_ns - mine_us * 1e3


def joined_spans(events, offset_ns: float) -> list:
    """``(name, start_ns, end_ns)`` on the trace's clock of the program's
    ``host`` events."""
    return [(e["name"], e["ts"] * 1e3 + offset_ns,
             (e["ts"] + e["dur"]) * 1e3 + offset_ns)
            for e in events if e.get("cat") == "host"]


def _devices(pd):
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(tracing._op_name(ev.name), ev.start_ns, ev.end_ns)
                           for ev in line.events]
                elif line.name == "XLA Modules":
                    mods = [(tracing._module_name(ev.name), ev.start_ns,
                             ev.end_ns) for ev in line.events]
            if ops:
                out.append((ops, mods))
    return out


def _attribute(gaps, spans) -> dict:
    """Seconds of ``gaps`` by the innermost of ``spans`` over each part:
    ``spans`` are ``(rank, name, start, end)``; a higher rank wins, then
    the later start, then the earlier end."""
    spans = sorted(spans, key=lambda sp: sp[2])
    starts = [sp[2] for sp in spans]
    reach = list(itertools.accumulate((sp[3] for sp in spans), max))
    out = defaultdict(float)
    for gs, ge in gaps:
        over = []
        i = bisect.bisect_left(starts, ge) - 1
        while i >= 0 and reach[i] > gs:
            if spans[i][3] > gs:
                over.append(spans[i])
            i -= 1
        cuts = sorted({gs, ge} | {x for sp in over for x in sp[2:]
                                  if gs < x < ge})
        for a, b in zip(cuts, cuts[1:]):
            cover = [sp for sp in over if sp[2] <= a and sp[3] >= b]
            best = max(cover, key=lambda sp: (sp[0], sp[2], -sp[3]),
                       default=None)
            out[best[1] if best else NO_SPAN] += (b - a) * 1e-9
    return out


def reduce_joined(pd, events, hlo_names=None) -> Joined:
    """``pd`` a ``jax.profiler.ProfileData`` of a traced window, ``events``
    the program tracer's events of it (with the anchor), ``hlo_names``
    ``hlo_op_names`` of the compiled train step where there is one."""
    hlo_names = hlo_names or {}
    host = host_events(pd)
    offset = anchor_offset_ns(host, events)
    bench = [h for h in host if h[0].startswith(tracing.SPAN_PREFIX)]
    windows = [(s, e) for n, s, e in bench if n == tracing.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no host span {tracing.WINDOW_SPAN!r}")
    devices = _devices(pd)
    if not devices:
        raise ValueError("trace has no device plane with XLA ops")
    w0, w1 = windows[0]
    program = joined_spans(events, offset)
    spans = [(0, n, s, e) for n, s, e in bench if n != tracing.WINDOW_SPAN] \
        + [(1, n, s, e) for n, s, e in program]
    busy_total, gaps = 0.0, []
    scope_s = defaultdict(float)
    for ops, mods in devices:
        inside = []
        step_mods = sorted((s, e) for n, s, e in mods if n == STEP_MODULE)
        mod_starts = [s for s, _ in step_mods]
        for name, s, e in ops:
            s, e = tracing._clip(s, e, w0, w1)
            if e <= s:
                continue
            inside.append((s, e))
            k = bisect.bisect_right(mod_starts, s) - 1
            if k < 0 or step_mods[k][1] < s:
                continue
            scope_s[scope_of(hlo_names.get(name, ""))] += (e - s) * 1e-9
        busy = tracing._union(inside)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n = len(devices)
    idle = {k: v / n for k, v in _attribute(gaps, spans).items()}
    names = {sp[1] for sp in spans if sp[0] == 1}
    ends = defaultdict(int)
    for e in events:
        if w0 <= (e["ts"] + e["dur"]) * 1e3 + offset < w1:
            ends[e["name"]] += 1
    return Joined(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total * 1e-9 / n,
        program_idle={k: v for k, v in idle.items() if k in names},
        idle_gaps=[[k, v] for k, v in sorted(idle.items(),
                                             key=lambda kv: -kv[1])[:tracing.TOP]],
        step_scope_s={k: scope_s[k] / n for k in SCOPES if k in scope_s},
        ends_in_window=dict(ends))
