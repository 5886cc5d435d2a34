"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The harness marks its traced window with a host span ``bench.window`` and
wraps its own calls into the program in host spans named ``bench.<what>``
(``jax.profiler.TraceAnnotation``).  Within that window this module gives

* device busy time: the union of the intervals in which an XLA op ran on
  a device, averaged over the devices that ran any;
* device time per XLA module (jitted program), by module name, and per
  XLA op, by op name;
* ``breakdown``: the device ops that took most time, and the device's
  idle time split by the ``bench.*`` host span that covered it (the
  harness's spans do not nest; time no span covers is "no bench span").
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import itertools
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
# XLA's collective ops; an async one runs as its "-start" and "-done" halves
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")
SPAN_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    devices: int              # the figures below are per device, averaged
    module_s: dict            # module name -> device seconds in the window
    module_calls: dict        # module name -> executions that began in it
    op_s: dict                # op name -> device seconds in the window
    device_ops: list          # [[op name, seconds]], most first
    idle_gaps: list           # [[host span, seconds]], most first


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _module_name(raw: str) -> str:
    """``jit_step(6807837483966664335)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", raw)


def _op_name(raw: str) -> str:
    """An XLA op event is named by its HLO text, ``%name = shape op(...)``;
    keep the name."""
    return raw.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce_profile(pd) -> TraceSummary:
    """``pd`` is a ``jax.profiler.ProfileData``."""
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(_op_name(ev.name), ev.start_ns,
                            ev.start_ns + ev.duration_ns) for ev in line.events]
                elif line.name == "XLA Modules":
                    mods = [(_module_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns) for ev in line.events]
            if ops:
                devices.append((ops, mods))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no host span {WINDOW_SPAN!r}")
    if not devices:
        raise ValueError("trace has no device plane with XLA ops")
    w0, w1 = windows[0]
    busy_total = 0.0
    module_s, module_calls = defaultdict(float), defaultdict(int)
    op_s = defaultdict(float)
    gaps = []
    for ops, mods in devices:
        inside = []
        for name, s, e in ops:
            s, e = _clip(s, e, w0, w1)
            if e > s:
                inside.append((s, e))
                op_s[name] += (e - s) * 1e-9
        busy = _union(inside)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for name, s, e in mods:
            if w0 <= s < w1:
                module_calls[name] += 1
            s, e = _clip(s, e, w0, w1)
            if e > s:
                module_s[name] += (e - s) * 1e-9
    n = len(devices)
    host = sorted((s, e, name) for name, s, e in spans if name != WINDOW_SPAN)
    starts = [s for s, _, _ in host]
    reach = list(itertools.accumulate((e for _, e, _ in host), max))
    idle = defaultdict(float)
    for gs, ge in gaps:
        left = ge - gs
        i = bisect.bisect_left(starts, ge) - 1
        while i >= 0 and reach[i] > gs:     # spans that can overlap the gap
            s, e, name = host[i]
            c = min(e, ge) - max(s, gs)
            if c > 0:
                idle[name] += c * 1e-9 / n
                left -= c
            i -= 1
        if left > 0:
            idle["no bench span"] += left * 1e-9 / n
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]  # noqa: E731
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total * 1e-9 / n, devices=n,
        module_s={k: v / n for k, v in module_s.items()},
        module_calls={k: v / n for k, v in module_calls.items()},
        op_s={k: v / n for k, v in op_s.items()},
        device_ops=top({k: v / n for k, v in op_s.items()}),
        idle_gaps=top(idle))


def is_collective(op: str) -> bool:
    """``all-gather-start.3``, ``all_to_all.12`` and the like: an op named
    for a collective kind, whole or one of its async halves (XLA names
    some for their HLO opcode, some for the JAX primitive)."""
    base = re.sub(r"\.\d+$", "", op).replace("_", "-")
    return any(base in (k, k + "-start", k + "-done") for k in COLLECTIVES)


def collective_ops(summary: TraceSummary) -> dict:
    """Device seconds of each collective op in the window, per device."""
    return {k: v for k, v in summary.op_s.items() if is_collective(k)}


def reduce_dir(trace_dir: str) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)))
