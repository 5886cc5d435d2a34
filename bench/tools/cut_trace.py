#!/usr/bin/env python3
"""Cut a profiler trace down to a few KB for ``tests/bench``: a dozen
device ops of the traced window around the end of its first program, the
modules and ``bench.*`` host spans that overlap them, and a
``bench.window`` span re-drawn around them.

    python3 bench/tools/cut_trace.py <trace dir or .xplane.pb> <out.xplane.pb>

Writes the cut as a serialized XSpace and, beside it, the same as text
(``.pbtxt``) for reading by hand.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.tracing import SPAN_PREFIX, WINDOW_SPAN, find_xplane  # noqa: E402


def _plane(pid: int, name: str, lines: dict) -> str:
    """Text proto of one XPlane; ``lines`` maps a line name to a list of
    ``(event name, start_ns, end_ns)``."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    out = [f"planes {{\n  id: {pid}\n  name: {name!r}"]
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        base = min(s for _, s, _ in evs)
        out.append(f"  lines {{\n    id: {lid}\n    name: {lname!r}\n"
                   f"    timestamp_ns: {base}")
        for n, s, e in evs:
            out.append(f"    events {{ metadata_id: {meta[n]} "
                       f"offset_ps: {(s - base) * 1000} "
                       f"duration_ps: {(e - s) * 1000} }}")
        out.append("  }")
    for n, i in meta.items():
        out.append(f"  event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: {n!r} }} }}")
    out.append("}")
    return "\n".join(out).replace("'", '"')


def cut(pd, ops: int = 12) -> str:
    spans, dev = [], None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, int(ev.start_ns), int(ev.end_ns))
                          for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
        elif plane.name.startswith("/device:") and dev is None:
            lines = {ln.name: [(ev.name, int(ev.start_ns), int(ev.end_ns))
                               for ev in ln.events] for ln in plane.lines}
            if lines.get("XLA Ops"):
                dev = (plane.name, lines)
    (w0, _), = [(s, e) for n, s, e in spans if n == WINDOW_SPAN][:1]
    name, lines = dev
    # the last ops of the window's first program and the first ops after
    # it, so the cut holds an idle gap between two programs
    by_start = lambda e: e[1]  # noqa: E731
    mods = sorted((e for e in lines["XLA Modules"] if e[1] >= w0), key=by_start)
    end = mods[0][2]
    all_ops = sorted(lines["XLA Ops"], key=by_start)
    before = [e for e in all_ops if w0 <= e[1] and e[2] <= end][-(ops // 2):]
    after = [e for e in all_ops if e[1] >= end][:ops - len(before)]
    kept = before + after
    a, b = kept[0][1] - 1000, kept[-1][2] + 1000
    # reach to the end of the first host span that starts after the
    # program, so the window's idle time falls under more than one span
    later = sorted((s, e) for n, s, e in spans if n != WINDOW_SPAN and s >= end)
    if later:
        b = max(b, later[0][1] + 1000)
    over = lambda evs: [e for e in evs if e[2] > a and e[1] < b]  # noqa: E731
    host = [e for e in over(spans) if e[0] != WINDOW_SPAN]
    host = [(WINDOW_SPAN, a, b)] + host
    dev_lines = {"XLA Modules": over(lines.get("XLA Modules", [])),
                 "XLA Ops": kept}
    return "\n".join([_plane(1, "/host:CPU", {"python": host}),
                      _plane(2, name, dev_lines)]) + "\n"


def main() -> int:
    from jax.profiler import ProfileData
    src, dst = Path(sys.argv[1]), Path(sys.argv[2])
    pd = ProfileData.from_file(str(src if src.is_file() else find_xplane(str(src))))
    text = cut(pd)
    dst.with_suffix(".pbtxt").write_text(text)
    dst.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return 0


if __name__ == "__main__":
    sys.exit(main())
