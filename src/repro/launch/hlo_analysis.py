"""Scan-aware cost analysis of optimized (post-SPMD) HLO text.

Why this exists: ``compiled.cost_analysis()`` visits each while body ONCE —
a 60-layer ``lax.scan`` transformer reports ~1/60 of its real FLOPs (we
verified this empirically).  Since the whole roofline methodology rests on
per-chip FLOPs / HBM bytes / collective wire bytes, we parse the optimized
HLO ourselves and multiply every while body by its trip count (XLA attaches
``backend_config={"known_trip_count":{"n":...}}`` to while ops).

Accounting rules (per-device program — SPMD shapes are already per-chip):
  * FLOPs: ``dot`` = 2 · |out| · K (K = product of lhs contracting dims);
    convolutions = 2 · |out| · K_window · C_in / groups; elementwise ignored
    (≪1% for these models).  Recurses into all called computations.
  * HBM bytes: per instruction = output + operand bytes, skipping pure
    plumbing (parameter/constant/tuple/get-tuple-element/bitcast) and
    *not* recursing into fusion bodies (fusion internals live in registers/
    cache — the fusion call site's operands/outputs are the HBM traffic).
    Recurses into while/conditional/call bodies with multipliers.
  * Collective wire bytes per chip, ring formulas with group size n:
      all-reduce       2·(n−1)/n · bytes
      all-gather       (n−1)/n · bytes        (result = gathered size)
      reduce-scatter   (n−1) · bytes          (result = scattered shard)
      all-to-all       (n−1)/n · bytes
      collective-permute   bytes
    ``*-start``/``*-done`` async pairs are counted once (at start).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

__all__ = ["analyze_hlo", "HloCost"]

_ITEM = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
         "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
         "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
         "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1}

_SHAPE_RE = re.compile(r"(" + "|".join(_ITEM) + r")\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s+=\s+(.*?)\s+([a-z][a-z0-9\-]*)\(")
_CALL_ATTRS = ("calls=", "body=", "condition=", "to_apply=", "branch_computations=")
_COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all"}
_SKIP_BYTES = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
               "while", "conditional", "call", "after-all", "partition-id",
               "replica-id", "custom-call", "copy-start", "copy-done", "opt-barrier"}


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _ITEM[dt]
    return total


def _type_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclasses.dataclass
class _Instr:
    name: str
    type_str: str
    opcode: str
    line: str


def _parse(text: str):
    comps: dict[str, list[_Instr]] = {}
    entry = None
    cur: list[_Instr] | None = None
    for line in text.splitlines():
        m = _COMP_HDR.match(line)
        if m:
            name = m.group(2)
            comps[name] = cur = []
            if m.group(1):
                entry = name
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            cur.append(_Instr(mi.group(2), mi.group(3), mi.group(4), line))
    return comps, entry


def _called(instr: _Instr) -> list[str]:
    out = []
    for attr in _CALL_ATTRS:
        for m in re.finditer(re.escape(attr) + r"\{?%?([\w.\-]+)", instr.line):
            name = m.group(1)
            out.append(name)
        if attr == "branch_computations=":
            m = re.search(r"branch_computations=\{([^}]*)\}", instr.line)
            if m:
                out.extend(x.strip().lstrip("%") for x in m.group(1).split(","))
    return out


def _trip_count(instr: _Instr) -> int | None:
    m = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', instr.line)
    return int(m.group(1)) if m else None


def _group_size(instr: _Instr, total_devices: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", instr.line)  # iota form
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", instr.line)
    if m:
        return len(m.group(1).split(","))
    return total_devices


def _dot_flops(instr: _Instr, symtab: dict[str, str]) -> float:
    out_elems = 1
    for d in _type_dims(instr.type_str):
        out_elems *= d
    # operands may carry inline types ("dot(f32[64,64]{1,0} %x, ...)") — take
    # the first %name after the opcode's paren, whatever precedes it
    ops = re.search(r"%([\w.\-]+)",
                    instr.line[instr.line.index(instr.opcode + "(") + len(instr.opcode) + 1:])
    lhs_name = ops.group(1) if ops else None
    k = 1
    mc = re.search(r"lhs_contracting_dims=\{([0-9, ]*)\}", instr.line)
    if lhs_name and lhs_name in symtab and mc and mc.group(1).strip():
        lhs_dims = _type_dims(symtab[lhs_name])
        for i in mc.group(1).split(","):
            idx = int(i)
            if idx < len(lhs_dims):
                k *= lhs_dims[idx]
    return 2.0 * out_elems * k


def _conv_flops(instr: _Instr, symtab: dict[str, str]) -> float:
    out_elems = 1
    for d in _type_dims(instr.type_str):
        out_elems *= d
    names = re.findall(r"%([\w.\-]+)",
                       instr.line[instr.line.index(instr.opcode + "(") + len(instr.opcode) + 1:])
    if len(names) < 2:
        return 0.0
    rhs = symtab.get(names[1], "")
    kdims = _type_dims(rhs)
    k = 1
    for d in kdims[:-1]:  # window dims * input features (approx; layout-dependent)
        k *= d
    return 2.0 * out_elems * k


def _operand_bytes_list(instr: _Instr, symtab: dict[str, str]) -> list[int]:
    seg = instr.line[instr.line.index(instr.opcode + "(") + len(instr.opcode) + 1:]
    # stop at attrs — operands are the leading %names
    out = []
    for m in re.finditer(r"%([\w.\-]+)", seg.split("), ")[0]):
        t = symtab.get(m.group(1))
        if t:
            out.append(_type_bytes(t))
    return out


# ops that touch only a slice of their big operand (in-place / gather):
# counting the full operand would charge a 35-layer weight stack per layer.
_SLICE_READS = {"dynamic-slice", "gather"}
_SLICE_WRITES = {"dynamic-update-slice", "scatter"}


def _instr_hbm_bytes(instr: _Instr, symtab: dict[str, str], comps) -> int:
    op = instr.opcode
    root_op = op
    if op == "fusion":
        callees = _called(instr)
        if callees:
            body = comps.get(callees[0], [])
            roots = [i for i in body if "ROOT" in i.line]
            if roots:
                root_op = roots[0].opcode
    out_b = _type_bytes(instr.type_str)
    ops_b = _operand_bytes_list(instr, symtab)
    if root_op in _SLICE_READS:
        return 2 * out_b  # read the slice + write the result
    if root_op in _SLICE_WRITES:
        # in-place: read+write the update region (operands minus the buffer)
        upd = sum(ops_b) - max(ops_b) if len(ops_b) > 1 else out_b
        return 2 * max(upd, 0)
    return out_b + sum(ops_b)


@dataclasses.dataclass
class HloCost:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    collectives: dict
    notes: list


def analyze_hlo(text: str, total_devices: int = 1) -> HloCost:
    comps, entry = _parse(text)
    symtabs = {name: {i.name: i.type_str for i in instrs}
               for name, instrs in comps.items()}
    notes: list[str] = []
    coll_detail: dict[str, dict] = defaultdict(lambda: {"count": 0.0, "wire_bytes": 0.0})
    memo: dict[tuple[str, bool], tuple[float, float, float]] = {}

    def comp_cost(name: str, in_fusion: bool) -> tuple[float, float, float]:
        key = (name, in_fusion)
        if key in memo:
            return memo[key]
        memo[key] = (0.0, 0.0, 0.0)  # cycle guard
        flops = hbm = coll = 0.0
        symtab = symtabs.get(name, {})
        for instr in comps.get(name, []):
            op = instr.opcode
            base = op[:-6] if op.endswith("-start") else op
            if op.endswith("-done"):
                continue
            if op == "dot":
                flops += _dot_flops(instr, symtab)
            elif op == "convolution":
                flops += _conv_flops(instr, symtab)
            if base in _COLLECTIVES:
                n = _group_size(instr, total_devices)
                b = _type_bytes(instr.type_str)
                if base == "all-reduce":
                    wire = 2.0 * (n - 1) / n * b
                elif base == "all-gather":
                    wire = (n - 1) / n * b
                elif base == "reduce-scatter":
                    wire = float(n - 1) * b
                elif base in ("all-to-all", "ragged-all-to-all"):
                    wire = (n - 1) / n * b
                else:  # collective-permute
                    wire = float(b)
                coll += wire
                coll_detail[base]["count"] += 1
                coll_detail[base]["wire_bytes"] += wire
            if not in_fusion and op not in _SKIP_BYTES and base not in _COLLECTIVES:
                hbm += _instr_hbm_bytes(instr, symtab, comps)
            # recurse into called computations
            callees = _called(instr)
            if not callees:
                continue
            mult = 1.0
            child_fusion = in_fusion or op == "fusion" or op == "reduce" or op == "sort" \
                or op == "scatter" or op == "select-and-scatter" or op == "map"
            if op == "while":
                tc = _trip_count(instr)
                if tc is None:
                    tc = 1
                    notes.append(f"while {instr.name} in {name}: unknown trip count (×1)")
                mult = float(tc)
            for c in callees:
                cf, ch, cc = comp_cost(c, child_fusion)
                if op == "while":
                    # condition runs trips+1 times; body runs trips times — both ~tc
                    flops += cf * mult
                    hbm += ch * mult
                    coll += cc * mult
                    if cc:
                        _scale_last(coll_detail, cc, mult)
                else:
                    flops += cf
                    hbm += ch
                    coll += cc
        memo[key] = (flops, hbm, coll)
        return memo[key]

    def _scale_last(detail, child_bytes, mult):
        # while-body collectives already added once during recursion memo; add the
        # remaining (mult-1)× to the aggregate breakdown under a loop marker.
        detail["(in-loop-extra)"]["count"] += 0
        detail["(in-loop-extra)"]["wire_bytes"] += child_bytes * (mult - 1)

    if entry is None:
        return HloCost(0, 0, 0, {}, ["no ENTRY computation found"])
    flops, hbm, coll = comp_cost(entry, False)
    return HloCost(flops, hbm, coll, {k: dict(v) for k, v in coll_detail.items()},
                   notes)


_UPCAST_RE = re.compile(
    r"= f32\[([0-9,]+)\]\S*\s+(convert|fusion)\(%?\S*?param")


def cpu_upcast_bytes(text: str) -> int:
    """Bytes of hoisted bf16→f32 *weight copies* the XLA CPU backend makes
    because it has no native bf16 dot.  These buffers do not exist on TPU
    (bf16 is MXU-native), so the TPU-expected temp memory is
    ``temp_size - cpu_upcast_bytes``.  Heuristic: f32 converts/convert-
    fusions of parameters ≥ 1 MiB, counted once per distinct shape+source.
    """
    seen = set()
    total = 0
    for line in text.splitlines():
        m = _UPCAST_RE.search(line)
        if not m:
            continue
        dims = [int(x) for x in m.group(1).split(",") if x]
        n = 4
        for d in dims:
            n *= d
        if n < 1 << 20:
            continue
        key = line.strip().split(" = ")[0]
        if key in seen:
            continue
        seen.add(key)
        total += n
    return total


def analyze_compiled(compiled, total_devices: int = 1) -> dict:
    """Full record for a compiled executable: parser + XLA's own numbers."""
    cost = analyze_hlo(compiled.as_text(), total_devices)
    xla = {}
    try:
        ca = compiled.cost_analysis()
        xla = {k: float(v) for k, v in ca.items()
               if isinstance(v, (int, float)) and k in ("flops", "bytes accessed")}
    except Exception as e:  # pragma: no cover
        xla = {"error": str(e)}
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
        up = cpu_upcast_bytes(compiled.as_text())
        # liveness cap: at peak, at most one f32 copy of every bf16 weight
        # (= 2x the bf16 argument bytes) can be resident simultaneously.
        up = min(up, 2 * mem.get("argument_size_in_bytes", up))
        mem["cpu_bf16_upcast_bytes"] = up
        if "temp_size_in_bytes" in mem:
            mem["temp_tpu_expected_bytes"] = max(0, mem["temp_size_in_bytes"] - up)
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}
    return {
        "flops_per_chip": cost.flops,
        "hbm_bytes_per_chip": cost.hbm_bytes,
        "collective_wire_bytes_per_chip": cost.collective_bytes,
        "collectives": cost.collectives,
        "notes": cost.notes,
        "xla_cost_analysis": xla,
        "memory_analysis": mem,
    }
