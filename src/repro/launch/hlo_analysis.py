"""Trip-count-aware cost analysis over optimized HLO text.

``compiled.cost_analysis()`` counts while-loop bodies ONCE, which silently
undercounts everything inside ``lax.scan`` (layer stacks, xent chunks,
linear-attention chunk scans) by the trip count — useless for a roofline of
scanned models.  This module re-derives the three roofline inputs from the
post-SPMD HLO text with while-loop multipliers applied:

  flops             2 * prod(result) * K for every dot (incl. dots inside
                    fusions), K = product of the lhs contracting dims
  bytes_accessed    per top-level (post-fusion) instruction:
                    result bytes + sum(operand bytes) — an HBM-traffic proxy
  collective_bytes  result bytes of all-gather / all-reduce / reduce-scatter
                    / all-to-all / collective-permute (tuple shapes summed)

Trip counts are read from each while's condition computation (the constant
compared against the induction variable — exact for lax.scan/fori_loop).
Validated against known matmul/scan programs in tests/test_hlo_analysis.py.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*?)\s*([a-z][\w\-]*)\("
)
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_ATTR_RE = re.compile(r"(\w+)=%?([\w\.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")

_SKIP_BYTES_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "bitcast-convert", "after-all", "partition-id", "replica-id", "iota",
    "while", "conditional", "call", "custom-call",
}
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _type_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        b = float(_DTYPE_BYTES[dt])
        if dims:
            for d in dims.split(","):
                b *= int(d)
        total += b
    return total


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


@dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    operands: List[str]
    raw: str

    @property
    def result_bytes(self) -> float:
        return _type_bytes(self.type_str)


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    by_name: Dict[str, Instr] = field(default_factory=dict)


def parse_module(hlo_text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_marked: Optional[str] = None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMP_HEADER_RE.match(line.strip())
            if m and "{" in line:
                cur = Computation(m.group(1))
                if line.strip().startswith("ENTRY"):
                    entry_marked = m.group(1)
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, opcode = m.group(1), m.group(2), m.group(3)
        paren = line[m.end():]
        # operands live before the closing paren of the op call; attrs after
        depth = 1
        end = 0
        for i, ch in enumerate(paren):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_str = paren[:end]
        operands = _OPERAND_RE.findall(operand_str)
        inst = Instr(name, type_str, opcode, operands, line)
        cur.instrs.append(inst)
        cur.by_name[name] = inst
    if entry_marked:
        comps["__entry__"] = comps[entry_marked]
    return comps


def _attr(raw: str, key: str) -> Optional[str]:
    m = re.search(rf"{key}=%?([\w\.\-]+)", raw)
    return m.group(1) if m else None


def _trip_count(cond: Computation) -> int:
    """Largest integer constant in the while condition (exact for scans)."""
    best = 1
    for inst in cond.instrs:
        for m in _CONST_RE.finditer(inst.raw):
            best = max(best, int(m.group(1)))
    return best


def _dot_flops(inst: Instr, comp: Computation,
               comps: Dict[str, Computation]) -> float:
    out_dims = _shape_dims(inst.type_str)
    out_elems = math.prod(out_dims) if out_dims else 1
    # K = product of lhs contracting dims
    mc = _LHS_CONTRACT_RE.search(inst.raw)
    k = 1
    if mc and inst.operands:
        lhs = comp.by_name.get(inst.operands[0])
        if lhs is not None:
            dims = _shape_dims(lhs.type_str)
            idxs = [int(i) for i in mc.group(1).split(",")] if mc.group(1) else []
            for i in idxs:
                if i < len(dims):
                    k *= dims[i]
    return 2.0 * out_elems * k


@dataclass
class Costs:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: Dict[str, float] = field(default_factory=dict)

    def add_coll(self, kind: str, b: float) -> None:
        self.collective_bytes[kind] = self.collective_bytes.get(kind, 0.0) + b

    @property
    def collective_total(self) -> float:
        return sum(self.collective_bytes.values())


def _fusion_bytes(inst: Instr, comp: Computation,
                  called: Optional[Computation]) -> float:
    """HBM traffic of a fusion: result + operands, EXCEPT operands whose
    only use inside the fused computation is a (dynamic-)slice/gather — a
    fused windowed read touches only the window, not the whole buffer
    (dominant for scan-carried KV caches / stacked params)."""
    b = inst.result_bytes
    if called is not None and called.instrs:
        # in-place DUS-rooted fusions (scan output stacking): traffic is the
        # update window, not the whole aliased buffer
        root = called.instrs[-1]
        roots = [root]
        if root.opcode == "tuple":
            roots = [called.by_name[o] for o in root.operands
                     if o in called.by_name]
        if roots and all(r.opcode == "dynamic-update-slice" for r in roots):
            b = 0.0
            for r in roots:
                upd = called.by_name.get(r.operands[1]) if len(r.operands) > 1 else None
                b += 2.0 * (upd.result_bytes if upd is not None
                            else r.result_bytes)
    sliced_param_windows: Dict[int, float] = {}
    if called is not None:
        params = {}
        for ci in called.instrs:
            if ci.opcode == "parameter":
                m = re.search(r"parameter\((\d+)\)", ci.raw)
                if m:
                    params[ci.name] = int(m.group(1))
        uses: Dict[str, List[Instr]] = {}
        for ci in called.instrs:
            for o in ci.operands:
                if o in params:
                    uses.setdefault(o, []).append(ci)
        for pname, idx in params.items():
            consumers = uses.get(pname, [])
            if consumers and all(
                c.opcode in ("dynamic-slice", "slice", "gather")
                for c in consumers
            ):
                sliced_param_windows[idx] = sum(
                    c.result_bytes for c in consumers
                )
    for i, o in enumerate(inst.operands):
        src = comp.by_name.get(o)
        if src is None or src.opcode == "constant":
            continue
        if i in sliced_param_windows:
            b += sliced_param_windows[i]
        else:
            b += src.result_bytes
    return b


def _walk(comp: Computation, comps: Dict[str, Computation], mult: float,
          costs: Costs, top_level: bool) -> None:
    for inst in comp.instrs:
        op = inst.opcode
        raw = inst.raw
        # collectives (sync or async -start; -done repeats no transfer)
        base = op.replace("-start", "")
        if base in _COLLECTIVES and not op.endswith("-done"):
            costs.add_coll(base, mult * inst.result_bytes)

        if op == "dot":
            costs.flops += mult * _dot_flops(inst, comp, comps)

        if op == "fusion":
            called = _attr(raw, "calls")
            if called and called in comps:
                # flops inside fusions count; bytes do not (fused in VMEM)
                _walk(comps[called], comps, mult, costs, top_level=False)
            if top_level:
                costs.bytes_accessed += mult * _fusion_bytes(
                    inst, comp, comps.get(called)
                )
            continue
        elif op == "while":
            body = _attr(raw, "body")
            cond = _attr(raw, "condition")
            trips = _trip_count(comps[cond]) if cond in comps else 1
            if body in comps:
                _walk(comps[body], comps, mult * trips, costs, top_level=True)
        elif op == "conditional":
            for m in re.finditer(r"(?:branch_computations=\{([^}]*)\}|"
                                 r"(?:true|false)_computation=%?([\w\.\-]+))",
                                 raw):
                names = (m.group(1) or m.group(2) or "").replace("%", "")
                for nm in filter(None, (s.strip() for s in names.split(","))):
                    if nm in comps:
                        _walk(comps[nm], comps, mult, costs, top_level=True)
        elif op in ("call", "async-start"):
            called = _attr(raw, "to_apply") or _attr(raw, "calls")
            if called and called in comps:
                _walk(comps[called], comps, mult, costs, top_level=top_level)

        # HBM-traffic proxy: top-level instructions only (fusions already
        # aggregate their internals)
        if top_level and op not in _SKIP_BYTES_OPS:
            if op in ("dynamic-slice", "gather", "slice"):
                # reads only the sliced window, not the whole operand
                b = 2.0 * inst.result_bytes
            elif op in ("dynamic-update-slice", "scatter"):
                # in-place update: traffic ~ 2x the update operand
                upd = None
                if len(inst.operands) >= 2:
                    upd = comp.by_name.get(inst.operands[1])
                b = 2.0 * (upd.result_bytes if upd is not None
                           else inst.result_bytes)
            else:
                b = inst.result_bytes
                for o in inst.operands:
                    src = comp.by_name.get(o)
                    if src is not None and src.opcode != "constant":
                        b += src.result_bytes
            costs.bytes_accessed += mult * b


def max_collective_elems(hlo_text: str) -> int:
    """Elements of the largest array any collective in ``hlo_text`` moves
    (each element of a tuple result counts alone; ``-done`` halves of
    async pairs repeat their ``-start`` and are skipped)."""
    worst = 0
    for comp in parse_module(hlo_text).values():
        for inst in comp.instrs:
            if (inst.opcode.replace("-start", "") not in _COLLECTIVES
                    or inst.opcode.endswith("-done")):
                continue
            for dt, dims in _SHAPE_RE.findall(inst.type_str):
                if dt in _DTYPE_BYTES:
                    worst = max(worst, math.prod(
                        int(d) for d in dims.split(",") if d))
    return worst


def analyze(hlo_text: str) -> Costs:
    comps = parse_module(hlo_text)
    entry = comps.get("__entry__")
    if entry is None:  # fall back: last computation
        entry = list(comps.values())[-1]
    costs = Costs()
    _walk(entry, comps, 1.0, costs, top_level=True)
    return costs
