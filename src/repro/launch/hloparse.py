"""Loop-aware roofline extraction from compiled (SPMD-partitioned) HLO text.

``compiled.cost_analysis()`` counts each ``while`` body ONCE, so any
scan-based program (layers, FL clients, flash-attention KV chunks) is
undercounted by the loop trip count — verified experimentally in this repo.
This parser recovers honest per-device totals:

1. split the HLO module into computations;
2. recover each while loop's trip count from the integer constant in its
   condition computation (scans lower to ``lt(counter, N)``);
3. weight every computation by the product of trip counts on the call path;
4. accumulate, per weighted instruction:
   - FLOPs: ``dot`` (2 · result_elems · contracted_elems) and
     ``convolution`` (2 · result_elems · window · in_features/group);
   - HBM bytes: operand + result bytes of top-level (post-fusion)
     instructions — fusion internals stay in registers/VMEM, so this is the
     natural roofline HBM-traffic model;
   - collective bytes: result-shape bytes of all-gather / all-reduce /
     reduce-scatter / all-to-all / collective-permute.

The parser is validated in tests against unrolled-vs-scanned programs.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Optional

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

def cost_analysis_dict(cost) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (``{}`` on backends
    without cost analysis, which return ``None``), so callers can
    ``.get("flops", 0.0)``."""
    return dict(cost) if cost else {}


# one shape token: f32[1,2,3] (layout braces optional)
_SHAPE_TOK = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPND = re.compile(r"%([\w.\-]+)")


def _shape_bytes_elems(dtype: str, dims: str) -> tuple[int, int]:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4), n


@dataclasses.dataclass
class Instr:
    name: str
    op: str
    result_bytes: int
    result_elems: int
    shapes: list            # [(dtype, [dims])] of the result(s)
    text: str
    operands: list


@dataclasses.dataclass
class Computation:
    name: str
    instrs: list
    is_fusion_body: bool = False


ENTRY_KEY = "__entry__"


def parse_module(hlo: str) -> dict[str, Computation]:
    """Parse computations; the ENTRY computation name is stored under the
    ``ENTRY_KEY`` sentinel (as a string) for ``computation_weights``."""
    comps: dict[str, Computation] = {}
    cur: Optional[Computation] = None
    fused_names: set[str] = set()
    entry_name = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and ("->" in line) and line.endswith("{"):
            m = _COMP_HDR.match(line.strip())
            if m:
                cur = Computation(m.group(1), [])
                comps[cur.name] = cur
                if line.startswith("ENTRY"):
                    entry_name = cur.name
                continue
        if line.strip() == "}":
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        # op name: first identifier after the result shape spec
        opm = re.search(r"\)?\s*([a-z][a-z0-9\-]*)\(", rhs)
        op = opm.group(1) if opm else ""
        head = rhs.split(op + "(", 1)[0] if op else rhs
        rbytes = relems = 0
        shapes = []
        for dtype, dims in _SHAPE_TOK.findall(head):
            if dtype in _DTYPE_BYTES:
                b, e = _shape_bytes_elems(dtype, dims)
                rbytes += b
                relems += e
                shapes.append((dtype, [int(d) for d in dims.split(",") if d]))
        body = rhs[len(head):]
        operands = _OPND.findall(body.split("),", 1)[0]) if op else []
        cur.instrs.append(Instr(name, op, rbytes, relems, shapes, rhs,
                                operands))
        if op == "fusion":
            fm = re.search(r"calls=%?([\w.\-]+)", rhs)
            if fm:
                fused_names.add(fm.group(1))
    for fname in fused_names:
        if fname in comps:
            comps[fname].is_fusion_body = True
    if entry_name is not None:
        comps[ENTRY_KEY] = entry_name  # type: ignore[assignment]
    return comps


def _trip_count_from_cond(cond: Computation) -> int:
    """Largest scalar int constant in the loop condition (counter bound)."""
    best = 1
    for ins in cond.instrs:
        cm = re.match(r"[su](?:32|64)\[\]\s*constant\((\d+)\)", ins.text)
        if cm:
            best = max(best, int(cm.group(1)))
    return best


_TRIP_RE = re.compile(r'known_trip_count[^}]*?"n"\s*:\s*"(\d+)"')


def _trip_count(while_ins: Instr, comps: dict[str, Computation]) -> int:
    """Trip count: backend_config known_trip_count, else condition constant."""
    m = _TRIP_RE.search(while_ins.text)
    if m:
        return int(m.group(1))
    cm = re.search(r"condition=%?([\w.\-]+)", while_ins.text)
    if cm and cm.group(1) in comps:
        return _trip_count_from_cond(comps[cm.group(1)])
    return 1


def _find_entry(comps: dict) -> str:
    if ENTRY_KEY in comps:
        return comps[ENTRY_KEY]
    # fallback: a computation never referenced as body/cond/calls target
    referenced: set[str] = set()
    for comp in comps.values():
        if isinstance(comp, str):
            continue
        for ins in comp.instrs:
            for key in ("body=", "condition=", "calls=", "to_apply="):
                for mm in re.finditer(key + r"%?([\w.\-]+)", ins.text):
                    referenced.add(mm.group(1))
    candidates = [c for c in comps if c not in referenced and c != ENTRY_KEY]
    return candidates[0] if candidates else next(iter(comps))


def computation_weights(comps: dict[str, Computation],
                        entry: Optional[str] = None) -> dict[str, float]:
    """Execution multiplicity of each computation (while-aware)."""
    if entry is None:
        entry = _find_entry(comps)
    comps = {k: v for k, v in comps.items() if not isinstance(v, str)}

    weights: dict[str, float] = defaultdict(float)

    def visit(name: str, w: float, depth=0):
        if name not in comps or depth > 50:
            return
        weights[name] += w
        for ins in comps[name].instrs:
            if ins.op == "while":
                bm = re.search(r"body=%?([\w.\-]+)", ins.text)
                cm = re.search(r"condition=%?([\w.\-]+)", ins.text)
                trips = _trip_count(ins, comps)
                if bm:
                    visit(bm.group(1), w * trips, depth + 1)
                if cm:
                    visit(cm.group(1), w * (trips + 1), depth + 1)
            else:
                for key in ("calls=", "to_apply="):
                    mm = re.search(key + r"%?([\w.\-]+)", ins.text)
                    if mm:
                        visit(mm.group(1), w, depth + 1)
                if ins.op == "conditional":
                    for mm in re.finditer(
                            r"(?:true_computation|false_computation|"
                            r"branch_computations=\{[^}]*)=?%?([\w.\-]+)",
                            ins.text):
                        visit(mm.group(1), w, depth + 1)
    visit(entry, 1.0)
    return dict(weights)


def _dot_flops(ins: Instr, symtab: dict[str, Instr]) -> float:
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.text)
    if not m or not ins.operands:
        return 2.0 * ins.result_elems
    lhs = symtab.get(ins.operands[0])
    if lhs is None or not lhs.shapes:
        return 2.0 * ins.result_elems
    dims = lhs.shapes[0][1]
    contract = 1
    for d in m.group(1).split(","):
        if d and int(d) < len(dims):
            contract *= dims[int(d)]
    return 2.0 * ins.result_elems * contract


def _conv_flops(ins: Instr, symtab: dict[str, Instr]) -> float:
    wm = re.search(r"window=\{size=([0-9x]+)", ins.text)
    window = 1
    if wm:
        for d in wm.group(1).split("x"):
            window *= int(d)
    in_feat = 1
    if len(ins.operands) >= 2:
        ker = symtab.get(ins.operands[1])
        # kernel input-feature dim ≈ total kernel elems / (window · out_feat)
        if ker is not None and ker.result_elems and window and ker.shapes:
            out_feat_guess = ker.shapes[0][1][-1]
            in_feat = max(1, ker.result_elems
                          // max(1, window * out_feat_guess))
    return 2.0 * ins.result_elems * window * in_feat


_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "while", "conditional", "call", "custom-call",
                   "copy-start", "copy-done", ""}


@dataclasses.dataclass
class HloTotals:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_by_type: dict = dataclasses.field(
        default_factory=lambda: {c: 0.0 for c in COLLECTIVES})
    loop_weighted: bool = True


def analyze(hlo: str) -> HloTotals:
    comps = parse_module(hlo)
    weights = computation_weights(comps)
    totals = HloTotals()
    for cname, comp in comps.items():
        if isinstance(comp, str):  # ENTRY_KEY sentinel
            continue
        w = weights.get(cname, 0.0)
        if w == 0.0:
            continue
        symtab = {i.name: i for i in comp.instrs}
        for ins in comp.instrs:
            if ins.op == "dot":
                totals.flops += w * _dot_flops(ins, symtab)
            elif ins.op == "convolution":
                totals.flops += w * _conv_flops(ins, symtab)
            for cop in COLLECTIVES:
                if ins.op.startswith(cop) and not ins.op.endswith("-done"):
                    totals.collective_bytes += w * ins.result_bytes
                    totals.collective_by_type[cop] += w * ins.result_bytes
            if comp.is_fusion_body:
                continue  # fusion internals don't touch HBM
            if ins.op in _SKIP_BYTES_OPS:
                continue
            opnd_bytes = sum(symtab[o].result_bytes for o in ins.operands
                             if o in symtab)
            totals.hbm_bytes += w * (ins.result_bytes + opnd_bytes)
    return totals
