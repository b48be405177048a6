"""Trip-weighted FLOP / HBM-byte accounting from HLO text.

``compiled.cost_analysis()`` counts every while-loop body ONCE (verified:
a lax.scan of 8 matmuls reports the flops of 1), so for scanned-layer models
it undercounts by the trip count. This module recomputes both terms from
the HLO text with the same reachability walk hlo_utils uses for collectives:

  * FLOPs: every ``dot`` = 2 * prod(output) * prod(lhs contracting dims)
    (operand shapes resolved via a per-computation symbol table built from
    instruction definitions and computation-header parameters), plus 1 flop
    per output element for elementwise arithmetic ops.
  * HBM bytes: operands + outputs of instructions OUTSIDE fusion
    computations (fusion internals live in registers/VMEM; the fusion call
    site's operands/outputs are the HBM traffic).

While bodies are weighted by ``trip_hints`` at their nesting depth; fusion
calls are descended for FLOPs but not for bytes.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from repro.launch.hlo_utils import _COMP_HEADER_RE, _DTYPE_BYTES

_SHAPE_TOK = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(.*?\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"([\w\-]+)\(")
_PARAM_RE = re.compile(r"%?([\w.\-]+)\s*:\s*([a-z0-9]+\[[0-9,]*\])")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_REF_SINGLE_RE = re.compile(r"\b(body|condition|to_apply|calls)=%([\w.\-]+)")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "tanh", "logistic", "rsqrt", "sqrt", "log", "negate",
    "abs", "floor", "cosine", "sine", "select", "compare", "and", "or",
    "convert", "exponential-minus-one",
}

# Movement/aliasing ops: HBM traffic ~= output size only (a dynamic-slice
# reads a slice, not its whole operand; while/tuple carries alias in place).
_MOVEMENT_OPS = {
    "dynamic-slice", "dynamic-update-slice", "gather", "scatter", "slice",
    "get-tuple-element", "tuple", "copy", "copy-start", "copy-done",
    "bitcast", "reshape", "broadcast", "transpose", "iota", "parameter",
    "constant", "while", "conditional", "call", "concatenate", "pad",
    "reverse", "convert", "optimization-barrier",
}


def _shape_elems(seg: str) -> int:
    total = 0
    for _, dims in _SHAPE_TOK.findall(seg):
        n = 1
        if dims.strip():
            for d in dims.split(","):
                n *= int(d)
        total += n
    return max(total, 0)


def _shape_bytes_seg(seg: str) -> int:
    total = 0
    for dt, dims in _SHAPE_TOK.findall(seg):
        n = 1
        if dims.strip():
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


class _CompCost:
    def __init__(self, name: str):
        self.name = name
        self.flops = 0.0
        self.bytes = 0.0
        self.while_bodies: List[str] = []
        self.fusion_calls: List[str] = []
        self.other_refs: List[str] = []


def _operand_segment(line: str, start: int) -> str:
    depth = 1
    for i in range(start, len(line)):
        ch = line[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return line[start:i]
    return line[start:]


def parse_costs(hlo_text: str) -> Tuple[Dict[str, _CompCost], Optional[str]]:
    comps: Dict[str, _CompCost] = {}
    symbols: Dict[str, Dict[str, str]] = defaultdict(dict)  # comp -> name -> shape seg
    cur: Optional[str] = "<toplevel>"
    comps["<toplevel>"] = _CompCost("<toplevel>")
    entry: Optional[str] = None
    for line in hlo_text.splitlines():
        if " = " not in line.split("(", 1)[0]:
            header = _COMP_HEADER_RE.match(line)
            if header:
                cur = header.group(2)
                comps.setdefault(cur, _CompCost(cur))
                if header.group(1):
                    entry = cur
                for pname, pshape in _PARAM_RE.findall(line):
                    symbols[cur][pname] = pshape
                continue
        m = _INSTR_RE.match(line)
        if not m or cur is None:
            continue
        name, out_seg, opcode = m.groups()
        symbols[cur][name] = out_seg
        comp = comps[cur]
        operands_seg = _operand_segment(line, m.end())
        attrs_seg = line[m.end() + len(operands_seg):]
        # references
        for attr, ref in _REF_SINGLE_RE.findall(attrs_seg):
            if attr == "body":
                comp.while_bodies.append(ref)
            elif attr == "calls" and opcode == "fusion":
                comp.fusion_calls.append(ref)
            elif attr in ("condition", "to_apply", "calls"):
                comp.other_refs.append(ref)
        # flops
        if opcode == "dot":
            out_elems = _shape_elems(out_seg)
            contract = 1
            cm = _CONTRACT_RE.search(attrs_seg)
            ops = _OPERAND_RE.findall(operands_seg)
            if cm and ops:
                lhs_shape = symbols[cur].get(ops[0], "")
                tok = _SHAPE_TOK.search(lhs_shape)
                if tok:
                    dims = [int(d) for d in tok.group(2).split(",")
                            if d.strip()]
                    for ci in cm.group(1).split(","):
                        if ci.strip() and int(ci) < len(dims):
                            contract *= dims[int(ci)]
            comp.flops += 2.0 * out_elems * contract
        elif opcode in _ELEMENTWISE:
            comp.flops += _shape_elems(out_seg)
        # bytes: operands + output (fusion internals excluded by the walker;
        # movement/aliasing ops count output only)
        b = _shape_bytes_seg(out_seg)
        if opcode not in _MOVEMENT_OPS:
            for op_name in _OPERAND_RE.findall(operands_seg):
                seg = symbols[cur].get(op_name)
                if seg:
                    b += _shape_bytes_seg(seg)
        comp.bytes += b
    return comps, entry


def normalize_cost_analysis(ca) -> Dict[str, float]:
    """The numeric properties of ``compiled.cost_analysis()`` (a flat
    dict, or None where the backend has no cost model) as a plain dict."""
    if ca is None:
        return {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


def trip_weighted_costs(hlo_text: str, trip_hints: Sequence[int] = ()
                        ) -> Dict[str, float]:
    """Returns {'flops', 'bytes'}: per-device totals with while bodies
    weighted by trip_hints (by nesting depth)."""
    comps, entry = parse_costs(hlo_text)
    totals = {"flops": 0.0, "bytes": 0.0}
    if entry is None:
        for c in comps.values():
            totals["flops"] += c.flops
            totals["bytes"] += c.bytes
        return totals
    stack: List[str] = []

    def walk(name: str, mult: float, depth: int, in_fusion: bool):
        comp = comps.get(name)
        if comp is None or name in stack:
            return
        stack.append(name)
        totals["flops"] += comp.flops * mult
        if not in_fusion:
            totals["bytes"] += comp.bytes * mult
        for ref in comp.other_refs:
            walk(ref, mult, depth, in_fusion)
        for ref in comp.fusion_calls:
            walk(ref, mult, depth, True)
        for body in comp.while_bodies:
            trip = trip_hints[depth] if depth < len(trip_hints) else 1
            walk(body, mult * max(1, trip), depth + 1, in_fusion)
        stack.pop()

    walk(entry, 1.0, 0, False)
    return totals
