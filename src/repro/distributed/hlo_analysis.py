"""Trip-count-aware cost analysis of optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts each ``while`` body ONCE, so any
program built around ``lax.scan`` (layers, microbatches, attention chunks)
under-reports FLOPs/bytes by orders of magnitude. This module re-derives
the roofline inputs from the HLO text itself:

  * parses every computation into ops (result shape, opcode, operands),
  * resolves the call graph (while bodies, fusions, calls, conditionals),
  * extracts while-loop trip counts from the canonical XLA pattern
    (condition: ``compare(iv, constant(N)), direction=LT``),
  * rolls up, multiplying by enclosing trip counts:
      - FLOPs: dot/convolution ops (2 × output elems × contraction size),
      - HBM bytes: operand + result bytes of materializing ops (XLA's
        fusion memory model: fusion internals are free),
      - collective link traffic (ring model, as in roofline.py).

The result is a per-device estimate faithful to what the compiled SPMD
program would execute on hardware, including remat recompute and GSPMD
padding waste.
"""

from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "s2": 1, "u2": 1,
}

# Ops that force a value through HBM even under TPU-grade fusion.
# Elementwise ops / converts / broadcasts are assumed fused into their
# neighbours (XLA:TPU does; XLA:CPU wraps each in a trivial kLoop fusion,
# which must not be double-counted as traffic).
_MATERIALIZING = {
    "dot", "convolution", "copy", "transpose",
    "concatenate", "pad", "scatter", "reduce", "reduce-window", "sort",
    "select-and-scatter", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all", "collective-permute", "rng", "cholesky",
    "triangular-solve", "fft", "custom-call",
}

#: ops inside a fusion that make the fusion's result a real materialization
_HEAVY_IN_FUSION = {
    "dot", "convolution", "reduce", "reduce-window", "scatter", "gather",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "pad", "sort",
    "transpose", "copy",
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\([^()]*\)|[a-z][a-z0-9]*\[[0-9,]*\][^\s]*)\s+"
    r"(?P<opcode>[\w\-]+)\("
)
_CALL_ATTR_RE = re.compile(
    r"(?:body|to_apply|calls|branch_computations)=\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_COND_ATTR_RE = re.compile(r"condition=%?([\w.\-]+)")
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^}]*\}[^,]*\}|\[[0-9,]+\]<=\[[0-9]+\])")
_OPERANDS_RE = re.compile(r"\(([^()]*(?:\([^()]*\)[^()]*)*)\)")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _shape_elems(text: str) -> int:
    m = _SHAPE_RE.search(text)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n


def _group_size(line: str, default: int = 2) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return default
    g = m.group(1)
    if g.startswith("{{"):
        first = g[2:].split("}")[0]
        return max(len([x for x in first.split(",") if x.strip() != ""]), 1)
    dims = g.split("<=")[0].strip("[]").split(",")
    return int(dims[-1])


@dataclasses.dataclass
class Totals:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_per_op: dict = dataclasses.field(default_factory=dict)

    def add(self, other: "Totals", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        self.coll_bytes += other.coll_bytes * mult
        for k, v in other.coll_per_op.items():
            self.coll_per_op[k] = self.coll_per_op.get(k, 0.0) + v * mult


@dataclasses.dataclass
class _Op:
    name: str
    shape: str
    opcode: str
    line: str


@dataclasses.dataclass
class _Computation:
    name: str
    ops: list


def _parse_computations(text: str) -> dict[str, _Computation]:
    comps: dict[str, _Computation] = {}
    current: _Computation | None = None
    for line in text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "->" in stripped and "=" not in \
                stripped.split("(")[0]:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)", stripped)
            if m:
                current = _Computation(m.group(1), [])
                comps[current.name] = current
                continue
        if stripped.startswith("}"):
            continue
        m = _OP_RE.match(line)
        if m and current is not None:
            current.ops.append(_Op(
                m.group("name"), m.group("shape"), m.group("opcode"), stripped))
    return comps


def _dot_flops(op: _Op, symtab: dict[str, str]) -> float:
    """2 × output elems × contraction size for dot/convolution."""
    out_elems = _shape_elems(op.shape)
    if op.opcode == "dot":
        mm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
        operands = _first_paren_operands(op.line)
        if mm and operands:
            lhs_shape = symtab.get(operands[0], "")
            sm = _SHAPE_RE.search(lhs_shape)
            if sm:
                dims = [int(d) for d in sm.group(2).split(",") if d]
                k = 1
                for idx in (int(i) for i in mm.group(1).split(",") if i):
                    if idx < len(dims):
                        k *= dims[idx]
                return 2.0 * out_elems * k
        return 2.0 * out_elems
    if op.opcode == "convolution":
        mm = re.search(r"window=\{size=([0-9x]+)", op.line)
        k = 1
        if mm:
            for d in mm.group(1).split("x"):
                k *= int(d)
        # multiply by input feature count when available
        return 2.0 * out_elems * k
    return 0.0


def _first_paren_operands(line: str) -> list[str]:
    # text after 'opcode(' up to matching ')': first-level %names
    m = re.search(r"[\w\-]+\((.*)\)", line)
    if not m:
        return []
    inner = m.group(1)
    names = re.findall(r"%([\w.\-]+)", inner)
    return names


_TRIP_RE = re.compile(
    r"compare\([^)]*\)[^,]*, direction=LT")


def _trip_count(cond: _Computation) -> float:
    """Extract N from the scan-style condition: compare(iv, const N), LT.

    XLA may wrap the compare in a kLoop fusion; the loop-bound constant
    then feeds the fusion in the condition computation itself, so the
    largest integer constant in the condition is the trip count."""
    consts: dict[str, int] = {}
    for op in cond.ops:
        if op.opcode == "constant":
            m = re.search(r"constant\((-?[0-9]+)\)", op.line)
            if m:
                consts[op.name] = int(m.group(1))
    for op in cond.ops:
        if op.opcode == "compare" and "direction=LT" in op.line:
            for nm in _first_paren_operands(op.line):
                if nm in consts:
                    return float(max(consts[nm], 1))
    if consts:
        return float(max(max(consts.values()), 1))
    return 1.0


def analyze_hlo(text: str, entry: str | None = None) -> Totals:
    comps = _parse_computations(text)
    if not comps:
        return Totals()
    if entry is None:
        m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.MULTILINE)
        entry = m.group(1) if m else next(iter(comps))

    memo: dict[str, Totals] = {}

    def visit(name: str, stack: frozenset) -> Totals:
        if name in memo:
            return memo[name]
        if name not in comps or name in stack:
            return Totals()
        comp = comps[name]
        symtab = {op.name: op.shape for op in comp.ops}
        # values that live in HBM at body boundaries (loop carries, weights)
        hbm_resident = {
            op.name for op in comp.ops
            if op.opcode in ("parameter", "get-tuple-element")
        }
        t = Totals()
        stack2 = stack | {name}
        for op in comp.ops:
            if op.opcode == "while":
                m = re.search(r"body=%?([\w.\-]+)", op.line)
                c = _COND_ATTR_RE.search(op.line)
                trips = 1.0
                if c and c.group(1) in comps:
                    trips = _trip_count(comps[c.group(1)])
                if m:
                    t.add(visit(m.group(1), stack2), trips)
                continue
            if op.opcode == "conditional":
                m = _CALL_ATTR_RE.search(op.line)
                if m:
                    branches = [visit(b.strip().lstrip("%"), stack2)
                                for b in m.group(1).split(",")]
                    if branches:
                        worst = max(branches, key=lambda b: b.flops + b.bytes)
                        t.add(worst)
                continue
            if op.opcode in ("call", "fusion", "custom-call", "map",
                             "reduce", "reduce-window", "sort", "scatter",
                             "select-and-scatter", "all-reduce",
                             "reduce-scatter"):
                m = _CALL_ATTR_RE.search(op.line)
                if m and op.opcode in ("call", "map"):
                    for b in m.group(1).split(","):
                        t.add(visit(b.strip().lstrip("%"), stack2))
                elif m and op.opcode == "fusion":
                    # fusion body: count its dot flops (fused matmuls),
                    # bytes counted at the fusion boundary below
                    sub = visit(m.group(1).strip().lstrip("%"), stack2)
                    t.flops += sub.flops
            # --- flops ---
            t.flops += _dot_flops(op, symtab)
            # --- collectives ---
            base = op.opcode.replace("-start", "")
            if base in _COLLECTIVES and not op.opcode.endswith("-done"):
                out_b = _shape_bytes(op.shape)
                # XLA:CPU float-normalization rewrites bf16 collectives as
                # convert→f32-collective→convert; TPU reduces in bf16
                # natively, so charge such collectives at bf16 width.
                if "f32[" in op.shape:
                    ops_ = _first_paren_operands(op.line)
                    prod = next((o for o in comp.ops
                                 if ops_ and o.name == ops_[0]), None)
                    if prod is not None and (
                            prod.opcode == "convert"
                            or (prod.opcode == "fusion"
                                and "convert" in prod.name)):
                        out_b //= 2
                g = _group_size(op.line)
                if base == "all-reduce":
                    traffic = 2.0 * out_b * (g - 1) / g
                elif base == "reduce-scatter":
                    traffic = out_b * (g - 1)
                else:
                    traffic = out_b * (g - 1) / g
                t.coll_bytes += traffic
                t.coll_per_op[base] = t.coll_per_op.get(base, 0.0) + traffic
            # --- bytes (HBM traffic model) ---
            # Each materialized value is written once and read once by its
            # consumer (2 × result bytes); reads of HBM-resident inputs
            # (loop carries / weights / entry params) are counted at the
            # consuming op. Counting every operand of every op would
            # multiply-count values shared by several fusions.
            base_op = op.opcode.replace("-start", "")
            if base_op in ("dynamic-slice", "slice", "gather"):
                t.bytes += 2.0 * _shape_bytes(op.shape)   # touches the slice
            elif base_op == "dynamic-update-slice":
                ops_ = _first_paren_operands(op.line)
                upd = symtab.get(ops_[1], "") if len(ops_) > 1 else op.shape
                t.bytes += 2.0 * _shape_bytes(upd)        # in-place update
            elif base_op == "fusion":
                mm = _CALL_ATTR_RE.search(op.line)
                callee = mm.group(1).split(",")[0].strip().lstrip("%") \
                    if mm else None
                kinds = {o.opcode for o in comps[callee].ops} \
                    if callee in comps else set()
                compute_heavy = kinds & {
                    "dot", "convolution", "reduce", "reduce-window",
                    "scatter", "sort", "concatenate", "pad", "copy",
                    "transpose"}
                if compute_heavy:
                    b = 2.0 * _shape_bytes(op.shape)
                    for nm in _first_paren_operands(op.line):
                        if nm in hbm_resident:
                            b += _shape_bytes(symtab.get(nm, ""))
                    t.bytes += b
                elif "dynamic-update-slice" in kinds:
                    # in-place update: traffic = the updated slice only
                    sub = comps[callee]
                    subtab = {o.name: o.shape for o in sub.ops}
                    for o in sub.ops:
                        if o.opcode == "dynamic-update-slice":
                            ops_ = _first_paren_operands(o.line)
                            upd = subtab.get(ops_[1], "") if len(ops_) > 1 \
                                else ""
                            t.bytes += 2.0 * _shape_bytes(upd)
                elif kinds & {"dynamic-slice", "slice", "gather"}:
                    # slice + elementwise: touches the slice, not the operand
                    t.bytes += 2.0 * _shape_bytes(op.shape)
                # pure-elementwise fusions fuse into neighbours: free
            elif base_op in _MATERIALIZING:
                b = 2.0 * _shape_bytes(op.shape)
                for nm in _first_paren_operands(op.line):
                    if nm in hbm_resident:
                        b += _shape_bytes(symtab.get(nm, ""))
                t.bytes += b
        memo[name] = t
        return t

    # While bodies and fusion computations must only be counted through
    # their call sites, so visit only the entry.
    return visit(entry, frozenset())
