"""Text-based HLO cost analysis with while-trip scaling.

XLA's ``compiled.cost_analysis()`` counts every while body ONCE — useless
for scanned-layer models where >95% of work is inside loops. This module
re-derives per-device FLOPs and memory traffic from the post-optimization
HLO text, per computation, and multiplies each computation by how often it
actually runs (``known_trip_count`` from the loop backend_config, times the
caller's own multiplier — fusions/calls inherit, nested whiles compound).

Counting rules (validated against cost_analysis on loop-free modules in
tests/test_hlo_cost.py):
  * dot: 2 * prod(result dims) * prod(lhs contracting dims)
  * elementwise arithmetic/transcendental: result elements
  * reduce: operand elements
  * bytes (two counters):
      - ``bytes``: result + operand bytes of every non-bookkeeping op —
        the same optimistic-HBM semantics as XLA's "bytes accessed";
      - ``bytes_fused``: only ops that would hit HBM on a TPU after
        fusion (dot / fusion I/O / gather / scatter / dynamic slices /
        copies / reduces / collectives / custom-calls); bare elementwise
        chains are assumed fused into neighbors. The roofline memory term
        uses this counter (methodology recorded in EXPERIMENTS.md).

HLO text is SSA-ordered (operands defined before use), so one pass with a
per-computation symbol table resolves all operand shapes.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_HEADER_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(.*\)\s*->")
_DEF_RE = re.compile(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_SHAPE_TOK = re.compile(r"^\(?\s*(\w+)\[([\d,]*)\]")
_SHAPES_ALL = re.compile(r"(\w+)\[([\d,]*)\]")
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")
_WHILE_RE = re.compile(r"condition=%([\w.\-]+), body=%([\w.\-]+)")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%([\w.\-]+)")
_BRANCH_RE = re.compile(r"(?:true|false)_computation=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERANDS_RE = re.compile(r"%([\w.\-]+)")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "log", "rsqrt", "sqrt", "tanh", "logistic",
    "power", "select", "compare", "and", "or", "xor", "not", "convert",
    "floor", "ceil", "sign", "cosine", "sine", "clamp", "remainder",
    "round-nearest-even", "atan2", "expm1", "log1p", "cbrt", "erf",
    "is-finite", "exponential-minus-one", "shift-left",
    "shift-right-logical", "shift-right-arithmetic",
}
_TRANSCENDENTAL = {"exponential", "log", "rsqrt", "sqrt", "tanh",
                   "logistic", "power", "cosine", "sine", "erf", "expm1",
                   "log1p", "cbrt", "atan2"}
_FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
         "after-all", "partition-id", "replica-id", "opt-barrier",
         "add-dependency"}


def _elems_bytes(type_str: str) -> Tuple[int, int]:
    elems, nbytes = 0, 0
    for dt, dims in _SHAPES_ALL.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES[dt]
    return elems, nbytes


def _f32_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPES_ALL.findall(type_str):
        if dt != "f32":
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * 4
    return total


_FUSED_HBM = {"dot", "fusion", "custom-call", "gather", "scatter",
              "dynamic-slice", "dynamic-update-slice", "concatenate",
              "copy", "sort", "reduce", "reduce-window", "all-gather",
              "all-reduce", "reduce-scatter", "all-to-all",
              "collective-permute", "rng-bit-generator", "pad", "reverse",
              "select-and-scatter", "map", "call", "transpose"}

# Ops whose operand/result traffic hits HBM even under TPU mega-fusion:
# GEMM I/O, irregular data movement, reductions and collectives. Fusion
# boundaries / copies / elementwise chains are assumed fused away (they are
# CPU-granularity artifacts). The roofline memory term uses this set.
_TIGHT_HBM = {"dot", "gather", "scatter", "dynamic-slice",
              "dynamic-update-slice", "sort", "reduce", "reduce-window",
              "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
              "collective-permute", "rng-bit-generator", "custom-call",
              "select-and-scatter"}


class Computation:
    __slots__ = ("name", "entry", "flops", "bytes", "bytes_fused",
                 "bytes_tight", "bytes_tight_f32", "bytes_scoped",
                 "flops_scoped", "transcendentals", "whiles", "calls",
                 "elems", "nbytes", "nbytes32", "dims")

    def __init__(self, name: str, entry: bool):
        self.name = name
        self.entry = entry
        self.flops = 0.0
        self.bytes = 0.0
        self.bytes_fused = 0.0
        self.bytes_tight = 0.0
        self.bytes_tight_f32 = 0.0
        self.bytes_scoped = 0.0     # fused-HBM bytes in scope_re-matched ops
        self.flops_scoped = 0.0
        self.transcendentals = 0.0
        self.whiles: List[Tuple[str, int]] = []
        self.calls: List[str] = []
        self.elems: Dict[str, int] = {}
        self.nbytes: Dict[str, int] = {}
        self.nbytes32: Dict[str, int] = {}
        self.dims: Dict[str, List[int]] = {}


def parse(hlo: str, scope_re: Optional[str] = None
          ) -> Tuple[Dict[str, Computation], Optional[str]]:
    scope = re.compile(scope_re) if scope_re else None
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_name: Optional[str] = None
    for raw in hlo.splitlines():
        s = raw.strip()
        hm = _HEADER_RE.match(s)
        if hm and s.endswith("{"):
            cur = Computation(hm.group(2), bool(hm.group(1)))
            comps[cur.name] = cur
            if cur.entry:
                entry_name = cur.name
            continue
        if cur is None:
            continue
        if s == "}":
            cur = None
            continue
        dm = _DEF_RE.match(s)
        if not dm:
            continue
        name, rest = dm.group(1), dm.group(2)
        op_m = re.search(r"\s([\w\-]+)\(", rest)
        if not op_m:
            continue
        opcode = op_m.group(1)
        type_str = rest[: op_m.start()]
        elems, nbytes = _elems_bytes(type_str)
        cur.elems[name] = elems
        cur.nbytes[name] = nbytes
        cur.nbytes32[name] = _f32_bytes(type_str)
        shp = _SHAPE_TOK.match(type_str.strip())
        if shp:
            cur.dims[name] = [int(x) for x in shp.group(2).split(",") if x]

        if opcode in _FREE:
            continue
        if opcode == "while":
            tm = _TRIP_RE.search(rest)
            wm = _WHILE_RE.search(rest)
            if wm:
                cur.whiles.append((wm.group(2),
                                   int(tm.group(1)) if tm else 1))
            continue
        if opcode == "conditional":
            for nm in _BRANCH_RE.findall(rest):
                cur.calls.append(nm)
            bm = _BRANCHES_RE.search(rest)
            if bm:
                for nm in re.findall(r"%([\w.\-]+)", bm.group(1)):
                    cur.calls.append(nm)
            continue

        # operand list = inside the opcode parens (strip attrs after ')')
        body = rest[op_m.end():]
        depth = 1
        end = 0
        for i, ch in enumerate(body):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operands = _OPERANDS_RE.findall(body[:end])
        attrs = body[end:]
        opnd_bytes = sum(cur.nbytes.get(o, 0) for o in operands)
        opnd_elems = sum(cur.elems.get(o, 0) for o in operands)

        cm = _CALLS_RE.search(attrs)
        if cm:
            cur.calls.append(cm.group(1))
        # to_apply bodies (reduce/all-reduce/sort combiners) are scalar —
        # skipping them is a deliberate approximation.

        op_flops = 0.0
        if opcode == "dot":
            contract = 1
            lm_ = _LHS_CONTRACT_RE.search(attrs)
            if lm_ and operands:
                dims = cur.dims.get(operands[0], [])
                for d in lm_.group(1).split(","):
                    if d and int(d) < len(dims):
                        contract *= dims[int(d)]
            op_flops = 2.0 * elems * contract
        elif opcode in _ELEMENTWISE:
            op_flops = float(elems)
            if opcode in _TRANSCENDENTAL:
                cur.transcendentals += elems
        elif opcode in ("reduce", "reduce-window"):
            op_flops = float(opnd_elems)
        cur.flops += op_flops
        cur.bytes += nbytes + opnd_bytes
        if opcode in _FUSED_HBM:
            cur.bytes_fused += nbytes + opnd_bytes
            if scope is not None and scope.search(s):
                cur.bytes_scoped += nbytes + opnd_bytes
        if opcode in _TIGHT_HBM:
            cur.bytes_tight += nbytes + opnd_bytes
            cur.bytes_tight_f32 += (_f32_bytes(type_str)
                                    + sum(cur.nbytes32.get(o, 0)
                                          for o in operands))
        if scope is not None and op_flops and scope.search(s):
            cur.flops_scoped += op_flops
    return comps, entry_name


def multipliers(comps: Dict[str, Computation], entry: str,
                fallback_trip: int = 1) -> Dict[str, float]:
    mult: Dict[str, float] = {}

    def visit(name: str, m: float, depth: int = 0):
        if depth > 12 or name not in comps:
            return
        mult[name] = mult.get(name, 0.0) + m
        c = comps[name]
        for body, trip in c.whiles:
            visit(body, m * max(trip, fallback_trip), depth + 1)
        for callee in c.calls:
            visit(callee, m, depth + 1)

    visit(entry, 1.0)
    return mult


def analyze(hlo: str, fallback_trip: int = 1,
            scope_re: Optional[str] = None) -> Dict[str, float]:
    """Per-device totals with trip scaling.

    ``scope_re`` buckets fused-HBM bytes and flops of instructions whose
    line (incl. metadata op_name) matches — used to swap XLA-level
    attention traffic for fused-Pallas-kernel traffic in the roofline.
    """
    comps, entry = parse(hlo, scope_re)
    keys = ("flops", "bytes", "bytes_fused", "bytes_tight",
            "bytes_tight_f32", "bytes_scoped", "flops_scoped",
            "transcendentals")
    out = {k: 0.0 for k in keys}
    if entry is None:
        return out
    mult = multipliers(comps, entry, fallback_trip)
    for name, m in mult.items():
        c = comps[name]
        out["flops"] += m * c.flops
        out["bytes"] += m * c.bytes
        out["bytes_fused"] += m * c.bytes_fused
        out["bytes_tight"] += m * c.bytes_tight
        out["bytes_tight_f32"] += m * c.bytes_tight_f32
        out["bytes_scoped"] += m * c.bytes_scoped
        out["flops_scoped"] += m * c.flops_scoped
        out["transcendentals"] += m * c.transcendentals
    return out
