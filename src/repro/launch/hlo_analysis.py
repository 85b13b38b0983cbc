"""Post-GSPMD HLO cost walker — the roofline term extractor.

``compiled.cost_analysis()`` counts every while-loop body exactly ONCE, which
under-counts scanned programs (layer scans, PCG scans) by the trip count.
This walker parses the optimized per-device HLO text instead:

  1. split the module into computations and index每 instruction's result
     shape (symbol table per computation);
  2. propagate execution MULTIPLIERS down the call graph — while bodies get
     ×\"known_trip_count\" (emitted by XLA for lax.scan), fusions/calls ×1,
     conditional branches ×1;
  3. accumulate, per computation × multiplier:
       · dot FLOPs      = 2 · prod(result dims) · prod(contracted dims)
       · HBM bytes      = result + operand bytes of top-level (unfused) ops
       · collective wire bytes with ring-algorithm factors:
           all-gather      (n−1)/n · result
           reduce-scatter  (n−1)/n · n · result           (operand-sized)
           all-reduce      2 (n−1)/n · result
           all-to-all      (n−1)/n · result
           collective-permute  result

The HLO here is the per-device SPMD program (shapes are shard-local), so the
totals are per-chip — exactly what the roofline terms need.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_CALL_RE = re.compile(r"(?:calls=|body=|condition=|branch_computations=\{)"
                      r"%?([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[\'\"]?:?\s*\{\s*[\'\"]?n[\'\"]?\s*:\s*[\'\"]?(\d+)')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_GROUP_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUP_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shapes_of(typestr: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """All array shapes in a type string (tuples expand to their parts)."""
    out = []
    for m in _SHAPE_RE.finditer(typestr):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        shape = tuple(int(x) for x in dims.split(",") if x) if dims else ()
        out.append((dt, shape))
    return out


def _nbytes(typestr: str) -> int:
    total = 0
    for dt, shape in _shapes_of(typestr):
        n = 1
        for d in shape:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    result_type: str
    operands: List[str]
    raw: str


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    shapes: Dict[str, str]          # symbol → result type string


_OPCODE_RE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-_]*)\(")


def _opcode_of(rhs: str) -> Tuple[str, str, int]:
    """Split '<type> <opcode>(...)' — returns (result_type, opcode, paren_at).

    The result type may itself be a tuple '(f32[...], ...)', so the opcode
    is found as the first lowercase token directly followed by '(' — HLO
    dtype tokens are always followed by '[' so they never false-match."""
    m = _OPCODE_RE.search(rhs)
    if not m:
        return rhs, "", -1
    return rhs[: m.start()].strip(), m.group(1), m.end() - 1


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_name = None
    for line in text.splitlines():
        stripped = line.strip()
        header = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{", stripped)
        if header and not stripped.startswith("//"):
            cur = Computation(header.group(2), [], {})
            comps[cur.name] = cur
            if header.group(1):
                entry_name = cur.name
            continue
        if stripped.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        result_type, opcode, par = _opcode_of(rhs)
        if par < 0:
            continue
        # operands: %refs inside the opcode's balanced paren group
        depth = 0
        end = par
        for i, ch in enumerate(rhs[par:], start=par):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operands = _OPERAND_RE.findall(rhs[par:end + 1])
        cur.instrs.append(Instr(name, opcode, result_type, operands, rhs))
        cur.shapes[name] = result_type
    if entry_name:
        comps["__entry__"] = comps[entry_name]
    return comps


def _dot_flops(instr: Instr, comp: Computation) -> float:
    res_shapes = _shapes_of(instr.result_type)
    if not res_shapes:
        return 0.0
    _, rshape = res_shapes[0]
    out = 1.0
    for d in rshape:
        out *= d
    # contracted dims from lhs shape
    mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.raw)
    contracted = 1.0
    if mc and instr.operands:
        lhs_type = comp.shapes.get(instr.operands[0])
        if lhs_type:
            ls = _shapes_of(lhs_type)
            if ls:
                _, lshape = ls[0]
                for idx in (int(x) for x in mc.group(1).split(",") if x):
                    if idx < len(lshape):
                        contracted *= lshape[idx]
    return 2.0 * out * contracted


def _group_size(instr: Instr, default: int) -> int:
    m = _GROUP_RE.search(instr.raw)
    if m:
        return len(m.group(1).split(","))
    m = _GROUP_V2_RE.search(instr.raw)
    if m:
        return int(m.group(2))
    return default


def _collective_wire_bytes(instr: Instr, n_default: int) -> float:
    size = _nbytes(instr.result_type)
    n = max(2, _group_size(instr, n_default))
    ring = (n - 1) / n
    if instr.opcode == "all-gather":
        return ring * size
    if instr.opcode == "reduce-scatter":
        return ring * size * n
    if instr.opcode == "all-reduce":
        return 2.0 * ring * size
    if instr.opcode == "all-to-all":
        return ring * size
    if instr.opcode == "collective-permute":
        return float(size)
    return 0.0


_SKIP_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "while", "conditional", "call", ""}

# elementwise-ish opcodes: ~1 flop per output element (covers the VPU work
# of scatter/segment-sum-heavy programs — GNN message passing and the
# solver's SpMV have almost no dots, so dot-only counting under-reports)
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "log", "rsqrt", "sqrt", "power", "tanh",
    "logistic", "select", "compare", "and", "or", "xor", "clamp",
    "scatter", "reduce", "reduce-window", "select-and-scatter",
}


def _elementwise_flops(instr: Instr) -> float:
    total = 0.0
    for dt, shape in _shapes_of(instr.result_type):
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def _fusion_flops(instr: Instr, comp: Computation) -> float:
    """Fusions: ~2 flops per output element (fused elementwise chains)."""
    return 2.0 * _elementwise_flops(instr)


@dataclasses.dataclass
class HloCosts:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    collective_counts: Dict[str, float]
    per_collective_bytes: Dict[str, float]


def analyze(text: str, n_shards_default: int = 1) -> HloCosts:
    comps = parse_hlo(text)
    entry = comps.get("__entry__")
    if entry is None:
        return HloCosts(0, 0, 0, {}, {})

    # call-graph edges: caller → [(callee, trip_multiplier)]
    edges: Dict[str, List[Tuple[str, float]]] = {c: [] for c in comps}
    for cname, comp in comps.items():
        if cname == "__entry__":
            continue
        for ins in comp.instrs:
            called = _CALL_RE.findall(ins.raw)
            if not called:
                continue
            trip = 1.0
            if ins.opcode == "while":
                tm = _TRIP_RE.search(ins.raw)
                trip = float(tm.group(1)) if tm else 1.0
            for tgt in called:
                if tgt in comps:
                    edges[cname].append((tgt, trip))

    # multipliers via DFS from the entry (HLO call graph is a DAG)
    mult: Dict[str, float] = {c: 0.0 for c in comps}
    mult[entry.name] = 1.0
    stack = [entry.name]
    visiting = set()
    # simple worklist with accumulation (DAG ⇒ converges; guard cycles)
    work = [(entry.name, 1.0)]
    mult = {c: 0.0 for c in comps}
    depth_guard = 0
    while work and depth_guard < 200000:
        depth_guard += 1
        cname, m0 = work.pop()
        mult[cname] = mult.get(cname, 0.0) + m0
        for tgt, trip in edges.get(cname, ()):  # propagate the INCREMENT
            work.append((tgt, m0 * trip))

    fusion_bodies = set()
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.opcode == "fusion":
                for tgt in _CALL_RE.findall(ins.raw):
                    fusion_bodies.add(tgt)

    flops = 0.0
    hbm = 0.0
    coll = 0.0
    coll_counts: Dict[str, float] = {}
    coll_bytes: Dict[str, float] = {}
    for cname, comp in comps.items():
        if cname == "__entry__":
            continue
        m0 = mult.get(cname, 0.0)
        if m0 == 0.0:
            continue
        top_level = cname not in fusion_bodies
        for ins in comp.instrs:
            if ins.opcode in ("dot", "convolution"):
                flops += m0 * _dot_flops(ins, comp)
            elif ins.opcode in _ELEMENTWISE:
                flops += m0 * _elementwise_flops(ins)
            elif ins.opcode == "fusion" and top_level:
                flops += m0 * _fusion_flops(ins, comp)
            base = ins.opcode.replace("-start", "").replace("-done", "")
            if base in COLLECTIVES and not ins.opcode.endswith("-done"):
                wb = m0 * _collective_wire_bytes(ins, n_shards_default)
                coll += wb
                coll_counts[base] = coll_counts.get(base, 0.0) + m0
                coll_bytes[base] = coll_bytes.get(base, 0.0) + wb
            if top_level and ins.opcode not in _SKIP_BYTES:
                sz = _nbytes(ins.result_type)
                for op in ins.operands:
                    t = comp.shapes.get(op)
                    if t:
                        sz += _nbytes(t)
                hbm += m0 * sz
    return HloCosts(flops=flops, hbm_bytes=hbm, collective_bytes=coll,
                    collective_counts=coll_counts,
                    per_collective_bytes=coll_bytes)


_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_WHILE_COND_RE = re.compile(r"condition=%?([\w.\-]+)")


def while_loop_collectives(text: str) -> List[Dict[str, object]]:
    """Per-while-loop DIRECT collective counts, annotated with loop depth.

    For every while loop, count the collective instructions
    (all-reduce/all-gather/…; ``-start`` counted once, ``-done`` skipped)
    its BODY *and* CONDITION execute per iteration — reachable through
    calls/fusions WITHOUT crossing a nested while (nested loops count
    their own) — and record the while nesting depth at which the loop runs
    (1 = top-level loop, 2 = loop in a loop, …; max over call paths).
    Counts are static instruction occurrences, NOT multiplied by trip
    counts — so a fixed-trip ``lax.scan`` and a dynamic early-exit
    ``while_loop`` compare directly, and a reduction hidden in the
    early-exit stopping test (the cond computation) is counted too.

    In the solver programs the depth-2 loops with collectives are the PCG
    loops inside the IRLS loop (CPU HLO also lowers scatters/cholesky to
    collective-free whiles — depth alone doesn't identify PCG, depth plus
    ``direct > 0`` does).  Comparing those counts between the fixed and the
    adaptive program is the "zero extra collectives per PCG step" check.
    Returns ``[{"body": name, "depth": d, "direct": k}, ...]`` for loops
    with ``direct > 0``, keyed by their body computation's name.
    """
    comps = parse_hlo(text)
    entry = comps.get("__entry__")
    if entry is None:
        return []

    parts_of: Dict[int, Tuple[str, ...]] = {}  # while-instr → (body[, cond])
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.opcode == "while":
                names = []
                for rx in (_WHILE_BODY_RE, _WHILE_COND_RE):
                    m = rx.search(ins.raw)
                    if m and m.group(1) in comps:
                        names.append(m.group(1))
                if names:
                    parts_of[id(ins)] = tuple(names)

    def direct_count(name: str, seen: set) -> int:
        coll = 0
        for ins in comps[name].instrs:
            base = ins.opcode.replace("-start", "").replace("-done", "")
            if base in COLLECTIVES and not ins.opcode.endswith("-done"):
                coll += 1
            if ins.opcode == "while":
                continue                     # nested loops count their own
            for tgt in _CALL_RE.findall(ins.raw):
                if tgt in comps and tgt not in seen:
                    seen.add(tgt)
                    coll += direct_count(tgt, seen)
        return coll

    depth: Dict[Tuple[str, ...], int] = {}   # (body[, cond]) → nesting depth

    def walk(name: str, d: int, seen: set) -> None:
        for ins in comps[name].instrs:
            if ins.opcode == "while":
                parts = parts_of.get(id(ins))
                if parts is None:
                    continue
                if depth.get(parts, 0) < d + 1:
                    depth[parts] = d + 1
                    for part in parts:
                        walk(part, d + 1, set())
                continue
            for tgt in _CALL_RE.findall(ins.raw):
                if tgt in comps and tgt not in seen:
                    seen.add(tgt)
                    walk(tgt, d, seen)

    walk(entry.name, 0, {entry.name})
    out = []
    for parts, d in sorted(depth.items()):
        k = sum(direct_count(part, {part}) for part in parts)
        if k > 0:
            out.append({"body": parts[0], "depth": d, "direct": k})
    return out


# ---------------------------------------------------------------------------
# Roofline terms (peaks from repro.obs.perf.peaks)
# ---------------------------------------------------------------------------

def roofline_terms(costs: HloCosts, device_kind: str) -> Dict[str, float]:
    """Per-chip times in seconds (the HLO is already the per-device
    program, so no further division by chip count) against the published
    peaks of ``device_kind``; KeyError for a chip the table lacks."""
    from repro.obs.perf.peaks import PEAKS
    pk = PEAKS[device_kind]
    t_compute = costs.flops / pk.flops
    t_memory = costs.hbm_bytes / pk.hbm_bytes_per_s
    t_collective = costs.collective_bytes / pk.ici_bytes_per_s
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_collective)), key=lambda kv: kv[1])[0]
    return {"t_compute": t_compute, "t_memory": t_memory,
            "t_collective": t_collective, "dominant": dominant}
