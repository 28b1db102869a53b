"""Loop-aware roofline accounting from optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE, but a
62-layer scan executes it 62×.  This module parses the optimized HLO,
builds the computation call graph (while bodies, fusions, to_apply),
propagates trip-count multipliers, and derives:

  * flops            — 2·M·N·K summed over every dot/convolution,
                       trip-count weighted (per-device, post-SPMD shapes)
  * hbm_bytes        — static HBM-traffic estimate: Σ over non-fusion-
                       internal instructions of (operand + output) buffer
                       bytes (fusions internalize their temporaries)
  * collective_bytes — Σ output bytes per collective op, trip-weighted

Methodology note: this is a STATIC estimate — reads that actually hit VMEM
reuse are counted as HBM traffic, so ``hbm_bytes`` is an upper bound; dots
dominated by the MXU are exact.  Both limitations are uniform across
configurations, so Δ comparisons in §Perf are meaningful.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_CALL_ATTR_RE = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)="
    r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SKIP_OPS = ("parameter(", "get-tuple-element(", "tuple(", "constant(",
             "bitcast(", "after-all(", "partition-id(", "iota(")


def _shape_elems_bytes(text: str) -> Tuple[int, int]:
    total_b = 0
    total_e = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total_e += n
        total_b += n * _DTYPE_BYTES[dt]
    return total_e, total_b


@dataclasses.dataclass
class Instr:
    name: str
    out_text: str      # text before the op name (shapes)
    op: str
    rest: str          # full remainder (operands + attrs)


_OP_RE = re.compile(
    r"^((?:\((?:[^()]*|\([^()]*\))*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*)"
    r"([a-z][\w\-]*)\((.*)$")


def parse_hlo(text: str):
    """-> (computations: name -> [Instr], order)."""
    comps: Dict[str, List[Instr]] = {}
    cur = "__top__"
    comps[cur] = []
    header_re = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        hm = header_re.match(line)
        if hm and line.endswith("{"):
            cur = hm.group(1)
            comps.setdefault(cur, [])
            continue
        if line == "}":
            continue
        dm = _DEF_RE.match(line)
        if not dm:
            continue
        name, rhs = dm.group(1), dm.group(2)
        om = _OP_RE.match(rhs)
        if not om:
            continue
        comps[cur].append(Instr(name, om.group(1), om.group(2), om.group(3)))
    return comps


def copies_of(text: str, shapes) -> List[str]:
    """``computation/name: result`` of every copy in optimized HLO — a
    ``copy`` or ``copy-start``, or a fusion XLA names ``copy*`` — whose
    result holds an array of one of ``shapes`` (dimensions only; dtype
    and layout aside)."""
    wanted = {tuple(s) for s in shapes}
    hits = []
    for comp, instrs in parse_hlo(text).items():
        for ins in instrs:
            if not (ins.op in ("copy", "copy-start") or (
                    ins.op == "fusion" and ins.name.startswith("copy"))):
                continue
            dims = {tuple(int(d) for d in ds.split(",") if d)
                    for _, ds in _SHAPE_RE.findall(ins.out_text)}
            if dims & wanted:
                hits.append(f"{comp}/{ins.name}: {ins.out_text.strip()}")
    return hits


def _multipliers(comps) -> Tuple[Dict[str, int], set]:
    """Propagate loop trip counts through the call graph.

    Returns (multiplier per computation, fusion-internal computation set).
    While bodies/conditions are TOP-LEVEL (their instruction I/O is real
    HBM traffic each iteration); computations entered via fusion ``calls=``
    or ``to_apply=`` are internal (temporaries live in VMEM/registers)."""
    # direct call edges with weights
    edges: Dict[str, List[Tuple[str, int]]] = {c: [] for c in comps}
    entry_candidates = set(comps)
    internal: set = set()
    for cname, instrs in comps.items():
        for ins in instrs:
            if ins.op == "while":
                m = re.search(r"body=%?([\w.\-]+)", ins.rest)
                c = re.search(r"condition=%?([\w.\-]+)", ins.rest)
                trip = 1
                if c and c.group(1) in comps:
                    consts = [int(x) for x in _CONST_RE.findall(
                        "\n".join(f"{i.op}({i.rest}"
                                  for i in comps[c.group(1)]))]
                    if consts:
                        trip = max(consts)
                if m and m.group(1) in comps:
                    edges[cname].append((m.group(1), max(trip, 1)))
                    entry_candidates.discard(m.group(1))
                if c and c.group(1) in comps:
                    edges[cname].append((c.group(1), max(trip, 1)))
                    entry_candidates.discard(c.group(1))
            else:
                for attr in _CALL_ATTR_RE.finditer(ins.rest):
                    for callee in re.split(r",\s*", attr.group(1)):
                        callee = callee.lstrip("%")
                        if callee in comps:
                            edges[cname].append((callee, 1))
                            entry_candidates.discard(callee)
                            if "calls=" in ins.rest or "to_apply=" in ins.rest:
                                internal.add(callee)

    mult: Dict[str, int] = {c: 0 for c in comps}

    def visit(c, m):
        if m <= mult.get(c, 0):
            return
        mult[c] = m
        for callee, w in edges.get(c, []):
            visit(callee, m * w)

    for c in entry_candidates:
        visit(c, 1)
    for c in comps:      # unreachable safety
        if mult[c] == 0:
            mult[c] = 1
    # internal-ness propagates down the call graph
    changed = True
    while changed:
        changed = False
        for c in list(internal):
            for callee, _ in edges.get(c, []):
                if callee not in internal:
                    internal.add(callee)
                    changed = True
    return mult, internal


def analyze(text: str) -> Dict[str, float]:
    comps = parse_hlo(text)
    mult, internal = _multipliers(comps)

    # symbol table: instruction name -> output bytes
    out_bytes: Dict[str, int] = {}
    for cname, instrs in comps.items():
        for ins in instrs:
            _, b = _shape_elems_bytes(ins.out_text)
            out_bytes[ins.name] = b

    flops = 0.0
    hbm = 0.0
    coll = {k: 0.0 for k in _COLLECTIVES}
    for cname, instrs in comps.items():
        m = mult[cname]
        for ins in instrs:
            # --- dot flops (counted even inside fusions) ---------------
            if ins.op in ("dot", "convolution"):
                flops += m * _dot_flops(ins, out_bytes, comps)
            # --- collectives -------------------------------------------
            for cop in _COLLECTIVES:
                if ins.op.startswith(cop) and not ins.op.endswith("-done"):
                    _, b = _shape_elems_bytes(ins.out_text)
                    coll[cop] += m * b
            # --- HBM traffic (top-level only) --------------------------
            if cname not in internal:
                if ins.op in ("parameter", "get-tuple-element", "tuple",
                              "constant", "bitcast", "after-all",
                              "partition-id", "iota", "while", "call",
                              "conditional"):
                    continue
                _, ob = _shape_elems_bytes(ins.out_text)
                if ins.op in ("dynamic-slice", "slice", "gather"):
                    # reads only the sliced region (+ tiny indices)
                    hbm += m * 2 * ob
                    continue
                if ins.op == "dynamic-update-slice":
                    # in-place: reads + writes the UPDATE region only
                    opnames = re.findall(r"%([\w.\-]+)", ins.rest)
                    upd = out_bytes.get(opnames[1], ob) if len(opnames) > 1 \
                        else ob
                    hbm += m * 2 * upd
                    continue
                opbytes = [out_bytes.get(o, 0)
                           for o in re.findall(r"%([\w.\-]+)", ins.rest)]
                rb = sum(opbytes)
                if ins.op == "fusion" and opbytes:
                    callee = re.search(r"calls=%?([\w.\-]+)", ins.rest)
                    callee_ops = {i.op for i in
                                  comps.get(callee.group(1), [])} \
                        if callee else set()
                    has_dus = "dynamic-update-slice" in callee_ops
                    has_ds = bool(callee_ops & {"dynamic-slice", "gather",
                                                "slice"})
                    if has_ds and not has_dus and ob < max(opbytes):
                        # slice-wrapping fusion: reads only the sliced
                        # region of its big operand, not the whole buffer
                        mx = max(opbytes)
                        t = 2 * ob + (rb - mx)
                        hbm += m * t
                        continue
                    if has_dus:
                        # update-in-place fusion: traffic is the update
                        # region (small operands), not the aliased buffer —
                        # whether the fusion's output is the slice or the
                        # whole carried buffer
                        mx = max(opbytes)
                        small = rb - mx
                        pos = [b for b in opbytes if b > 0 and b < mx]
                        floor = min(pos) if pos else ob
                        hbm += m * 2 * max(min(ob, small), min(floor, ob))
                        continue
                hbm += m * (ob + rb)

    coll_total = sum(coll.values())
    return {"flops": flops, "hbm_bytes": hbm,
            "collective_bytes": coll_total,
            "collectives": coll}


# dot flops need operand shapes; build a resolver on demand
_DOT_CACHE: Dict[int, Dict[str, str]] = {}


def _dot_flops(ins: Instr, out_bytes, comps) -> float:
    """2 * out_elems * contraction_size.

    Operand shapes resolve through the global def table (by element count
    and the contracting-dims attribute on the lhs)."""
    out_e, _ = _shape_elems_bytes(ins.out_text)
    # operand element counts
    key = id(comps)
    if key not in _DOT_CACHE:
        table = {}
        for instrs in comps.values():
            for i2 in instrs:
                e, _ = _shape_elems_bytes(i2.out_text)
                table[i2.name] = (e, i2.out_text)
        _DOT_CACHE.clear()           # keep one entry — bounded memory
        _DOT_CACHE[key] = table
    table = _DOT_CACHE[key]
    ops = re.findall(r"%([\w.\-]+)", ins.rest)
    if len(ops) < 2:
        return 0.0
    lhs_name = ops[0]
    lhs = table.get(lhs_name)
    if lhs is None:
        return 0.0
    lhs_e, lhs_text = lhs
    cm = re.search(r"lhs_contracting_dims=\{([0-9,]+)\}", ins.rest)
    bm = re.search(r"lhs_batch_dims=\{([0-9,]*)\}", ins.rest)
    sm = _SHAPE_RE.search(lhs_text)
    if not (cm and sm):
        # convolution or unparsable: fall back to out*lhs/out heuristic
        return 2.0 * out_e * max(lhs_e // max(out_e, 1), 1)
    dims = [int(d) for d in sm.group(2).split(",") if d]
    kdims = [int(i) for i in cm.group(1).split(",") if i]
    k = 1
    for i in kdims:
        if i < len(dims):
            k *= dims[i]
    return 2.0 * out_e * k
