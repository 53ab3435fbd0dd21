"""Static analysis of compiled HLO text: flops, HBM traffic, collective bytes.

Why this exists: XLA's ``compiled.cost_analysis()`` counts while-loop bodies
exactly once, so scanned-layer
models under-report flops by ~n_layers. This module parses
``compiled.as_text()`` and walks the call graph, multiplying loop bodies by
their parsed trip counts.

Cost model:
  * flops: 2 * prod(out_dims) * prod(contracted lhs dims) per ``dot``.
  * bytes (HBM-traffic estimate): every *top-level* op (fusions = one op;
    their intermediates stay in registers/VMEM) writes its output once and
    that output is read ~once downstream -> 2 x sum(output bytes), plus the
    entry parameters read once. This avoids the gross overcount of charging
    a dynamic-slice fusion for its full (unsliced) operand. Pure-metadata
    ops (parameter/tuple/gte/constant/bitcast) are free.
  * collective bytes: sum of operand bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute / ragged-all-to-all.
  * while: trip_count x (body + cond); conditional: max over branches;
    fusion/call: dot flops + collectives recursed (bytes are not).

Trip counts are parsed from the canonical jax scan condition
(``compare(iv, constant(N)), direction=LT`` with iv starting at 0); a
``trip_hints`` override is available for non-canonical loops.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import lru_cache

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*?)\s*([\w\-]+)\((.*)$"
)
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*->.*\{\s*$")

COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "ragged-all-to-all",
)


def shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string; tuples summed."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> tuple[list[int], str]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return [], ""
    dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    return dims, m.group(1)


@dataclass
class OpInfo:
    name: str
    out_type: str
    opcode: str
    rest: str  # operand list + attributes


@dataclass
class Computation:
    name: str
    ops: list = field(default_factory=list)
    symbols: dict = field(default_factory=dict)  # op name -> out_type


@dataclass
class HloCost:
    flops: float = 0.0
    bytes_moved: float = 0.0
    collective_bytes: float = 0.0
    collective_counts: dict = field(default_factory=dict)
    while_trip_counts: dict = field(default_factory=dict)
    #: bytes of hoisted bf16->f32 whole-tensor upcasts at entry level.
    #: XLA's *CPU* dot emitter cannot consume bf16 operands natively, so it
    #: converts entire (stacked) bf16 weight arrays to f32 and LICM hoists
    #: those converts out of the layer loops — buffers that do not exist on
    #: TPU (native bf16 MXU). Subtract from peak for the TPU estimate.
    cpu_upcast_artifact_bytes: float = 0.0
    #: TPU-fusion-modeled HBM traffic: dot operands+outputs, collective
    #: payloads, while-loop carries (read+write per iteration) and entry
    #: parameters. Elementwise/norm chains are assumed fused into their
    #: consumers (which is what the TPU compiler does); ``bytes_moved`` is
    #: the conservative every-op model and upper-bounds this.
    bytes_moved_fused: float = 0.0


_FREE_OPS = {
    "parameter", "tuple", "get-tuple-element", "constant", "bitcast",
    "after-all", "partition-id", "replica-id", "domain",
    # loop-carry copies are CPU-backend artifacts (elided on TPU, which
    # updates buffers in place); real layout changes appear as transpose/fusion
    "copy", "copy-start", "copy-done",
}


def parse_computations(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        m = _COMP_RE.match(line)
        if m and line.rstrip().endswith("{"):
            cur = Computation(m.group(1))
            comps[cur.name] = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        om = _OP_RE.match(line)
        if om:
            op = OpInfo(om.group(1), om.group(2), om.group(3), om.group(4))
            cur.ops.append(op)
            cur.symbols[op.name] = op.out_type
    return comps


def _operand_names(rest: str) -> list[str]:
    """Names inside the top-level operand parens of ``rest``."""
    depth, out, cur = 0, [], ""
    for ch in rest:
        if ch == "(":
            depth += 1
            continue
        if ch == ")":
            depth -= 1
            if depth <= 0:
                out.append(cur)
                break
            continue
        if depth >= 1:
            cur += ch
    # split on commas outside []/{} — operands may carry an inline type
    # ("f32[32,128]{1,0} %copy.10", older HLO text) whose dims also use commas
    parts, cur, bdepth = [], "", 0
    for ch in "".join(out):
        if ch in "[{":
            bdepth += 1
        elif ch in "]}":
            bdepth -= 1
        if ch == "," and bdepth == 0:
            parts.append(cur)
            cur = ""
        else:
            cur += ch
    parts.append(cur)
    names = []
    for part in parts:
        part = part.strip()
        # with an inline type the name is the last token; bare names stand alone
        pm = re.search(r"%?([\w\.\-]+)$", part)
        if pm:
            names.append(pm.group(1))
    return names


def _called_comp(rest: str, key: str) -> str | None:
    m = re.search(key + r"=%?([\w\.\-]+)", rest)
    return m.group(1) if m else None


def _dot_flops(op: OpInfo, comp: Computation) -> float:
    out_dims, _ = _shape_dims(op.out_type)
    names = _operand_names(op.opcode + "(" + op.rest)
    # lhs operand type
    lhs_type = comp.symbols.get(names[0], "") if names else ""
    lhs_dims, _ = _shape_dims(lhs_type)
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
    contracted = 1
    if cm and cm.group(1):
        for i in cm.group(1).split(","):
            idx = int(i)
            if idx < len(lhs_dims):
                contracted *= lhs_dims[idx]
    out_n = 1
    for d in out_dims:
        out_n *= d
    return 2.0 * out_n * contracted


def _trip_count(cond: Computation) -> int | None:
    """Canonical jax scan cond: compare(iv, constant(N)) LT, iv from 0."""
    consts: dict[str, int] = {}
    for op in cond.ops:
        if op.opcode == "constant":
            m = re.match(r"\(?(-?\d+)\)?", op.rest)
            if m and ("s32" in op.out_type or "u32" in op.out_type or "s64" in op.out_type):
                consts[op.name] = int(m.group(1))
    best = None
    for op in cond.ops:
        if "compare" in op.opcode or op.opcode == "fusion":
            names = _operand_names(op.opcode + "(" + op.rest)
            for n in names:
                if n in consts:
                    best = max(best or 0, consts[n])
    if best is None and consts:
        best = max(consts.values())
    return best


def analyze_hlo(
    text: str,
    trip_hints: dict[str, int] | None = None,
    *,
    dynamic_trip_default: int = 1,
) -> HloCost:
    """``dynamic_trip_default``: trip count assumed for while loops whose
    bound is data-dependent (e.g. the causal flash KV loop, whose trips vary
    per shard — pass the *average* block count)."""
    comps = parse_computations(text)
    trip_hints = trip_hints or {}
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line)
            if m:
                entry = m.group(1)
            break
    if entry is None:  # fall back: computation named main-ish
        entry = max(comps, key=lambda c: len(comps[c].ops))

    total = HloCost()
    memo: dict[tuple[str, bool], tuple[float, float, float, float]] = {}

    def comp_cost(name: str, top_level: bool) -> tuple[float, float, float, float]:
        """Returns (flops, bytes, collective_bytes, fused_bytes)."""
        key = (name, top_level)
        if key in memo:
            return memo[key]
        comp = comps.get(name)
        if comp is None:
            return (0.0, 0.0, 0.0, 0.0)
        fl = by = cb = fb = 0.0
        for op in comp.ops:
            oc = op.opcode
            if oc == "dot":
                fl += _dot_flops(op, comp)
                fb += _operand_bytes(op, comp) + shape_bytes(op.out_type)
                if top_level:
                    by += _op_bytes(op, comp)
            elif oc in COLLECTIVES or any(oc.startswith(c + "-") for c in COLLECTIVES):
                base = next((c for c in COLLECTIVES if oc == c or oc.startswith(c + "-")), oc)
                b = _operand_bytes(op, comp)
                cb += b
                fb += b + shape_bytes(op.out_type)
                total.collective_counts[base] = total.collective_counts.get(base, 0) + 1
                if top_level:
                    by += _op_bytes(op, comp)
            elif oc == "while":
                body = _called_comp(op.rest, "body")
                cond = _called_comp(op.rest, "condition")
                trips = trip_hints.get(op.name)
                if trips is None and cond in comps:
                    trips = _trip_count(comps[cond])
                trips = trips if trips and trips > 0 else dynamic_trip_default
                total.while_trip_counts[op.name] = trips
                bf, bb, bc, bfb = comp_cost(body, top_level) if body else (0, 0, 0, 0)
                cf, cbk, cc, cfb = comp_cost(cond, False) if cond else (0, 0, 0, 0)
                fl += trips * (bf + cf)
                by += trips * bb
                cb += trips * (bc + cc)
                fb += trips * (bfb + cfb)
            elif oc == "conditional":
                branches = re.findall(r"%([\w\.\-]+)", op.rest)
                sub = [comp_cost(b, top_level) for b in branches if b in comps]
                if sub:
                    fl += max(s[0] for s in sub)
                    by += max(s[1] for s in sub)
                    cb += max(s[2] for s in sub)
                    fb += max(s[3] for s in sub)
            elif oc in ("fusion", "call", "async-start", "async-done", "custom-call", "map", "reduce", "sort", "scatter", "select-and-scatter"):
                callee = _called_comp(op.rest, "calls") or _called_comp(op.rest, "to_apply")
                if callee and callee in comps:
                    sf, _, sc, sfb = comp_cost(callee, False)
                    fl += sf
                    cb += sc
                    fb += sfb
                if oc in ("scatter", "select-and-scatter"):
                    fb += shape_bytes(op.out_type)
                if top_level and oc not in _FREE_OPS:
                    by += _op_bytes(op, comp)
            else:
                if oc in ("dynamic-update-slice", "gather", "dynamic-slice", "concatenate", "transpose", "reshape"):
                    # data-movement ops hit HBM even under TPU fusion
                    fb += shape_bytes(op.out_type)
                if top_level and oc not in _FREE_OPS:
                    by += _op_bytes(op, comp)
        memo[key] = (fl, by, cb, fb)
        return memo[key]

    def _operand_bytes(op: OpInfo, comp: Computation) -> float:
        names = _operand_names(op.opcode + "(" + op.rest)
        return float(sum(shape_bytes(comp.symbols.get(n, "")) for n in names))

    def _op_bytes(op: OpInfo, comp: Computation) -> float:
        # write once + read ~once downstream
        return 2.0 * shape_bytes(op.out_type)

    fl, by, cb, fb = comp_cost(entry, True)
    # entry parameters (weights, inputs) are read at least once
    for op in comps[entry].ops:
        if op.opcode == "parameter":
            by += shape_bytes(op.out_type)
            fb += shape_bytes(op.out_type)

    # CPU-backend artifact: entry-level whole-array bf16->f32 upcasts
    def _is_upcast(op: OpInfo, comp: Computation) -> bool:
        dims, dt = _shape_dims(op.out_type)
        if dt != "f32" or not dims:
            return False
        n = 1
        for d in dims:
            n *= d
        if n * 4 < (1 << 26):  # only count big (>=64 MiB) hoisted stacks
            return False
        if op.opcode == "convert":
            names = _operand_names(op.opcode + "(" + op.rest)
            src = comp.symbols.get(names[0], "") if names else ""
            sdims, sdt = _shape_dims(src)
            return sdt == "bf16" and sdims == dims
        if op.opcode == "fusion":
            callee = _called_comp(op.rest, "calls")
            sub = comps.get(callee)
            if sub and len([o for o in sub.ops if o.opcode != "parameter"]) == 1:
                root = [o for o in sub.ops if o.opcode != "parameter"][0]
                if root.opcode == "convert":
                    pdims = [
                        _shape_dims(o.out_type) for o in sub.ops if o.opcode == "parameter"
                    ]
                    return any(pd == dims and pt == "bf16" for pd, pt in pdims)
        return False

    artifact = 0.0
    for op in comps[entry].ops:
        if _is_upcast(op, comps[entry]):
            artifact += shape_bytes(op.out_type)

    total.flops = fl
    total.bytes_moved = by
    total.collective_bytes = cb
    total.bytes_moved_fused = fb
    total.cpu_upcast_artifact_bytes = artifact
    return total
