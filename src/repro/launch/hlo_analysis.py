"""Trip-count-weighted HLO analysis.

``compiled.cost_analysis()`` counts every computation ONCE — a layer stack
expressed as ``lax.scan`` (a while loop with known_trip_count=L) is
undercounted by ~L×.  This module parses ``compiled.as_text()`` directly:

  1. splits the module into computations and instructions,
  2. propagates execution multiplicity through the call graph
     (while bodies × known_trip_count, fusions, calls, conditionals),
  3. derives per-device totals:
       * flops       — exact for dot/convolution (shapes from the symbol
                       table), 1 flop/elem for elementwise/reduce ops
       * hbm_bytes   — interface bytes (operands + outputs) of each executed
                       non-fused instruction (XLA's bytes-accessed model)
       * collective_bytes — output bytes of all-reduce / all-gather /
                       reduce-scatter / all-to-all / collective-permute,
                       trip-count weighted (this is what feeds §Roofline)

This is the dry-run "profile": no real hardware, reasoning from lowered IR.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

_COMP_HEADER = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$"
)
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[a-z0-9].*?[\]\})])\s+([a-z][\w\-]*)\((.*)$"
)
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_TRIP = re.compile(r'known_trip_count[^0-9]*(\d+)')
_OPERAND = re.compile(r"%([\w.\-]+)")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_ZERO_COST_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "copy-start",
    "copy-done", "custom-call", "while", "conditional", "call",
    "optimization-barrier",
}


def _shape_elems_bytes(shape_str: str) -> Tuple[int, int]:
    elems_total, bytes_total = 0, 0
    for dt, dims in _SHAPE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems_total += n
        bytes_total += n * _DTYPE_BYTES[dt]
    return elems_total, bytes_total


class Instruction:
    __slots__ = ("name", "shape_str", "op", "rest", "elems", "bytes")

    def __init__(self, name, shape_str, op, rest):
        self.name = name
        self.shape_str = shape_str
        self.op = op
        self.rest = rest
        self.elems, self.bytes = _shape_elems_bytes(shape_str)


def parse_module(hlo: str) -> Dict[str, List[Instruction]]:
    comps: Dict[str, List[Instruction]] = {}
    current: Optional[str] = None
    for line in hlo.splitlines():
        if current is None:
            m = _COMP_HEADER.match(line.strip()) if "{" in line else None
            if m and "->" in line:
                current = m.group(1)
                comps[current] = []
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR.match(line)
        if m:
            comps[current].append(Instruction(*m.groups()))
    return comps


def _entry_name(hlo: str, comps) -> str:
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
    if m and m.group(1) in comps:
        return m.group(1)
    # fallback: the largest computation
    return max(comps, key=lambda c: len(comps[c]))


def _called_comps(instr: Instruction) -> List[Tuple[str, float]]:
    """(computation, weight) pairs invoked by this instruction."""
    out: List[Tuple[str, float]] = []
    rest = instr.rest
    if instr.op == "while":
        body = re.search(r"body=%?([\w.\-]+)", rest)
        cond = re.search(r"condition=%?([\w.\-]+)", rest)
        trip = _TRIP.search(rest)
        n = float(trip.group(1)) if trip else 1.0
        if body:
            out.append((body.group(1), n))
        if cond:
            out.append((cond.group(1), n + 1))
    elif instr.op == "fusion":
        m = re.search(r"calls=%?([\w.\-]+)", rest)
        if m:
            out.append((m.group(1), 1.0))
    elif instr.op == "call":
        m = re.search(r"to_apply=%?([\w.\-]+)", rest)
        if m:
            out.append((m.group(1), 1.0))
    elif instr.op == "conditional":
        for m in re.finditer(r"%([\w.\-]+)", rest.split("branch_computations")[-1]):
            out.append((m.group(1), 1.0))
    return out


def _multiplicities(comps, entry: str):
    """Returns (multiplicity map, per-computation loop trip count).  The trip
    count lets byte accounting recognize loop-carried STACKED tensors (leading
    dim == trip): a scan-over-layers carries (L, ...) param/cache stacks but
    each iteration only touches one (1/L) slice — counting the full stack per
    iteration overstates HBM traffic by ~L x."""
    mult: Dict[str, float] = {entry: 1.0}
    trip_of: Dict[str, float] = {}
    for _ in range(64):
        changed = False
        for comp, m in list(mult.items()):
            for instr in comps.get(comp, []):
                for callee, w in _called_comps(instr):
                    if callee in comps:
                        new = m * w
                        if mult.get(callee, 0.0) < new:
                            if abs(mult.get(callee, -1.0) - new) > 1e-9:
                                mult[callee] = max(mult.get(callee, 0.0), new)
                                changed = True
                        if instr.op == "while" and w > 1:
                            trip_of[callee] = max(trip_of.get(callee, 1.0), w)
        if not changed:
            break
    return mult, trip_of


def _fusion_comps(comps) -> set:
    fused = set()
    for instrs in comps.values():
        for i in instrs:
            if i.op == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", i.rest)
                if m:
                    fused.add(m.group(1))
    return fused


def _dot_flops(instr: Instruction, symtab) -> float:
    ops = _OPERAND.findall(instr.rest.split(")")[0])
    if not ops:
        return 0.0
    lhs = symtab.get(ops[0])
    if lhs is None:
        return 2.0 * instr.elems
    lhs_dims = []
    m = _SHAPE.search(lhs.shape_str)
    if m:
        lhs_dims = [int(d) for d in m.group(2).split(",") if d]
    mc = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.rest)
    contract = 1
    if mc and lhs_dims:
        for d in mc.group(1).split(","):
            if d and int(d) < len(lhs_dims):
                contract *= lhs_dims[int(d)]
    return 2.0 * instr.elems * max(contract, 1)


def _conv_flops(instr: Instruction, symtab) -> float:
    ops = _OPERAND.findall(instr.rest.split(")")[0])
    if len(ops) < 2:
        return 2.0 * instr.elems
    rhs = symtab.get(ops[1])
    if rhs is None:
        return 2.0 * instr.elems
    m = _SHAPE.search(rhs.shape_str)
    if not m:
        return 2.0 * instr.elems
    rhs_dims = [int(d) for d in m.group(2).split(",") if d]
    k_elems = 1
    for d in rhs_dims:
        k_elems *= d
    # output-feature dim from dim_labels (...->..f or io ordering); assume the
    # largest of the last two dims is features-out -> per-output MACs:
    dl = re.search(r"dim_labels=\w+_(\w+)->", instr.rest)
    out_feat = rhs_dims[-1]
    if dl:
        spec = dl.group(1)
        o_pos = spec.index("o")
        out_feat = rhs_dims[o_pos]
    per_out = k_elems / max(out_feat, 1)
    return 2.0 * instr.elems * per_out


def analyze_hlo(hlo: str) -> Dict[str, Any]:
    comps = parse_module(hlo)
    entry = _entry_name(hlo, comps)
    mult, trip_of = _multiplicities(comps, entry)
    fused = _fusion_comps(comps)

    # fusions called from a while body inherit its trip context
    fusion_parent_trip: Dict[str, float] = {}
    for comp, instrs in comps.items():
        t = trip_of.get(comp)
        if not t:
            continue
        for i in instrs:
            if i.op == "fusion":
                mm = re.search(r"calls=%?([\w.\-]+)", i.rest)
                if mm:
                    fusion_parent_trip[mm.group(1)] = t

    symtab: Dict[str, Instruction] = {}
    for instrs in comps.values():
        for i in instrs:
            symtab[i.name] = i

    flops = 0.0
    dot_flops = 0.0
    hbm_bytes = 0.0
    coll_bytes: Dict[str, float] = {}
    coll_counts: Dict[str, float] = {}

    for comp, instrs in comps.items():
        m = mult.get(comp, 0.0)
        if m <= 0.0:
            continue
        in_fusion = comp in fused
        for instr in instrs:
            if instr.op in ("dot", "dot-general"):
                f = _dot_flops(instr, symtab) * m
                flops += f
                dot_flops += f
            elif instr.op == "convolution":
                f = _conv_flops(instr, symtab) * m
                flops += f
                dot_flops += f
            elif instr.op not in _ZERO_COST_OPS and instr.op not in COLLECTIVES:
                flops += instr.elems * m

            base_op = instr.op
            for kind in COLLECTIVES:
                if base_op == kind or base_op in (f"{kind}-start", f"{kind}-done"):
                    if base_op.endswith("-done"):
                        break
                    coll_bytes[kind] = coll_bytes.get(kind, 0.0) + instr.bytes * m
                    coll_counts[kind] = coll_counts.get(kind, 0.0) + m
                    break

            if not in_fusion and instr.op not in _ZERO_COST_OPS:
                trip = trip_of.get(comp) or fusion_parent_trip.get(comp)

                def _eff_bytes(ins: Instruction) -> float:
                    # loop-carried stack (leading dim == trip): one slice/iter
                    if trip and trip > 1:
                        msh = _SHAPE.search(ins.shape_str)
                        if msh:
                            dims = msh.group(2).split(",")
                            if dims and dims[0] and float(dims[0]) == trip:
                                return ins.bytes / trip
                    return float(ins.bytes)

                if instr.op == "dynamic-update-slice":
                    # aliased in-place on real hardware: traffic = the update
                    # slice (read) + written region, NOT the whole buffer
                    ops = _OPERAND.findall(instr.rest.split("),")[0])
                    upd = symtab.get(ops[1]) if len(ops) > 1 else None
                    b = 2 * (upd.bytes if upd is not None else 0)
                elif instr.op in ("dynamic-slice", "gather", "slice"):
                    # reads only the extracted region
                    b = 2 * instr.bytes
                else:
                    b = _eff_bytes(instr)
                    for opname in _OPERAND.findall(instr.rest.split("),")[0]):
                        src = symtab.get(opname)
                        if src is not None:
                            b += _eff_bytes(src)
                hbm_bytes += b * m

    return {
        "flops": flops,
        "dot_flops": dot_flops,
        "hbm_bytes": hbm_bytes,
        "collective_bytes": sum(coll_bytes.values()),
        "collective_bytes_by_kind": coll_bytes,
        "collective_counts": coll_counts,
        "n_computations": len(comps),
        "entry": entry,
    }


_TPU_CALL = re.compile(
    r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=.*custom_call_target="tpu_custom_call"'
)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def tpu_custom_calls(hlo: str) -> List[str]:
    """One entry per Pallas kernel call compiled into a TPU executable: the
    instruction name and its ``op_name`` (which carries the
    ``pallas_call(name=...)``, also under ``jax.vmap`` where XLA renames the
    instruction itself)."""
    calls = []
    for line in hlo.splitlines():
        m = _TPU_CALL.match(line)
        if m:
            op = _OP_NAME.search(line)
            calls.append(f"{m.group(1)} {op.group(1) if op else ''}")
    return calls


def has_tpu_kernel(hlo: str, name: str) -> bool:
    """Whether the kernel ``name`` is compiled into the executable."""
    return any(name in call for call in tpu_custom_calls(hlo))
