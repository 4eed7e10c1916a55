"""Loop-aware analysis of post-optimization HLO text.

XLA's ``cost_analysis()`` counts each while-loop *body once* — under
scan-over-layers that undercounts FLOPs, bytes and collectives by ~L×.
This module parses the optimized HLO and multiplies every computation's
contribution by its loop trip count:

  * computations are parsed into (name -> instructions) with a per-
    computation symbol table (instruction name -> shape);
  * a call graph is built from while bodies/conditions, fusion calls,
    conditionals, and plain calls;
  * while trip counts are recovered from the loop condition's comparison
    constant (scan lowers to a counted loop);
  * FLOPs: 2·prod(result)·prod(contracting dims) per ``dot`` (einsums and
    matmuls; models here have no convolutions);
  * bytes: Σ (operands + result) per instruction at fusion granularity
    (fused computations contribute 0 — their internals stay in
    registers/VMEM), approximating HBM traffic;
  * collectives: result-shape bytes × ring-traffic factor (see
    roofline.py) × trip multiplier.

It also reports ``cpu_bf16_legalization_bytes``: f32 stacks written by
dynamic-update-slice that shadow a bf16 tensor of identical dims — an
artifact of XLA:CPU rewriting bf16 dots to f32 (TPU executes bf16 on the
MXU natively, so these buffers do not exist on the target hardware).
The dry-run's adjusted fit check subtracts them.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["analyze_hlo", "normalize_cost_analysis", "HloStats"]


def normalize_cost_analysis(ca) -> dict:
    """``Compiled.cost_analysis()`` as a dict: it is None for empty
    programs, so callers can always index ``["flops"]``."""
    if ca is None:
        return {}
    return dict(ca)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COMP_HDR = re.compile(r"^(?:ENTRY )?%?([\w\-.]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\-.]+)\s*=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]\S*))\s+([\w\-]+)\((.*)$")
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OPERAND = re.compile(r"%([\w\-.]+)")
_CALLED = re.compile(
    r"(?:calls|body|condition|to_apply|branch_computations)=\{?%?([\w\-.,% ]+)\}?")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
# Fused-traffic model: XLA:TPU fuses elementwise chains into neighboring
# matmuls/reductions, so HBM traffic is dominated by these op classes.  The
# CPU-optimized HLO we analyze fuses far less — counting every elementwise
# op would overstate TPU traffic by ~10×.
_INCLUDE_BYTES_OPS = {"dot", "dot-general", "fusion", "dynamic-update-slice",
                      "dynamic-slice", "scatter", "gather", "sort",
                      "convolution", "reduce-window", "concatenate"}


def _shape_dims(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        d = tuple(int(x) for x in dims.split(",")) if dims else ()
        out.append((dt, d))
    return out


def _shape_bytes(text: str) -> int:
    return sum(math.prod(d) * _DTYPE_BYTES[dt] for dt, d in _shape_dims(text))


def _shape_bytes2(text: str, bf16_shapes) -> Tuple[int, int]:
    """(raw, tpu-corrected) bytes: f32 tensors whose dims also appear in
    bf16 anywhere in the module are counted at bf16 width — they are
    XLA:CPU's bf16->f32 op legalization, absent on TPU (native bf16)."""
    raw = corr = 0
    for dt, d in _shape_dims(text):
        b = math.prod(d) * _DTYPE_BYTES[dt]
        raw += b
        if dt in ("f32", "u32", "s32") and d in bf16_shapes:
            corr += math.prod(d) * 2
        else:
            corr += b
    return raw, corr


@dataclasses.dataclass
class _Instr:
    name: str
    shape: str
    op: str
    rest: str            # everything after the opening paren


@dataclasses.dataclass
class HloStats:
    flops: float
    bytes_accessed: float            # raw (CPU-HLO dtypes)
    bytes_accessed_tpu: float        # f32-with-bf16-twin counted at bf16 size
    collective_bytes: float          # traffic-model bytes (ring factors), raw
    collective_bytes_tpu: float
    collective_count: int
    collective_by_op: Dict[str, float]
    while_trip_counts: Dict[str, int]
    cpu_bf16_legalization_bytes: int


def _parse_computations(text: str) -> Dict[str, List[_Instr]]:
    comps: Dict[str, List[_Instr]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_HDR.match(line.strip())
            if m and line.rstrip().endswith("{"):
                cur = m.group(1)
                comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            comps[cur].append(_Instr(m.group(1), m.group(2), m.group(3),
                                     m.group(4)))
    return comps


def _split_operands(rest: str) -> str:
    """Return the operand segment (up to the matching close paren)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


def _trip_count(cond_instrs: List[_Instr]) -> int:
    """Scan loops compare an s32 induction variable against the trip count;
    take the largest s32 constant in the condition computation."""
    best = 1
    for ins in cond_instrs:
        if ins.op == "constant" and ins.shape.startswith("s32"):
            m = re.match(r"([0-9]+)\)?", ins.rest)
            if m:
                best = max(best, int(m.group(1)))
    return best


def analyze_hlo(text: str) -> HloStats:
    comps = _parse_computations(text)

    # symbol tables: per computation, instruction name -> result shape text
    symtab: Dict[str, Dict[str, str]] = {}
    for cname, instrs in comps.items():
        tab: Dict[str, str] = {}
        for ins in instrs:
            tab[ins.name] = ins.shape
        symtab[cname] = tab

    # call graph: computation -> multiplier
    mult: Dict[str, float] = {}
    entry = None
    for cname in comps:
        if cname.endswith("main") or entry is None:
            # the ENTRY computation is printed with "ENTRY %main ..."
            pass
    # find entry: computation not called by anyone
    called = set()
    calls: Dict[str, List[Tuple[str, float]]] = {c: [] for c in comps}
    trip_counts: Dict[str, int] = {}
    for cname, instrs in comps.items():
        for ins in instrs:
            if ins.op == "while":
                m = re.search(r"condition=%?([\w\-.]+)", ins.rest)
                c_cond = m.group(1) if m else None
                m = re.search(r"body=%?([\w\-.]+)", ins.rest)
                c_body = m.group(1) if m else None
                trips = _trip_count(comps.get(c_cond, [])) if c_cond else 1
                if c_body:
                    calls[cname].append((c_body, float(trips)))
                    called.add(c_body)
                    trip_counts[c_body] = trips
                if c_cond:
                    calls[cname].append((c_cond, float(trips + 1)))
                    called.add(c_cond)
            else:
                m = _CALLED.search(ins.rest)
                if m:
                    for sub in re.split(r"[,\s]+", m.group(1)):
                        sub = sub.strip().lstrip("%")
                        if sub in comps:
                            calls[cname].append((sub, 1.0))
                            called.add(sub)
    roots = [c for c in comps if c not in called]
    mult = {c: 0.0 for c in comps}
    stack = [(r, 1.0) for r in roots]
    seen_guard = 0
    while stack:
        cname, m = stack.pop()
        mult[cname] += m
        seen_guard += 1
        if seen_guard > 100000:
            break
        for sub, k in calls.get(cname, []):
            stack.append((sub, m * k))

    # fused computations contribute zero *bytes* (their internals are not
    # HBM traffic) but their dots still count flops.
    fused_called_by_fusion = set()
    fusion_target: Dict[Tuple[str, str], str] = {}
    for cname, instrs in comps.items():
        for ins in instrs:
            if ins.op == "fusion":
                m = re.search(r"calls=%?([\w\-.]+)", ins.rest)
                if m:
                    fused_called_by_fusion.add(m.group(1))
                    fusion_target[(cname, ins.name)] = m.group(1)

    # A fusion's result/operands count as HBM traffic only if it contains a
    # structural op (matmul/reduce/scatter/...).  Pure elementwise/convert
    # fusions — ubiquitous in CPU HLO because of bf16->f32 dot legalization —
    # fuse into their neighbors on TPU and move no extra HBM bytes.
    _STRUCTURAL = {"dot", "reduce", "scatter", "dynamic-update-slice",
                   "gather", "sort", "convolution", "dynamic-slice"}
    structural_fusion = {
        c: any(i.op in _STRUCTURAL for i in instrs)
        for c, instrs in comps.items()}

    flops = 0.0
    bytes_acc = 0.0
    bytes_acc_tpu = 0.0
    coll_bytes = 0.0
    coll_bytes_tpu = 0.0
    coll_count = 0
    coll_by_op: Dict[str, float] = {}
    legal_shapes = set()
    # pre-pass: every bf16 shape in the module (for the dtype correction)
    bf16_shapes = set()
    for instrs in comps.values():
        for ins in instrs:
            for dt, d in _shape_dims(ins.shape):
                if dt == "bf16":
                    bf16_shapes.add(d)

    for cname, instrs in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        tab = symtab[cname]
        for ins in instrs:
            dims_all = _shape_dims(ins.shape)
            # ---- flops (dots)
            if ins.op in ("dot", "dot-general") or ins.op.startswith("dot"):
                cm = _CONTRACT.search(ins.rest)
                contracting = ([int(x) for x in cm.group(1).split(",") if x]
                               if cm else [])
                ops = _OPERAND.findall(_split_operands(ins.rest))
                lhs_shape = tab.get(ops[0], "") if ops else ""
                lhs_dims = _shape_dims(lhs_shape)
                k = 1
                if lhs_dims:
                    ld = lhs_dims[0][1]
                    for c in contracting:
                        if c < len(ld):
                            k *= ld[c]
                result_elems = sum(math.prod(d) for _, d in dims_all)
                flops += m * 2.0 * result_elems * k
            # ---- bytes (fused-traffic model, see _INCLUDE_BYTES_OPS)
            if (ins.op == "fusion"
                    and not structural_fusion.get(
                        fusion_target.get((cname, ins.name), ""), True)):
                pass                      # elementwise-only fusion: no bytes
            elif (ins.op in _INCLUDE_BYTES_OPS
                    and cname not in fused_called_by_fusion):
                pairs = [_shape_bytes2(tab.get(o, ""), bf16_shapes) for o in
                         _OPERAND.findall(_split_operands(ins.rest))]
                op_bytes = [pq[0] for pq in pairs]
                op_bytes_t = [pq[1] for pq in pairs]
                r_raw, r_tpu = _shape_bytes2(ins.shape, bf16_shapes)
                inplace = (ins.op in ("dynamic-update-slice", "scatter")
                           or ins.name.startswith("dynamic-update-slice")
                           or ins.name.startswith("scatter"))
                sliced = (ins.op in ("dynamic-slice", "gather")
                          or ins.name.startswith("dynamic-slice")
                          or ins.name.startswith("gather"))
                if inplace and op_bytes:
                    # aliased in-place update: traffic = 2 x slice, not the
                    # whole buffer (XLA aliases the dest)
                    b = 2 * (sum(op_bytes) - max(op_bytes))
                    bt = 2 * (sum(op_bytes_t) - max(op_bytes_t))
                elif sliced:
                    b, bt = 2 * r_raw, 2 * r_tpu
                elif ins.op == "fusion":
                    # a fusion wrapping a dynamic-slice reads a *slice* of
                    # its big operand (e.g. the per-layer read of a saved
                    # carry stack inside the bwd loop) — cap each operand's
                    # traffic at the fusion's result size
                    b = r_raw + sum(min(o, r_raw) for o in op_bytes)
                    bt = r_tpu + sum(min(o, r_tpu) for o in op_bytes_t)
                else:
                    b = r_raw + sum(op_bytes)
                    bt = r_tpu + sum(op_bytes_t)
                bytes_acc += m * b
                bytes_acc_tpu += m * bt
            # ---- collectives
            base_op = ins.op[:-6] if ins.op.endswith("-start") else ins.op
            if base_op in _COLL_OPS and not ins.op.endswith("-done"):
                rb, rb_tpu = _shape_bytes2(ins.shape, bf16_shapes)
                gm = _GROUPS_IOTA.search(ins.rest)
                if gm:
                    n = int(gm.group(2))
                else:
                    gm = _GROUPS_LIST.search(ins.rest)
                    n = len(gm.group(1).split(",")) if gm else 2
                if n > 1:
                    if base_op == "all-gather":
                        f = (n - 1) / n
                    elif base_op == "reduce-scatter":
                        f = float(n - 1)
                    elif base_op == "all-reduce":
                        f = 2.0 * (n - 1) / n
                    elif base_op == "all-to-all":
                        f = (n - 1) / n
                    else:
                        f = 1.0
                else:
                    f = 0.0
                coll_bytes += m * rb * f
                coll_bytes_tpu += m * rb_tpu * f
                coll_count += int(m)
                coll_by_op[base_op] = coll_by_op.get(base_op, 0.0) + m * rb * f
            # ---- CPU bf16->f32 legalization artifact (saved f32 stacks)
            if (ins.op == "dynamic-update-slice" and ins.shape.startswith("f32")
                    and dims_all and len(dims_all[0][1]) >= 4):
                legal_shapes.add(dims_all[0][1])

    legal_bytes = sum(math.prod(d) * 4 for d in legal_shapes
                      if d in bf16_shapes)
    return HloStats(
        flops=flops,
        bytes_accessed=bytes_acc,
        bytes_accessed_tpu=bytes_acc_tpu,
        collective_bytes=coll_bytes,
        collective_bytes_tpu=coll_bytes_tpu,
        collective_count=coll_count,
        collective_by_op=coll_by_op,
        while_trip_counts=trip_counts,
        cpu_bf16_legalization_bytes=legal_bytes,
    )
