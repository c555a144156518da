"""Optimized-HLO cost analyzer — exact roofline inputs.

``compiled.cost_analysis()`` counts each ``while`` body ONCE, so any graph
with scan-over-layers / gradient-accumulation / chunked-attention scans
undercounts FLOPs, bytes and collective traffic by the trip counts.  This
module parses the *partitioned, optimized* HLO text instead:

  1. symbol table: every ``%name = dtype[dims]...`` definition + computation
     header parameters,
  2. computation segmentation + call graph (while body/condition, fusion
     ``calls=``, ``to_apply=``),
  3. trip-count extraction from while condition regions (max integer
     constant — scan lowers to ``i < N``),
  4. execution-count multipliers propagated from ENTRY through the graph,
  5. cost sums:
       * flops        — 2 * prod(result) * K for every dot (batch dims via
                        result shape), times multiplier,
       * collectives  — per-op traffic from result shapes with ring-model
                        factors (all-reduce 2x, others 1x), times multiplier,
       * hbm_bytes    — sum of (result + distinct operand) bytes of
                        top-level ops (fusion internals excluded: a kLoop
                        fusion is one read-modify-write), times multiplier.
                        An upper-bound traffic model: assumes no cross-op
                        fusion beyond what XLA:CPU already fused.

Everything is computed per-device (the HLO is the per-device SPMD program).
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\((.*)\)\s*->")
_PARAM_RE = re.compile(r"([^\s:,()]+):\s*([a-z0-9]+\[[0-9,]*\])")
_ATTR_RE = {
    "body": re.compile(r"body=%?([^\s,)]+)"),
    "condition": re.compile(r"condition=%?([^\s,)]+)"),
    "calls": re.compile(r"calls=%?([^\s,)]+)"),
    "to_apply": re.compile(r"to_apply=%?([^\s,)]+)"),
    "branches": re.compile(r"branch_computations=\{([^}]*)\}"),
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
# ring-model traffic factor applied to the RESULT size
_COLL_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "partition-id", "replica-id",
    "opt-barrier",
}


def _shape_elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _first_shape(text: str) -> Optional[Tuple[str, Tuple[int, ...]]]:
    m = _SHAPE_RE.search(text)
    if not m:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def _all_shapes_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        total += _shape_elems(m.group(2)) * _DTYPE_BYTES.get(m.group(1), 4)
    return total


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    result_text: str  # everything between '=' and opcode
    operands: List[str]
    attrs_text: str
    line: str


@dataclasses.dataclass
class HLOAnalysis:
    flops: float
    hbm_bytes: float
    collective_bytes: Dict[str, float]
    dot_flops_by_meta: Dict[str, float]
    trip_counts: Dict[str, int]
    n_ops: int
    cost_flops_unscaled: float = 0.0

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


_OPCODE_RE = re.compile(
    r"^(?:\(?[a-z0-9]+\[[0-9,]*\][^)]*\)?[^ ]*\s+)?([a-z][a-z0-9-]*)\("
)


def _parse_op(line: str) -> Optional[Op]:
    m = _DEF_RE.match(line)
    if not m:
        return None
    name, rhs = m.group(1), m.group(2)
    # opcode = the token immediately before the first '(' that isn't a shape
    # rhs looks like: "f32[16,2]{1,0} dot(%a, %b), attrs" or
    # "(f32[..], f32[..]) while(%t), condition=..., body=..."
    paren = rhs.find("(")
    # skip a leading tuple-type "( ... )" result
    if paren == 0:
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    paren = rhs.find("(", i + 1)
                    break
    if paren < 0:
        return None
    # token before '('
    head = rhs[:paren].rstrip()
    sp = head.rfind(" ")
    opcode = head[sp + 1:]
    result_text = head[:sp + 1] if sp >= 0 else ""
    # operand section: balanced parens from `paren`
    depth = 0
    end = paren
    for i in range(paren, len(rhs)):
        if rhs[i] == "(":
            depth += 1
        elif rhs[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    operand_text = rhs[paren + 1:end]
    operands = re.findall(r"%([^\s,()]+)", operand_text)
    attrs = rhs[end + 1:]
    return Op(name=name, opcode=opcode, result_text=result_text, operands=operands, attrs_text=attrs, line=line)


def analyze_hlo(hlo: str) -> HLOAnalysis:
    # ---- segmentation + symbol table ------------------------------------
    computations: Dict[str, List[Op]] = {}
    shapes: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
    entry: Optional[str] = None
    current: Optional[str] = None

    for raw in hlo.splitlines():
        line = raw.rstrip()
        if line.endswith("{") and ("->" in line or line.lstrip().startswith("ENTRY")):
            h = _HDR_RE.match(line.strip())
            if h:
                current = h.group(2)
                computations[current] = []
                if h.group(1):
                    entry = current
                for pm in _PARAM_RE.finditer(h.group(3)):
                    sh = _first_shape(pm.group(2))
                    if sh:
                        shapes[pm.group(1)] = sh
                continue
        if line.strip() == "}":
            continue
        op = _parse_op(line)
        if op is None or current is None:
            continue
        computations[current].append(op)
        sh = _first_shape(op.result_text)
        if sh:
            shapes[op.name] = sh

    # ---- call graph + trip counts ----------------------------------------
    def trip_count(cond_name: str) -> int:
        best = 1
        for op in computations.get(cond_name, []):
            if op.opcode == "constant":
                mm = re.search(r"constant\((-?\d+)\)", op.line)
                if mm:
                    best = max(best, int(mm.group(1)))
        return best

    mult: Dict[str, float] = defaultdict(float)
    trips: Dict[str, int] = {}
    if entry is None:
        entry = next(iter(computations), None)
    if entry is None:
        return HLOAnalysis(0, 0, {c: 0.0 for c in _COLLECTIVES}, {}, {}, 0)

    # BFS propagate execution multipliers
    pending = [(entry, 1.0)]
    seen_pairs = set()
    fusion_comps = set()
    while pending:
        comp, m = pending.pop()
        if m <= mult[comp]:
            continue
        mult[comp] = m
        for op in computations.get(comp, []):
            if op.opcode == "while":
                b = _ATTR_RE["body"].search(op.attrs_text)
                c = _ATTR_RE["condition"].search(op.attrs_text)
                if b and c:
                    t = trip_count(c.group(1))
                    trips[b.group(1)] = t
                    pending.append((b.group(1), m * t))
                    pending.append((c.group(1), m * (t + 1)))
            elif op.opcode == "conditional":
                br = _ATTR_RE["branches"].search(op.attrs_text)
                if br:
                    for b in re.findall(r"%?([^\s,]+)", br.group(1)):
                        pending.append((b, m))
            else:
                for key in ("calls", "to_apply"):
                    a = _ATTR_RE[key].search(op.attrs_text)
                    if a:
                        if key == "calls":
                            fusion_comps.add(a.group(1))
                        pending.append((a.group(1), m))

    # ---- cost sums --------------------------------------------------------
    flops = 0.0
    hbm = 0.0
    coll = {c: 0.0 for c in _COLLECTIVES}
    dot_by_meta: Dict[str, float] = defaultdict(float)
    n_ops = 0

    for comp, ops in computations.items():
        m = mult.get(comp, 0.0)
        if m == 0.0:
            continue
        in_fusion = comp in fusion_comps
        for op in ops:
            n_ops += 1
            res = _first_shape(op.result_text)
            # FLOPs: dots can live inside fusions on some backends — count
            # them wherever they appear.
            if op.opcode == "dot" and res is not None:
                k = 1.0
                lc = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs_text)
                if lc and op.operands:
                    lhs = shapes.get(op.operands[0])
                    if lhs:
                        for idx in lc.group(1).split(","):
                            if idx:
                                k *= lhs[1][int(idx)]
                f = 2.0 * _shape_elems(",".join(map(str, res[1]))) * k
                flops += m * f
                meta = re.search(r'op_name="([^"]*)"', op.attrs_text)
                dot_by_meta[meta.group(1) if meta else op.name] += m * f
            if op.opcode in ("convolution",) and res is not None:
                # depthwise/standard conv: 2 * out_elems * kernel_elems
                kshape = shapes.get(op.operands[1]) if len(op.operands) > 1 else None
                kelems = _shape_elems(",".join(map(str, kshape[1]))) if kshape else 1
                flops += m * 2.0 * _shape_elems(",".join(map(str, res[1]))) * kelems
            if op.opcode == "fft" and res is not None:
                # 5 N log2 N per length-N transform (standard FFT cost model)
                fl = re.search(r"fft_length=\{([0-9,]+)\}", op.attrs_text)
                if fl:
                    n_fft = 1
                    for d in fl.group(1).split(","):
                        n_fft *= int(d)
                    total_elems = _shape_elems(",".join(map(str, res[1])))
                    rows = max(1, total_elems // max(res[1][-1], 1))
                    flops += m * 5.0 * rows * n_fft * max(math.log2(n_fft), 1.0)

            if in_fusion:
                continue  # bytes of fusion internals don't touch HBM

            if op.opcode in _SKIP_BYTES_OPS:
                continue
            # collectives
            if op.opcode.rstrip("-start").rstrip("-done") in _COLLECTIVES or op.opcode in _COLLECTIVES:
                base = op.opcode.replace("-start", "").replace("-done", "")
                if base in _COLLECTIVES and not op.opcode.endswith("-done"):
                    size = _all_shapes_bytes(op.result_text)
                    coll[base] += m * size * _COLL_FACTOR[base]
                continue
            # HBM traffic: result write + operand reads.  Slicing ops touch
            # only the slice, not the full operand (a scan reading one step
            # of a stacked array must not be charged the whole stack):
            #   dynamic-slice / slice / gather : read+write = 2 x result
            #   dynamic-update-slice / scatter : read+write = 2 x update
            size = _all_shapes_bytes(op.result_text)
            if op.opcode in ("dynamic-slice", "slice", "gather"):
                hbm += m * 2.0 * size
                continue
            if op.opcode in ("dynamic-update-slice", "scatter"):
                upd = shapes.get(op.operands[1]) if len(op.operands) > 1 else None
                ub = (
                    _shape_elems(",".join(map(str, upd[1]))) * _DTYPE_BYTES.get(upd[0], 4)
                    if upd
                    else size
                )
                hbm += m * 2.0 * ub
                continue
            opnd = 0
            for o in op.operands:
                sh = shapes.get(o)
                if sh:
                    opnd += _shape_elems(",".join(map(str, sh[1]))) * _DTYPE_BYTES.get(sh[0], 4)
            hbm += m * (size + opnd)

    return HLOAnalysis(
        flops=flops,
        hbm_bytes=hbm,
        collective_bytes=coll,
        dot_flops_by_meta=dict(dot_by_meta),
        trip_counts=trips,
        n_ops=n_ops,
    )


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (50 GB/s per link).
# A device that is not here has no roofline: lookups raise KeyError.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9, "hbm_bytes": 16e9},
}
# The chip the analytic tiers (tune cost model, production dry-run) model.
TARGET_DEVICE_KIND = "TPU v5 lite"


def roofline_terms(analysis: HLOAnalysis, device_kind: str = TARGET_DEVICE_KIND) -> Dict[str, float]:
    peak = PEAKS[device_kind]
    t_compute = analysis.flops / peak["flops"]
    t_memory = analysis.hbm_bytes / peak["hbm_bw"]
    t_coll = analysis.total_collective_bytes / peak["ici_bw"]
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": t_compute,
        "memory_s": t_memory,
        "collective_s": t_coll,
        "dominant": dominant,
        "bound_s": max(t_compute, t_memory, t_coll),
    }
