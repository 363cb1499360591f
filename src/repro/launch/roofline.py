"""Roofline-term extraction from a compiled dry-run artifact.

Three terms (per device = per chip; the SPMD module is per-device):
  compute    = HLO_FLOPs / peak_FLOPs          (197 TFLOP/s bf16, v5e)
  memory     = HLO_bytes / HBM_bw              (819 GB/s)
  collective = wire_bytes / ICI_link_bw        (~50 GB/s per link)

cost_analysis() provides FLOPs/bytes; collective bytes are parsed from the
post-partitioning HLO text with ring-algorithm wire factors:
  all-gather / reduce-scatter / all-to-all : (n-1)/n x full size
  all-reduce                               : 2 (n-1)/n x size
  collective-permute                       : 1 x size
`n` comes from replica_groups (explicit or iota form).
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12       # bf16 per chip
HBM_BW = 819e9            # bytes/s
ICI_BW = 50e9             # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


def _group_size(line: str) -> int:
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))   # [n_groups, group_size]<=[total]
    return 2


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-device wire bytes by collective kind (ring factors applied)."""
    out = {k: 0.0 for k in COLLECTIVES}
    count = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        for kind in COLLECTIVES:
            token = f" {kind}("
            token_start = f" {kind}-start("
            if token not in line and token_start not in line:
                continue
            shapes = _SHAPE_RE.findall(line.split("=", 1)[0]) or \
                _SHAPE_RE.findall(line)
            # full logical size: the largest shape on the line (result for
            # all-gather, operand for reduce-scatter)
            allshapes = _SHAPE_RE.findall(line)
            size = max((_shape_bytes(d, s) for d, s in allshapes),
                       default=0)
            n = _group_size(line)
            if kind == "all-reduce":
                wire = 2 * (n - 1) / n * size
            elif kind == "collective-permute":
                wire = size
            else:
                wire = (n - 1) / n * size
            out[kind] += wire
            count[kind] += 1
            break
    out["_counts"] = count
    return out


# ----------------------------------------------------------------------------
# Loop-aware HLO cost analyzer.
#
# XLA's compiled.cost_analysis() counts a while/scan BODY ONCE regardless of
# trip count (verified empirically), which silently undercounts every scanned
# transformer by ~n_layers x.  We therefore re-derive the three terms from
# the HLO text with computation multipliers: ENTRY x1, while bodies x
# known_trip_count (backend_config), fusions inherit the caller's weight.
#   flops: dot instructions (2 * prod(result) * prod(contracting)) -- matmul
#          dominated, matching XLA's own convention;
#   bytes: operand + result sizes of top-level (non-fused) instructions --
#          fusion internals don't touch HBM;
#   wire:  collective ops with ring factors (parse_collective_bytes) x weight.
# ----------------------------------------------------------------------------

_COMP_HDR_RE = re.compile(r"^(ENTRY )?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
_TRIP_RE = re.compile(r'known_trip_count[\\"]*:\s*\{[\\"]*n[\\"]*:[\\"]*(\d+)')
_CALLS_RE = re.compile(r"(?:calls|body)=%?([\w\.\-]+)")
_DOT_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _split_computations(text: str) -> dict:
    comps, cur, name = {}, None, None
    for line in text.splitlines():
        m = _COMP_HDR_RE.match(line.strip()) if "{" in line else None
        if m and ("->" in line):
            name = m.group(2)
            cur = []
            comps[name] = cur
            continue
        if line.strip() == "}":
            name, cur = None, None
            continue
        if cur is not None:
            cur.append(line)
    return comps


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*([a-z]+\d*)\[([\d,]*)\]")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")


def _symbol_table(lines) -> dict:
    tbl = {}
    for line in lines:
        m = _DEF_RE.match(line)
        if m:
            tbl[m.group(1)] = [int(d) for d in m.group(3).split(",") if d]
    return tbl


def _inst_flops(line: str, tbl: dict) -> float:
    if " dot(" not in line:
        return 0.0
    shapes = _SHAPE_RE.findall(line.split(" dot(")[0])
    if not shapes:
        return 0.0
    res_elems = 1
    for d in shapes[0][1].split(","):
        if d:
            res_elems *= int(d)
    k = 1
    mc = _DOT_CONTRACT_RE.search(line)
    args = line.split(" dot(", 1)[1].split(")", 1)[0]
    ops = _OPERAND_RE.findall(args)
    if mc and ops:
        lhs_dims = tbl.get(ops[0])
        if lhs_dims:
            for ci in mc.group(1).split(","):
                if ci and int(ci) < len(lhs_dims):
                    k *= lhs_dims[int(ci)]
    return 2.0 * res_elems * k


def _inst_bytes(line: str) -> float:
    return float(sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(line)))


def analyze_hlo(text: str) -> dict:
    comps = _split_computations(text)
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR_RE.match(line.strip())
            entry = m.group(2) if m else None
            break
    weights = {entry: 1.0} if entry else {}
    order = [entry] if entry else []
    # propagate weights breadth-first through while/fusion/call edges
    seen = set(order)
    i = 0
    while i < len(order):
        cname = order[i]
        i += 1
        w = weights[cname]
        for line in comps.get(cname, ()):
            trip = 1.0
            if " while(" in line:
                mt = _TRIP_RE.search(line)
                trip = float(mt.group(1)) if mt else 1.0
            for callee in _CALLS_RE.findall(line):
                if callee in comps:
                    weights[callee] = weights.get(callee, 0.0) + w * trip
                    if callee not in seen:
                        seen.add(callee)
                        order.append(callee)
    flops = bytes_ = 0.0
    wire = {k: 0.0 for k in COLLECTIVES}
    fused = set()
    for cname, lines in comps.items():
        for line in lines:
            if " fusion(" in line:
                for callee in _CALLS_RE.findall(line):
                    fused.add(callee)
    for cname, lines in comps.items():
        w = weights.get(cname, 0.0)
        if w == 0.0:
            continue
        in_fusion = cname in fused
        tbl = _symbol_table(lines)
        for line in lines:
            flops += w * _inst_flops(line, tbl)
            if not in_fusion and "=" in line and " parameter(" not in line:
                bytes_ += w * _inst_bytes(line)
            for kind in COLLECTIVES:
                if f" {kind}(" in line or f" {kind}-start(" in line:
                    allshapes = _SHAPE_RE.findall(line)
                    size = max((_shape_bytes(d, s) for d, s in allshapes),
                               default=0)
                    n = _group_size(line)
                    if kind == "all-reduce":
                        wire[kind] += w * 2 * (n - 1) / n * size
                    elif kind == "collective-permute":
                        wire[kind] += w * size
                    else:
                        wire[kind] += w * (n - 1) / n * size
                    break
    return {"flops": flops, "bytes": bytes_, "wire": wire,
            "wire_total": sum(wire.values())}


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    wire_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    collective_detail: dict
    model_flops: float | None = None
    useful_ratio: float | None = None

    def as_dict(self):
        return dataclasses.asdict(self)


def cost_analysis_dict(compiled) -> dict:
    """compiled.cost_analysis() as a dict ({} where XLA gives none)."""
    return compiled.cost_analysis() or {}


def analyze(compiled, *, model_flops: float | None = None,
            n_chips: int = 1) -> Roofline:
    text = compiled.as_text()
    la = analyze_hlo(text)                      # loop-aware (trip-weighted)
    cost = cost_analysis_dict(compiled)
    flops = max(la["flops"], float(cost.get("flops", 0.0)))
    hbm = max(la["bytes"], float(cost.get("bytes accessed", 0.0)))
    det = la["wire"]
    det["_xla_flops_once"] = float(cost.get("flops", 0.0))
    det["_xla_bytes_once"] = float(cost.get("bytes accessed", 0.0))
    wire = la["wire_total"]
    c, m, w = flops / PEAK_FLOPS, hbm / HBM_BW, wire / ICI_BW
    dom = max((("compute", c), ("memory", m), ("collective", w)),
              key=lambda t: t[1])[0]
    ratio = None
    if model_flops:
        # model_flops is GLOBAL; flops is per-device
        ratio = model_flops / max(flops * n_chips, 1.0)
    return Roofline(flops=flops, hbm_bytes=hbm, wire_bytes=wire,
                    compute_s=c, memory_s=m, collective_s=w, dominant=dom,
                    collective_detail=det, model_flops=model_flops,
                    useful_ratio=ratio)
