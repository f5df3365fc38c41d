"""Roofline terms from a compiled dry-run artifact (no real hardware).

  compute    = HLO_FLOPs / (chips × peak_FLOP/s)
  memory     = HLO_bytes / (chips × HBM_bw)
  collective = collective_bytes / (chips × link_bw)

HLO_FLOPs / bytes come from ``compiled.cost_analysis()``; collective bytes
are parsed out of the post-SPMD HLO text (sum of operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

# ------------------------------------------------- target hardware (v5e)
HW = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # B/s per chip
    "link_bw": 50e9,             # B/s per ICI link
    "hbm_bytes": 16e9,           # capacity per chip
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# dtype[dims]{layout} tokens, e.g. bf16[16,1024,128]{2,1,0}
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.+)$")
_OP_RE = re.compile(r"=\s*(.*?)\s(" + "|".join(_COLLECTIVES) +
                    r")(-start|-done)?\(([^)]*)\)")
_NAME_RE = re.compile(r"%[\w.\-]+")


def parse_hlo_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-kind {count, bytes, transit_bytes} from post-SPMD HLO.

    * ``bytes`` — sum of operand sizes (what each device *contributes*),
      the roofline recipe's metric. Resolved through a def-map because
      post-optimization HLO references operands as bare ``%name``.
    * ``transit_bytes`` — bandwidth-weighted bytes actually moved per
      device under the standard ring algorithms: all-gather receives
      result−operand, all-reduce moves ≈2×operand (reduce-scatter +
      all-gather phases), the rest ≈ operand. The operand metric hides
      all-gather fan-in (see EXPERIMENTS.md §Perf H3) — both are reported.
    """
    # pass 1: instruction name → result bytes (tuples summed)
    def_bytes: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        rhs = m.group(2)
        # result type(s): shape tokens before the opcode's '('
        head = rhs.split("(", 1)[0]
        toks = _SHAPE_RE.findall(head)
        if toks:
            def_bytes[m.group(1)] = sum(_shape_bytes(d, s) for d, s in toks)

    out = {k: {"count": 0, "bytes": 0.0, "transit_bytes": 0.0}
           for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind, suffix, args = m.group(2), m.group(3), m.group(4)
        if suffix == "-done":
            continue  # counted at -start
        operands = _NAME_RE.findall(args)
        nbytes = sum(def_bytes.get(op, 0.0) for op in operands)
        if nbytes == 0:
            # inline operand types (unoptimized HLO) or fall back to result
            toks = _SHAPE_RE.findall(args) or _SHAPE_RE.findall(m.group(1))
            nbytes = sum(_shape_bytes(d, s) for d, s in toks)
        # result bytes of this op (for all-gather fan-in accounting)
        head = line.split("(", 1)[0]
        rtoks = _SHAPE_RE.findall(head)
        rbytes = sum(_shape_bytes(d, s) for d, s in rtoks) or nbytes
        if kind == "all-gather":
            transit = max(rbytes - nbytes, nbytes)
        elif kind == "all-reduce":
            transit = 2.0 * nbytes
        else:
            transit = nbytes
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
        out[kind]["transit_bytes"] += transit
    return out


def collective_bytes_from_hlo(hlo_text: str) -> float:
    return sum(v["bytes"] for v in parse_hlo_collectives(hlo_text).values())


def roofline_report(*, flops: float, bytes_accessed: float,
                    collective_bytes: float, chips: int,
                    model_flops: Optional[float] = None) -> Dict:
    """The three terms (seconds), dominant term, and MFU-style ratios.

    ``flops``/``bytes_accessed`` are whole-module (all devices) totals as
    reported by cost_analysis on the SPMD module; collective_bytes likewise.
    """
    t_compute = flops / (chips * HW["peak_flops_bf16"])
    t_memory = bytes_accessed / (chips * HW["hbm_bw"])
    t_collective = collective_bytes / (chips * HW["link_bw"])
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_collective}
    dominant = max(terms, key=terms.get)
    rep = {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "bound_time_s": terms[dominant],
        "hlo_flops": flops,
        "hlo_bytes": bytes_accessed,
        "collective_bytes": collective_bytes,
        "chips": chips,
    }
    if model_flops is not None:
        rep["model_flops"] = model_flops
        rep["useful_flops_ratio"] = model_flops / flops if flops else 0.0
        rep["roofline_fraction"] = (
            (model_flops / (chips * HW["peak_flops_bf16"])) / terms[dominant]
            if terms[dominant] else 0.0)
    return rep
