"""Compiled-artifact analysis: collective-byte parsing + roofline terms.

TPU v5e hardware model (per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.  The dry-run is single-controller with placeholder
devices, so wall-clock is meaningless — the roofline terms below are the
perf report (EXPERIMENTS.md §Roofline).
"""
from __future__ import annotations

import re
from typing import Dict

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (one direction)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast",
                "ragged-all-to-all")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# e.g.:  %ag = bf16[2,512]{1,0} all-gather(...)   or tuple results
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(" + "|".join(_COLLECTIVES) + r")(-start)?\(")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op, by kind.

    ``-done`` ops are skipped (their ``-start`` was counted); convention:
    payload == result bytes (documented in EXPERIMENTS.md §Roofline).
    """
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        type_str, kind, _ = m.groups()
        out[kind] = out.get(kind, 0) + _shape_bytes(type_str)
    return out


def roofline_terms(flops: float, hbm_bytes: float, collective_bytes: float,
                   n_chips: int) -> Dict[str, float]:
    """The three roofline terms in seconds.

    IMPORTANT convention: ``compiled.cost_analysis()`` on an SPMD-partitioned
    module reports PER-DEVICE flops/bytes (verified against 6·N·D/chips), and
    the collective shapes in the partitioned HLO are per-device payloads —
    so every term is per-chip work over per-chip capability; n_chips is only
    used for reporting.
    """
    compute_s = flops / PEAK_FLOPS
    memory_s = hbm_bytes / HBM_BW
    collective_s = collective_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    total = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    terms["roofline_fraction"] = compute_s / total if total > 0 else 0.0
    return terms


def active_param_count(cfg, n_params: int) -> int:
    """MoE: subtract un-routed expert params (6·N_active·D convention)."""
    if getattr(cfg, "moe", None) is None:
        return n_params
    m = cfg.moe
    n_moe_layers = cfg.n_layers - m.n_dense_layers
    inactive = n_moe_layers * 3 * cfg.d_model * m.d_ff_expert \
        * (m.n_experts - m.n_experts_per_tok)
    return n_params - inactive


def model_flops(n_params: int, n_tokens: int, kind: str = "train") -> float:
    """6·N·D for train, 2·N·D for inference forward (N = active params)."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params * n_tokens


def cost_analysis_terms(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    return {"hlo_flops": flops, "hlo_bytes": bytes_accessed}
