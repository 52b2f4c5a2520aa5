"""Roofline terms from compiled XLA programs.

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = collective_bytes / link_bw       (per chip)

``compiled.cost_analysis()`` reports the *per-device* SPMD program, so the
terms divide by per-chip peaks directly.  collective_bytes is not in
cost_analysis: we parse the post-partitioning HLO (``compiled.as_text()``)
and sum the result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (result bytes == the
per-device traffic each op moves through the ICI, up to the reduction
factor; documented convention).

Hardware constants (TPU v5e-class target): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12  # bf16 FLOP/s per chip
    hbm_bw: float = 819e9  # B/s per chip
    ici_bw: float = 50e9  # B/s per link


HW = HardwareSpec()


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, Any]:
    """Sum per-device result bytes of every collective in the HLO.

    Returns {"total": int, "by_op": {op: bytes}, "count": int}."""
    by_op: dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    count = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        lhs, _, rhs = stripped.partition(" = ")
        # rhs starts with the result type then the op name; tuple types may
        # contain /*index=N*/ comments, so match to the closing paren
        m = re.match(r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+([a-z0-9-]+)", rhs)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        base = None
        for c in COLLECTIVE_OPS:
            if op == c or op.startswith(c + "-start") or op == c + "-done":
                base = c
                break
        if base is None or op.endswith("-done"):
            continue
        by_op[base] += _shape_bytes(type_str)
        count += 1
    return {"total": int(sum(by_op.values())), "by_op": by_op, "count": count}


def roofline_terms(
    flops_per_device: float,
    bytes_per_device: float,
    coll_bytes_per_device: float,
    hw: HardwareSpec = HW,
) -> dict[str, float]:
    ct = flops_per_device / hw.peak_flops
    mt = bytes_per_device / hw.hbm_bw
    lt = coll_bytes_per_device / hw.ici_bw
    dominant = max(("compute", ct), ("memory", mt), ("collective", lt), key=lambda t: t[1])
    bound = max(ct, mt, lt)
    return {
        "compute_s": ct,
        "memory_s": mt,
        "collective_s": lt,
        "dominant": dominant[0],
        "roofline_bound_s": bound,
        # fraction of the bound attributable to useful compute
        "compute_fraction_of_bound": ct / bound if bound > 0 else 0.0,
    }
