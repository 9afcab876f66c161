"""Roofline-term derivation from compiled dry-run artifacts.

Peaks are per chip and keyed by ``device_kind`` as JAX reports it
(:data:`PEAKS`); a kind missing from the table is an error, never a
default.  The dry-run compiles for a pod of TPU v5e chips
(:data:`DRYRUN_DEVICE_KIND`).

The compiled module is the *per-device* SPMD program, so
``cost_analysis()`` FLOPs/bytes and parsed collective bytes are already
per-chip; terms are seconds-per-step on one chip:

  compute  = flops / peak_flops
  memory   = bytes_accessed / hbm_bw
  collective = collective_bytes / ici_bw

collective_bytes sums the *result* buffer of every collective op in the
optimized HLO (start/done pairs counted once); all-reduce is counted
twice (reduce-scatter + all-gather phases of a ring).  This is a
bandwidth-optimal-ring lower bound — latency terms and DCN (pod axis)
slowdown are noted qualitatively in EXPERIMENTS.md.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
    # HBM at 819 GB/s, 1,600 Gbit/s of interchip interconnect per chip
    # (~50 GB/s per link of four).
    "TPU v5 lite": {
        "peak_flops": 197e12,        # bf16
        "hbm_bw": 819e9,             # bytes/s
        "ici_bw": 50e9,              # bytes/s per link
        "source": "Google Cloud documentation, TPU v5e",
    },
}

#: the chip the dry-run's production mesh is made of
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """The :data:`PEAKS` row of ``device_kind``; raises for unknown kinds."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

_ARRAY_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _array_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _ARRAY_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Per-collective-kind result bytes from optimized HLO text."""
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        lhs, _, rhs = line.partition("=")
        rhs = rhs.strip()
        m = re.match(r"^(\([^)]*\)|\S+)\s+([\w-]+)", rhs)
        if not m:
            continue
        type_str, opname = m.group(1), m.group(2)
        base = None
        for c in _COLLECTIVES:
            if opname == c or opname == c + "-start":
                base = c
                break
        if base is None:
            continue       # -done ops carry no new transfer
        out[base] += _array_bytes(type_str)
        out["count"] += 1
    out["total"] = sum(v for k, v in out.items()
                       if k in _COLLECTIVES)
    # all-reduce moves ~2x its buffer over the wire (RS + AG ring phases)
    out["weighted_total"] = out["total"] + out["all-reduce"]
    return out


def roofline_terms(flops: float, bytes_accessed: float,
                   collective_bytes: float,
                   device_kind: str) -> Dict[str, float]:
    hw = peaks(device_kind)
    terms = {
        "compute_s": flops / hw["peak_flops"],
        "memory_s": bytes_accessed / hw["hbm_bw"],
        "collective_s": collective_bytes / hw["ici_bw"],
    }
    dominant = max(terms, key=terms.get)
    terms["dominant"] = dominant
    bound = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    terms["roofline_fraction"] = (terms["compute_s"] / bound) if bound else 0.0
    return terms


def summarize_cell(record: Dict, model_flops: Optional[float] = None) -> Dict:
    """record: one dry-run JSON dict -> roofline summary row."""
    cost = record.get("cost_analysis", {})
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    coll = record.get("collectives", {})
    terms = roofline_terms(flops, bytes_accessed,
                           float(coll.get("weighted_total", 0.0)),
                           record["meta"]["device_kind"])
    out = dict(record.get("meta", {}))
    out.update(terms)
    out["flops"] = flops
    out["bytes_accessed"] = bytes_accessed
    out["collective_bytes"] = coll.get("weighted_total", 0.0)
    if model_flops:
        out["model_flops"] = model_flops
        out["useful_flops_ratio"] = model_flops / flops if flops else 0.0
    return out
