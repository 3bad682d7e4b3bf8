"""Three-term roofline model from compiled artifacts, per device kind.

  compute    = HLO_FLOPs_per_device / peak_FLOPs
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_bytes_per_device / link_bw

The peaks come from PEAKS, keyed by the device's `device_kind`; a kind that
is not in the table is an error, never a default.  `compiled.cost_analysis()`
reports the per-device (post-SPMD) module, so its flops/bytes are already
per-chip.  Collective bytes are NOT in cost_analysis: we parse the
post-partitioning HLO and sum the (per-device) result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
Cross-pod collectives (replica_groups spanning pods) ride DCN; we report
them separately with a 25 GB/s assumption.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

__all__ = [
    "HW", "PEAKS", "V5E", "hw_for", "collective_bytes", "roofline_report",
    "model_flops",
]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per link
    dcn_bw: float = 25e9  # bytes/s cross-pod (an assumption, not a chip peak)


V5E = "TPU v5 lite"  # device_kind JAX reports for a TPU v5e chip

# Published per-chip peaks, keyed by device_kind.  TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of interchip interconnect over 4 links (50 GB/s each).
PEAKS = {
    V5E: HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def hw_for(device_kind: str) -> HW:
    """Peaks of one device kind; an unknown kind raises ValueError."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add them to roofline.analysis.PEAKS"
        )
    return PEAKS[device_kind]


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(sig: str, f32_bytes: int = 4) -> int:
    """Sum byte sizes of every typed shape in an HLO result signature.

    `f32_bytes=2` applies the bf16-wire correction: the XLA *CPU* backend
    (the dry-run host) legalizes every bf16 dot to f32, so activation
    collectives appear as f32 in host-compiled HLO even though the TPU-target
    program moves bf16.  Counting f32 at 2 B/elem recovers the intended wire
    size (fp32 master params are cast to bf16 before any gather — see
    train.loop — so no large intended-f32 collective remains).
    """
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        b = f32_bytes if dt == "f32" else _DTYPE_BYTES[dt]
        total += n * b
    return total


def collective_bytes(hlo_text: str, bf16_wire: bool = True) -> dict:
    """Per-device bytes moved by each collective kind (result-shape proxy)."""
    f32b = 2 if bf16_wire else 4
    out = {k: 0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result line looks like:  %name = TYPE[dims] op-name(...)
        m = re.match(r"%?[\w.\-]+ = (.+?) ([\w\-]+)\(", s)
        if not m:
            continue
        sig, op = m.groups()
        for kind in _COLLECTIVES:
            if op == kind or op == kind + "-start" or op.startswith(kind):
                out[kind] += _shape_bytes(sig, f32b)
                count[kind] += 1
                break
    out = {k: v for k, v in out.items()}
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = count
    return out


def model_flops(cfg, shape_kind: str, tokens: int) -> float:
    """Useful model FLOPs: 6*N*D train, 2*N*D inference (N = active params)."""
    n = cfg.active_param_count()
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * n * tokens


def roofline_report(
    flops_per_device: float,
    hbm_bytes_per_device: float,
    coll_bytes_per_device: float,
    hw: HW,
    dcn_bytes_per_device: float = 0.0,
) -> dict:
    t_comp = flops_per_device / hw.peak_flops
    t_mem = hbm_bytes_per_device / hw.hbm_bw
    t_coll = coll_bytes_per_device / hw.ici_bw + dcn_bytes_per_device / hw.dcn_bw
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    bound = max(t_comp, t_mem, t_coll)
    return {
        **terms,
        "dominant": dom.replace("_s", ""),
        "step_lower_bound_s": bound,
        # fraction of roofline if perfectly overlapped: useful-compute share
        "roofline_fraction": t_comp / bound if bound > 0 else 0.0,
    }
