"""Roofline machinery: HLO collective parser, terms, model flops."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.roofline.analysis import (
    HW,
    PEAKS,
    V5E,
    collective_bytes,
    hw_for,
    model_flops,
    roofline_report,
)

FAKE_HLO = """
HloModule jit_step
  %all-gather.1 = f32[16,128]{1,0} all-gather(%x), dimensions={0}
  %all-reduce.2 = (bf16[256]{0}, f32[]) all-reduce(%y, %z), to_apply=%add
  %reduce-scatter.3 = s8[1024]{0} reduce-scatter(%w), dimensions={0}
  %all-to-all.4 = u32[64,2]{1,0} all-to-all(%v), dimensions={0}
  %collective-permute-start.5 = bf16[8,8]{1,0} collective-permute-start(%u)
  %dot.6 = f32[128,128]{1,0} dot(%a, %b)
"""


def test_collective_parser_counts_and_bytes():
    out = collective_bytes(FAKE_HLO, bf16_wire=False)
    assert out["all-gather"] == 16 * 128 * 4
    assert out["all-reduce"] == 256 * 2 + 4
    assert out["reduce-scatter"] == 1024
    assert out["all-to-all"] == 64 * 2 * 4
    assert out["collective-permute"] == 8 * 8 * 2
    assert out["counts"]["all-gather"] == 1
    assert out["total"] == sum(
        out[k] for k in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute")
    )


def test_bf16_wire_correction_halves_f32_only():
    raw = collective_bytes(FAKE_HLO, bf16_wire=False)
    cor = collective_bytes(FAKE_HLO, bf16_wire=True)
    assert cor["all-gather"] == raw["all-gather"] // 2  # f32 halved
    assert cor["collective-permute"] == raw["collective-permute"]  # bf16 kept
    assert cor["reduce-scatter"] == raw["reduce-scatter"]  # int8 kept


def test_roofline_dominant_and_fraction():
    hw = HW(peak_flops=1e12, hbm_bw=1e9, ici_bw=1e9)
    r = roofline_report(1e12, 0.5e9, 2e9, hw=hw)  # 1s comp, 0.5s mem, 2s coll
    assert r["dominant"] == "collective"
    assert abs(r["step_lower_bound_s"] - 2.0) < 1e-9
    assert abs(r["roofline_fraction"] - 0.5) < 1e-9


def test_model_flops_moe_counts_active_only():
    dense = get_config("qwen2.5-3b")
    moe = get_config("mixtral-8x7b")
    assert model_flops(dense, "train", 1000) == 6.0 * dense.param_count() * 1000
    assert moe.active_param_count() < moe.param_count() / 2
    assert model_flops(moe, "prefill", 10) == 2.0 * moe.active_param_count() * 10


def test_param_counts_order_of_magnitude():
    """Config param counts land near the models' nameplate sizes."""
    expect = {
        "qwen2.5-3b": (2.5e9, 4.5e9),
        "deepseek-67b": (60e9, 75e9),
        "mixtral-8x7b": (40e9, 52e9),
        "mamba2-1.3b": (1.0e9, 1.6e9),
        "gemma3-4b": (3.0e9, 5.5e9),
        "chameleon-34b": (30e9, 40e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, (arch, n)


def test_peaks_keyed_by_device_kind():
    assert hw_for(V5E) is PEAKS[V5E]
    assert hw_for(V5E).peak_flops == 197e12 and hw_for(V5E).hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        hw_for("cpu")
