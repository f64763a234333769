"""Each bench/work count at a small shape against hand arithmetic, and
the peak table."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import harness, peaks  # noqa: E402


def work(kernel):
    return harness.load_module(harness.work_file(ROOT, kernel), "work").work


def test_adc_quantize_population():
    # x 10*3, tables 2*3*4, rows 2*3, out 2*10*3 floats
    w = work("adc_quantize_population")(p=2, m=10, c=3, bits=2)
    assert w == {"flops": 0.0, "bytes": 4.0 * (30 + 24 + 6 + 60)}


def test_mc_eval_population():
    # x 5*2, lb+ub 2*(3*4*2*4), ladder 2*4, rows 2*(4*2), out 3*4*5*2
    w = work("mc_eval_population")(d=3, s=4, m=5, c=2, bits=2)
    assert w == {"flops": 0.0, "bytes": 4.0 * (10 + 192 + 8 + 16 + 120)}


def test_mlp_qat_and_forward():
    # per step: forward 2*10*(6+4)=200, dW1 2*10*6=120, dW2+dh 2*10*4*2=160
    w = work("mlp_qat")(m_train=10, m_test=5, c=3, h=2, o=2, steps=3)
    assert w["flops"] == 3 * (200 + 120 + 160) + 2 * 5 * 10
    assert work("mlp_forward")(m=5, c=3, h=2, o=2)["flops"] == 100.0


def test_peaks_known_and_unknown_device():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks_for("TPU v5e") == p
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
