"""CPU test of the cluster_sums_roofline reader on a synthetic trace.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os

import pytest

from bench.lib import harness
from bench.lib import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _ev(a, b, name="fusion", cat=""):
    return tr.Event(float(a), float(b), name, cat)


def test_cluster_sums_roofline_on_a_trace():
    """The segment-sum kernel's calls by their own name: an op that
    reads the kernel's output names it in its HLO text and is not
    counted; a trace without the kernel (the parent's) reads None."""
    mod = harness.load_module(os.path.join(
        ROOT, "bench", "metrics", "cluster_sums_roofline.py"), "t_sums")
    # 256 x 1,628,170 f32 on a v5e: 3.33 GB moved, bound by bytes,
    # 4.07 ms a call
    v5e = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = mod.min_seconds(256, 1_628_170, v5e)
    assert least == pytest.approx((8 * 256 * 1_628_170 + 4 * 256) / 819e9)
    assert least == pytest.approx(4.07e-3, rel=1e-3)
    dev = [_ev(0, 8e6, "%segment_rows.14 = f32[256,1628170] custom-call()"),
           _ev(8e6, 9e6, "%broadcast_divide_fusion.2 = f32[256,1628170] "
                         "fusion(f32[256,1628170] %segment_rows.14)"),
           _ev(9e6, 1.7e7, "%segment_rows = f32[256,1628170] custom-call()"),
           _ev(1.7e7, 1.8e7, "%dynamic-update-slice.232 = f32[] fusion()")]
    host = [("python", _ev(0, 2e7, tr.WINDOW_SPAN))]
    view = {"trace": tr.Trace({"/device:TPU:0": dev}, host), "chips": 1,
            "window_ns": (0.0, 2e7), "peak": v5e, "merge_k": 256,
            "psi_dim": 1_628_170}
    assert mod.read(view) == pytest.approx(100.0 * 2 * least / 16e-3)
    view["trace"] = tr.Trace({"/device:TPU:0": dev[1:2] + dev[3:]}, host)
    assert mod.read(view) is None
