"""The trace reduction and the roofline byte count, on a small trace
recorded on the chip (TPU v5 lite, PR 24: 0.2 s of paced traffic and one
16,384-row device decode). Runs on a CPU in seconds."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import roofline  # noqa: E402
import trace as trace_mod  # noqa: E402

XPLANE = os.path.join(HERE, "data", "paced_tail.xplane.pb")


@pytest.fixture(scope="module")
def xplane():
    return trace_mod.read_xplane(XPLANE)


def test_planes_and_marker(xplane):
    assert list(xplane["devices"]) == ["/device:TPU:0"]
    dev = xplane["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 1 and len(dev["ops"]) > 10
    assert dev["modules"][0][0].startswith("jit_fn")
    assert all(" = " not in name for name, _, _ in dev["ops"])
    assert xplane["marker_ns"] is not None


def test_reduction(xplane):
    dev = xplane["devices"]["/device:TPU:0"]
    marker = xplane["marker_ns"]
    lo, hi = marker, max(b for _, _, b in dev["ops"]) + 1_000_000
    mod_a, mod_b = dev["modules"][0][1:]
    spans = {"fetch": np.array([[mod_a - 200_000, mod_b + 200_000]]),
             "frame_walk": np.array([[lo, hi]])}
    out = trace_mod.reduce_trace(xplane, marker, (lo, hi), spans,
                                 {"fetch": 70, "frame_walk": 30})
    # busy = the union of the ops, which sit inside the one module
    assert 0 < out["busy_s"] <= (mod_b - mod_a) / 1e9 + 1e-9
    assert out["program_s"] == pytest.approx((mod_b - mod_a) / 1e9)
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["devices"] == 1
    ops = out["device_ops"]
    assert len(ops) == 10 and ops == sorted(ops, key=lambda kv: -kv[1])
    assert sum(v for _, v in ops) <= out["busy_s"] + 1e-9
    # every idle instant is given to the highest-ranked open span
    gaps = dict(out["idle_gaps"])
    assert trace_mod.UNSPANNED not in gaps
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], abs=2 * trace_mod.BIN_NS / 1e9)
    assert gaps["fetch"] == pytest.approx(
        (mod_b - mod_a + 400_000) / 1e9 - out["busy_s"], abs=2e-4)
    # a clock offset moves nothing
    shifted = trace_mod.reduce_trace(
        xplane, marker + 10**12, (lo + 10**12, hi + 10**12),
        {k: v + 10**12 for k, v in spans.items()},
        {"fetch": 70, "frame_walk": 30})
    assert shifted["busy_s"] == pytest.approx(out["busy_s"])


def test_interval_arithmetic():
    iv = np.array([[0.0, 10.0], [5.0, 12.0], [20.0, 30.0]])
    assert trace_mod.union_length(iv) == 22.0
    assert trace_mod.union_length(trace_mod.clip(iv, 8.0, 25.0)) == 9.0
    assert trace_mod.span_busy_share(iv, 0.0, 40.0) == pytest.approx(0.55)


def test_roofline_bytes():
    cols = [{"name": "aid", "type": "int4"}, {"name": "bid", "type": "int4"},
            {"name": "abalance", "type": "int4"},
            {"name": "filler", "type": "bpchar", "text_bytes": 84}]
    # payload 130 B/row: 8 message + 4x5 column headers + 84 filler + 18 text
    assert roofline.decode_bytes(cols, 1000, 130.0, False) == 1000 * 142.0
    assert roofline.decode_bytes(cols, 1000, 130.0, True) == 1000 * 160.0
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v9")
