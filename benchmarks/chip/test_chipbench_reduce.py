"""The reduction from a profiler trace to the benchmark's numbers, on a
hand-made extract with known answers and on one recorded on a TPU v5e."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import trace

NS = 1e-9

HAND = {
    "devices": {
        "/device:TPU:0": {
            "ops": [["fusion.1", 100, 200], ["fusion.2", 150, 300], ["fusion.1", 500, 600]],
            "modules": [["jit__simulate_core(1)", 100, 300], ["jit_other(2)", 500, 600]],
        },
        "/device:TPU:1": {
            "ops": [["fusion.1", 0, 1000]],
            "modules": [["jit_call_wrapped(3)", 0, 1000]],
        },
    },
    "spans": [["window", 0, 1000], ["evaluate", 50, 400], ["evaluate", 450, 700]],
}


def test_reduce_hand_made_extract():
    got = trace.reduce(HAND)
    assert got["window_s"] == pytest.approx(1000 * NS)
    # device 0 is busy over [100, 300) and [500, 600), device 1 throughout
    assert got["busy_s"] == pytest.approx((300 + 1000) / 2 * NS)
    assert got["devices"] == 2
    assert got["kernel_s"] == pytest.approx((200 + 1000) * NS)
    assert got["device_ops"] == [["fusion.1", pytest.approx(1200 * NS)],
                                 ["fusion.2", pytest.approx(150 * NS)]]
    # device 0's idle time, averaged over two devices: 300 ns inside the
    # two evaluate spans, 400 ns outside them
    assert dict(got["idle_gaps"]) == {"window": pytest.approx(200 * NS),
                                      "evaluate": pytest.approx(150 * NS)}
    assert got["calls"] == [[pytest.approx(50 * NS), pytest.approx(200 * NS),
                             pytest.approx(100 * NS)],
                            [pytest.approx(50 * NS), pytest.approx(100 * NS),
                             pytest.approx(100 * NS)]]


def test_reduce_clips_to_the_window_and_needs_device_work():
    ex = {"devices": {"/device:TPU:0": {"ops": [["f", 900, 1300]], "modules": []}},
          "spans": [["window", 1000, 2000]]}
    got = trace.reduce(ex)
    assert got["busy_s"] == pytest.approx(300 * NS)
    assert got["kernel_s"] == 0.0
    assert trace.reduce({"devices": {}, "spans": [["window", 0, 10]]}) is None
    assert trace.reduce({"devices": ex["devices"], "spans": []}) is None


def test_reduce_recorded_tpu_extract():
    path = Path(__file__).with_name("testdata") / "tpu_extract.json"
    ex = json.loads(path.read_text())
    got = trace.reduce(ex)
    assert 0.0 < got["busy_s"] <= got["window_s"]
    assert got["kernel_s"] > 0.0
    assert any(trace.is_kernel(name) for name, _t in got["modules"])
    assert len(got["calls"]) == 2
    assert sum(t for _n, t in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
