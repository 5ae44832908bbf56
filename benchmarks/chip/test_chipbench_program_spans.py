"""The reduction of the program's own spans (``chipbench/program_spans.py``)
on hand-made extracts with known answers, and on one recorded on a TPU v5e,
which holds no program span."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import program_spans, trace

NS = 1e-9

#: one sweep call: ``trevor.evaluate`` holds a pad span (which holds a full
#: collection), a host→device span and the kernel wait; a stack span
#: starts before the window, and a second pad span lies outside the call
SWEEP = {
    "devices": {"/device:TPU:0": {"ops": [["fusion.1", 100, 200], ["fusion.2", 600, 700]],
                                  "modules": []}},
    "spans": [["window", 0, 1000], ["evaluate", 50, 900]],
    "program_spans": [
        ["trevor.stage.stack", -50, 30],
        ["trevor.evaluate", 60, 880],
        ["trevor.stage.pad", 80, 300],
        ["trevor.gc", 150, 250],
        ["trevor.stage.h2d", 300, 400],
        ["trevor.kernel.wait", 500, 800],
        ["trevor.stage.pad", 910, 950],
    ],
}

#: one control step: the loop's forecast and plan spans, the plan holding
#: the evaluator's call
CONTROL = {
    "devices": {"/device:TPU:0": {"ops": [["fusion.1", 400, 900]], "modules": []}},
    "spans": [["window", 0, 1000], ["control_step", 0, 1000]],
    "program_spans": [
        ["trevor.control.step", 10, 990],
        ["trevor.control.forecast", 10, 60],
        ["trevor.control.plan", 60, 950],
        ["trevor.evaluate", 100, 940],
        ["trevor.stage.pad", 100, 300],
        ["trevor.kernel.wait", 350, 930],
    ],
}


def _ns(table: dict) -> dict:
    return {n: [pytest.approx(t / NS), c, pytest.approx(s / NS)]
            for n, (t, c, s) in table.items()}


def test_names_and_split():
    names = program_spans.Names(("window", "evaluate"))
    assert "window" in names and "evaluate" in names
    assert "trevor.stage.pad" in names and "control_step" not in names
    ex = program_spans.split({"devices": {}, "spans": [
        ["window", 0, 9], ["trevor.evaluate", 1, 2], ["evaluate", 1, 3]]})
    assert ex["spans"] == [["window", 0, 9], ["evaluate", 1, 3]]
    assert ex["program_spans"] == [["trevor.evaluate", 1, 2]]


def test_totals_counts_and_self_times():
    got = program_spans.reduce(SWEEP)
    assert _ns(got["program"]) == {
        "trevor.stage.stack": [30, 1, 30],            # clipped to the window
        "trevor.evaluate": [820, 1, 200],             # less pad, h2d, wait
        "trevor.stage.pad": [260, 2, 160],            # less the collection
        "trevor.gc": [100, 1, 100],
        "trevor.stage.h2d": [100, 1, 100],
        "trevor.kernel.wait": [300, 1, 300],
    }


def test_idle_time_goes_to_the_innermost_span():
    got = program_spans.reduce(SWEEP)
    assert {n: pytest.approx(t / NS) for n, t in got["idle_gaps"]} == {
        "window": 80, "evaluate": 30, "trevor.evaluate": 200,
        "trevor.stage.pad": 110, "trevor.gc": 50, "trevor.stage.h2d": 100,
        "trevor.kernel.wait": 200, "trevor.stage.stack": 30,
    }
    # still the window less its busy time
    assert sum(t for _n, t in got["idle_gaps"]) == pytest.approx(800 * NS)
    control = program_spans.reduce(CONTROL)
    assert {n: pytest.approx(t / NS) for n, t in control["idle_gaps"]} == {
        "control_step": 20, "trevor.control.forecast": 50,
        "trevor.control.plan": 50, "trevor.stage.pad": 200,
        "trevor.evaluate": 60, "trevor.kernel.wait": 80,
        "trevor.control.step": 40,
    }
    assert sum(t for _n, t in control["idle_gaps"]) == pytest.approx(500 * NS)


def test_the_five_readings_and_none_without_their_spans():
    sweep = dict(trace=program_spans.reduce(SWEEP),
                 counters=dict(calls=1, h2d_bytes=3_000_000))
    # pad 260 + h2d 100 + stack 30 ns over one call
    assert program_spans.stage_ms_per_call(sweep) == pytest.approx(390e-6)
    assert program_spans.staged_mb_per_call(sweep) == pytest.approx(3.0)
    assert program_spans.loop_self_ms_per_call(sweep) is None
    control = dict(trace=program_spans.reduce(CONTROL),
                   counters=dict(calls=2, h2d_bytes=None))
    assert program_spans.stage_ms_per_call(control) == pytest.approx(200e-6 / 2)
    # step 980 - (50 + 890), forecast 50, plan 890 - 840
    assert program_spans.loop_self_ms_per_call(control) == pytest.approx(140e-6 / 2)
    assert program_spans.staged_mb_per_call(control) is None
    # a program without spans or the counter (the parent of this reduction)
    bare = dict(SWEEP, program_spans=[])
    bare_run = dict(trace=program_spans.reduce(bare), counters=dict(calls=1))
    for read in (program_spans.stage_ms_per_call, program_spans.staged_mb_per_call,
                 program_spans.loop_self_ms_per_call):
        assert read(bare_run) is None
        assert read(dict(trace=None, counters=dict(calls=1))) is None
    assert program_spans.reduce({"devices": {}, "spans": [["window", 0, 9]]}) is None


def test_without_program_spans_it_charges_idle_time_as_trace_reduce_does():
    path = Path(__file__).with_name("testdata") / "tpu_extract.json"
    ex = json.loads(path.read_text())
    ours = program_spans.reduce(ex)
    theirs = trace.reduce(ex)
    assert ours["program"] == {}
    assert dict(ours["idle_gaps"]) == pytest.approx(dict(theirs["idle_gaps"]), rel=1e-9)
