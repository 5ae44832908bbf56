"""The check that decides ``correct``, driven through the rest of a run at a
size the CPU holds (the look for a chip is skipped): sound runs pass, and
the control (the reference with bfloat16 flows in the program's place) and
each fault planted under the timed path fail."""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
_spec = importlib.util.spec_from_file_location("chipbench_run", HERE / "run.py")
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)

SEED = 4_000_000_007
#: each cell cut to a size the CPU runs in seconds (the DAGs' widths and the
#: 800-tick horizon are as the configurations state), with a window of 0 s:
#: exactly one call, so what the check sees does not hang on the CPU's speed.
#: The control cell's one step falls on the rising side of its curve, where
#: some candidates saturate.
SMALL = {
    "heron-wordcount.sweep512": (dict(width=8, containers=[4, 8]),
                                 dict(batch=16, spread=2, check_rows=24)),
    "heron-wordcount.control": (dict(width=8),
                                dict(peak_ktps=3000.0, period=24, start_step=12,
                                     warmup_steps=20, check_steps=16)),
}


def _run(cell: str, flows_dtype=None) -> dict:
    spec = bench.load_cell(cell)
    config, mix = SMALL[cell]
    spec["config"].update(config)
    spec["mix"].update(mix)
    return bench.run(spec, SEED, 0.0, False, flows_dtype=flows_dtype)


def _failed(result: dict) -> list[str]:
    return [n for n, c in result["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0
    assert set(result["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_bfloat16_control_is_not_correct(cell):
    result = _run(cell, flows_dtype=jnp.bfloat16)
    assert not result["correct"]
    assert _failed(result) == ["achieved_gap"]


def _half(evaluate_batch):
    """Half of every batch left out: its rows answered by the other half."""
    def broken(self, configs, *a, **kw):
        res = evaluate_batch(self, configs, *a, **kw)
        h = len(res) // 2
        return res[:len(res) - h] + res[:h]
    return broken


def _altered(run_batch):
    """Every answer altered where it is produced, by one part in a thousand."""
    def broken(*a, **kw):
        res = run_batch(*a, **kw)
        for r in res:
            r._achieved = r.achieved_ktps * 1.001
        return res
    return broken


def _stale_call(evaluate_batch):
    """A call that hands back its previous answers."""
    last = {}

    def broken(self, configs, *a, **kw):
        res = evaluate_batch(self, configs, *a, **kw)
        prev = last.get(id(self), res)
        last[id(self)] = res
        return prev if len(prev) == len(res) else res
    return broken


def _stale_step(step):
    """A control step that leaves its state unchanged: the previous event."""
    last = {}

    def broken(self, load):
        ev = step(self, load)
        prev = last.get(id(self), ev)
        last[id(self)] = ev
        return prev
    return broken


EVALUATE = ("repro.streams.engine", "SimulatorEvaluator", "evaluate_batch")
#: per fault, where it is planted: (module, owner or None, attribute, fault)
FAULTS = {
    "half": lambda cell: EVALUATE + (_half,),
    "altered": lambda cell: ("repro.streams.simulator", None, "_run_batch", _altered),
    "stale": lambda cell: {
        "heron-wordcount.sweep512": EVALUATE + (_stale_call,),
        "heron-wordcount.control": ("repro.control.loop", "ControlLoop", "step",
                                    _stale_step),
    }[cell],
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(SMALL))
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    module, owner, attr, make = FAULTS[fault](cell)
    target = importlib.import_module(module)
    if owner is not None:
        target = getattr(target, owner)
    monkeypatch.setattr(target, attr, make(getattr(target, attr)))
    result = _run(cell)
    assert not result["correct"], result["checks"]


#: a run of the four-chip sweep cell on four virtual CPU devices, where the
#: evaluator shards each batch by pmap as it does over four chips; with
#: ``exchange`` every chip's answers are replaced by the first chip's, as if
#: the gather across chips were left out
SHARDED = """
import importlib.util, json, sys
import jax
import jax.numpy as jnp
spec = importlib.util.spec_from_file_location("chipbench_run", sys.argv[1])
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)
from repro.streams import simulator
if sys.argv[3] == "exchange":
    make = simulator._get_batch_kernel
    def broken(*a, **kw):
        fn = make(*a, **kw)
        if a[5] == 1:
            return fn
        def first_chip_only(*args):
            return {k: jnp.broadcast_to(v[:1], v.shape) for k, v in fn(*args).items()}
        return first_chip_only
    simulator._get_batch_kernel = broken
cell = bench.load_cell("heron-wordcount.sweep512-x4")
cell["config"].update(width=8, containers=[4, 8])
cell["mix"].update(batch=16, spread=2, check_rows=24)
result = bench.run(cell, int(sys.argv[2]), 0.0, False)
print(json.dumps(dict(correct=result["correct"], checks=result["checks"],
                      metrics=sorted(result["metrics"]),
                      devices=len(jax.devices()),
                      staged=simulator.transfer_info()["staged_devices"])))
"""


@pytest.mark.parametrize("fault", ["none", "exchange"])
def test_sharded_sweep_over_four_devices(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(HERE), str(HERE.parents[1] / "src")]))
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED, str(HERE / "run.py"), str(SEED), fault],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4 and got["staged"] == 4
    assert got["metrics"] == ["candidates_per_s.x4", "setup_s"]
    assert got["correct"] is (fault == "none"), got["checks"]
