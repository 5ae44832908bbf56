"""The program's profiler spans change nothing they wrap.

A control loop and a sweep answer bitwise the same with a profiler session
running as without one; every span name the profiler records is one of
``spans.SPANS``; and ``transfer_info()["bytes_h2d"]`` counts what one batch
stages on the device.
"""
import dataclasses
import gc
import glob
import os

import jax
import numpy as np
import pytest

from repro.control import (
    ControlLoop,
    GuardBands,
    HoltWintersForecaster,
    ModelStore,
    PredictivePolicy,
    make_trace,
)
from repro.core import ContainerDim, oracle_models, round_robin_configuration
from repro.streams import (
    OVERLOAD_KTPS,
    SimParams,
    SimulatorEvaluator,
    bucket_size,
    clear_kernel_cache,
    clear_resident_cache,
    clear_structure_cache,
    clear_transfer_stats,
    pad_structure,
    simulate_batch,
    transfer_info,
    wordcount,
)
from repro.streams import spans
from repro.streams.simulator import structure_for

DIM = ContainerDim(cpus=3.0, mem_mb=4096.0)
PARAMS = SimParams()
DAG = wordcount()
MODELS = oracle_models(DAG, PARAMS.sm_cost_per_ktuple)
DURATION_S = 2.0


class Recording(SimulatorEvaluator):
    """Keeps every row each ``evaluate_batch`` call scored."""

    def __init__(self, **kw) -> None:
        super().__init__(**kw)
        self.rows: list = []

    def evaluate_batch(self, configs, offered_ktps=OVERLOAD_KTPS):
        res = super().evaluate_batch(configs, offered_ktps)
        loads = ([offered_ktps] * len(res) if np.isscalar(offered_ktps)
                 else list(offered_ktps))
        self.rows += [(r.config, float(load).hex(), _bits(r.achieved_ktps),
                       r.bottleneck) for r, load in zip(res, loads)]
        return res


def _bits(x):
    """Floats as their exact hex form, so equal means bitwise equal (and NaN
    equals NaN)."""
    return float(x).hex() if isinstance(x, float) else x


def _control_run() -> tuple[list, list]:
    evaluator = Recording(params=PARAMS, duration_s=DURATION_S)
    loop = ControlLoop(
        PredictivePolicy(DAG, ModelStore(MODELS), preferred_dim=DIM),
        guards=GuardBands(headroom=1.05, deadband=0.1),
        evaluator=evaluator,
        forecaster=HoltWintersForecaster(season=6),
        horizon=4,
        saturation_threshold=0.9,
    )
    events = []
    for load in make_trace("diurnal", 30, base_ktps=300.0, seed=5):
        ev = loop.step(load)
        fields = dataclasses.asdict(ev)
        del fields["plan_seconds"]                  # the one timing field
        events.append({k: _bits(v) for k, v in fields.items()})
    return events, evaluator.rows


def _sweep_configs() -> list:
    return [round_robin_configuration(DAG, {"W": w, "C": c}, k, DIM)
            for w in (1, 2, 3) for c in (1, 2) for k in (2, 3)]


def _sweep_run() -> list:
    res = SimulatorEvaluator(params=PARAMS, duration_s=DURATION_S).evaluate_batch(
        _sweep_configs(), 400.0)
    return [(_bits(r.achieved_ktps), r.bottleneck) for r in res]


def _fresh_caches() -> None:
    clear_resident_cache()
    clear_structure_cache()


def _host_span_names(trace_dir: str) -> set[str]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    assert paths, "the profiler wrote no trace"
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return {e.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("trevor.")}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The control loop and the sweep, once inside a profiler session (with
    a cold kernel cache, so a compile is traced too) and once without."""
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    clear_kernel_cache()
    _fresh_caches()
    jax.profiler.start_trace(trace_dir)
    try:
        traced = dict(control=_control_run(), sweep=_sweep_run())
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    _fresh_caches()
    plain = dict(control=_control_run(), sweep=_sweep_run())
    return dict(traced=traced, plain=plain, names=_host_span_names(trace_dir))


def test_control_loop_is_bitwise_the_same_under_the_profiler(runs):
    traced_events, traced_rows = runs["traced"]["control"]
    plain_events, plain_rows = runs["plain"]["control"]
    assert len(plain_events) == 30
    assert any(e["acted"] for e in plain_events[1:])   # it replanned mid-trace
    assert traced_events == plain_events
    assert traced_rows == plain_rows


def test_sweep_is_bitwise_the_same_under_the_profiler(runs):
    assert runs["traced"]["sweep"] == runs["plain"]["sweep"]


def test_the_profiler_records_only_named_spans(runs):
    names = runs["names"]
    assert names <= set(spans.SPANS), names - set(spans.SPANS)
    expected = {
        spans.EVALUATE, spans.DEDUP, spans.STAGE_STRUCTURE, spans.STAGE_PAD,
        spans.STAGE_STACK, spans.STAGE_H2D, spans.KERNEL_COMPILE,
        spans.KERNEL_DISPATCH, spans.KERNEL_WAIT, spans.STAGE_D2H,
        spans.RESULTS, spans.CONTROL_STEP, spans.CONTROL_FORECAST,
        spans.CONTROL_PLAN, spans.CONTROL_MEASURE, spans.CONTROL_LEARN,
        spans.GC,
    }
    assert expected <= names, expected - names
    assert len(set(spans.SPANS)) == len(spans.SPANS)
    assert all(n.startswith("trevor.") and "#" not in n for n in spans.SPANS)


def test_bytes_h2d_counts_staged_arrays_and_on_a_resident_hit_only_loads():
    configs = _sweep_configs()[:5]
    n_ticks = int(DURATION_S / PARAMS.dt) // PARAMS.sample_every * PARAMS.sample_every

    def run():
        simulate_batch(configs, 400.0, duration_s=DURATION_S, params=PARAMS,
                       devices=1, resident=True, samples="summary")

    sts = [structure_for(c, PARAMS) for c in configs]
    n_inst = bucket_size(max(st.n_inst for st in sts))
    n_cont = bucket_size(max(st.n_cont for st in sts))
    staged = sum(v.nbytes for st in sts
                 for v in pad_structure(st, n_inst, n_cont).values())
    loads_and_seeds = len(configs) * (n_ticks * 4 + 4)   # float32 loads, int32 seeds

    clear_resident_cache()
    clear_transfer_stats()
    run()                                   # fresh: everything is staged
    assert transfer_info()["bytes_h2d"] == staged + loads_and_seeds
    run()                                   # resident hit: loads and seeds only
    assert transfer_info()["bytes_h2d"] == staged + 2 * loads_and_seeds
    clear_transfer_stats()
    assert transfer_info()["bytes_h2d"] == 0
