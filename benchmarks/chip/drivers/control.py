"""Control: one job's ``ControlLoop`` with a ``PredictivePolicy`` and a
Holt-Winters forecaster, stepped back to back over a seeded load curve.

Mix keys: the diurnal curve (``peak_ktps``, ``peak_ratio``, ``period``,
``start_step``, ``jitter``, ``steps``), ``warmup_steps``, ``policy`` (the
policy's and guards' settings) and ``check_steps``.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import check as compare
from chipbench import program, traffic
from chipbench.reference import Row

#: the host span around each call the window makes
SPAN = "control_step"


def load_curve(mix: dict, seed: int) -> np.ndarray:
    """A diurnal load curve (ktps): ``peak_ratio`` between trough and peak
    over ``period`` steps, entered ``start_step`` steps after the trough,
    with seeded multiplicative jitter."""
    n, period = mix["steps"], mix["period"]
    t = np.arange(n) + mix["start_step"]
    day = 0.5 * (1.0 + np.sin(2.0 * np.pi * t / period - np.pi / 2.0))
    base = mix["peak_ktps"] / mix["peak_ratio"]
    curve = base * (1.0 + (mix["peak_ratio"] - 1.0) * day)
    return curve * (1.0 + mix["jitter"] * traffic.rng_for(seed, "control").standard_normal(n))


class Driver:
    """One job's control loop, stepped back to back over a load curve."""

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        from repro.control import (ControlLoop, GuardBands, HoltWintersForecaster,
                                   ModelStore, PredictivePolicy)
        from repro.core import ContainerDim, oracle_models

        self.config = config
        dag = program.program_dag(config["dag"], config["dags"][config["dag"]])
        models = oracle_models(dag, config["physics"]["sm_cost_per_ktuple"])
        self.trace = load_curve(mix, seed)
        p = mix["policy"]
        self.loop = ControlLoop(
            PredictivePolicy(dag, ModelStore(models),
                             preferred_dim=ContainerDim(**config["container"]),
                             n_candidates=p["n_candidates"]),
            guards=GuardBands(headroom=p["headroom"], deadband=p["deadband"]),
            evaluator=program.recording_evaluator(config),
            forecaster=HoltWintersForecaster(season=mix["period"]),
            horizon=p["horizon"],
            saturation_threshold=p["saturation_threshold"],
        )
        self.next = 0
        for _ in range(mix["warmup_steps"]):
            self.loop.step(self.trace[self.next])
            self.next += 1
        self.done: list[tuple] = []

    def window(self, seconds: float) -> dict:
        before = program.counters()
        spans = []
        self.loop.evaluator.calls = self.calls = []
        t_open = time.perf_counter()
        while True:
            # the curve holds whole periods, so it repeats seamlessly
            load = float(self.trace[self.next % len(self.trace)])
            t0 = time.perf_counter()
            with program.annotate(SPAN):
                ev = self.loop.step(load)
            t1 = time.perf_counter()
            self.done.append((load, ev, self.loop.action.config))
            spans.append((t0, t1))
            self.next += 1
            if t1 - t_open >= seconds:
                break
        self.loop.evaluator.calls = None
        window_s = spans[-1][1] - spans[0][0]
        step_ms = np.array([(b - a) * 1e3 for a, b in spans])
        return dict(
            e2e=dict(decision_ms=window_s * 1e3 / len(spans),
                     decision_p90_ms=float(np.percentile(step_ms, 90))),
            counters=dict(program.delta(before, program.counters()), calls=len(spans)),
            spans=spans, attempted=len(spans))

    def release(self) -> None:
        self.loop = None

    def _row(self, cfg, load: float) -> Row:
        return Row(self.config["dag"], cfg.packing, tuple(d.cpus for d in cfg.dims),
                   float(load))

    def check_rows(self, rng: np.random.Generator, n: int) -> list[tuple]:
        """A seeded sample of the window's steps, with what each reported,
        and one of the rows its evaluator scored (candidates at the
        forecast window's loads, and the deployment at the step's load)."""
        pick = sorted(rng.choice(len(self.done), min(n, len(self.done)), replace=False))
        out = []
        for i in pick:
            load, ev, cfg = self.done[i]
            out.append((self._row(cfg, load),
                        dict(achieved=ev.achieved, acted=ev.acted,
                             same_input=ev.load == load)))
        scored = [(cfg, offered if np.isscalar(offered) else offered[j], r)
                  for cfgs, offered, res in self.calls
                  for j, (cfg, r) in enumerate(zip(cfgs, res))]
        pick = sorted(rng.choice(len(scored), min(n, len(scored)), replace=False))
        for i in pick:
            cfg, load, r = scored[i]
            out.append((self._row(cfg, load),
                        dict(achieved=r.achieved_ktps, acted=False,
                             same_input=r.config is cfg)))
        return out


def check(driver: Driver, config: dict, mix: dict, seed: int,
          flows_dtype) -> list[tuple[str, float, float]]:
    """``(name, number, limit)``: the widest achieved-rate gap over a seeded
    sample of steps and of the rows their evaluator scored (a step that
    acted reports its deployment's rate capped at the load), and steps
    answered for another load."""
    pairs = driver.check_rows(traffic.rng_for(seed, "check"), mix["check_steps"])
    refs = compare.score(config, [row for row, _got in pairs], flows_dtype)
    gap = 0.0
    for (row, got), ref in zip(pairs, refs):
        want = min(ref["achieved"], row.offered_ktps) if got["acted"] else ref["achieved"]
        gap = max(gap, compare.gap(got["achieved"], want))
    missing = sum(1 for _row, got in pairs if not got["same_input"])
    return [("achieved_gap", gap, compare.GAP_LIMIT), ("steps_missing", missing, 0)]
