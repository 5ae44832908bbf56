"""Sweep: a closed loop of configuration searches for a new job, each one
``SimulatorEvaluator.evaluate_batch`` on a list of distinct candidates,
decision after decision.  The evaluator shards a batch over every chip of
the machine, as a user's does by default.

Mix keys: ``batch`` (candidates per decision), ``spread`` (each operator's
parallelism within the configuration's ``width`` plus or minus it),
``offered_ktps``, ``max_decisions``, ``warmup_decisions``, ``check_rows``.
"""
from __future__ import annotations

import math
import sys
import time

import numpy as np

from chipbench import check as compare
from chipbench import program, traffic
from chipbench.reference import Row

#: the host span around each call the window makes
SPAN = "evaluate"


def decisions(config: dict, mix: dict, seed: int) -> list[list[tuple]]:
    """Candidate lists: every (parallelism per operator, containers) point of
    the grid ``width ± spread`` per operator by the configuration's container
    range, in a seeded order, cut into decisions of ``batch``, at most
    ``max_decisions`` of them.  Each candidate appears once across warm-up
    and window."""
    dag = config["dags"][config["dag"]]
    w, s = config["width"], mix["spread"]
    k_lo, k_hi = config["containers"]
    axes = [np.arange(w - s, w + s + 1)] * len(dag["nodes"]) + [
        np.arange(k_lo, k_hi + 1)]
    sizes = [len(a) for a in axes]
    total = math.prod(sizes)
    b = mix["batch"]
    count = min(total // b, mix["max_decisions"]) * b
    flat = traffic.rng_for(seed, "sweep").choice(total, count, replace=False)
    points = np.stack(np.unravel_index(flat, sizes), axis=1)
    cands = [(tuple(int(axes[d][i]) for d, i in enumerate(p[:-1])),
              int(axes[-1][p[-1]])) for p in points]
    return [cands[j:j + b] for j in range(0, count, b)]


class Driver:
    """Closed loop of candidate sweeps; all candidates distinct."""

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        from repro.core.dag import Configuration, ContainerDim

        self.config = config
        spec = config["dags"][config["dag"]]
        self.names = [n["name"] for n in spec["nodes"]]
        dag = program.program_dag(config["dag"], spec)
        dim = ContainerDim(**config["container"])
        self.points = decisions(config, mix, seed)
        self.batches = [
            [Configuration(dag=dag, packing=program.round_robin(self.names, par, k),
                           dims=(dim,) * k) for par, k in d]
            for d in self.points]
        self.offered = float(mix["offered_ktps"])
        self.evaluator = program.evaluator(config)
        self.next = 0
        for _ in range(mix["warmup_decisions"]):
            self.evaluator.evaluate_batch(self.batches[self.next], self.offered)
            self.next += 1
        self.done: list[tuple[int, list]] = []

    def window(self, seconds: float) -> dict:
        before = program.counters()
        spans = []
        t_open = time.perf_counter()
        while True:
            if self.next >= len(self.batches):
                raise RuntimeError(
                    f"the sweep's {len(self.batches)} decisions of distinct "
                    "candidates ran out inside the window")
            i = self.next
            t0 = time.perf_counter()
            with program.annotate(SPAN):
                res = self.evaluator.evaluate_batch(self.batches[i], self.offered)
            t1 = time.perf_counter()
            self.done.append((i, res))
            spans.append((t0, t1))
            print(f"decision {len(spans)}: {len(res)} candidates in "
                  f"{t1 - t0:.6f} s", file=sys.stderr, flush=True)
            self.next += 1
            if t1 - t_open >= seconds:
                break
        window_s = spans[-1][1] - spans[0][0]
        n = sum(len(r) for _i, r in self.done)
        return dict(
            e2e=dict(candidates_per_s=n / window_s),
            counters=dict(program.delta(before, program.counters()),
                          calls=len(spans), n_ticks=self._n_ticks()),
            spans=spans, attempted=n)

    def _n_ticks(self) -> int:
        ph = self.config["physics"]
        return int(self.config["horizon_s"] / ph["dt"]) // ph["sample_every"] * ph["sample_every"]

    def release(self) -> None:
        self.evaluator = None
        self.batches = None

    def check_rows(self, rng: np.random.Generator, n: int) -> list[tuple]:
        """(reference row, what the program answered) for a seeded sample of
        the window's candidates."""
        flat = [(i, j) for i, res in self.done for j in range(len(res))]
        pick = sorted(rng.choice(len(flat), min(n, len(flat)), replace=False))
        done = dict(self.done)
        out = []
        for f in pick:
            i, j = flat[f]
            par, k = self.points[i][j]
            row = Row(self.config["dag"], program.round_robin(self.names, par, k),
                      (self.config["container"]["cpus"],) * k, self.offered)
            r = done[i][j]
            out.append((row, dict(achieved=r.achieved_ktps, bottleneck=r.bottleneck,
                                  same_input=r.config.packing == row.packing)))
        return out


def check(driver: Driver, config: dict, mix: dict, seed: int,
          flows_dtype) -> list[tuple[str, float, float]]:
    """``(name, number, limit)``: the widest achieved-rate gap over a seeded
    sample of the window's candidates, bottlenecks that differ where the
    reference's is no tie, and candidates answered for another input or
    not at all."""
    pairs = driver.check_rows(traffic.rng_for(seed, "check"), mix["check_rows"])
    gap, wrong = compare.compare_scores(config, pairs, flows_dtype)
    missing = sum(1 for _row, got in pairs
                  if not got["same_input"] or not got["achieved"] > 0.0)
    return [("achieved_gap", gap, compare.GAP_LIMIT),
            ("bottleneck_mismatches", wrong, 0),
            ("answers_missing", missing, 0)]
