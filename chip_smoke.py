"""Bring-up smoke run of Trevor's scoring path on a TPU.

Drives the main path once, through the entry points a user calls, at the
sizes users run, and checks the answers against a CPU reference:

1. candidate sweeps through ``SimulatorEvaluator.evaluate_batch`` (the
   evaluator's 8 s horizon, summary mode, 512 distinct candidates each):
   wordcount at 128-wide operators (512-instance bucket) and deep_pipeline
   at parallelism ~60 (~25k edges: the sparse tick on a CPU, while a TPU
   runs every tick dense);
2. a 1,000-tenant fleet over 8 (workload, target) archetypes through
   ``FleetScheduler``: one cold schedule, one settle round, three warm
   rounds with 5% churn (at most 2 new kernel compiles), and a replan with
   unchanged demand (0 container moves);
3. one ``ControlLoop`` trace: a deep_pipeline job, 12 diurnal steps,
   ``PredictivePolicy`` with a Holt-Winters forecaster;
4. 32 seeded rows of each sweep recomputed in a child process pinned to the
   CPU: ``achieved_ktps`` must agree within 1% relative on every row, and
   the bottleneck must match on every row whose decision is not a tie.

``--chips 4`` runs only the multi-chip path, the sharded joint score: both
phase-1 sweeps through ``simulate_batch(devices=4)`` and again with
``devices=1`` in the same process, agreeing within the same 1%, with the
staged shards on four distinct devices.

The script fails when JAX finds no TPU; there is no CPU fallback.  Each
phase prints one JSON line; ``smoke_wall_s`` is the wall time of a smoke
run, not a benchmark.  The last line of standard output is one JSON object
naming the device.  Candidate data is made from ``--seed``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on four chips
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

# the program's own modules, from the checkout this script sits in
import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import enable_compile_cache  # noqa: E402
from repro.control import (  # noqa: E402
    ControlLoop, GuardBands, HoltWintersForecaster, ModelStore,
    PredictivePolicy, make_trace,
)
from repro.core import (  # noqa: E402
    ContainerDim, allocate, oracle_models, round_robin_configuration,
)
from repro.fleet import (  # noqa: E402
    Cluster, FleetScheduler, MachineClass, QosTier, TenantSpec,
)
from repro.streams import (  # noqa: E402
    OVERLOAD_KTPS, WORKLOADS, SimParams, SimulatorEvaluator,
    clear_transfer_stats, deep_pipeline, kernel_cache_info, simulate_batch,
    transfer_info,
)

#: relative bound on ``achieved_ktps`` between the chip and the reference
REL_TOL = 0.01
#: a bottleneck decision whose competing quantities lie within this
#: relative margin of each other is a tie and is not compared
TIE_MARGIN = 0.01
#: rows of each sweep recomputed by the CPU reference
REFERENCE_ROWS = 32

#: Phase-1 sweeps: a shuffle-heavy and a pipeline DAG (dense and sparse
#: tick on the CPU reference) at the widths users run.  ``width`` is the
#: per-operator parallelism the candidates vary around.
SWEEPS = {
    "wordcount": dict(workload="wordcount", width=128, n=512),
    "deep_pipeline": dict(workload="deep_pipeline", width=60, n=512),
}

#: Fleet archetypes: (workload, base target ktps, QoS tier name).
ARCHETYPES = (
    ("wordcount", 40.0, "GUARANTEED"),
    ("wordcount", 160.0, "STANDARD"),
    ("adanalytics", 120.0, "GUARANTEED"),
    ("adanalytics", 300.0, "STANDARD"),
    ("mobile_analytics", 150.0, "STANDARD"),
    ("diamond", 100.0, "STANDARD"),
    ("diamond", 250.0, "BEST_EFFORT"),
    ("deep_pipeline", 150.0, "BEST_EFFORT"),
)
N_TENANTS = 1000
CHURN = 0.05
WARM_ROUNDS = 3
CONTROL_STEPS = 12
DIM = ContainerDim(cpus=3.0, mem_mb=4096.0)


def candidates(workload: str, width: int, n: int, seed: int) -> list:
    """``n`` distinct round-robin configurations of ``workload``: every
    operator's parallelism within ``width ± width/16`` and 3/4·width to
    width containers, drawn from ``seed``."""
    dag = WORKLOADS[workload]()
    rng = np.random.default_rng(seed)
    spread = max(1, width // 16)
    k_lo = max(1, 3 * width // 4)
    if (2 * spread) ** len(dag.node_names) * (width + 1 - k_lo) < n:
        raise ValueError(f"width {width} has fewer than {n} distinct candidates")
    seen: set = set()
    out = []
    while len(out) < n:
        par = tuple(
            int(p) for p in rng.integers(width - spread, width + spread,
                                         size=len(dag.node_names))
        )
        k = int(rng.integers(k_lo, width + 1))
        if (par, k) in seen:
            continue
        seen.add((par, k))
        out.append(round_robin_configuration(
            dag, dict(zip(dag.node_names, par)), k, DIM
        ))
    return out


def decision_margin(sim, saturation: float = 0.8, sm_threshold: float = 0.9) -> float:
    """Smallest relative gap among the quantities the bottleneck decision
    of one ``SimResult`` compares (at its default thresholds): the two
    busiest nodes, the busiest node against the saturation threshold, and
    the stream manager against both."""
    st = sim.structure
    caputil = np.asarray(sim.summary["caputil_half_mean"], np.float64)
    node_max = np.zeros(len(st.node_names))
    np.maximum.at(node_max, st.node_of, caputil)
    top = np.sort(node_max)[::-1]
    sm_half = np.asarray(sim.summary["sm_half_mean"])
    sm = float(sm_half.max()) if sm_half.size else 0.0
    gaps = [abs(top[0] - saturation), abs(sm - sm_threshold), abs(sm - top[0])]
    if top.size > 1:
        gaps.append(top[0] - top[1])
    return float(min(gaps) / max(top[0], sm, 1e-9))


def _row(sim, bottleneck) -> dict:
    return dict(achieved=float(sim.achieved_ktps), bottleneck=bottleneck,
                margin=decision_margin(sim))


def _rows(results) -> list[dict]:
    """Rows of ``EvalResult``s, as the evaluator labelled them."""
    return [_row(r.sim, r.bottleneck) for r in results]


def _emit(phase: str, t0: float, compiles0: int, **fields) -> None:
    """One phase line: its fields, kernel compiles since ``compiles0`` and
    wall seconds since ``t0``."""
    print(json.dumps(dict(
        phase=phase,
        **fields,
        kernel_compiles=kernel_cache_info()["misses"] - compiles0,
        smoke_wall_s=time.perf_counter() - t0,
    )), flush=True)


def _check_finite(name: str, rows: list[dict]) -> None:
    bad = [i for i, r in enumerate(rows)
           if not (math.isfinite(r["achieved"]) and r["achieved"] > 0.0)]
    if bad:
        raise AssertionError(f"{name}: non-finite or zero achieved_ktps at rows {bad[:8]}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def sweep_phase(name: str, spec: dict, seed: int) -> list[dict]:
    """Phase 1: score one sweep through the evaluator; per-row results."""
    cfgs = candidates(spec["workload"], spec["width"], spec["n"], seed)
    compiles0 = kernel_cache_info()["misses"]
    t0 = time.perf_counter()
    rows = _rows(SimulatorEvaluator().evaluate_batch(cfgs))
    _check_finite(name, rows)
    backend = kernel_cache_info()["entries"][-1]["backend"]
    _emit(f"sweep_{name}", t0, compiles0, rows=len(rows), tick_kernel=backend,
          max_instances=max(len(c.instances()) for c in cfgs))
    return rows


def _fleet(n_tenants: int):
    """Tenants over the archetypes, and a cluster that fits them at base."""
    sm_cost = SimParams().sm_cost_per_ktuple
    kinds = []
    for workload, target, qos in ARCHETYPES:
        dag = WORKLOADS[workload]()
        models = oracle_models(dag, sm_cost)
        cpus = allocate(dag, models, target, preferred_dim=DIM).total_cpus
        kinds.append((dag, models, target, QosTier[qos], cpus))
    tenants, need = [], 0.0
    for i in range(n_tenants):
        dag, models, target, qos, cpus = kinds[i % len(kinds)]
        tenants.append(TenantSpec(
            name=f"t{i:04d}", dag=dag, target_ktps=target, qos=qos,
            models=models, guards=GuardBands(), preferred_dim=DIM,
        ))
        need += cpus
    # the closed-form footprint at base demand plus 30% headroom, on
    # 16-core hosts: tight enough to be honest, never shedding at base
    hosts = max(4, math.ceil(need * 1.3 / 16))
    cluster = Cluster([MachineClass("std", count=hosts, cores=16.0, mem_mb=65536.0)])
    return tenants, cluster


def fleet_phase(n_tenants: int, seed: int) -> None:
    """Phase 2: cold, settle, warm churned rounds and a no-change replan."""
    tenants, cluster = _fleet(n_tenants)
    sched = FleetScheduler(cluster, evaluator=SimulatorEvaluator(sticky_batch=True))
    demand = {t.name: t.target_ktps for t in tenants}

    def demands():
        return [(t, demand[t.name]) for t in tenants]

    compiles0 = kernel_cache_info()["misses"]
    t0 = time.perf_counter()
    plan = sched.schedule(demands())
    shed = [a.tenant for a in plan.allocations if not a.admitted or a.degraded]
    if shed:
        raise AssertionError(f"{len(shed)} tenants shed at base demand: {shed[:5]}")
    _emit("fleet_cold", t0, compiles0, tenants=n_tenants,
          hosts=len(cluster.inventory()), eval_rows=plan.eval_rows,
          moves=plan.total_moves)

    compiles0 = kernel_cache_info()["misses"]
    t0 = time.perf_counter()
    plan = sched.schedule(demands(), previous=plan)
    _emit("fleet_settle", t0, compiles0, tenants=n_tenants,
          touched=len(plan.touched), moves=plan.total_moves)

    rng = np.random.default_rng(seed)
    n_churn = max(1, int(n_tenants * CHURN))
    compiles0 = kernel_cache_info()["misses"]
    t0 = time.perf_counter()
    touched = moves = 0
    for _ in range(WARM_ROUNDS):
        for i in rng.choice(n_tenants, n_churn, replace=False):
            t = tenants[int(i)]
            demand[t.name] = t.target_ktps * float(rng.uniform(0.8, 1.25))
        plan = sched.schedule(demands(), previous=plan)
        touched += len(plan.touched)
        moves += plan.total_moves
    warm_compiles = kernel_cache_info()["misses"] - compiles0
    _emit("fleet_warm", t0, compiles0, tenants=n_tenants, rounds=WARM_ROUNDS,
          churned_per_round=n_churn, touched=touched, moves=moves)
    if warm_compiles > 2:
        raise AssertionError(f"warm rounds compiled {warm_compiles} kernels (> 2)")

    compiles0 = kernel_cache_info()["misses"]
    t0 = time.perf_counter()
    plan = sched.schedule(demands(), previous=plan)
    _emit("fleet_no_change", t0, compiles0, tenants=n_tenants,
          touched=len(plan.touched), moves=plan.total_moves)
    if plan.total_moves != 0:
        raise AssertionError(f"unchanged demand moved {plan.total_moves} containers")


def control_phase(steps: int, seed: int) -> None:
    """Phase 3: one predictive ControlLoop trace over a deep_pipeline job,
    driven as ``examples/autoscale_stream.py`` drives it."""
    dag = deep_pipeline()
    models = oracle_models(dag, SimParams().sm_cost_per_ktuple)
    trace = make_trace("diurnal", steps, base_ktps=300.0, seed=seed)
    threshold = 0.95
    loop = ControlLoop(
        PredictivePolicy(dag, ModelStore(models), preferred_dim=DIM),
        guards=GuardBands(headroom=1.0, deadband=0.2),
        evaluator=SimulatorEvaluator(),
        forecaster=HoltWintersForecaster(season=max(2, steps // 2)),
        horizon=4,
        saturation_threshold=threshold,
    )
    compiles0 = kernel_cache_info()["misses"]
    t0 = time.perf_counter()
    loop.run(trace)
    events = loop.events
    if len(events) != steps or not all(math.isfinite(e.achieved) for e in events):
        raise AssertionError(f"control loop logged {len(events)} events for {steps} steps")
    _emit("control_loop", t0, compiles0, steps=steps,
          acted=sum(e.acted for e in events),
          breach_steps=sum(e.achieved < threshold * e.load for e in events))


def compare(name: str, chip: list[dict], ref: list[dict]) -> dict:
    """Check chip rows against reference rows; raise on any miss."""
    rel = [abs(c["achieved"] - r["achieved"]) / max(abs(r["achieved"]), 1e-12)
           for c, r in zip(chip, ref, strict=True)]
    decided = [i for i, (c, r) in enumerate(zip(chip, ref))
               if min(c["margin"], r["margin"]) >= TIE_MARGIN]
    wrong = [i for i in decided if chip[i]["bottleneck"] != ref[i]["bottleneck"]]
    over = [i for i, x in enumerate(rel) if x > REL_TOL]
    if over or wrong:
        raise AssertionError(
            f"{name}: achieved_ktps off by > {REL_TOL:.0%} at rows {over} "
            f"(max {max(rel):.3e}); bottleneck differs at rows {wrong}: "
            f"{[(chip[i]['bottleneck'], ref[i]['bottleneck']) for i in wrong]}"
        )
    return dict(rows=len(rel), max_rel_diff=max(rel), bottleneck_rows=len(decided))


def reference_phase(sweeps: dict, chip_rows: dict, seed: int) -> None:
    """Phase 4: recompute seeded rows in a CPU-only child and compare."""
    rng = np.random.default_rng(seed + 1)
    spec = {
        name: dict(sweep, rows=sorted(int(i) for i in rng.choice(
            sweep["n"], min(REFERENCE_ROWS, sweep["n"]), replace=False)))
        for name, sweep in sweeps.items()
    }
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    compiles0 = kernel_cache_info()["misses"]
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--reference",
         json.dumps(spec), "--seed", str(seed)],
        capture_output=True, text=True, timeout=900, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(f"CPU reference failed:\n{out.stderr[-4000:]}")
    ref = json.loads(out.stdout.strip().splitlines()[-1])
    result = {
        name: compare(name, [chip_rows[name][i] for i in s["rows"]], ref[name])
        for name, s in spec.items()
    }
    _emit("reference_cpu", t0, compiles0, reference=result)


def reference_child(spec: dict, seed: int) -> None:
    """The CPU side of phase 4 (runs in the child; never touches a chip)."""
    if jax.default_backend() != "cpu":
        raise RuntimeError("the reference must run on the CPU backend")
    out = {}
    for name, s in spec.items():
        cfgs = candidates(s["workload"], s["width"], s["n"], seed)
        out[name] = _rows(SimulatorEvaluator().evaluate_batch(
            [cfgs[i] for i in s["rows"]]))
    print(json.dumps(out))


def sharded_phase(sweeps: dict, seed: int, n_devices: int) -> None:
    """``--chips N``: each sweep sharded over N devices against one device."""
    horizon_s = SimulatorEvaluator().duration_s
    for name, spec in sweeps.items():
        cfgs = candidates(spec["workload"], spec["width"], spec["n"], seed)
        compiles0 = kernel_cache_info()["misses"]
        t0 = time.perf_counter()
        clear_transfer_stats()
        sharded = simulate_batch(cfgs, OVERLOAD_KTPS, duration_s=horizon_s,
                                 devices=n_devices, samples="summary")
        staged = transfer_info()["staged_devices"]
        single = simulate_batch(cfgs, OVERLOAD_KTPS, duration_s=horizon_s,
                                devices=1, samples="summary")
        if staged != n_devices:
            raise AssertionError(f"{name}: shards staged on {staged} devices, "
                                 f"not {n_devices}")
        rows = {
            k: [_row(r, r.bottleneck_node()) for r in res]
            for k, res in (("sharded", sharded), ("single", single))
        }
        _check_finite(name, rows["sharded"])
        _emit(f"sharded_{name}", t0, compiles0, devices=staged,
              compared=compare(name, rows["sharded"], rows["single"]))


class CompileStats:
    """Backend compile seconds and persistent-cache hits of this process,
    from JAX's own monitoring events."""

    def __init__(self, cache_dir: str) -> None:
        self.cache_dir = cache_dir
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return dict(cache_dir=self.cache_dir, backend_compiles=self.compiles,
                    compile_s=self.compile_s, cache_hits=self.cache_hits,
                    cache_misses=self.cache_misses)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reference", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.reference is not None:
        reference_child(json.loads(args.reference), args.seed)
        return 0

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this run measures nothing", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2

    compile_stats = CompileStats(enable_compile_cache())
    if args.chips > 1:
        sharded_phase(SWEEPS, args.seed, args.chips)
    else:
        chip_rows = {name: sweep_phase(name, spec, args.seed)
                     for name, spec in SWEEPS.items()}
        fleet_phase(N_TENANTS, args.seed)
        control_phase(CONTROL_STEPS, args.seed)
        reference_phase(SWEEPS, chip_rows, args.seed)
    print(json.dumps(dict(phase="compile", **compile_stats.snapshot())))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
