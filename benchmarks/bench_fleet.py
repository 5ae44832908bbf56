"""Fleet layer: device-sharded candidate sweeps, joint scheduling latency,
warm-vs-cold container churn, and preemption time-to-fit.

Four questions:

* does sharding ``simulate_batch`` across devices pay on a wide candidate
  sweep (the fleet scheduler's joint-scoring shape)?  A 128-candidate
  sweep is timed on the single-device vmap path and the pmap-sharded path.
  Sharding needs >1 device.  On a CPU host that sees one device the
  measurement re-execs itself in a CPU-only subprocess with
  ``--xla_force_host_platform_device_count=8`` (the multi-device-smoke CI
  pattern) and labels its rows as CPU; on a one-chip accelerator host the
  sharded row is ``not measured`` (a child process cannot share the chip
  this process holds);
* what does one joint 3-tenant scheduling round cost end to end
  (budget-constrained allocation + bin-packing + one batched scoring
  call)?
* how many containers does a replan actually churn?  The same 3-tenant
  demand trace is scheduled warm (each round handed the previous plan) and
  cold (every round repacks from an empty inventory): moves-per-replan
  must show a strict reduction for warm scheduling;
* how long does the defragment-then-preempt ladder take to admit a
  guaranteed tenant onto a fragmented cluster (time-to-fit), and how many
  best-effort containers does it cost?
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import EXTRAS, emit, timed

N_CANDIDATES = 128
DURATION_S = 2.0
_SWEEP_ENV = "BENCH_FLEET_SWEEP_CHILD"


def _sweep_times() -> dict:
    """Time the 128-candidate sweep unsharded vs sharded (current process)."""
    import jax

    from repro.core import ContainerDim, round_robin_configuration
    from repro.streams import SimParams, simulate_batch, deep_pipeline

    # the fleet sweep shape: a wide candidate batch over a DAG big enough to
    # land in the 32-instance bucket (real per-candidate compute)
    dag = deep_pipeline()
    dim = ContainerDim(cpus=3.0, mem_mb=4096.0)
    cfgs = [
        round_robin_configuration(
            dag,
            {n: 1 + (i + j) % 3 for j, n in enumerate(dag.node_names)},
            3 + i % 5,
            dim,
        )
        for i in range(N_CANDIDATES)
    ]
    params = SimParams()

    def run(devices):
        return simulate_batch(
            cfgs, 1e6, duration_s=DURATION_S, params=params, devices=devices
        )

    _, us_single = timed(run, 1, repeats=3, warmup=1)
    us_sharded = None
    if jax.local_device_count() > 1:
        _, us_sharded = timed(run, None, repeats=3, warmup=1)
    return {
        "platform": jax.default_backend(),
        "devices": jax.local_device_count(),
        "us_single": us_single,
        "us_sharded": us_sharded,
    }


def _sweep_times_forced_multidevice() -> dict:
    """Re-exec the sweep on the CPU with 8 fake host devices (subprocess:
    XLA device count is fixed at backend init, so it cannot change
    in-process).  Only for a CPU parent: the child is pinned to the CPU."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env[_SWEEP_ENV] = "1"
    env.setdefault("PYTHONPATH", "src")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_fleet"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(f"forced-multidevice sweep failed:\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run() -> dict:
    import jax

    if jax.local_device_count() > 1 or jax.default_backend() != "cpu":
        sweep = _sweep_times()
    else:
        sweep = _sweep_times_forced_multidevice()
    where = f"platform={sweep['platform']}"
    emit(
        f"simulate_batch_{N_CANDIDATES}cand_single_device",
        sweep["us_single"],
        f"{where};devices=1;candidates={N_CANDIDATES}",
    )
    if sweep["us_sharded"] is None:
        print(f"simulate_batch_{N_CANDIDATES}cand_sharded,not measured,"
              f"{where};devices=1")
    else:
        speedup = sweep["us_single"] / max(sweep["us_sharded"], 1e-9)
        emit(
            f"simulate_batch_{N_CANDIDATES}cand_sharded",
            sweep["us_sharded"],
            f"{where};devices={sweep['devices']};speedup={speedup:.2f}x_vs_vmap",
        )

    # one joint 3-tenant scheduling round, end to end
    from repro.control import GuardBands
    from repro.core import ContainerDim, oracle_models
    from repro.fleet import Cluster, FleetScheduler, MachineClass, QosTier, TenantSpec
    from repro.streams import (
        SimParams, SimulatorEvaluator, adanalytics, diamond, wordcount,
    )

    params = SimParams()
    dim = ContainerDim(cpus=3.0, mem_mb=4096.0)

    def tenant(name, dag, qos, target):
        return TenantSpec(
            name=name, dag=dag, target_ktps=target, qos=qos,
            models=oracle_models(dag, params.sm_cost_per_ktuple),
            guards=GuardBands(), preferred_dim=dim,
        )

    tenants = [
        (tenant("ads", adanalytics(), QosTier.GUARANTEED, 400.0), 480.0),
        (tenant("clicks", diamond(), QosTier.STANDARD, 250.0), 300.0),
        (tenant("wc", wordcount(), QosTier.BEST_EFFORT, 800.0), 960.0),
    ]
    cluster = Cluster([MachineClass("std", count=8, cores=4.0, mem_mb=16384.0)])
    sched = FleetScheduler(
        cluster, SimulatorEvaluator(params=params, duration_s=2.0)
    )
    plan, us_sched = timed(sched.schedule, tenants, repeats=3, warmup=1)
    emit(
        "fleet_schedule_3tenants",
        us_sched,
        f"cores_used={plan.cores_used:.0f}of{plan.cores_total:.0f};"
        f"degraded={sum(a.degraded for a in plan.allocations)}",
    )
    # the per-phase wall-time breakdown of the last round, as emitted rows
    # AND as a structured extras payload in the BENCH JSON artifact (the
    # perf trajectory can then attribute a regression to a phase)
    total_s = max(plan.timings.get("total", 0.0), 1e-12)
    for phase in ("restore", "allocate", "pack", "score", "repair"):
        secs = plan.timings.get(phase, 0.0)
        emit(
            f"fleet_schedule_phase_{phase}",
            secs * 1e6,
            f"share={secs / total_s * 100:.0f}pct",
        )
    EXTRAS["fleet_schedule_3tenants_timings"] = {
        k: round(v * 1e6, 1) for k, v in plan.timings.items()
    }

    # -- moves-per-replan: warm vs cold on the 3-tenant scenario ----------
    # the same demand trace (the guaranteed tenant breathing up and down)
    # is replanned round by round; warm scheduling carries the previous
    # plan, cold repacks from an empty inventory every time
    specs = [t for t, _d in tenants]
    trace = [
        {"ads": 480.0, "clicks": 300.0, "wc": 960.0},
        {"ads": 720.0, "clicks": 300.0, "wc": 960.0},
        {"ads": 1100.0, "clicks": 360.0, "wc": 960.0},
        {"ads": 720.0, "clicks": 300.0, "wc": 1200.0},
        {"ads": 480.0, "clicks": 300.0, "wc": 960.0},
        {"ads": 480.0, "clicks": 300.0, "wc": 960.0},
    ]
    pack_sched = FleetScheduler(cluster)          # packing-only: no scoring

    def replay(warm: bool) -> int:
        prev = None
        total = 0
        for loads in trace:
            p = pack_sched.schedule(
                [(s, loads[s.name]) for s in specs],
                previous=prev if warm else None,
            )
            total += p.total_moves
            prev = p
        return total

    warm_moves, us_warm = timed(replay, True, repeats=3, warmup=1)
    cold_moves, us_cold = timed(replay, False, repeats=3, warmup=1)
    n = len(trace)
    emit(
        "fleet_moves_per_replan_warm",
        us_warm / n,
        f"moves_per_replan={warm_moves / n:.2f};steps={n}",
    )
    emit(
        "fleet_moves_per_replan_cold",
        us_cold / n,
        f"moves_per_replan={cold_moves / n:.2f};"
        f"warm_reduction={(1 - warm_moves / max(cold_moves, 1)) * 100:.0f}pct",
    )
    assert warm_moves < cold_moves, (
        f"warm scheduling must strictly reduce container moves "
        f"(warm={warm_moves}, cold={cold_moves})"
    )

    # -- time-to-fit: preemption + defragmentation latency ----------------
    # best-effort residents hold one 3-cpu container on EVERY host of a
    # 4-host cluster; the arriving guaranteed tenant fits nowhere until
    # the ladder evicts/compacts
    from repro.core import round_robin_configuration
    from repro.fleet import FleetPlan, Placement, TenantAllocation

    frag_cluster = Cluster(
        [MachineClass("std", count=4, cores=4.0, mem_mb=16384.0)]
    )
    frag_sched = FleetScheduler(frag_cluster)
    be_spec = tenant("wc", wordcount(), QosTier.BEST_EFFORT, 400.0)
    gold_spec = tenant("ads", wordcount(), QosTier.GUARANTEED, 400.0)
    be_cfg = round_robin_configuration(be_spec.dag, {"W": 1, "C": 1}, 4, dim)
    prev = FleetPlan(
        allocations=[TenantAllocation(
            tenant="wc", qos=QosTier.BEST_EFFORT, requested_ktps=400.0,
            planned_ktps=400.0, config=be_cfg,
            placement=Placement(
                host_of=(0, 1, 2, 3),
                host_names=("std/0", "std/1", "std/2", "std/3"),
                min_speed=1.0,
            ),
            cpus=12.0, predicted_ktps=400.0, bottleneck=None,
            shortfall_ktps=0.0, degraded=False,
        )],
        cores_total=frag_cluster.total_cores(), cores_used=12.0,
    )
    frag_demands = [(gold_spec, 400.0), (be_spec, 400.0)]
    frag_plan, us_fit = timed(
        frag_sched.schedule, frag_demands, previous=prev, repeats=3, warmup=1
    )
    assert frag_plan.allocation("ads").admitted
    emit(
        "fleet_preemption_time_to_fit",
        us_fit,
        f"evictions={sum(frag_plan.evictions.values())};"
        f"moves={frag_plan.total_moves};admitted=1",
    )
    return {"sweep": sweep, "plan": plan}


if __name__ == "__main__":
    if os.environ.get(_SWEEP_ENV):
        print(json.dumps(_sweep_times()))
    else:
        run()
