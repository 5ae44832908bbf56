"""Fleet scheduling at production scale: incremental replanning (touched
sets), candidate-set pruning, move budgets, eviction grace, sticky batch
bucketing / structure memoization, and tenant-sharded joint scoring."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.control import GuardBands
from repro.core import (
    ContainerDim,
    minimal_footprint,
    oracle_models,
    round_robin_configuration,
)
from repro.fleet import (
    Cluster,
    FleetLoop,
    FleetScheduler,
    MachineClass,
    QosTier,
    TenantSpec,
)
from repro.streams import (
    SimParams,
    SimulatorEvaluator,
    batch_bucket_size,
    clear_structure_cache,
    kernel_cache_info,
    simulate_batch,
    structure_cache_info,
    wordcount,
)

PARAMS = SimParams()
DIM = ContainerDim(cpus=3.0, mem_mb=4096.0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tenant(name, qos=QosTier.STANDARD, target=40.0):
    dag = wordcount()
    return TenantSpec(
        name=name, dag=dag, target_ktps=target, qos=qos,
        models=oracle_models(dag, PARAMS.sm_cost_per_ktuple),
        guards=GuardBands(headroom=1.2, deadband=0.15), preferred_dim=DIM,
    )


def _cluster(hosts=30, cores=16.0):
    return Cluster(
        [MachineClass("std", count=hosts, cores=cores, mem_mb=65536.0)]
    )


def _identical(a, b):
    return (
        a.tenant == b.tenant
        and a.config == b.config
        and (a.placement.host_names if a.placement else None)
            == (b.placement.host_names if b.placement else None)
        and a.planned_ktps == b.planned_ktps
        and a.predicted_ktps == b.predicted_ktps
        and a.cpus == b.cpus
    )


# ---------------------------------------------------------------------------
# Incremental replanning: the touched set
# ---------------------------------------------------------------------------


def test_noop_incremental_replan_is_identical_and_empty_touched():
    sched = FleetScheduler(_cluster())
    demands = [(_tenant(f"t{i}"), 40.0 + i) for i in range(8)]
    p1 = sched.schedule(demands)
    p2 = sched.schedule(demands, previous=p1)
    assert p2.touched == () and p2.deferred == ()
    assert p2.total_moves == 0
    assert all(_identical(a, b) for a, b in zip(p1.allocations, p2.allocations))


def test_touched_set_replans_only_changed_tenants():
    sched = FleetScheduler(_cluster())
    demands = [(_tenant(f"t{i}"), 40.0) for i in range(10)]
    p1 = sched.schedule(demands)
    p1 = sched.schedule(demands, previous=p1)      # settle
    changed = list(demands)
    changed[4] = (demands[4][0], 120.0)
    p2 = sched.schedule(changed, previous=p1)
    assert p2.touched == ("t4",)
    for a, b in zip(p1.allocations, p2.allocations):
        if a.tenant != "t4":
            assert _identical(a, b) and b.moves == 0


def test_window_change_touches_tenant():
    sched = FleetScheduler(_cluster())
    demands = [(_tenant(f"t{i}"), 40.0) for i in range(4)]
    p1 = sched.schedule(demands, windows={"t1": [40.0, 44.0]})
    p1 = sched.schedule(demands, windows={"t1": [40.0, 44.0]}, previous=p1)
    assert p1.touched == ()
    p2 = sched.schedule(demands, windows={"t1": [40.0, 52.0]}, previous=p1)
    assert p2.touched == ("t1",)


def test_incremental_off_replans_everyone():
    sched = FleetScheduler(_cluster(), incremental=False)
    demands = [(_tenant(f"t{i}"), 40.0) for i in range(5)]
    p1 = sched.schedule(demands)
    p2 = sched.schedule(demands, previous=p1)
    assert sorted(p2.touched) == [f"t{i}" for i in range(5)]
    assert p2.total_moves == 0                     # warm placement still holds


def test_noop_incremental_replan_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(
        targets=st.lists(
            st.floats(min_value=20.0, max_value=300.0),
            min_size=1, max_size=12,
        ),
        qos=st.lists(st.sampled_from(list(QosTier)), min_size=12, max_size=12),
    )
    def check(targets, qos):
        sched = FleetScheduler(_cluster(hosts=40))
        demands = [
            (_tenant(f"t{i:02d}", qos=qos[i]), t)
            for i, t in enumerate(targets)
        ]
        p1 = sched.schedule(demands)
        p1 = sched.schedule(demands, previous=p1)  # settle any churn
        p2 = sched.schedule(demands, previous=p1)
        assert p2.touched == ()
        assert p2.total_moves == 0
        assert all(
            _identical(a, b) for a, b in zip(p1.allocations, p2.allocations)
        )

    check()


# ---------------------------------------------------------------------------
# Move budgets
# ---------------------------------------------------------------------------


def _scale_up_scenario(n=8, budget=3):
    cluster = _cluster(hosts=40)
    tenants = [_tenant(f"t{i:02d}") for i in range(n)]
    small = [(t, 60.0) for t in tenants]
    big = [(t, 400.0) for t in tenants]            # forces a second container
    return cluster, tenants, small, big, budget


def test_move_budget_caps_moves_and_converges_within_ceil_rounds():
    cluster, _tenants, small, big, budget = _scale_up_scenario()
    ref = FleetScheduler(cluster)
    r = ref.schedule(small)
    unbudgeted = ref.schedule(big, previous=r)
    need = unbudgeted.total_moves
    assert need > budget                           # the budget actually binds

    sched = FleetScheduler(cluster, move_budget=budget)
    q = sched.schedule(small)
    rounds = 0
    while True:
        q = sched.schedule(big, previous=q)
        rounds += 1
        assert q.total_moves <= budget
        if not q.deferred:
            break
        assert rounds < 50
    assert rounds <= -(-need // budget)            # ceil(moves / budget)
    for a, b in zip(q.allocations, unbudgeted.allocations):
        assert a.config == b.config and a.planned_ktps == b.planned_ktps


def test_move_budget_defers_carry_previous_deployment():
    cluster, _tenants, small, big, _b = _scale_up_scenario(budget=2)
    sched = FleetScheduler(cluster, move_budget=2)
    p1 = sched.schedule(small)
    p2 = sched.schedule(big, previous=p1)
    assert p2.deferred
    for name in p2.deferred:
        a = p2.allocation(name)
        b = p1.allocation(name)
        assert a.deferred and a.moves == 0
        assert a.config == b.config                # previous deployment kept
        assert a.requested_ktps == 400.0           # but judged at new demand
        assert a.shortfall_ktps > 0.0


def test_move_budget_zero_defers_all_voluntary_moves():
    cluster, _tenants, small, big, _b = _scale_up_scenario(budget=0)
    sched = FleetScheduler(cluster, move_budget=0)
    p1 = sched.schedule(small)
    p2 = sched.schedule(big, previous=p1)
    assert p2.total_moves == 0
    assert sorted(p2.deferred) == sorted(a.tenant for a in p1.allocations)


def test_move_budget_property_never_exceeds_budget():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(
        n=st.integers(min_value=2, max_value=8),
        budget=st.integers(min_value=1, max_value=4),
    )
    def check(n, budget):
        cluster, _t, small, big, _b = _scale_up_scenario(n=n, budget=budget)
        ref = FleetScheduler(cluster)
        unbudgeted = ref.schedule(big, previous=ref.schedule(small))
        sched = FleetScheduler(cluster, move_budget=budget)
        q = sched.schedule(small)
        for _round in range(50):
            q = sched.schedule(big, previous=q)
            assert q.total_moves <= budget
            if not q.deferred:
                break
        assert not q.deferred
        for a, b in zip(q.allocations, unbudgeted.allocations):
            assert a.config == b.config

    check()


# ---------------------------------------------------------------------------
# Eviction grace
# ---------------------------------------------------------------------------


def _fragmented_prev(cluster, be):
    """Best-effort holds one container on every host (the fragmentation
    demo from test_fleet) — a guaranteed arrival fits nowhere until the
    ladder reclaims space."""
    from repro.fleet import FleetPlan, Placement, TenantAllocation

    be_cfg = round_robin_configuration(be.dag, {"W": 1, "C": 1}, 4, DIM)
    return FleetPlan(
        allocations=[TenantAllocation(
            tenant=be.name, qos=be.qos, requested_ktps=400.0,
            planned_ktps=400.0, config=be_cfg,
            placement=Placement(
                host_of=(0, 1, 2, 3),
                host_names=("std/0", "std/1", "std/2", "std/3"),
                min_speed=1.0,
            ),
            cpus=float(sum(d.cpus for d in be_cfg.dims)),
            predicted_ktps=400.0, bottleneck=None,
            shortfall_ktps=0.0, degraded=False,
        )],
        cores_total=cluster.total_cores(), cores_used=12.0,
    )


def test_eviction_grace_victim_serves_marked_round_then_reclaimed():
    cluster = Cluster([MachineClass("std", count=4, cores=4.0, mem_mb=16384.0)])
    sched = FleetScheduler(cluster, eviction_grace=True)
    gold = _tenant("gold", qos=QosTier.GUARANTEED, target=400.0)
    be = _tenant("be", qos=QosTier.BEST_EFFORT, target=400.0)
    prev = _fragmented_prev(cluster, be)
    hosts = cluster.inventory()
    Cluster.seat(
        prev.allocations[0].config.dims,
        prev.allocations[0].placement.host_names, hosts,
    )
    assert not Cluster.trial_pack(
        minimal_footprint(gold.dag, gold.node_models(), DIM).dims, hosts
    )

    demands = [(gold, 400.0), (be, 400.0)]
    p1 = sched.schedule(demands, previous=prev)
    g1, b1 = p1.allocation("gold"), p1.allocation("be")
    # grace round: the victim is only MARKED — it keeps its full deployment
    assert b1.draining and b1.admitted
    assert b1.config == prev.allocations[0].config
    assert b1.placement.host_names == prev.allocations[0].placement.host_names
    assert b1.evicted >= 1                         # the eviction is booked...
    assert p1.eviction_log                         # ...and logged at mark time
    assert not g1.admitted                         # beneficiary waits a round
    assert p1.draining == {"be": len(b1.draining)}

    p2 = sched.schedule(demands, previous=p1)
    g2, b2 = p2.allocation("gold"), p2.allocation("be")
    # next round: drained capacity reclaimed, beneficiary admitted
    assert g2.admitted
    assert not b2.draining
    assert b2.cpus < b1.cpus                       # victim actually shrank


def test_eviction_grace_off_evicts_immediately():
    cluster = Cluster([MachineClass("std", count=4, cores=4.0, mem_mb=16384.0)])
    sched = FleetScheduler(cluster)                # grace off (default)
    gold = _tenant("gold", qos=QosTier.GUARANTEED, target=400.0)
    be = _tenant("be", qos=QosTier.BEST_EFFORT, target=400.0)
    prev = _fragmented_prev(cluster, be)
    p1 = sched.schedule([(gold, 400.0), (be, 400.0)], previous=prev)
    assert p1.allocation("gold").admitted          # no waiting round
    assert not p1.allocation("be").draining


def test_fleet_loop_replans_to_finish_grace_and_deferrals():
    cluster = Cluster([MachineClass("std", count=4, cores=4.0, mem_mb=16384.0)])
    gold = _tenant("gold", qos=QosTier.GUARANTEED, target=400.0)
    be = _tenant("be", qos=QosTier.BEST_EFFORT, target=400.0)
    loop = FleetLoop([be, gold], cluster, eviction_grace=True)
    ev1 = loop.step({"gold": 400.0, "be": 400.0})
    if ev1.tenant("be").draining:
        # the carried plan has draining containers: the next step must
        # replan even though every guard holds
        ev2 = loop.step({"gold": 400.0, "be": 400.0})
        assert ev2.replanned and ev2.cause == "deferred"
        assert ev2.tenant("be").draining == 0


# ---------------------------------------------------------------------------
# Candidate-set pruning
# ---------------------------------------------------------------------------


def test_pruning_bounds_scored_candidates():
    evaluator = SimulatorEvaluator(params=PARAMS, duration_s=2.0)
    wide = FleetScheduler(_cluster(), evaluator, prune_band=100.0)
    tight = FleetScheduler(_cluster(), evaluator, prune_band=1.0)
    demands = [(_tenant("a", target=200.0), 240.0)]
    p_wide = wide.schedule(demands)
    p_tight = tight.schedule(demands)
    a_wide, a_tight = p_wide.allocation("a"), p_tight.allocation("a")
    assert a_wide.admitted and a_tight.admitted
    assert 1 <= a_tight.candidates_scored <= a_wide.candidates_scored
    # pruning must not change the committed outcome on a healthy cluster
    assert a_tight.config == a_wide.config
    assert a_tight.predicted_ktps == a_wide.predicted_ktps


def test_pruning_keeps_default_repair_headroom():
    # the default band keeps at least the winner plus a fallback, so the
    # measured-repair path still has somewhere to go
    evaluator = SimulatorEvaluator(params=PARAMS, duration_s=2.0)
    sched = FleetScheduler(_cluster(), evaluator)
    dag = wordcount()
    spec = TenantSpec(
        name="a", dag=dag, target_ktps=300.0, qos=QosTier.GUARANTEED,
        models=oracle_models(dag, PARAMS.sm_cost_per_ktuple),
        preferred_dim=DIM,
        candidate_dims=[DIM, ContainerDim(cpus=1.5, mem_mb=1024.0)],
    )
    p = sched.schedule([(spec, 300.0)])
    assert p.allocation("a").candidates_scored >= 2


# ---------------------------------------------------------------------------
# Per-phase timings
# ---------------------------------------------------------------------------


def test_schedule_reports_phase_timings():
    evaluator = SimulatorEvaluator(params=PARAMS, duration_s=2.0)
    sched = FleetScheduler(_cluster(), evaluator)
    p = sched.schedule([(_tenant("a"), 60.0), (_tenant("b"), 60.0)])
    for phase in ("restore", "allocate", "pack", "score", "repair", "total"):
        assert phase in p.timings
        assert p.timings[phase] >= 0.0
    assert p.timings["score"] > 0.0                # the evaluator really ran
    assert p.timings["total"] >= max(
        v for k, v in p.timings.items() if k != "total"
    )


# ---------------------------------------------------------------------------
# Batch bucketing + structure memoization (the scoring fast path)
# ---------------------------------------------------------------------------


def test_batch_bucket_ladder():
    assert batch_bucket_size(1) == 8
    assert batch_bucket_size(8) == 8
    assert batch_bucket_size(9) == 16
    assert batch_bucket_size(40) == 64
    assert batch_bucket_size(3, floor=32) == 32
    assert batch_bucket_size(600) == 1024          # beyond ladder: 512-multiple
    assert all(b % 8 == 0 for b in (8, 16, 32, 64, 128, 256, 512))


def test_min_batch_bucket_results_identical():
    dag = wordcount()
    cfgs = [
        round_robin_configuration(
            dag, {"W": 1 + i % 2, "C": 1 + (i + 1) % 2}, 2 + i % 3, DIM
        )
        for i in range(5)
    ]
    plain = simulate_batch(cfgs, 1e6, duration_s=2.0, params=PARAMS)
    padded = simulate_batch(
        cfgs, 1e6, duration_s=2.0, params=PARAMS, min_batch_bucket=16
    )
    assert len(plain) == len(padded) == 5
    for a, b in zip(plain, padded):
        assert a.achieved_ktps == b.achieved_ktps
        for k in a.samples:
            np.testing.assert_array_equal(a.samples[k], b.samples[k])


def test_structure_cache_reuses_built_structures():
    clear_structure_cache()
    dag = wordcount()
    cfg = round_robin_configuration(dag, {"W": 2, "C": 1}, 3, DIM)
    simulate_batch([cfg], 1e6, duration_s=2.0, params=PARAMS)
    first = structure_cache_info()
    simulate_batch([cfg], 1e6, duration_s=2.0, params=PARAMS)
    second = structure_cache_info()
    assert second["misses"] == first["misses"]     # no new builds
    assert second["hits"] > first["hits"]


def test_executor_evaluator_precalibrates_each_group_once():
    pytest.importorskip("jax")
    from repro.streams import ExecutorEvaluator

    ev = ExecutorEvaluator(n_batches=2)
    calls = []
    original = ev.precalibrate
    ev.precalibrate = lambda dags: (calls.append(len(dags)), original(dags))
    dag = wordcount()
    cfgs = [round_robin_configuration(dag, {"W": 1, "C": 1}, 2, DIM)]
    ev.evaluate_batch(cfgs, 100.0)
    ev.evaluate_batch(cfgs, 120.0)                 # same group: memoized
    assert len(calls) == 1


def test_simulator_evaluator_layout_memo_reused():
    ev = SimulatorEvaluator(params=PARAMS, duration_s=2.0)
    dag = wordcount()
    cfgs = [round_robin_configuration(dag, {"W": 1, "C": 1}, 2, DIM)]
    ev.evaluate_batch(cfgs, 100.0)
    assert len(ev._layout_memo) == 1
    ev.evaluate_batch(cfgs, 120.0)                 # same list object: one entry
    assert len(ev._layout_memo) == 1


# ---------------------------------------------------------------------------
# Sticky batch: compile stability across a fleet trace
# ---------------------------------------------------------------------------


def test_fleet_trace_compiles_at_most_twice_with_sticky_batch():
    evaluator = SimulatorEvaluator(
        params=PARAMS, duration_s=2.0, sticky_batch=True
    )
    tenants = [
        _tenant("a", qos=QosTier.GUARANTEED, target=60.0),
        _tenant("b", qos=QosTier.BEST_EFFORT, target=60.0),
    ]
    loop = FleetLoop(tenants, _cluster(hosts=8, cores=8.0), evaluator)
    before = kernel_cache_info()["misses"]
    loop.run({
        "a": [60.0, 60.0, 90.0, 90.0, 140.0, 60.0],
        "b": [60.0, 80.0, 60.0, 100.0, 60.0, 80.0],
    })
    misses = kernel_cache_info()["misses"] - before
    assert misses <= 2, (
        f"fleet trace must hold a stable compiled kernel: {misses} compiles"
    )


# ---------------------------------------------------------------------------
# Tenant-sharded joint scoring: bitwise consistency
# ---------------------------------------------------------------------------


def _fleet_plan_fingerprint(devices):
    evaluator = SimulatorEvaluator(
        params=PARAMS, duration_s=2.0, devices=devices, sticky_batch=True
    )
    sched = FleetScheduler(_cluster(hosts=10, cores=8.0), evaluator)
    demands = [
        (_tenant("a", qos=QosTier.GUARANTEED, target=120.0), 140.0),
        (_tenant("b", target=80.0), 90.0),
        (_tenant("c", qos=QosTier.BEST_EFFORT, target=60.0), 70.0),
    ]
    windows = {"a": [150.0, 160.0], "b": [95.0]}
    plan = sched.schedule(demands, windows=windows)
    return [
        (a.tenant, a.predicted_ktps, tuple(a.horizon_ktps),
         a.horizon_feasible, a.candidates_scored)
        for a in plan.allocations
    ]


def test_sharded_joint_scoring_matches_unsharded_in_process():
    assert _fleet_plan_fingerprint(1) == _fleet_plan_fingerprint(None)


def test_sharded_joint_scoring_matches_unsharded_forced_8_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
        import json, sys
        sys.path.insert(0, %r)
        import jax
        from repro.streams import clear_transfer_stats, transfer_info
        from test_fleet_scale import _fleet_plan_fingerprint
        single = _fleet_plan_fingerprint(1)
        clear_transfer_stats()
        sharded = _fleet_plan_fingerprint(None)
        print(json.dumps({
            "devices": jax.local_device_count(),
            "staged_devices": transfer_info()["staged_devices"],
            "identical": single == sharded,
        }))
    """ % os.path.join(REPO, "tests"))
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 8
    assert res["staged_devices"] > 1          # the shards really spread out
    assert res["identical"]
