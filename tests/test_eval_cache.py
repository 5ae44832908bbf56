"""Cache-first evaluation path: Tier-1 in-batch request dedup, Tier-2
cross-call result memoization, Tier-3 vectorized structure building — every
tier must be bitwise-transparent, and every invalidation rule must fire."""
import numpy as np
import pytest

from repro.control import ControlLoop, DeclarativePolicy, GuardBands, ModelStore
from repro.core import (
    Configuration,
    ContainerDim,
    Grouping,
    oracle_models,
    round_robin_configuration,
)
from repro.fleet import Cluster, FleetLoop, MachineClass, QosTier, TenantSpec
from repro.streams import (
    ExecutorEvaluator,
    ResultCache,
    SimParams,
    SimulatorEvaluator,
    adanalytics,
    cache_stats,
    clear_dedup_stats,
    dedup_info,
    deep_pipeline,
    diamond,
    measure_capacity,
    mobile_analytics,
    simulate_batch,
    wordcount,
)
from repro.streams.simulator import build_structure

DIM = ContainerDim(cpus=3.0, mem_mb=4096.0)
PARAMS = SimParams()
WORKLOADS = (wordcount, adanalytics, diamond, deep_pipeline, mobile_analytics)


def _cfg(dag, par: int = 2, n_cont: int = 3) -> Configuration:
    return round_robin_configuration(
        dag, {n: par for n in dag.node_names}, n_cont, DIM
    )


def _wc_cfg() -> Configuration:
    return Configuration(wordcount(), packing=(("W",), ("C",)), dims=(DIM, DIM))


# ---------------------------------------------------------------------------
# Tier 3 — vectorized structure building (bitwise vs the loop reference)
# ---------------------------------------------------------------------------


def _reference_structure(config: Configuration, params: SimParams) -> dict:
    """The historical per-instance-pair loop form of ``build_structure``,
    kept here as the bitwise oracle for the vectorized implementation."""
    dag = config.dag
    instances = config.instances()
    n_inst = len(instances)
    n_cont = config.n_containers
    cont_of = np.array([c for _n, c, _s in instances], np.int32)
    specs = [dag.node(nm) for nm, _c, _s in instances]
    busy_cost = np.array([s.cpu_cost_per_ktuple for s in specs])
    cpu_cost = np.array(
        [
            s.cpu_cost_per_ktuple * (1.0 - s.io_fraction)
            * params.cpu_overhead_mult
            for s in specs
        ]
    )
    gamma = np.array([s.gamma for s in specs])
    mem_base = np.array([s.mem_mb_base for s in specs])
    mem_slope = np.array([s.mem_mb_per_ktps for s in specs])

    inst_of_node: dict = {}
    for i, (nm, _c, _s) in enumerate(instances):
        inst_of_node.setdefault(nm, []).append(i)
    W = np.zeros((n_inst, n_inst))
    for e in dag.edges:
        ups = inst_of_node[e.src]
        downs = inst_of_node[e.dst]
        w = 1.0 if e.grouping is Grouping.ALL else 1.0 / len(downs)
        for p in ups:
            for q in downs:
                W[p, q] += w

    sm_cost_eff = np.zeros(n_cont)
    for c in range(n_cont):
        peers = set()
        for p in range(n_inst):
            for q in range(n_inst):
                if W[p, q] <= 0 or cont_of[p] == cont_of[q]:
                    continue
                if cont_of[p] == c:
                    peers.add(int(cont_of[q]))
                elif cont_of[q] == c:
                    peers.add(int(cont_of[p]))
        sm_cost_eff[c] = params.sm_cost_per_ktuple * (
            1.0 + params.sm_fanout_coef * len(peers)
        )
    return {
        "busy_cost": busy_cost, "cpu_cost": cpu_cost, "gamma": gamma,
        "mem_base": mem_base, "mem_slope": mem_slope, "W": W,
        "sm_cost_eff": sm_cost_eff,
    }


@pytest.mark.parametrize("workload", WORKLOADS, ids=lambda w: w.__name__)
def test_vectorized_structure_bitwise_matches_loop_reference(workload):
    cfg = _cfg(workload())
    st = build_structure(cfg, PARAMS)
    ref = _reference_structure(cfg, PARAMS)
    for k, want in ref.items():
        got = np.asarray(getattr(st, k))
        assert got.dtype == want.dtype and np.array_equal(got, want), (
            f"{workload.__name__}: SimStructure.{k} not bitwise identical"
        )
    # derived edge-list views stay consistent with W
    src, dst = np.nonzero(ref["W"])
    assert np.array_equal(st.edge_src, src.astype(np.int32))
    assert np.array_equal(st.edge_dst, dst.astype(np.int32))
    assert np.array_equal(st.edge_w, ref["W"][src, dst])


def test_vectorized_metrics_store_matches_reference():
    res = simulate_batch([_wc_cfg()], [300.0], duration_s=4.0, params=PARAMS)[0]
    store = res.to_metrics_store()
    st = res.structure
    dt = res.params.dt
    proc = np.asarray(res.samples["proc"]) / dt
    mem = np.asarray(res.samples["mem"])
    trav = np.asarray(res.samples["sm_trav"]) / dt
    inst_rows = store.samples[: st.n_inst]
    for i, row in enumerate(inst_rows):
        assert row.node == st.node_names[int(st.node_of[i])]
        assert row.container == int(st.cont_of[i]) and row.slot == i
        assert np.array_equal(row.rate_in_ktps, proc[:, i])
        assert np.array_equal(row.memutil_mb, mem[:, i])
    sm_rows = store.samples[st.n_inst :]
    assert len(sm_rows) == st.n_cont
    for c, row in enumerate(sm_rows):
        assert row.container == c and row.slot == -1
        assert np.array_equal(row.rate_in_ktps, trav[:, c])
        assert np.array_equal(row.memutil_mb, np.full(trav.shape[0], 256.0))


# ---------------------------------------------------------------------------
# Tier 1 — in-batch dedup: bitwise scatter-back
# ---------------------------------------------------------------------------


def _assert_rows_bitwise(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.samples.keys() == y.samples.keys()
        for k in x.samples:
            ax, ay = np.asarray(x.samples[k]), np.asarray(y.samples[k])
            assert ax.dtype == ay.dtype and np.array_equal(ax, ay), k


def _run_pattern(loads, seeds, dedup):
    cfg = _wc_cfg()
    return simulate_batch(
        [cfg] * len(loads), list(loads), duration_s=1.0, params=PARAMS,
        seeds=list(seeds), dedup=dedup,
    )


def test_dedup_scatter_back_bitwise_identical():
    loads = [300.0, 200.0, 300.0, 250.0, 200.0, 300.0]
    seeds = [7, 7, 7, 7, 7, 7]
    clear_dedup_stats()
    deduped = _run_pattern(loads, seeds, dedup=True)
    info = dedup_info()
    assert info["rows_in"] == 6 and info["rows_unique"] == 3
    plain = _run_pattern(loads, seeds, dedup=False)
    _assert_rows_bitwise(deduped, plain)


def test_dedup_distinguishes_seeds_and_traces():
    # same load value, different seed -> distinct rows; equal-valued traces
    # collapse, distinct traces don't
    trace = np.full(8, 220.0)
    loads = [300.0, 300.0, trace, np.array(trace), trace + 1.0]
    seeds = [1, 2, 7, 7, 7]
    clear_dedup_stats()
    deduped = _run_pattern(loads, seeds, dedup=True)
    assert dedup_info()["rows_unique"] == 4
    _assert_rows_bitwise(deduped, _run_pattern(loads, seeds, dedup=False))


def test_dedup_random_duplicate_patterns_bitwise():
    rng = np.random.default_rng(42)
    pool_loads = [200.0, 260.0, 320.0]
    for _ in range(3):
        picks = rng.integers(0, len(pool_loads), size=9)
        loads = [pool_loads[i] for i in picks]
        seeds = [int(7 + (i % 2)) for i in picks]
        _assert_rows_bitwise(
            _run_pattern(loads, seeds, dedup=True),
            _run_pattern(loads, seeds, dedup=False),
        )


def test_dedup_property_random_patterns():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=10, deadline=None)
    @hyp.given(st.lists(st.integers(min_value=0, max_value=2), min_size=1,
                        max_size=8))
    def check(picks):
        loads = [200.0 + 50.0 * p for p in picks]
        seeds = [7] * len(picks)
        clear_dedup_stats()
        deduped = _run_pattern(loads, seeds, dedup=True)
        info = dedup_info()
        assert info["rows_in"] == len(picks)
        assert info["rows_unique"] == len(set(picks))
        _assert_rows_bitwise(deduped, _run_pattern(loads, seeds, dedup=False))

    check()


def test_fleet_scale_dedup_factor():
    """The acceptance bar: a 1,000-tenant batch over 8 archetypes must
    execute >=5x fewer tick-kernel rows, bitwise-identically."""
    n, arch = 1000, 8
    loads = [200.0 + 15.0 * (i % arch) for i in range(n)]
    seeds = [7] * n
    clear_dedup_stats()
    deduped = _run_pattern(loads, seeds, dedup=True)
    info = dedup_info()
    assert info["rows_in"] == n and info["rows_unique"] == arch
    factor = info["rows_in"] / info["rows_executed"]
    assert factor >= 5.0
    plain = _run_pattern(loads[:32], seeds[:32], dedup=False)
    _assert_rows_bitwise(deduped[:32], plain)


# ---------------------------------------------------------------------------
# Tier 2 — result memoization + invalidation
# ---------------------------------------------------------------------------


def test_identical_resubmission_hits():
    cfg = _wc_cfg()
    rc = ResultCache()
    kw = dict(duration_s=1.0, params=PARAMS, seeds=[7], cache=rc)
    first = simulate_batch([cfg], [300.0], **kw)
    again = simulate_batch([cfg], [300.0], **kw)
    assert again[0] is first[0]                  # same object: a pure lookup
    assert rc.info()["hits"] == 1 and rc.info()["misses"] == 1


def test_changed_seed_misses():
    cfg = _wc_cfg()
    rc = ResultCache()
    kw = dict(duration_s=1.0, params=PARAMS, cache=rc)
    simulate_batch([cfg], [300.0], seeds=[7], **kw)
    simulate_batch([cfg], [300.0], seeds=[8], **kw)
    assert rc.info()["hits"] == 0 and rc.info()["misses"] == 2


def test_changed_params_seed_misses():
    import dataclasses

    cfg = _wc_cfg()
    rc = ResultCache()
    simulate_batch([cfg], [300.0], duration_s=1.0, params=PARAMS, seeds=[7],
                   cache=rc)
    bumped = dataclasses.replace(PARAMS, seed=PARAMS.seed + 1)
    simulate_batch([cfg], [300.0], duration_s=1.0, params=bumped, seeds=[7],
                   cache=rc)
    assert rc.info()["hits"] == 0 and rc.info()["misses"] == 2


def test_caches_key_on_platform(monkeypatch):
    """Result, resident-staging and kernel caches never let an entry made on
    one backend answer a request made on another."""
    from repro.streams import simulator as sim

    cfg = _wc_cfg()
    rc = ResultCache()
    kw = dict(duration_s=1.0, params=PARAMS, seeds=[7], cache=rc,
              resident=True, samples="summary")
    sim.clear_resident_cache()
    first = simulate_batch([cfg], [300.0], **kw)
    monkeypatch.setattr(sim, "_platform", lambda: "elsewhere")
    other = simulate_batch([cfg], [300.0], **kw)
    assert other[0] is not first[0]
    assert rc.info()["hits"] == 0 and rc.info()["misses"] == 2
    assert sim.resident_cache_info()["misses"] == 2
    assert "elsewhere" in {e["platform"] for e in sim.kernel_cache_info()["entries"]}
    assert other[0].achieved_ktps == first[0].achieved_ktps


def test_model_version_bump_invalidates_evaluator_cache():
    dag = wordcount()
    store = ModelStore(oracle_models(dag, PARAMS.sm_cost_per_ktuple))
    ev = SimulatorEvaluator(params=PARAMS, duration_s=1.0,
                            version_source=store)
    cfg = _wc_cfg()
    ev.evaluate(cfg, 300.0)
    ev.evaluate(cfg, 300.0)
    assert ev.result_cache.info()["hits"] == 1
    store.observe(cfg, 290.0)                    # version bump -> stale keys
    ev.evaluate(cfg, 300.0)
    info = ev.result_cache.info()
    assert info["hits"] == 1 and info["misses"] == 2


def test_retrain_invalidates_evaluator_cache():
    dag = wordcount()
    store = ModelStore(oracle_models(dag, PARAMS.sm_cost_per_ktuple))
    ev = SimulatorEvaluator(params=PARAMS, duration_s=1.0,
                            version_source=store)
    cfg = _wc_cfg()
    res = simulate_batch([cfg], [1e6], duration_s=2.0, params=PARAMS)[0]
    store.pool(res.to_metrics_store())
    ev.evaluate(cfg, 300.0)
    assert store.retrain() is not None           # bumps version
    ev.evaluate(cfg, 300.0)
    assert ev.result_cache.info()["hits"] == 0


def test_escape_hatch_reproduces_uncached_path():
    cfg = _wc_cfg()
    clear_dedup_stats()
    plain = simulate_batch([cfg, cfg], [300.0, 300.0], duration_s=1.0,
                           params=PARAMS, seeds=[7, 7], dedup=False)
    assert dedup_info()["batches"] == 0          # stats untouched: no new path
    deduped = simulate_batch([cfg, cfg], [300.0, 300.0], duration_s=1.0,
                             params=PARAMS, seeds=[7, 7], dedup=True)
    _assert_rows_bitwise(plain, deduped)
    ev_off = SimulatorEvaluator(params=PARAMS, duration_s=1.0, dedup=False,
                                cache=False)
    assert ev_off.result_cache is None
    ev_on = SimulatorEvaluator(params=PARAMS, duration_s=1.0)
    a = ev_off.evaluate_batch([cfg, cfg], 300.0)
    b = ev_on.evaluate_batch([cfg, cfg], 300.0)
    assert [r.achieved_ktps for r in a] == [r.achieved_ktps for r in b]


def test_result_cache_bounds_and_eviction():
    rc = ResultCache(max_entries=2, max_bytes=1000)
    rc.put("a", 1, nbytes=400)
    rc.put("b", 2, nbytes=400)
    rc.put("c", 3, nbytes=400)                   # evicts "a" (bytes + entries)
    assert rc.get("a") is None and rc.get("c") == 3
    assert rc.info()["evictions"] >= 1
    rc.put("huge", 4, nbytes=2000)               # larger than the whole budget
    assert rc.get("huge") is None


def test_executor_evaluator_memoizes_and_invalidates():
    dag = wordcount()
    store = ModelStore(oracle_models(dag, PARAMS.sm_cost_per_ktuple))
    ev = ExecutorEvaluator(n_batches=1, version_source=store)
    cfg = _wc_cfg()
    first = ev.evaluate(cfg, 300.0)
    assert ev.evaluate(cfg, 300.0) is first
    assert ev.result_cache.info()["hits"] == 1
    store.observe(cfg, 290.0)
    ev.evaluate(cfg, 300.0)
    assert ev.result_cache.info()["hits"] == 1   # version bump missed


# ---------------------------------------------------------------------------
# Wiring + observability
# ---------------------------------------------------------------------------


def test_control_loop_wires_learner_as_version_source():
    dag = wordcount()
    models = oracle_models(dag, PARAMS.sm_cost_per_ktuple)
    ev = SimulatorEvaluator(params=PARAMS, duration_s=1.0)
    learner = ModelStore(models)
    loop = ControlLoop(
        DeclarativePolicy(dag, ModelStore(models)),
        guards=GuardBands(headroom=1.2, deadband=0.15),
        evaluator=ev, learner=learner,
    )
    assert loop.evaluator.version_source is learner
    # explicit wiring wins: the loop must not overwrite it
    other = ModelStore(models)
    ev2 = SimulatorEvaluator(params=PARAMS, duration_s=1.0,
                             version_source=other)
    ControlLoop(
        DeclarativePolicy(dag, ModelStore(models)),
        evaluator=ev2, learner=learner,
    )
    assert ev2.version_source is other


def test_fleet_loop_wires_aggregate_version_clock():
    dag = wordcount()
    stores = [
        ModelStore(oracle_models(dag, PARAMS.sm_cost_per_ktuple))
        for _ in range(2)
    ]
    tenants = [
        TenantSpec(name=f"t{i}", dag=dag, target_ktps=300.0,
                   qos=QosTier.STANDARD, models=stores[i],
                   guards=GuardBands(), preferred_dim=DIM)
        for i in range(2)
    ]
    cluster = Cluster([MachineClass("std", count=6, cores=4.0, mem_mb=16384.0)])
    ev = SimulatorEvaluator(params=PARAMS, duration_s=1.0)
    FleetLoop(tenants, cluster, ev)
    v0 = ev.version_source.version
    assert v0 == (0, 0)
    stores[1].observe(_wc_cfg(), 290.0)
    assert ev.version_source.version == (0, 1)   # any tenant's bump shows


def test_cache_stats_shape():
    # warm every tier at least once
    rc = ResultCache()
    simulate_batch([_wc_cfg()], [300.0], duration_s=1.0, params=PARAMS,
                   seeds=[7], cache=rc)
    stats = cache_stats()
    assert set(stats) == {
        "kernel", "structure", "resident", "result", "dedup", "transfer",
    }
    for section in ("kernel", "structure", "result"):
        assert {"hits", "misses"} <= set(stats[section])
    for k in ("evictions", "bytes", "caches", "size"):
        assert k in stats["result"]
    assert {"batches", "rows_in", "rows_unique", "rows_executed"} <= set(
        stats["dedup"]
    )
    assert {"batches", "bytes_full", "bytes_summary", "bytes_h2d",
            "refetches"} <= set(stats["transfer"])


def test_steady_trace_reaches_high_hit_rate():
    dag = wordcount()
    models = oracle_models(dag, PARAMS.sm_cost_per_ktuple)
    ev = SimulatorEvaluator(params=PARAMS, duration_s=1.0)
    loop = ControlLoop(
        DeclarativePolicy(dag, ModelStore(models)),
        guards=GuardBands(headroom=1.2, deadband=0.15),
        evaluator=ev, learner=ModelStore(models),
    )
    loop.run([60.0] * 4)                         # warmup: compile + fill
    warm = ev.result_cache.info()
    loop.run([60.0] * 12)                        # steady state
    after = ev.result_cache.info()
    hits = after["hits"] - warm["hits"]
    misses = after["misses"] - warm["misses"]
    assert hits / max(hits + misses, 1) >= 0.9
