"""Sparse-routing tick kernel: edge-list physics vs the dense oracle,
EDGE_LADDER bucketing invariants, auto backend selection, the Pallas fused
flow step, and the device-resident batch cache."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # property tests skip; the deterministic suite still runs
    HAVE_HYPOTHESIS = False

    def given(**kwargs):  # noqa: D103 - inert stand-ins keep decorators valid
        return lambda fn: fn

    def settings(**kwargs):
        return lambda fn: fn

    class st:  # noqa: D101
        @staticmethod
        def integers(*a, **k):
            return None

        @staticmethod
        def sampled_from(*a, **k):
            return None

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed"
)

import jax.numpy as jnp

from repro.core import ContainerDim, round_robin_configuration
from repro.core.dag import DagSpec, EdgeSpec, Grouping, NodeSpec
from repro.kernels.stream_flow import stream_flow, stream_flow_reference
from repro.streams import (
    EDGE_LADDER,
    SimParams,
    SimulatorEvaluator,
    adanalytics,
    clear_kernel_cache,
    clear_resident_cache,
    deep_pipeline,
    diamond,
    edge_bucket_size,
    kernel_cache_info,
    mobile_analytics,
    resident_cache_info,
    resolve_tick_kernel,
    simulate,
    simulate_batch,
    wordcount,
)
from repro.streams.simulator import (
    SPARSE_DENSITY_THRESHOLD,
    _per_tick_trace,
    structure_for,
)

DIM = ContainerDim(cpus=3.0, mem_mb=4096.0)
PARAMS = SimParams()


def _metrics_close(a, b, rtol=5e-4, atol=5e-4):
    for k in a.samples:
        x, y = np.asarray(a.samples[k]), np.asarray(b.samples[k])
        scale = max(float(np.abs(x).max()), 1.0)
        np.testing.assert_allclose(x, y, rtol=rtol, atol=atol * scale,
                                   err_msg=f"metric {k}")


# --------------------------------------------------- sparse vs dense oracle

@pytest.mark.parametrize(
    "workload", [wordcount, adanalytics, diamond, mobile_analytics, deep_pipeline]
)
def test_sparse_matches_dense_under_overload(workload):
    """The edge-list kernel reproduces the dense flow matrix to float
    tolerance with every throttle engaged (offered load ≫ capacity)."""
    dag = workload()
    cfg = round_robin_configuration(
        dag, {n: 1 + i % 2 for i, n in enumerate(dag.node_names)}, 3, DIM
    )
    rd = simulate(cfg, 1e6, duration_s=6.0, params=PARAMS, tick_kernel="dense")
    rs = simulate(cfg, 1e6, duration_s=6.0, params=PARAMS, tick_kernel="sparse")
    assert rs.achieved_ktps == pytest.approx(rd.achieved_ktps, rel=1e-4)
    _metrics_close(rd, rs)


def test_sparse_matches_dense_underloaded():
    dag = diamond()
    cfg = round_robin_configuration(
        dag, {n: 2 for n in dag.node_names}, 4, DIM
    )
    rd = simulate(cfg, 150.0, duration_s=6.0, params=PARAMS, tick_kernel="dense")
    rs = simulate(cfg, 150.0, duration_s=6.0, params=PARAMS, tick_kernel="sparse")
    assert rs.achieved_ktps == pytest.approx(rd.achieved_ktps, rel=1e-4)
    _metrics_close(rd, rs)


def _random_dag(n_nodes, extra_edges, rng) -> DagSpec:
    """A random connected DAG: a spine plus random forward skip edges."""
    nodes = tuple(
        NodeSpec(
            f"n{i}",
            cpu_cost_per_ktuple=1.0 / float(rng.uniform(200.0, 1500.0)),
            gamma=float(rng.uniform(0.3, 1.0)) if i < n_nodes - 1 else 0.0,
            mem_mb_base=64.0,
            tuple_bytes=64.0,
            is_source=(i == 0),
        )
        for i in range(n_nodes)
    )
    edges = {(i, i + 1) for i in range(n_nodes - 1)}
    for _ in range(extra_edges):
        a = int(rng.integers(0, n_nodes - 1))
        b = int(rng.integers(a + 1, n_nodes))
        edges.add((a, b))
    groupings = (Grouping.SHUFFLE, Grouping.FIELDS)
    return DagSpec(
        "rand",
        nodes=nodes,
        edges=tuple(
            EdgeSpec(f"n{a}", f"n{b}", groupings[(a + b) % 2])
            for a, b in sorted(edges)
        ),
    )


def _check_random_dag_equivalence(n_nodes, extra_edges, par, n_cont, seed):
    rng = np.random.default_rng(seed)
    dag = _random_dag(n_nodes, extra_edges, rng)
    parallelism = {
        n: 1 + (par + i) % 3 for i, n in enumerate(dag.node_names)
    }
    cfg = round_robin_configuration(dag, parallelism, n_cont, DIM)
    rd = simulate(cfg, 1e6, duration_s=4.0, params=PARAMS, tick_kernel="dense")
    rs = simulate(cfg, 1e6, duration_s=4.0, params=PARAMS, tick_kernel="sparse")
    assert rs.achieved_ktps == pytest.approx(
        rd.achieved_ktps, rel=1e-4, abs=1e-3
    )
    _metrics_close(rd, rs)


@needs_hypothesis
@settings(max_examples=8, deadline=None)
@given(
    n_nodes=st.integers(3, 7),
    extra_edges=st.integers(0, 4),
    par=st.integers(1, 3),
    n_cont=st.integers(2, 5),
    seed=st.integers(0, 10_000),
)
def test_property_sparse_matches_dense_on_random_dags(
    n_nodes, extra_edges, par, n_cont, seed
):
    """Random topology × grouping × packing: both kernels agree on the
    achieved rate and every sampled metric to tolerance."""
    _check_random_dag_equivalence(n_nodes, extra_edges, par, n_cont, seed)


@pytest.mark.parametrize(
    "case",
    [(3, 0, 1, 2, 11), (5, 2, 2, 3, 23), (6, 4, 3, 5, 37), (7, 3, 1, 4, 53)],
)
def test_sparse_matches_dense_on_random_dags_deterministic(case):
    """Fixed-seed slice of the property test: runs even without
    hypothesis installed."""
    _check_random_dag_equivalence(*case)


# ------------------------------------------------- EDGE_LADDER + selection

def test_edge_bucket_size_ladder_and_floor():
    assert edge_bucket_size(1) == EDGE_LADDER[0]
    assert edge_bucket_size(EDGE_LADDER[0]) == EDGE_LADDER[0]
    assert edge_bucket_size(EDGE_LADDER[0] + 1) == EDGE_LADDER[1]
    assert edge_bucket_size(EDGE_LADDER[-1]) == EDGE_LADDER[-1]
    # past the ladder: multiples of the last rung
    assert edge_bucket_size(EDGE_LADDER[-1] + 1) == 2 * EDGE_LADDER[-1]
    # sticky floor pins the bucket
    assert edge_bucket_size(3, floor=512) == 512


def test_edge_bucket_is_bitwise_invariant():
    """Padded edges carry zero share: growing the edge bucket must not
    change a single bit of the outputs (mirrors the instance-bucket
    invariance guarantees)."""
    dag = deep_pipeline()
    cfg = round_robin_configuration(dag, {n: 2 for n in dag.node_names}, 4, DIM)
    r1 = simulate_batch(
        [cfg], [1e6], duration_s=4.0, params=PARAMS, tick_kernel="sparse"
    )[0]
    r2 = simulate_batch(
        [cfg], [1e6], duration_s=4.0, params=PARAMS, tick_kernel="sparse",
        min_edge_bucket=2048,
    )[0]
    for k in r1.samples:
        assert np.array_equal(
            np.asarray(r1.samples[k]), np.asarray(r2.samples[k])
        ), k


def test_resolve_tick_kernel_threshold_and_validation():
    # explicit choices pass through
    assert resolve_tick_kernel(10, 100, "dense") == "dense"
    assert resolve_tick_kernel(10, 1, "sparse") == "sparse"
    # auto: sparse at/below the density threshold, dense above
    n = 16
    edges_at = int(SPARSE_DENSITY_THRESHOLD * n * n)
    assert resolve_tick_kernel(n, edges_at, "auto") == "sparse"
    assert resolve_tick_kernel(n, edges_at + 1, "auto") == "dense"
    with pytest.raises(ValueError):
        resolve_tick_kernel(10, 10, "csr")


def test_auto_selection_by_workload_density():
    """deep_pipeline (long sparse chain) routes sparse; wordcount's tiny
    dense 2-node graph stays on the dense oracle."""
    deep = round_robin_configuration(
        deep_pipeline(), {n: 2 for n in deep_pipeline().node_names}, 4, DIM
    )
    wc = round_robin_configuration(wordcount(), {"W": 2, "C": 2}, 1, DIM)
    st_deep = structure_for(deep, PARAMS)
    st_wc = structure_for(wc, PARAMS)
    assert resolve_tick_kernel(st_deep.n_inst, st_deep.n_edges, "auto") == "sparse"
    assert resolve_tick_kernel(st_wc.n_inst, st_wc.n_edges, "auto") == "dense"


def test_auto_selection_is_dense_on_tpu(monkeypatch):
    """A TPU runs the dense tick for every density; an explicit choice
    still passes through."""
    from repro.streams import simulator as sim

    monkeypatch.setattr(sim, "_platform", lambda: "tpu")
    assert resolve_tick_kernel(480, 25_200, "auto") == "dense"
    assert resolve_tick_kernel(480, 25_200, "sparse") == "sparse"
    monkeypatch.setattr(sim, "_platform", lambda: "cpu")
    assert resolve_tick_kernel(480, 25_200, "auto") == "sparse"


def test_sticky_sparse_evaluator_compiles_at_most_twice():
    """The evaluator pins the auto-resolved backend and edge bucket, so a
    growing candidate stream costs at most two sparse compiles."""
    clear_kernel_cache()
    clear_resident_cache()
    dag = deep_pipeline()
    ev = SimulatorEvaluator(params=PARAMS, duration_s=2.0)
    small = round_robin_configuration(dag, {n: 1 for n in dag.node_names}, 2, DIM)
    big = round_robin_configuration(dag, {n: 3 for n in dag.node_names}, 6, DIM)
    ev.evaluate(small)
    ev.evaluate(big)     # buckets grow: second (and last) compile
    ev.evaluate(small)
    ev.evaluate(big)
    info = kernel_cache_info()
    assert info["misses"] <= 2
    assert all(e["backend"] == "sparse" for e in info["entries"])


# ------------------------------------------------------- Pallas fused step

def _random_flow_problem(rng, n_inst, n_cont, n_edges):
    qout = rng.uniform(0.0, 5.0, n_inst).astype(np.float32)
    src = rng.integers(0, n_inst, n_edges).astype(np.int32)
    dst = rng.integers(0, n_inst, n_edges).astype(np.int32)
    share = rng.uniform(0.0, 1.0, n_edges).astype(np.float32)
    cont_of = rng.integers(0, n_cont, n_inst).astype(np.int32)
    src_c, dst_c = cont_of[src], cont_of[dst]
    remote = (src_c != dst_c).astype(np.float32)
    budget = rng.uniform(0.5, 4.0, n_cont).astype(np.float32)
    return qout, src, dst, share, remote, src_c, dst_c, budget


@pytest.mark.parametrize(
    "shape", [(4, 2, 7), (16, 4, 40), (32, 8, 100), (11, 5, 513)]
)
def test_pallas_stream_flow_matches_reference(shape):
    n_inst, n_cont, n_edges = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    args = _random_flow_problem(rng, n_inst, n_cont, n_edges)
    jargs = [jnp.asarray(a) for a in args]
    out = stream_flow(*jargs, block_edges=64, interpret=True)
    ref = stream_flow_reference(*jargs, n_inst=n_inst, n_cont=n_cont)
    for o, r, name in zip(out, ref, ("delivered", "arrivals", "trav_c")):
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(r), rtol=1e-5, atol=1e-5, err_msg=name
        )


@needs_hypothesis
@settings(max_examples=10, deadline=None)
@given(
    n_inst=st.integers(2, 24),
    n_cont=st.integers(1, 6),
    n_edges=st.integers(1, 200),
    block=st.sampled_from([16, 64, 256]),
    seed=st.integers(0, 10_000),
)
def test_property_pallas_stream_flow(n_inst, n_cont, n_edges, block, seed):
    rng = np.random.default_rng(seed)
    args = _random_flow_problem(rng, n_inst, n_cont, n_edges)
    jargs = [jnp.asarray(a) for a in args]
    out = stream_flow(*jargs, block_edges=block, interpret=True)
    ref = stream_flow_reference(*jargs, n_inst=n_inst, n_cont=n_cont)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(r), rtol=1e-5, atol=1e-5
        )


# --------------------------------------------------- resident batch cache

def test_resident_cache_hits_and_is_bitwise_identical():
    clear_resident_cache()
    dag = deep_pipeline()
    cfgs = [
        round_robin_configuration(dag, {n: 1 + i % 2 for n in dag.node_names},
                                  2 + i, DIM)
        for i in range(3)
    ]
    ra = simulate_batch(cfgs, 1e6, duration_s=2.0, params=PARAMS, resident=True)
    rb = simulate_batch(cfgs, 1e6, duration_s=2.0, params=PARAMS, resident=True)
    info = resident_cache_info()
    assert info["misses"] == 1 and info["hits"] == 1
    assert info["bytes"] > 0
    for a, b in zip(ra, rb):
        for k in a.samples:
            assert np.array_equal(
                np.asarray(a.samples[k]), np.asarray(b.samples[k])
            ), k
    # resident results equal the plain (non-resident) path exactly
    rc = simulate_batch(cfgs, 1e6, duration_s=2.0, params=PARAMS)
    for a, c in zip(ra, rc):
        for k in a.samples:
            assert np.array_equal(
                np.asarray(a.samples[k]), np.asarray(c.samples[k])
            ), k


def test_resident_cache_misses_on_different_candidate_set():
    clear_resident_cache()
    dag = wordcount()
    a = round_robin_configuration(dag, {"W": 1, "C": 1}, 2, DIM)
    b = round_robin_configuration(dag, {"W": 2, "C": 2}, 2, DIM)
    simulate_batch([a], 300.0, duration_s=2.0, params=PARAMS, resident=True)
    simulate_batch([b], 300.0, duration_s=2.0, params=PARAMS, resident=True)
    assert resident_cache_info()["misses"] == 2


# ------------------------------------------------------- satellite checks

def test_bottleneck_threshold_is_callers_choice():
    dag = wordcount()
    cfg = round_robin_configuration(dag, {"W": 2, "C": 2}, 2, DIM)
    res = simulate(cfg, 1e6, duration_s=6.0, params=PARAMS)
    # saturated run: the default threshold names a bottleneck, an
    # impossible one names nothing
    assert res.bottleneck_node() is not None
    assert res.bottleneck_node(1.1, sm_threshold=1.1) is None
    assert res.bottleneck_node() == res.bottleneck_node(0.8)


def test_per_tick_trace_rejects_empty_and_documents_tiling():
    with pytest.raises(ValueError, match="empty"):
        _per_tick_trace(np.array([]), 100, 0.01)
    # piecewise-constant: each entry held ceil(n_ticks / L) ticks
    out = _per_tick_trace(np.array([1.0, 2.0, 3.0]), 8, 1.0)
    assert out.tolist() == [1.0, 1.0, 1.0, 2.0, 2.0, 2.0, 3.0, 3.0]


def test_kernel_cache_info_describes_entries():
    clear_kernel_cache()
    dag = wordcount()
    cfg = round_robin_configuration(dag, {"W": 1, "C": 1}, 2, DIM)
    simulate_batch([cfg], 300.0, duration_s=2.0, params=PARAMS,
                   tick_kernel="dense")
    entries = kernel_cache_info()["entries"]
    assert len(entries) == 1
    e = entries[0]
    assert e["backend"] == "dense" and e["batch"] == 1
    assert e["n_inst"] >= 2 and e["devices"] >= 1 and e["n_ticks"] > 0
    assert e["platform"] == "cpu"


@pytest.mark.parametrize("samples, donated", [("full", (0, 1, 2)), ("summary", ())])
def test_accelerator_donates_only_buffers_outputs_can_reuse(monkeypatch, samples, donated):
    """Full trajectories may reuse the staged batch buffers; O(B·I)
    summaries alias none of them, so summary kernels donate nothing."""
    from repro.streams import simulator as sim

    monkeypatch.setattr(sim, "_KERNEL_CACHE", {})
    monkeypatch.setattr(sim, "_CACHE_STATS", {"hits": 0, "misses": 0})
    monkeypatch.setattr(sim, "_platform", lambda: "tpu")
    sim._get_batch_kernel(8, 8, 8, 50, 25, samples_mode=samples)
    (key,) = sim._KERNEL_CACHE
    assert key[11] == donated
