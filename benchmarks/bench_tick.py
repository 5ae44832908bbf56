"""Tick-kernel benchmarks: the sparse-routing data path end to end.

Four sections:

1. **Full-sim ladder** — dense (I, I) flow-matrix kernel vs the sparse
   ELL edge-list kernel on ``deep_pipeline`` at every instance bucket
   (8 / 32 / 128 / 512).  The BENCH row for the 128 bucket is load-bearing:
   this module *asserts* sparse ≥ dense there (the crossover the auto
   selector banks on), and records the speedups in ``EXTRAS["tick"]``.
2. **Flow-step microbench** — one fused gather–throttle–scatter step in
   dense, sparse-ELL and Pallas (interpret mode on CPU — functional
   validation + relative cost only; real perf is TPU) form.
3. **Edge-density sweep** — dense vs sparse full-sim across the five
   workload topologies at one packing, annotated with each structure's
   ``E/I²`` density (the axis the ``"auto"`` threshold cuts).
4. **Batch staging** — repeated ``simulate_batch`` over the same candidate
   set with and without the device-residency cache (cold stage vs warm
   reuse), the fleet-replan path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import round_robin_configuration
from repro.core.dag import ContainerDim
from repro.kernels.stream_flow import stream_flow, stream_flow_reference
from repro.streams import (
    WORKLOADS,
    SimParams,
    clear_resident_cache,
    deep_pipeline,
    edge_bucket_size,
    resident_cache_info,
    simulate_batch,
)
from repro.streams.simulator import _expand_routing, pad_structure, structure_for

from .common import EXTRAS, emit, timed

DIM = ContainerDim(cpus=3.0, mem_mb=4096.0)
OVERLOAD = 1e6

#: (parallelism per node, containers) -> instance bucket on deep_pipeline
LADDER = [(1, 2, 8), (4, 8, 32), (16, 16, 128), (64, 32, 512)]


def _config(dag, par: int, cont: int):
    return round_robin_configuration(
        dag, {n: par for n in dag.node_names}, cont, DIM
    )


def _full_sim_ladder() -> dict:
    per_bucket: dict[int, dict] = {}
    for par, cont, bucket in LADDER:
        cfg = _config(deep_pipeline(), par, cont)
        reps = 1 if bucket >= 512 else 2
        times = {}
        for kern in ("dense", "sparse"):
            _, us = timed(
                lambda k=kern: simulate_batch(
                    [cfg], OVERLOAD, duration_s=10.0, tick_kernel=k
                ),
                repeats=reps,
            )
            times[kern] = us
            emit(f"tick_full_{bucket}_{kern}", us, f"deep_pipeline;bucket={bucket}")
        speedup = times["dense"] / times["sparse"]
        emit(f"tick_full_{bucket}_speedup", 0.0, f"dense/sparse={speedup:.2f}x")
        per_bucket[bucket] = {
            "dense_us": round(times["dense"], 1),
            "sparse_us": round(times["sparse"], 1),
            "speedup": round(speedup, 3),
        }
    # The acceptance bar for the sparse data path: at the 128-instance
    # bucket on deep_pipeline the O(E) kernel must not lose to the O(I²)
    # oracle.  Fail the bench (and the smoke job) loudly if it regresses.
    if per_bucket[128]["speedup"] < 1.0:
        raise AssertionError(
            f"sparse tick kernel lost to dense at the 128 bucket: "
            f"{per_bucket[128]}"
        )
    return per_bucket


def _flow_step() -> dict:
    """One fused flow step at the 128-instance bucket, three ways."""
    params = SimParams()
    st = structure_for(_config(deep_pipeline(), 16, 16), params)
    I, K = 128, 32
    E = edge_bucket_size(st.n_edges)
    dense = pad_structure(st, I, K)
    sparse = pad_structure(st, I, K, n_edge_bucket=E)
    rng = np.random.default_rng(0)
    qout = jnp.asarray(rng.uniform(0.0, 50.0, I).astype(np.float32))
    sm_budget = jnp.full(K, 400.0, jnp.float32)
    C = jnp.asarray(
        (dense["cont_of"][:, None] == np.arange(K)[None, :]).astype(np.float32)
    )
    W, remote = _expand_routing(
        dense["W_node"], dense["node_of"], dense["cont_of"], dense["inst_mask"]
    )
    rowsum = W.sum(axis=1)

    @jax.jit
    def dense_step(qout):
        share = W / jnp.maximum(rowsum, 1e-9)[:, None]
        F_want = qout[:, None] * share
        orig_c = C.T @ F_want.sum(axis=1)
        arr_c = ((F_want * remote).sum(axis=0)) @ C
        s_c = jnp.minimum(1.0, sm_budget / jnp.maximum(orig_c + arr_c, 1e-9))
        eff = jnp.minimum((C @ s_c)[:, None], jnp.where(remote, (C @ s_c)[None, :], 1.0))
        F = F_want * eff
        return F.sum(axis=1), F.sum(axis=0), C.T @ F.sum(axis=1) + (F * remote).sum(axis=0) @ C

    e_share = jnp.asarray(sparse["edge_share"])
    e_src = jnp.asarray(sparse["edge_src"])
    e_remote = jnp.asarray(sparse["edge_remote"])
    e_sc = jnp.asarray(sparse["edge_src_cont"])
    e_dc = jnp.asarray(sparse["edge_dst_cont"])
    ell_src = jnp.asarray(sparse["ell_src"])
    ell_dst = jnp.asarray(sparse["ell_dst"])

    @jax.jit
    def ell_step(qout):
        def rsum(vals, ell):
            return jnp.concatenate([vals, jnp.zeros(1, vals.dtype)])[ell].sum(axis=1)
        f_want = qout[e_src] * e_share
        orig_c = rsum(f_want, ell_src) @ C
        arr_c = rsum(f_want * e_remote, ell_dst) @ C
        s_c = jnp.minimum(1.0, sm_budget / jnp.maximum(orig_c + arr_c, 1e-9))
        f = f_want * jnp.minimum(s_c[e_sc], jnp.where(e_remote > 0, s_c[e_dc], 1.0))
        return rsum(f, ell_src), rsum(f, ell_dst), rsum(f, ell_src) @ C + rsum(f * e_remote, ell_dst) @ C

    d_ref, us_dense = timed(lambda: jax.block_until_ready(dense_step(qout)), repeats=10)
    d_ell, us_ell = timed(lambda: jax.block_until_ready(ell_step(qout)), repeats=10)
    pallas_args = (qout, e_src, jnp.asarray(sparse["edge_dst"]), e_share,
                   e_remote, e_sc, e_dc, sm_budget)
    d_pal, us_pal = timed(
        lambda: jax.block_until_ready(
            stream_flow(*pallas_args, block_edges=512, interpret=True)
        ),
        repeats=1,
    )
    ref = stream_flow_reference(*pallas_args, n_inst=I, n_cont=K)
    err_ell = max(float(jnp.abs(a - b).max()) for a, b in zip(d_ell, ref))
    err_pal = max(float(jnp.abs(a - b).max()) for a, b in zip(d_pal, ref))
    emit("tick_step_dense_128", us_dense, f"I={I};E={st.n_edges}")
    emit("tick_step_ell_128", us_ell, f"maxerr_vs_ref={err_ell:.1e}")
    emit("tick_step_pallas_128", us_pal, f"interpret;maxerr_vs_ref={err_pal:.1e}")
    assert err_ell < 1e-3 and err_pal < 1e-3
    return {
        "dense_us": round(us_dense, 1),
        "ell_us": round(us_ell, 1),
        "pallas_interpret_us": round(us_pal, 1),
        "ell_maxerr": err_ell,
        "pallas_maxerr": err_pal,
    }


def _density_sweep() -> list[dict]:
    rows = []
    for name, make in sorted(WORKLOADS.items()):
        cfg = _config(make(), 4, 8)
        st = structure_for(cfg, SimParams())
        density = st.n_edges / max(st.n_inst, 1) ** 2
        times = {}
        for kern in ("dense", "sparse"):
            _, us = timed(
                lambda k=kern: simulate_batch(
                    [cfg], OVERLOAD, duration_s=5.0, tick_kernel=k
                ),
                repeats=2,
            )
            times[kern] = us
        emit(
            f"tick_density_{name}", times["sparse"],
            f"density={density:.3f};dense_us={times['dense']:.0f}",
        )
        rows.append({
            "workload": name,
            "density": round(density, 4),
            "n_inst": st.n_inst,
            "n_edges": st.n_edges,
            "dense_us": round(times["dense"], 1),
            "sparse_us": round(times["sparse"], 1),
        })
    return rows


def _staging() -> dict:
    """Same candidate set replayed — the fleet-replan staging path."""
    cfgs = [_config(deep_pipeline(), p, 8) for p in (1, 2, 3, 4)]
    kw = dict(duration_s=2.0, tick_kernel="sparse")
    clear_resident_cache()
    _, us_cold = timed(
        lambda: simulate_batch(cfgs, OVERLOAD, resident=True, **kw),
        repeats=1, warmup=0,
    )
    _, us_warm = timed(
        lambda: simulate_batch(cfgs, OVERLOAD, resident=True, **kw),
        repeats=5,
    )
    _, us_off = timed(
        lambda: simulate_batch(cfgs, OVERLOAD, resident=False, **kw),
        repeats=5,
    )
    info = resident_cache_info()
    emit("tick_stage_cold", us_cold, "resident=True;first call (incl. compile)")
    emit("tick_stage_warm", us_warm, f"resident hit;hits={info['hits']}")
    emit("tick_stage_off", us_off, "resident=False;restages every call")
    return {
        "cold_us": round(us_cold, 1),
        "warm_us": round(us_warm, 1),
        "no_cache_us": round(us_off, 1),
        "cache": info,
    }


def run() -> dict:
    out = {
        "full_sim": _full_sim_ladder(),
        "flow_step": _flow_step(),
        "density_sweep": _density_sweep(),
        "staging": _staging(),
    }
    EXTRAS["tick"] = out
    return out


if __name__ == "__main__":
    run()
