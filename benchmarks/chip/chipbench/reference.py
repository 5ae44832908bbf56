"""The plain reference: the simulated Heron cluster written out once more,
straight from the physics the configuration file states.

It imports nothing of the program under test.  A configuration is given as
plain data: the DAG (per-node costs, edges and groupings) from the
configuration file, and the packing (the node name of every instance, per
container) with the per-container CPUs.  One configuration at a time is
stepped tick by tick in float32; ``jax.vmap`` only runs several of them side
by side, each padded with inert instances and one inert container that carry
no work.

``flows_dtype=jnp.bfloat16`` computes the (I, I) routing and flow matrices in
bfloat16: the control, the step down in precision that a faster tick would
tempt.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

STREAM_MANAGER = "__stream_manager__"


@dataclasses.dataclass(frozen=True)
class Row:
    """One configuration to score: ``packing[c]`` names the node of every
    instance in container ``c``; ``cpus[c]`` is that container's CPUs."""

    dag: str
    packing: tuple
    cpus: tuple
    offered_ktps: float


def structure(dag: dict, row: Row, physics: dict) -> dict:
    """Unpadded per-instance and per-container arrays of one configuration."""
    names = [n["name"] for n in dag["nodes"]]
    node = {n["name"]: n for n in dag["nodes"]}
    inst_node, inst_cont = [], []
    for c, cont in enumerate(row.packing):
        for name in cont:
            inst_node.append(names.index(name))
            inst_cont.append(c)
    node_of = np.array(inst_node, np.int32)
    cont_of = np.array(inst_cont, np.int32)
    n_inst, n_cont = len(node_of), len(row.packing)
    has_input = {e["dst"] for e in dag["edges"]}
    cost = np.array([1.0 / node[n]["peak_ktps"] for n in names])
    io = np.array([node[n].get("io_fraction", 0.0) for n in names])
    members = {n: np.flatnonzero(node_of == i) for i, n in enumerate(names)}
    W = np.zeros((n_inst, n_inst))
    for e in dag["edges"]:
        ups, downs = members[e["src"]], members[e["dst"]]
        w = 1.0 if e["grouping"] == "all" else 1.0 / len(downs)
        W[np.ix_(ups, downs)] += w
    remote = cont_of[:, None] != cont_of[None, :]
    src, dst = np.nonzero(W)
    cross = cont_of[src] != cont_of[dst]
    peers = np.zeros((n_cont, n_cont), bool)
    peers[cont_of[src[cross]], cont_of[dst[cross]]] = True
    n_peers = (peers | peers.T).sum(axis=1)
    return dict(
        node_of=node_of,
        cont_of=cont_of,
        is_source=np.array([names[i] not in has_input for i in node_of]),
        busy_cost=cost[node_of],
        cpu_cost=(cost * (1.0 - io) * physics["cpu_overhead_mult"])[node_of],
        gamma=np.array([node[n].get("gamma", 1.0) for n in names])[node_of],
        W=W,
        remote=remote,
        cont_cpus=np.array(row.cpus, np.float64),
        sm_cost=physics["sm_cost_per_ktuple"] * (1.0 + physics["sm_fanout_coef"] * n_peers),
    )


def _pad(st: dict, n_inst: int, n_cont: int) -> dict:
    """Pad to ``n_inst`` instances and ``n_cont`` containers; padding is
    never a source, costs nothing, routes nothing and lives in the last
    container, which holds no real instance."""
    ni, nc = len(st["node_of"]), len(st["cont_cpus"])

    def vec(x, n, fill, dtype=np.float32):
        out = np.full(n, fill, dtype)
        out[: len(x)] = x
        return out

    W = np.zeros((n_inst, n_inst), np.float32)
    W[:ni, :ni] = st["W"]
    remote = np.zeros((n_inst, n_inst), bool)
    remote[:ni, :ni] = st["remote"]
    return dict(
        inst_mask=vec(np.ones(ni), n_inst, 0.0),
        cont_mask=vec(np.ones(nc), n_cont, 0.0),
        cont_of=vec(st["cont_of"], n_inst, n_cont - 1, np.int32),
        is_source=vec(st["is_source"], n_inst, False, bool),
        busy_cost=vec(st["busy_cost"], n_inst, 1.0),
        cpu_cost=vec(st["cpu_cost"], n_inst, 0.0),
        gamma=vec(st["gamma"], n_inst, 0.0),
        cont_cpus=vec(st["cont_cpus"], n_cont, 1.0),
        sm_cost=vec(st["sm_cost"], n_cont, 1.0),
        W=W,
        remote=remote,
    )


def _simulate(a: dict, offered: jnp.ndarray, keys: jnp.ndarray, physics: dict,
              sample_every: int, flows_dtype) -> dict:
    """One configuration's run; returns its per-window means of source
    throughput (S,), per-instance capacity use (S, I) and per-container
    stream-manager busy (S, K)."""
    dt = physics["dt"]
    n_cont = a["cont_cpus"].shape[0]
    cont_of, is_source, mask = a["cont_of"], a["is_source"], a["inst_mask"]

    def by_container(x):
        return jax.ops.segment_sum(x, cont_of, num_segments=n_cont)

    rowsum = a["W"].sum(axis=1)
    share = (a["W"] / jnp.maximum(rowsum, 1e-9)[:, None]).astype(flows_dtype)
    remote = a["remote"]
    n_src = jnp.maximum(is_source.sum(), 1)
    sm_budget = dt / jnp.maximum(a["sm_cost"], 1e-9)

    def tick(state, inp):
        qin, qout, admit, sm_cpu_prev = state
        offered_t, key = inp
        noise = jnp.clip(
            1.0 + physics["noise_std"] * jax.random.normal(key, mask.shape),
            0.7, 1.3)
        busy = a["busy_cost"] * noise
        admitted = jnp.minimum(offered_t, admit)
        cap = dt / jnp.maximum(busy, 1e-9)
        want = jnp.where(is_source, jnp.minimum(admitted / n_src, cap),
                         jnp.minimum(qin, cap)) * mask
        demand = by_container(want * a["cpu_cost"]) + sm_cpu_prev
        scale = jnp.minimum(1.0, a["cont_cpus"] * dt / jnp.maximum(demand, 1e-9))
        proc = want * scale[cont_of]
        qin = qin - jnp.where(is_source, 0.0, proc)
        qout = qout + proc * a["gamma"] * rowsum

        f_want = qout.astype(flows_dtype)[:, None] * share
        sent = f_want.sum(axis=1, dtype=jnp.float32)
        crossing = jnp.where(remote, f_want, 0).sum(axis=0, dtype=jnp.float32)
        sm_scale = jnp.minimum(1.0, sm_budget / jnp.maximum(
            by_container(sent) + by_container(crossing), 1e-9))
        s = sm_scale[cont_of].astype(flows_dtype)
        f = f_want * jnp.minimum(s[:, None], jnp.where(remote, s[None, :], 1))
        delivered = f.sum(axis=1, dtype=jnp.float32)
        arrivals = f.sum(axis=0, dtype=jnp.float32)
        trav = (by_container(delivered) + by_container(
            jnp.where(remote, f, 0).sum(axis=0, dtype=jnp.float32))) * a["cont_mask"]
        qout = qout - delivered
        qin = qin + jnp.where(is_source, 0.0, arrivals)

        high, low = physics["queue_high_ktuples"], physics["queue_low_ktuples"]
        q_max = jnp.maximum(qin.max(), qout.max())
        admit = jnp.where(q_max > high, admit * 0.98,
                          jnp.where(q_max < low, admit * 1.02, admit))
        admit = jnp.clip(admit, 1e-3, 1e9)
        sm_cpu = trav * a["sm_cost"]
        out = dict(src=(proc * is_source).sum(), caputil=proc * busy / dt,
                   sm=sm_cpu / dt)
        return (qin, qout, admit, sm_cpu), out

    src_cap = jnp.where(is_source, dt / jnp.maximum(a["busy_cost"], 1e-9), 0.0).sum()
    state = (jnp.zeros_like(mask), jnp.zeros_like(mask), src_cap * 0.05,
             jnp.zeros(n_cont, jnp.float32))
    n_windows = offered.shape[0] // sample_every

    def window(state, inp):
        state, per_tick = jax.lax.scan(tick, state, inp)
        return state, {k: v.mean(axis=0) for k, v in per_tick.items()}

    def split(x):
        return x.reshape(n_windows, sample_every, *x.shape[1:])

    _, means = jax.lax.scan(window, state, (split(offered), split(keys)))
    return means


@partial(jax.jit, static_argnames=("physics_items", "n_ticks", "flows_dtype"))
def _run_block(arrays, offered_per_tick, physics_items, n_ticks, flows_dtype):
    physics = dict(physics_items)
    keys = jax.random.split(jax.random.PRNGKey(physics["seed"]), n_ticks)
    per_tick = jnp.broadcast_to(
        offered_per_tick[:, None], (offered_per_tick.shape[0], n_ticks))

    def one(a, offered):
        means = _simulate(a, offered, keys, physics, physics["sample_every"],
                          flows_dtype)
        half = means["src"].shape[0] // 2
        return dict(
            src_half_mean=means["src"][half:].mean(),
            caputil_half=means["caputil"][half:].mean(axis=0),
            sm_half=means["sm"][half:].mean(axis=0),
        )

    return jax.vmap(one)(arrays, per_tick)


def _round_up(n: int, step: int) -> int:
    return -(-n // step) * step


def score(dags: dict, rows: list[Row], physics: dict, horizon_s: float,
          saturation: float, sm_threshold: float, flows_dtype=jnp.float32,
          block: int = 64) -> list[dict]:
    """Score every row: its achieved rate (ktps), bottleneck and the
    smallest relative margin among the quantities the bottleneck compares.

    Rows run in blocks of ``block``; every block is padded to one shape
    (instances and containers rounded up to 128), so a sample of mixed
    sizes compiles a few programs, not one per row.  The bottleneck rule:
    the busiest node (the first in instance order among equals) unless the
    busiest stream manager is busier and above ``sm_threshold``; no
    bottleneck when the busiest node is at or under ``saturation``.
    """
    dt = physics["dt"]
    n_ticks = int(horizon_s / dt) // physics["sample_every"] * physics["sample_every"]
    physics_items = tuple(sorted(physics.items()))
    sts = [structure(dags[r.dag], r, physics) for r in rows]
    out: list[dict] = []
    for b0 in range(0, len(rows), block):
        chunk = list(range(b0, min(b0 + block, len(rows))))
        n_inst = _round_up(max(len(sts[i]["node_of"]) for i in chunk), 128)
        n_cont = _round_up(max(len(sts[i]["cont_cpus"]) for i in chunk) + 1, 128)
        pads = [_pad(sts[i], n_inst, n_cont) for i in chunk]
        pads += [pads[-1]] * (block - len(chunk))
        arrays = {k: jnp.asarray(np.stack([p[k] for p in pads])) for k in pads[0]}
        # the offer per tick: the rate times dt in float64, then float32
        loads = [rows[i].offered_ktps * dt for i in chunk]
        loads += [loads[-1]] * (block - len(chunk))
        res = jax.device_get(_run_block(
            arrays, jnp.asarray(np.asarray(loads, np.float32)), physics_items,
            n_ticks, flows_dtype))
        for j, i in enumerate(chunk):
            st = sts[i]
            ni, nc = len(st["node_of"]), len(st["cont_cpus"])
            out.append(_decide(
                dags[rows[i].dag], st["node_of"],
                float(res["src_half_mean"][j]) / dt,
                np.asarray(res["caputil_half"][j][:ni], np.float64),
                np.asarray(res["sm_half"][j][:nc], np.float64),
                saturation, sm_threshold))
    return out


def _decide(dag: dict, node_of: np.ndarray, achieved: float, caputil: np.ndarray,
            sm: np.ndarray, saturation: float, sm_threshold: float) -> dict:
    names = [n["name"] for n in dag["nodes"]]
    busiest: dict[int, float] = {}
    for i, v in zip(node_of.tolist(), caputil.tolist()):
        busiest[i] = max(busiest.get(i, 0.0), v)
    order = sorted(busiest.values(), reverse=True)
    top_node = max(busiest, key=lambda i: busiest[i])   # first among equals
    top = busiest[top_node]
    sm_max = float(sm.max()) if sm.size else 0.0
    if sm_max > top and sm_max > sm_threshold:
        bottleneck = STREAM_MANAGER
    else:
        bottleneck = names[top_node] if top > saturation else None
    gaps = [abs(top - saturation), abs(sm_max - sm_threshold), abs(sm_max - top)]
    if len(order) > 1:
        gaps.append(order[0] - order[1])
    margin = min(gaps) / max(top, sm_max, 1e-9)
    return dict(achieved=achieved, bottleneck=bottleneck, margin=margin)
