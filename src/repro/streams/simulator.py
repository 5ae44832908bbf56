"""Vectorized discrete-time cluster simulator — the "physical truth".

This plays the role of the Heron cluster in the paper: it executes a
:class:`~repro.core.dag.Configuration` tick by tick (a jitted ``lax.scan``)
and emits exactly the runtime metrics Heron exposes (§4): per-instance tuple
rates, ``cputil``, ``capacityutil``, sawtooth ``memutil``, ``gctime`` and
``backpressure`` — plus the same metrics for every stream manager.

The simulator deliberately contains *non-linear* physics that Trevor's linear
models do NOT know about, reproducing the paper's observed phenomena:

* every tuple crossing a container boundary traverses **two** stream managers
  (the paper's key communication-cost insight),
* container CPU contention (processor sharing) when packed instances plus the
  stream manager demand more cores than the container has,
* runtime-overhead threads: ``cputil`` can exceed 1.0 for a single-threaded
  instance (§3.1.1's parenthetical observation),
* stream-manager fan-out overhead: per-tuple routing cost grows mildly with
  the number of remote peers (drives the over-parallelization drop of
  Table 2 ID=9 / fig. 4c),
* Heron-style spout backpressure gating with hysteresis,
* JVM-style memory sawtooth with GC pauses (fig. 11),
* multiplicative measurement noise.

Because of these effects, Trevor's learned linear models are *approximations*
— which is precisely the regime the paper evaluates (≈10 % prediction error,
over-provisioning calibration, drift).

Batched evaluation
------------------
Every configuration is padded to a **shape bucket** (``bucket_size``) with
instance/container masks threaded through the tick kernel, so that any two
configurations in the same bucket share one XLA compilation.
:func:`simulate_batch` stacks N padded structures and evaluates them under
``jax.vmap`` — the paper's "score many candidate configurations cheaply"
lever.  Compiled kernels live in a module-level cache keyed on
``(batch, bucket_shape, n_ticks)``; see :func:`kernel_cache_info`.

On a multi-device host, large candidate batches are additionally **sharded
across devices**: the batch is padded to a multiple of the device count and
the vmapped kernel runs under ``jax.pmap``, one shard per device (the fleet
scheduler's joint multi-tenant sweeps are exactly this shape).  Per-shard
computation is the same vmapped kernel, so sharded and unsharded evaluation
agree bitwise; a single-device host falls back to plain vmap.

Summary evaluation mode
-----------------------
Scoring consumers (the fleet scheduler, predictive policies, capacity
probes) read only scalar reductions of each trajectory.
``simulate_batch(samples="summary")`` folds those reductions
(:func:`_summarize_windowed`) into the kernel epilogue so the trajectory
never leaves the device: the batch returns O(B·I) summary bytes in ONE
host transfer instead of O(B·S·I) trajectory bytes.  Summary-backed
:class:`SimResult`\\ s answer ``achieved_ktps`` / ``bottleneck_node``
exactly as full results do, and lazily *refetch* a full run on trajectory
access (learning paths); :func:`transfer_info` accounts the bytes moved.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import cached_property, partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.dag import Configuration, Grouping
from ..core.metrics import STREAM_MANAGER, InstanceSamples, MetricsStore
from . import spans
from .spans import span


@dataclasses.dataclass(frozen=True)
class SimParams:
    """Physics of the simulated cluster."""

    dt: float = 0.01                   # tick length (seconds)
    sm_cost_per_ktuple: float = 1.0 / 724.0   # sec CPU per ktuple traversal
    sm_fanout_coef: float = 0.015      # per-remote-peer routing overhead
    cpu_overhead_mult: float = 1.12    # runtime helper threads (cputil > caputil)
    noise_std: float = 0.03            # multiplicative per-tick cost noise
    queue_high_ktuples: float = 50.0   # backpressure high watermark
    queue_low_ktuples: float = 10.0    # resume watermark
    gc_heap_mb: float = 512.0          # per-instance heap above live set
    gc_cost_frac: float = 0.05         # gc time fraction while collecting
    mem_alloc_mb_per_ktuple: float = 0.02
    sample_every: int = 25             # ticks per metric sample
    seed: int = 0


def _routing_view(key: str, doc: str) -> property:
    """A read-only view of one array of ``SimStructure._routing``."""
    return property(lambda st: st._routing[key], doc=doc)


@dataclasses.dataclass
class SimStructure:
    """Static arrays describing one configuration (host-side, numpy).

    Routing is held at node granularity: ``W_node[a, b]`` is the weight of
    every (instance of ``a``, instance of ``b``) pair, so the instance-level
    ``W`` is ``W_node[np.ix_(node_of, node_of)]`` and ``remote`` is
    ``cont_of[i] != cont_of[j]``.  The dense tick kernel expands both on the
    device; the host forms (``W``, ``remote`` and the edge list) are built
    on first use, for the sparse layout and for inspection, and counted in
    ``structure_cache_info()["dense_expansions"]``.
    """

    config: Configuration
    n_inst: int
    n_cont: int
    node_of: np.ndarray          # (n_inst,) node index
    cont_of: np.ndarray          # (n_inst,) container index
    is_source: np.ndarray        # (n_inst,) bool
    busy_cost: np.ndarray        # (n_inst,) sec per ktuple (capacity cost)
    cpu_cost: np.ndarray         # (n_inst,) CPU-sec per ktuple (on-CPU, incl. overhead)
    gamma: np.ndarray            # (n_inst,)
    mem_base: np.ndarray         # (n_inst,) MB
    mem_slope: np.ndarray        # (n_inst,) MB per ktps
    W_node: np.ndarray           # (n_nodes, n_nodes) routing weight per node pair
    cont_cpus: np.ndarray        # (n_cont,)
    cont_mem: np.ndarray         # (n_cont,)
    sm_cost_eff: np.ndarray      # (n_cont,) per-traversal SM cost incl. fan-out overhead
    node_names: list[str]
    n_edges: int                 # nonzeros of ``W``
    d_out: int                   # max out-degree (edges per source instance)
    d_in: int                    # max in-degree (edges per dest instance)

    @property
    def n_nodes(self) -> int:
        return len(self.node_names)

    @cached_property
    def _routing(self) -> dict:
        """The host (I, I) routing and its CSR-like edge list — the nonzeros
        of ``W`` in row-major order, which the sparse tick kernel scales
        with.  Built once per structure, on first use."""
        _STRUCTURE_STATS["dense_expansions"] += 1
        W = self.W_node[np.ix_(self.node_of, self.node_of)]   # C-ordered
        remote = self.cont_of[:, None] != self.cont_of[None, :]
        edge_src, edge_dst = (x.astype(np.int32) for x in np.nonzero(W))
        return dict(
            W=W, remote=remote, edge_src=edge_src, edge_dst=edge_dst,
            edge_w=W[edge_src, edge_dst], edge_remote=remote[edge_src, edge_dst],
        )

    W = _routing_view("W", "(n_inst, n_inst) routing weights (copies per output tuple).")
    remote = _routing_view("remote", "(n_inst, n_inst) bool, cross-container.")
    edge_src = _routing_view("edge_src", "(n_edges,) int32 source instance.")
    edge_dst = _routing_view("edge_dst", "(n_edges,) int32 destination instance.")
    edge_w = _routing_view("edge_w", "(n_edges,) routing weight ``W[src, dst]``.")
    edge_remote = _routing_view("edge_remote", "(n_edges,) bool, cross-container edge.")


def build_structure(config: Configuration, params: SimParams) -> SimStructure:
    dag = config.dag
    # instances in ``config.instances()`` order: container-major, slot-minor
    per_cont = [len(c) for c in config.packing]
    n_inst = sum(per_cont)
    n_cont = config.n_containers
    n_nodes = len(dag.node_names)
    name_to_idx = {n: i for i, n in enumerate(dag.node_names)}
    node_of = np.fromiter(
        (name_to_idx[nm] for c in config.packing for nm in c), np.int32, n_inst
    )
    cont_of = np.repeat(np.arange(n_cont, dtype=np.int32), per_cont)
    src_names = {s.name for s in dag.sources()}
    is_source = np.array([nm in src_names for nm in dag.node_names], bool)[node_of]

    # per-NODE cost vectors gathered onto instances by ``node_of`` fancy
    # indexing — O(nodes + instances) instead of an attribute-access loop
    # over every instance
    node_specs = [dag.node(nm) for nm in dag.node_names]
    busy_cost = np.array([s.cpu_cost_per_ktuple for s in node_specs])[node_of]
    cpu_cost = np.array(
        [s.cpu_cost_per_ktuple * (1.0 - s.io_fraction) * params.cpu_overhead_mult
         for s in node_specs]
    )[node_of]
    gamma = np.array([s.gamma for s in node_specs])[node_of]
    mem_base = np.array([s.mem_mb_base for s in node_specs])[node_of]
    mem_slope = np.array([s.mem_mb_per_ktps for s in node_specs])[node_of]

    # routing weights per node pair, accumulated edge-major from 0.0: every
    # instance pair of a node pair receives the same sequence of adds, so
    # ``W_node[np.ix_(node_of, node_of)]`` is bitwise the instance-level W
    n_of_node = np.bincount(node_of, minlength=n_nodes)
    W_node = np.zeros((n_nodes, n_nodes))
    for e in dag.edges:
        a, b = name_to_idx[e.src], name_to_idx[e.dst]
        if not n_of_node[a] or not n_of_node[b]:
            raise ValueError(f"edge {e.src}->{e.dst} lacks instances")
        W_node[a, b] += 1.0 if e.grouping is Grouping.ALL else 1.0 / n_of_node[b]

    # edge counts in closed form: a nonzero node pair (a, b) routes every
    # instance of ``a`` to every instance of ``b``
    linked = (W_node != 0).astype(np.int64)
    present = n_of_node > 0
    out_deg = linked @ n_of_node
    in_deg = linked.T @ n_of_node

    # fan-out overhead: number of distinct remote peer containers each SM
    # talks to.  Two containers are peers when they hold instances of a
    # linked node pair: the outer product of the nodes' container sets,
    # without the diagonal (same-container flows cross no SM), symmetrized.
    on_cont = np.zeros((n_nodes, n_cont), np.int64)
    on_cont[node_of, cont_of] = 1
    conn = (on_cont.T @ linked @ on_cont) > 0
    np.fill_diagonal(conn, False)
    n_peers = (conn | conn.T).sum(axis=1)
    sm_cost_eff = params.sm_cost_per_ktuple * (
        1.0 + params.sm_fanout_coef * n_peers
    )
    return SimStructure(
        config=config,
        n_inst=n_inst,
        n_cont=n_cont,
        node_of=node_of,
        cont_of=cont_of,
        is_source=is_source,
        busy_cost=busy_cost,
        cpu_cost=cpu_cost,
        gamma=gamma,
        mem_base=mem_base,
        mem_slope=mem_slope,
        W_node=W_node,
        cont_cpus=np.array([d.cpus for d in config.dims]),
        cont_mem=np.array([d.mem_mb for d in config.dims]),
        sm_cost_eff=sm_cost_eff,
        node_names=list(dag.node_names),
        n_edges=int(n_of_node @ linked @ n_of_node),
        d_out=int(out_deg[present].max(initial=0)),
        d_in=int(in_deg[present].max(initial=0)),
    )


# ---------------------------------------------------------------------------
# Structure memoization
# ---------------------------------------------------------------------------

#: ``build_structure`` is pure in ``(config, params)`` — both are frozen
#: (hashable-by-value) dataclasses — and recurring configurations (the
#: fleet scheduler re-scores largely the same candidate ladder every
#: replan) skip its per-instance host work.  Bounded LRU keyed by value, so
#: two distinct-but-equal Configuration objects share one structure.
#: ``dense_expansions`` counts host (I, I) routing builds (``SimStructure``'s
#: ``W`` / ``remote`` / edge list): the sparse layout needs them, the dense
#: one never does.
_STRUCTURE_CACHE: "OrderedDict[tuple, SimStructure]" = OrderedDict()
_PAD_CACHE: "OrderedDict[tuple, dict]" = OrderedDict()
_STRUCTURE_CACHE_MAX = 4096
_STRUCTURE_STATS = {"hits": 0, "misses": 0, "dense_expansions": 0}


def _lru_get(cache: OrderedDict, key, build):
    hit = cache.get(key)
    if hit is not None:
        _STRUCTURE_STATS["hits"] += 1
        cache.move_to_end(key)
        return hit
    _STRUCTURE_STATS["misses"] += 1
    out = build()
    cache[key] = out
    if len(cache) > _STRUCTURE_CACHE_MAX:
        cache.popitem(last=False)
    return out


def structure_for(config: Configuration, params: SimParams) -> SimStructure:
    """Memoized :func:`build_structure` (treat the result as read-only)."""
    return _lru_get(
        _STRUCTURE_CACHE, (config, params), lambda: build_structure(config, params)
    )


def _padded_for(
    st: SimStructure,
    params: SimParams,
    n_inst_bucket: int,
    n_cont_bucket: int,
    n_edge_bucket: int | None = None,
    d_out_bucket: int | None = None,
    d_in_bucket: int | None = None,
    n_node_bucket: int | None = None,
) -> dict:
    """Memoized :func:`pad_structure` — the bucket layout for one config.

    The returned arrays are shared across calls and must be treated as
    read-only (``simulate_batch`` copies them when stacking the batch).
    """
    return _lru_get(
        _PAD_CACHE,
        (st.config, params, n_inst_bucket, n_cont_bucket, n_edge_bucket,
         d_out_bucket, d_in_bucket, n_node_bucket),
        lambda: pad_structure(st, n_inst_bucket, n_cont_bucket, n_edge_bucket,
                              d_out_bucket, d_in_bucket, n_node_bucket),
    )


def structure_cache_info() -> dict:
    """Host-side structure/padding memoization statistics: entries held,
    lookups answered (``hits``) or built (``misses``), and host (I, I)
    routing materializations (``dense_expansions``)."""
    return {
        "structures": len(_STRUCTURE_CACHE),
        "padded": len(_PAD_CACHE),
        **_STRUCTURE_STATS,
    }


def clear_structure_cache() -> None:
    _STRUCTURE_CACHE.clear()
    _PAD_CACHE.clear()
    for k in _STRUCTURE_STATS:
        _STRUCTURE_STATS[k] = 0


# ---------------------------------------------------------------------------
# Shape bucketing + padding
# ---------------------------------------------------------------------------

#: Coarse ladder so that an autoscaling run over a whole load trace lands in
#: at most a couple of buckets (each bucket = one XLA compilation).
BUCKET_LADDER = (8, 32, 128, 512)


def bucket_size(n: int, floor: int = 0) -> int:
    """Round ``n`` up to the shape-bucket ladder (``floor`` enforces a sticky
    lower bound so a caller can pin the bucket it already compiled for)."""
    n = max(int(n), int(floor), 1)
    for b in BUCKET_LADDER:
        if n <= b:
            return b
    return -(-n // BUCKET_LADDER[-1]) * BUCKET_LADDER[-1]


#: Finer ladder for the *batch* axis (candidate count), used by the fleet
#: scheduler's joint scoring: batch sizes are padded up to a rung (with a
#: sticky floor) so the per-device batch — and therefore the compiled kernel
#: shape — stays stable while the touched set fluctuates across replans.
#: Every rung is a multiple of 8, so an 8-way device shard divides evenly.
BATCH_LADDER = (8, 16, 32, 64, 128, 256, 512)


def batch_bucket_size(n: int, floor: int = 0) -> int:
    """Round a batch size up to the batch ladder (``floor`` is sticky)."""
    n = max(int(n), int(floor), 1)
    for b in BATCH_LADDER:
        if n <= b:
            return b
    return -(-n // BATCH_LADDER[-1]) * BATCH_LADDER[-1]


#: Ladder for the *edge* axis of the sparse tick kernel.  Coarse for the
#: same reason as :data:`BUCKET_LADDER` (each rung = one compilation), and
#: every rung is lane-aligned (a multiple of 128 from the second rung up)
#: so the Pallas flow kernel's edge blocks tile cleanly.
EDGE_LADDER = (32, 128, 512, 2048, 8192)


def edge_bucket_size(n: int, floor: int = 0) -> int:
    """Round an edge count up to the edge ladder (``floor`` is sticky)."""
    n = max(int(n), int(floor), 1)
    for b in EDGE_LADDER:
        if n <= b:
            return b
    return -(-n // EDGE_LADDER[-1]) * EDGE_LADDER[-1]


#: Ladder for the ELL row width (max in-/out-degree).  Deliberately as
#: coarse as :data:`BUCKET_LADDER` (4× steps): topology growth along a
#: trace then crosses few rungs, so the sparse path adds at most a couple
#: of degree-driven recompiles on the way up — row padding stays ≤ 4×, and
#: padded slots gather an exact 0.0 (free beyond the wasted lanes).
DEGREE_LADDER = (4, 16, 64, 256)


def degree_bucket_size(n: int, floor: int = 0) -> int:
    """Round an ELL row width (max in-/out-degree) up to the degree ladder
    (``floor`` is sticky)."""
    n = max(int(n), int(floor), 1)
    for b in DEGREE_LADDER:
        if n <= b:
            return b
    return -(-n // DEGREE_LADDER[-1]) * DEGREE_LADDER[-1]


#: Ladder for the *node* axis of the dense kernel's node-pair routing
#: table.  Most DAGs have at most 8 nodes, so one rung covers a fleet's
#: mixed DAGs; the table is a few hundred bytes a row at any rung.
NODE_LADDER = (8, 32, 128)


def node_bucket_size(n: int, floor: int = 0) -> int:
    """Round a DAG's node count up to the node ladder (``floor`` is sticky)."""
    n = max(int(n), int(floor), 1)
    for b in NODE_LADDER:
        if n <= b:
            return b
    return -(-n // NODE_LADDER[-1]) * NODE_LADDER[-1]


#: ``tick_kernel="auto"`` picks the sparse kernel when the densest
#: structure in the batch has edge density ``E / I²`` below this.  The
#: margin (vs the naive 1.0 crossover) pays for the sparse path's
#: gather/scatter overhead per edge; the decision uses *unpadded* counts,
#: so it is invariant to bucket floors and batch padding (bitwise-stable
#: bucketing semantics).  Shuffle-heavy DAGs (wordcount's p×p exchange,
#: density ≈ 1/4) stay dense; pipelines (deep_pipeline ≈ 0.11) go sparse
#: — on the CPU; a TPU always runs dense (see :func:`resolve_tick_kernel`).
SPARSE_DENSITY_THRESHOLD = 0.125

TICK_KERNELS = ("dense", "sparse", "auto")

#: Evaluation payload modes for :func:`simulate_batch`.  ``"full"`` ships the
#: whole windowed metric trajectory to the host (the historical behaviour);
#: ``"summary"`` keeps trajectories on device and transfers only the O(B·I)
#: summary pytree every scoring consumer needs — see
#: :func:`_summarize_windowed` for the exact reductions.
SAMPLES_MODES = ("full", "summary")


def resolve_tick_kernel(n_inst: int, n_edges: int, tick_kernel: str = "auto") -> str:
    """Resolve a ``tick_kernel`` selector to a concrete backend.

    ``n_inst`` / ``n_edges`` are the *unpadded* maxima across the batch;
    ``"auto"`` picks ``"sparse"`` when ``n_edges ≤ threshold · n_inst²``
    and ``"dense"`` otherwise (the dense path stays the oracle).  On a TPU
    ``"auto"`` is always ``"dense"``: the sparse path's per-edge gathers run
    far slower there than the dense path's (I, I) vector work (on a TPU v5e
    a 512-candidate deep_pipeline sweep at the 32,768-edge bucket did not
    finish in 13 minutes; a 512-candidate wordcount sweep at the same
    512-instance bucket, dense, took 15 s with its compile).
    """
    if tick_kernel not in TICK_KERNELS:
        raise ValueError(
            f"tick_kernel={tick_kernel!r} not in {TICK_KERNELS}"
        )
    if tick_kernel != "auto":
        return tick_kernel
    if _platform() == "tpu":
        return "dense"
    dense_cells = max(int(n_inst), 1) ** 2
    return "sparse" if n_edges <= SPARSE_DENSITY_THRESHOLD * dense_cells else "dense"


def pad_structure(
    st: SimStructure,
    n_inst_bucket: int,
    n_cont_bucket: int,
    n_edge_bucket: int | None = None,
    d_out_bucket: int | None = None,
    d_in_bucket: int | None = None,
    n_node_bucket: int | None = None,
) -> dict:
    """Pad a :class:`SimStructure` to static bucket shapes.

    Returns the exact array dict consumed by the tick kernel, with
    ``inst_mask`` / ``cont_mask`` marking the real (unpadded) entries.  Padded
    instances have zero routing weight, zero cost and are never sources, so
    they process nothing; padded containers receive no traffic.  Real entries
    always occupy the leading positions, so per-config metrics are recovered
    by slicing ``[: n_inst]`` / ``[: n_cont]``.

    ``n_edge_bucket=None`` (default) lays out the **dense** kernel's arrays:
    ``node_of`` (I,) and the node-pair routing table ``W_node`` (Nb, Nb)
    in float32, zero-padded to the node bucket ``n_node_bucket`` (default:
    the structure's own :data:`NODE_LADDER` rung).  The kernel expands the
    ``(I, I)`` routing/remote matrices from them and ``cont_of`` on the
    device, so O(I + Nb²) bytes a row cross to it; padded instances' node
    ids are arbitrary, since ``inst_mask`` zeroes their routing.  An
    integer ``n_edge_bucket`` instead lays out
    the **sparse** kernel's padded edge list (``edge_src`` / ``edge_dst`` /
    ``edge_share`` / ``edge_remote`` / container ids / ``edge_mask``) plus
    the ELL row-gather matrices ``ell_src`` (I, d_out) / ``ell_dst``
    (I, d_in) that turn per-edge → per-instance reductions into gathers +
    row-sums: the dense matrices are dropped, padded edges carry zero share
    (so they move exactly nothing wherever their indices point — results
    are bitwise invariant to the edge and degree buckets), and per-tick
    flow cost is O(E), not O(I²).  ``d_out_bucket`` / ``d_in_bucket``
    default to the structure's own degree-ladder buckets; callers batching
    several structures pass the shared (sticky) buckets explicitly.
    """
    I, K = int(n_inst_bucket), int(n_cont_bucket)
    if I < st.n_inst or K < st.n_cont:
        raise ValueError(
            f"bucket ({I},{K}) smaller than structure ({st.n_inst},{st.n_cont})"
        )

    def pad1(x, n, fill, dtype):
        out = np.full(n, fill, dtype)
        out[: x.shape[0]] = x
        return out

    sm_pad = float(st.sm_cost_eff.max()) if st.sm_cost_eff.size else 1e-3
    inst_mask = np.zeros(I, np.float32)
    inst_mask[: st.n_inst] = 1.0
    cont_mask = np.zeros(K, np.float32)
    cont_mask[: st.n_cont] = 1.0
    cont_of = pad1(st.cont_of, I, K - 1, np.int32)
    arrays = dict(
        busy_cost=pad1(st.busy_cost, I, 1.0, np.float32),
        cpu_cost=pad1(st.cpu_cost, I, 0.0, np.float32),
        gamma=pad1(st.gamma, I, 0.0, np.float32),
        is_source=pad1(st.is_source, I, False, bool),
        cont_of=cont_of,
        cont_cpus=pad1(st.cont_cpus, K, 1.0, np.float32),
        sm_cost_eff=pad1(st.sm_cost_eff, K, sm_pad, np.float32),
        mem_base=pad1(st.mem_base, I, 0.0, np.float32),
        mem_slope=pad1(st.mem_slope, I, 0.0, np.float32),
        inst_mask=inst_mask,
        cont_mask=cont_mask,
    )
    if n_edge_bucket is None:
        Nb = (int(n_node_bucket) if n_node_bucket is not None
              else node_bucket_size(st.n_nodes))
        if Nb < st.n_nodes:
            raise ValueError(
                f"node bucket {Nb} smaller than structure ({st.n_nodes} nodes)"
            )
        W_node = np.zeros((Nb, Nb), np.float32)
        W_node[: st.n_nodes, : st.n_nodes] = st.W_node
        arrays.update(node_of=pad1(st.node_of, I, 0, np.int32), W_node=W_node)
        return arrays

    E = int(n_edge_bucket)
    if E < st.n_edges:
        raise ValueError(
            f"edge bucket {E} smaller than structure ({st.n_edges} edges)"
        )
    # per-edge share of the source's output queue, in float32 exactly as the
    # dense kernel derives it from the padded W (share = w / max(rowsum, ε))
    rowsum32 = st.W.astype(np.float32).sum(axis=1)
    share = st.edge_w.astype(np.float32) / np.maximum(
        rowsum32[st.edge_src], 1e-9
    )
    edge_mask = np.zeros(E, np.float32)
    edge_mask[: st.n_edges] = 1.0
    # padded edges point at the last (padded) instance/container with zero
    # share: inert contributions, exact under summation
    edge_src = pad1(st.edge_src, E, I - 1, np.int32)
    edge_dst = pad1(st.edge_dst, E, I - 1, np.int32)
    # ELL row-gather matrices for vectorized segment sums: per-tick
    # reductions become gather((I, D) edge ids) + row-sum — no scatters,
    # which XLA CPU serializes per element, and no cumsum dependency chain.
    # Rows are built from the REAL edges only, so the layout (and therefore
    # every summation order) is independent of the edge bucket; row padding
    # holds the sentinel id ``E``, which gathers an appended exact 0.0.
    D_out = int(d_out_bucket) if d_out_bucket is not None else degree_bucket_size(st.d_out)
    D_in = int(d_in_bucket) if d_in_bucket is not None else degree_bucket_size(st.d_in)
    if D_out < st.d_out or D_in < st.d_in:
        raise ValueError(
            f"degree bucket ({D_out},{D_in}) smaller than structure "
            f"degrees ({st.d_out},{st.d_in})"
        )
    ell_src = np.full((I, D_out), E, np.int32)
    ell_dst = np.full((I, D_in), E, np.int32)
    if st.n_edges:
        eid = np.arange(st.n_edges)
        # edge_src is sorted (row-major nonzero order): rank within each
        # source's contiguous run = position - run start
        starts = np.searchsorted(st.edge_src, np.arange(st.n_inst))
        ell_src[st.edge_src, eid - starts[st.edge_src]] = eid
        perm = np.argsort(st.edge_dst, kind="stable")
        dsts = st.edge_dst[perm]
        dstarts = np.searchsorted(dsts, np.arange(st.n_inst))
        ell_dst[dsts, eid - dstarts[dsts]] = perm
    arrays.update(
        rowsum=pad1(rowsum32, I, 0.0, np.float32),
        edge_src=edge_src,
        edge_dst=edge_dst,
        edge_share=pad1(share, E, 0.0, np.float32),
        edge_remote=pad1(st.edge_remote.astype(np.float32), E, 0.0, np.float32),
        edge_src_cont=pad1(st.cont_of[st.edge_src], E, K - 1, np.int32),
        edge_dst_cont=pad1(st.cont_of[st.edge_dst], E, K - 1, np.int32),
        edge_mask=edge_mask,
        ell_src=ell_src,
        ell_dst=ell_dst,
    )
    return arrays


# ---------------------------------------------------------------------------
# The tick kernel (pure JAX; scanned, vmapped over configurations)
# ---------------------------------------------------------------------------


def _one_hot(cont_of: jnp.ndarray, n_cont: int) -> jnp.ndarray:
    return (cont_of[:, None] == jnp.arange(n_cont)[None, :]).astype(jnp.float32)


#: Precision of the one-hot instance→container contractions.  A TPU matmul
#: unit pass at default precision rounds f32 operands to bf16 (≈0.4% per
#: tick, which the multiplicative backpressure loop would compound over
#: hundreds of ticks); HIGHEST keeps them f32-exact whichever lowering the
#: compiler picks for a shape.  CPU dots are f32 either way.
_EXACT = jax.lax.Precision.HIGHEST


def _platform() -> str:
    """The backend new arrays land on: the ``jax.default_device`` override
    when one is set, else the default backend.  Every cache that holds
    device results or buffers keys on it, so an entry made on one backend
    never answers another (a CPU reference run beside a TPU run)."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def _summarize_windowed(samples: dict, is_source) -> dict:
    """THE summary reductions — the single definition both modes share.

    ``samples`` is the windowed metric pytree of one run ((S, I) per-instance
    series, (S, K) per-container series, (S,) gate); ``is_source`` marks the
    source instances.  Returns the per-run summary pytree:

    * ``src_half_mean`` — second-half mean of the per-sample total source
      throughput (the ``achieved_ktps`` numerator, in ktuples/tick),
    * ``caputil_half_mean`` / ``bp_half_mean`` — (I,) second-half means,
    * ``sm_half_mean`` — (K,) second-half mean SM busy,
    * ``mem_peak`` — (I,) trajectory peak memory,
    * ``gate_final`` — final admission-gate value.

    In summary mode this runs *inside* the tick kernel (fused epilogue,
    under vmap/pmap, on bucket-padded arrays); in full mode the same
    function is jitted standalone over the sliced host trajectory
    (:func:`_host_summary`).  Padded instances/containers contribute exact
    zeros to the masked source sum and occupy trailing slots of the
    per-instance vectors (sliced away on unpack), and CPU XLA reductions
    are sequential — so the two routes agree bitwise, which is the
    summary-vs-full numerical contract the test matrix pins down.
    """
    proc = samples["proc"]
    half = proc.shape[0] // 2
    src = is_source.astype(proc.dtype)
    per_sample_src = (proc * src[None, :]).sum(axis=1)
    return dict(
        src_half_mean=per_sample_src[half:].mean(),
        caputil_half_mean=samples["caputil"][half:].mean(axis=0),
        sm_half_mean=samples["sm_cpu"][half:].mean(axis=0),
        bp_half_mean=samples["bp"][half:].mean(axis=0),
        mem_peak=samples["mem"].max(axis=0),
        gate_final=samples["gate"][-1],
    )


#: Metric keys :func:`_summarize_windowed` actually reads — the host-side
#: jit below is traced on exactly this subset so its compile cache is
#: insensitive to unrelated trajectory keys.
_SUMMARY_INPUT_KEYS = ("proc", "caputil", "sm_cpu", "bp", "mem", "gate")


@jax.jit
def _summarize_jit(samples: dict, is_source):
    return _summarize_windowed(samples, is_source)


def _host_summary(samples: dict, is_source: np.ndarray) -> dict:
    """Full-mode lazy summary: the shared jitted reductions applied to a
    host-side (already sliced) trajectory, returned as numpy."""
    sub = {k: jnp.asarray(np.asarray(samples[k])) for k in _SUMMARY_INPUT_KEYS}
    out = _summarize_jit(sub, jnp.asarray(np.asarray(is_source)))
    return {k: np.asarray(v) for k, v in jax.device_get(out).items()}


def _expand_routing(W_node, node_of, cont_of, inst_mask):
    """The dense kernel's (I, I) routing weights and cross-container mask,
    expanded from the node-pair table once per row, before the tick scan:
    ``W[i, j] = W_node[node_of[i], node_of[j]]`` and ``remote[i, j] =
    cont_of[i] != cont_of[j]``, both zero wherever ``i`` or ``j`` is a
    padded instance.  Exact: each lookup is a select-sum over the node axis
    with at most one nonzero term, so no per-element 2-D gather (slow on a
    TPU) and no matmul pass rounds a weight."""
    real = inst_mask > 0
    # (I, Nb) one-hot of each instance's node; a padded instance has none,
    # so its row and its column of W sum nothing
    hot = (node_of[:, None] == jnp.arange(W_node.shape[0])[None, :]) & real[:, None]
    rows = jnp.where(hot[:, :, None], W_node[None, :, :], 0.0).sum(axis=1)
    W = jnp.where(hot.T[:, None, :], rows.T[:, :, None], 0.0).sum(axis=0)
    remote = (cont_of[:, None] != cont_of[None, :]) & real[:, None] & real[None, :]
    return W, remote


def _simulate_core(
    arrays: dict,
    offered_per_tick: jnp.ndarray,  # (n_ticks,) total source ktuples per tick
    seed: jnp.ndarray,              # () int32
    dt: float,
    noise_std: float,
    q_high: float,
    q_low: float,
    gc_heap: float,
    gc_cost: float,
    mem_alloc: float,
    *,
    n_ticks: int,
    sample_every: int,
    backend: str = "dense",
    samples_mode: str = "full",
):
    """One padded configuration's trajectory.  Pure function of bucket-shaped
    arrays — batched via ``jax.vmap`` and compiled once per bucket.

    ``backend`` selects the SM-transfer formulation: ``"dense"`` is the
    original (I, I) flow-matrix oracle; ``"sparse"`` runs the numerically
    equivalent edge-list step — per-edge gathers plus ELL segment sums
    (static (I, D) row-gather matrices + row reductions, see
    :func:`pad_structure`) — whose per-tick cost is O(E + I·D) instead of
    O(I²).  The same fused step, in segment-sum form, is the
    contract of :mod:`repro.kernels.stream_flow` (jnp reference + Pallas
    TPU kernel).

    ``samples_mode`` picks the output payload: ``"full"`` returns the
    windowed metric trajectory ((S, ...) per metric), ``"summary"`` fuses
    :func:`_summarize_windowed` into the kernel epilogue and returns only
    the O(I) summary pytree — the trajectory never leaves the device.
    The tick physics is identical; the scan is window-nested in both modes
    (per-window metric means accumulate inside the outer scan instead of
    materializing per-tick (T, ...) stacks), which is bitwise-identical to
    the historical flat scan + reshape + mean and measurably faster.
    """
    busy_cost = arrays["busy_cost"]
    cpu_cost = arrays["cpu_cost"]
    gamma = arrays["gamma"]
    is_source = arrays["is_source"]
    cont_cpus = arrays["cont_cpus"]
    sm_cost_eff = arrays["sm_cost_eff"]
    mem_base = arrays["mem_base"]
    mem_slope = arrays["mem_slope"]
    inst_mask = arrays["inst_mask"]
    cont_mask = arrays["cont_mask"]
    cont_of = arrays["cont_of"]
    # (I, K) one-hot: container sums are exact contractions against it;
    # container → instance broadcasts are the exact gather ``x[cont_of]``
    C = _one_hot(cont_of, cont_cpus.shape[0])
    n_inst = busy_cost.shape[0]
    n_cont = cont_cpus.shape[0]
    n_src = jnp.maximum(is_source.sum(), 1)
    if backend == "dense":
        W, remote = _expand_routing(
            arrays["W_node"], arrays["node_of"], cont_of, inst_mask
        )
        rowsum = W.sum(axis=1)
    else:
        rowsum = arrays["rowsum"]
        e_src = arrays["edge_src"]
        e_share = arrays["edge_share"]
        e_remote = arrays["edge_remote"]
        e_sc = arrays["edge_src_cont"]
        e_dc = arrays["edge_dst_cont"]
        ell_src = arrays["ell_src"]
        ell_dst = arrays["ell_dst"]

        def _ell_sum(vals: jnp.ndarray, ell: jnp.ndarray) -> jnp.ndarray:
            # segment sum in ELL form: gather the per-edge values into the
            # static (I, D) row layout and reduce rows — pure gathers, no
            # scatters (XLA CPU serializes scatter-adds per element) and no
            # cumsum dependency chain.  Row padding gathers the appended
            # exact 0.0 sentinel, a no-op under summation.
            return jnp.concatenate([vals, jnp.zeros(1, vals.dtype)])[ell].sum(axis=1)

        def _by_src(vals: jnp.ndarray) -> jnp.ndarray:
            return _ell_sum(vals, ell_src)

        def _by_dst(vals: jnp.ndarray) -> jnp.ndarray:
            return _ell_sum(vals, ell_dst)

    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, n_ticks)

    def tick(state, inp):
        qin, qout, mem, admit, sm_cpu_prev = state
        offered, k = inp
        noise = 1.0 + noise_std * jax.random.normal(k, (n_inst,))
        noise = jnp.clip(noise, 0.7, 1.3)
        busy = busy_cost * noise

        # 1) spouts are pull-based: they admit min(offered, admit) per tick;
        #    ``admit`` is the backpressure-driven rate limit (token bucket).
        admitted = jnp.minimum(offered, admit)
        src_want = admitted / n_src

        # 2) desired processing, limited by single-thread capacity; padded
        #    instances are masked to zero so they never consume or emit.
        cap_tuples = dt / jnp.maximum(busy, 1e-9)
        want = jnp.where(is_source, jnp.minimum(src_want, cap_tuples),
                         jnp.minimum(qin, cap_tuples))
        want = want * inst_mask

        # 3) container CPU contention (incl. last tick's SM CPU)
        demand = jnp.matmul(C.T, want * cpu_cost, precision=_EXACT) + sm_cpu_prev
        scale_c = jnp.minimum(1.0, cont_cpus * dt / jnp.maximum(demand, 1e-9))
        proc = want * scale_c[cont_of]
        qin = qin - jnp.where(is_source, 0.0, proc)
        out_copies = proc * gamma * rowsum
        qout = qout + out_copies

        # 4) SM transfer with per-container capacity
        sm_budget = dt / jnp.maximum(sm_cost_eff, 1e-9)     # traversals per tick
        if backend == "dense":
            # desired flow matrix if everything in qout were released this tick
            share = W / jnp.maximum(rowsum, 1e-9)[:, None]
            F_want = qout[:, None] * share                  # (I, I) copies
            # per-source-SM traversals and per-dest-SM net arrivals
            orig_c = jnp.matmul(C.T, F_want.sum(axis=1), precision=_EXACT)
            arr_c = jnp.matmul((F_want * remote).sum(axis=0), C, precision=_EXACT)
            s_c = jnp.minimum(1.0, sm_budget / jnp.maximum(orig_c + arr_c, 1e-9))
            s_inst = s_c[cont_of]
            # a flow is limited by the slowest SM on its path (source SM
            # always; destination SM only when crossing containers)
            eff = jnp.minimum(
                s_inst[:, None], jnp.where(remote, s_inst[None, :], 1.0)
            )
            F = F_want * eff
            delivered_from = F.sum(axis=1)
            arrivals = F.sum(axis=0)
            trav_c = (
                jnp.matmul(C.T, F.sum(axis=1), precision=_EXACT)
                + jnp.matmul((F * remote).sum(axis=0), C, precision=_EXACT)
            )
        else:
            # same physics in edge-list form: gather → throttle → gather,
            # with per-instance CSR sums aggregated to containers by the
            # (I, K) one-hot matmul (identical grouping, O(E + I·K) per tick)
            f_want = qout[e_src] * e_share
            orig_c = jnp.matmul(_by_src(f_want), C, precision=_EXACT)
            arr_c = jnp.matmul(_by_dst(f_want * e_remote), C, precision=_EXACT)
            s_c = jnp.minimum(1.0, sm_budget / jnp.maximum(orig_c + arr_c, 1e-9))
            eff = jnp.minimum(
                s_c[e_sc], jnp.where(e_remote > 0, s_c[e_dc], 1.0)
            )
            f = f_want * eff
            delivered_from = _by_src(f)
            arrivals = _by_dst(f)
            trav_c = (
                jnp.matmul(delivered_from, C, precision=_EXACT)
                + jnp.matmul(_by_dst(f * e_remote), C, precision=_EXACT)
            )
        qout = qout - delivered_from
        qin = qin + jnp.where(is_source, 0.0, arrivals)

        # SM CPU consumed this tick (feeds next tick's contention); padded
        # containers are masked out.
        trav_c = trav_c * cont_mask
        sm_cpu = trav_c * sm_cost_eff

        # 5) memory sawtooth + GC
        mem_live = mem_base + mem_slope * (proc / dt)
        mem = jnp.maximum(mem + proc * mem_alloc, mem_live)
        gc_trigger = mem > (mem_live + gc_heap)
        mem = jnp.where(gc_trigger, mem_live, mem)

        # 6) spout throttle: Heron-style backpressure adjusts the admission
        #    rate multiplicatively (gentle steps -> tight equilibrium at the
        #    sustainable rate); growth only once queues have drained.
        congested = (qin.max() > q_high) | (qout.max() > q_high)
        relaxed = (qin.max() < q_low) & (qout.max() < q_low)
        admit = jnp.where(
            congested, admit * 0.98, jnp.where(relaxed, admit * 1.02, admit)
        )
        admit = jnp.clip(admit, 1e-3, 1e9)

        metrics = dict(
            proc=proc,
            out=proc * gamma,
            caputil=proc * busy / dt,
            cputil=proc * cpu_cost / dt,
            mem=mem,
            gc=gc_trigger.astype(jnp.float32) * gc_cost,
            bp=jnp.where(is_source, (admitted < 0.98 * offered).astype(jnp.float32),
                         (qin > q_high).astype(jnp.float32)),
            sm_trav=trav_c,
            sm_cpu=sm_cpu / dt,
            gate=admit,
        )
        return (qin, qout, mem, admit, sm_cpu), metrics

    # initial admission: start LOW and grow multiplicatively — approaching the
    # ceiling from below avoids flooding deep pipelines with backlog that
    # takes the whole run to drain (slow-start, like TCP)
    src_cap0 = jnp.where(is_source, dt / jnp.maximum(busy_cost, 1e-9), 0.0).sum()
    state0 = (
        jnp.zeros(n_inst),
        jnp.zeros(n_inst),
        mem_base + 0.0,
        src_cap0 * 0.05,
        jnp.zeros(cont_cpus.shape[0]),
    )
    # window-nested scan: the outer scan walks the S sample windows, the
    # inner scan runs the ``sample_every`` ticks of one window and its
    # per-tick metrics are reduced to the window mean on the spot — the
    # (T, ...) per-tick stacks of the historical flat scan never
    # materialize.  Reduction order over each window's ticks is unchanged,
    # so the sampled trajectory is bitwise-identical to the flat form.
    n_samples = n_ticks // sample_every

    def window(carry, inp):
        carry, traj = jax.lax.scan(tick, carry, inp)
        return carry, {k: v.mean(axis=0) for k, v in traj.items()}

    def to_windows(x):
        return x[: n_samples * sample_every].reshape(
            n_samples, sample_every, *x.shape[1:]
        )

    _, samples = jax.lax.scan(
        window, state0, (to_windows(offered_per_tick), to_windows(keys))
    )
    if samples_mode == "summary":
        return _summarize_windowed(samples, is_source)
    return samples


# ---------------------------------------------------------------------------
# Compile cache: one jitted vmapped kernel per (batch, bucket, n_ticks)
# ---------------------------------------------------------------------------

_KERNEL_CACHE: dict[tuple, object] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def shard_count(batch: int, devices: int | None = None) -> int:
    """How many devices :func:`simulate_batch` shards a batch over.

    ``devices=None`` means auto: shard over local devices only while every
    shard keeps at least two configurations (small per-step batches stay on
    the single-device vmap path — pmap dispatch and one compile per batch
    shape are not worth paying for a 3-config measurement).  An explicit
    count overrides the threshold; ``devices=1`` forces the vmap path, and
    asking for more devices than the host has fails here, at the call
    site, rather than as a replica-count error deep inside ``pmap``.
    """
    available = jax.local_device_count()
    if devices is None:
        n = min(available, int(batch) // 2)
    else:
        n = int(devices)
        if n > available:
            raise ValueError(
                f"devices={n} requested but only {available} local "
                f"device(s) are available"
            )
    return max(1, min(n, int(batch)))


def _get_batch_kernel(batch: int, n_inst: int, n_cont: int, n_ticks: int,
                      sample_every: int, n_devices: int = 1,
                      backend: str = "dense", n_edges: int = 0,
                      d_out: int = 0, d_in: int = 0,
                      donate_batch: bool = True,
                      samples_mode: str = "full",
                      n_nodes: int = 0):
    """``batch`` is the per-device batch when ``n_devices > 1``;
    ``n_nodes`` is the dense layout's node bucket (0 for sparse)."""
    # Donate the padded batch buffers (stacked structure arrays,
    # per-tick loads, seeds): they are rebuilt from host numpy on every
    # call, so XLA may reuse their memory for full-mode trajectories.
    # Summary outputs are O(B·I) and alias none of them, and CPU XLA
    # cannot donate at all (either would only warn at every compile), so
    # donation is enabled for full mode on accelerators only.  Resident
    # batches (the staging cache) must survive the call, so they exclude
    # the structure arrays (arg 0).  The cache key carries the *effective*
    # donate tuple, so where nothing is donated a resident and a
    # non-resident call at the same shapes share one compile.
    platform = _platform()
    donate = (0, 1, 2) if donate_batch else (1, 2)
    if platform == "cpu" or samples_mode == "summary":
        donate = ()
    key = (batch, n_inst, n_cont, n_ticks, sample_every, n_devices,
           backend, n_edges, d_out, d_in, samples_mode, donate, platform,
           n_nodes)
    fn = _KERNEL_CACHE.get(key)
    if fn is None:
        _CACHE_STATS["misses"] += 1
        core = partial(_simulate_core, n_ticks=n_ticks,
                       sample_every=sample_every, backend=backend,
                       samples_mode=samples_mode)
        vmapped = jax.vmap(core, in_axes=(0, 0, 0) + (None,) * 7)
        if n_devices > 1:
            # one shard of the batch per device; scalars are broadcast
            fn = jax.pmap(
                vmapped,
                in_axes=(0, 0, 0) + (None,) * 7,
                donate_argnums=donate,
            )
        else:
            fn = jax.jit(vmapped, donate_argnums=donate)
        _KERNEL_CACHE[key] = fn
    else:
        _CACHE_STATS["hits"] += 1
    return fn


def kernel_cache_info() -> dict:
    """Tick-kernel compile-cache statistics.  ``misses`` counts distinct
    ``(batch, bucket_shape, n_ticks, backend, platform)`` traces — i.e. XLA
    compilations.  ``entries`` describes each resident compiled kernel
    (per-device batch, bucket shape, edge or node bucket, tick count,
    device count, backend, platform), so BENCH extras record exactly what
    compiled.
    """
    return {
        "size": len(_KERNEL_CACHE),
        **_CACHE_STATS,
        "entries": [
            {
                "batch": k[0],
                "n_inst": k[1],
                "n_cont": k[2],
                "n_ticks": k[3],
                "sample_every": k[4],
                "devices": k[5],
                "backend": k[6],
                "n_edges": k[7],
                "d_out": k[8],
                "d_in": k[9],
                "samples": k[10],
                "platform": k[12],
                "n_nodes": k[13],
            }
            for k in _KERNEL_CACHE
        ],
    }


def clear_kernel_cache() -> None:
    _KERNEL_CACHE.clear()
    _CACHE_STATS["hits"] = 0
    _CACHE_STATS["misses"] = 0


# ---------------------------------------------------------------------------
# Device-resident batch cache (staged, stacked structure arrays)
# ---------------------------------------------------------------------------

#: Stacked + device-resident batch arrays keyed by (configs, params, bucket
#: shapes, backend, shard layout, platform).  A fleet replan that re-scores the same
#: pruned candidate ladder reuses the resident buffers instead of paying
#: np.stack + host→device staging every round.  Value-keyed (Configuration
#: is hashable-by-value), so identical candidate sets hit regardless of
#: object identity.  LRU-bounded by entries *and* approximate bytes — a
#: 512-bucket dense batch would otherwise pin hundreds of MB.
_RESIDENT_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_RESIDENT_STATS = {"hits": 0, "misses": 0, "bytes": 0}
_RESIDENT_CACHE_MAX_ENTRIES = 32
_RESIDENT_CACHE_MAX_BYTES = 1 << 28      # 256 MB of staged batch arrays


def _resident_put(key: tuple, arrays: dict) -> None:
    nbytes = sum(int(np.asarray(v).nbytes) for v in arrays.values())
    if nbytes > _RESIDENT_CACHE_MAX_BYTES:
        return                            # larger than the whole budget
    _RESIDENT_CACHE[key] = (arrays, nbytes)
    _RESIDENT_STATS["bytes"] += nbytes
    while (
        len(_RESIDENT_CACHE) > _RESIDENT_CACHE_MAX_ENTRIES
        or _RESIDENT_STATS["bytes"] > _RESIDENT_CACHE_MAX_BYTES
    ):
        _, (_, evicted) = _RESIDENT_CACHE.popitem(last=False)
        _RESIDENT_STATS["bytes"] -= evicted


def resident_cache_info() -> dict:
    """Batch-staging (device-residency) cache statistics."""
    return {"size": len(_RESIDENT_CACHE), **_RESIDENT_STATS}


def clear_resident_cache() -> None:
    _RESIDENT_CACHE.clear()
    _RESIDENT_STATS["hits"] = 0
    _RESIDENT_STATS["misses"] = 0
    _RESIDENT_STATS["bytes"] = 0


# ---------------------------------------------------------------------------
# Host-side API
# ---------------------------------------------------------------------------

#: Host-transfer accounting for the evaluation path.  ``bytes_full`` /
#: ``bytes_summary`` count device→host bytes moved by :func:`_run_batch`'s
#: single per-batch ``jax.device_get`` (split by payload mode);
#: ``bytes_h2d`` counts host→device bytes it stages (the stacked structure
#: arrays, unless the resident cache already holds them, plus the per-tick
#: loads and seeds of every call);
#: ``refetches`` counts summary-backed results that lazily re-ran full-mode
#: for trajectory access (learning paths); ``staged_devices`` is the most
#: distinct devices one batch's staged structure arrays occupied (the shard
#: count a sharded sweep really reached).  BENCH extras and
#: :func:`repro.streams.cache.cache_stats` embed this snapshot.
_TRANSFER_STATS = {
    "batches": 0, "bytes_full": 0, "bytes_summary": 0, "bytes_h2d": 0,
    "refetches": 0, "staged_devices": 0,
}


def transfer_info() -> dict:
    """Host↔device transfer statistics for the evaluation path (see
    ``_TRANSFER_STATS`` for field meanings)."""
    return dict(_TRANSFER_STATS)


def clear_transfer_stats() -> None:
    for k in _TRANSFER_STATS:
        _TRANSFER_STATS[k] = 0


class TrajectoryUnavailable(RuntimeError):
    """Raised on trajectory access (``SimResult.samples``) when the result
    is summary-backed and has no refetch hook — the trajectory was never
    shipped to the host and cannot be recovered."""


def _bottleneck_from_reductions(
    node_of: np.ndarray,
    node_names: list,
    half: np.ndarray,
    sm_busy: float,
    saturation_threshold: float,
    sm_threshold: float,
) -> str | None:
    """Vectorized bottleneck attribution from second-half reductions.

    ``half`` is the per-instance second-half mean caputil, ``sm_busy`` the
    max per-container second-half mean SM busy.  Group-max per node runs as
    one ``np.maximum.at`` gather-scatter instead of a per-instance Python
    loop; ties resolve to the node that *first appears* in instance order,
    which is exactly the dict-insertion ``max()`` semantics of the loop
    form (kept as a test oracle in ``tests/test_summary_mode.py``) — the
    two are bitwise-identical on the same inputs.
    """
    node_of = np.asarray(node_of)
    vals = np.asarray(half, np.float64)
    # 0.0 floor mirrors the loop's ``per_node.get(nm, 0.0)`` seed
    node_max = np.zeros(len(node_names), np.float64)
    np.maximum.at(node_max, node_of, vals)
    uniq, first = np.unique(node_of, return_index=True)
    order = uniq[np.argsort(first, kind="stable")]
    j = int(np.argmax(node_max[order]))          # first max wins
    name = node_names[int(order[j])]
    val = float(node_max[order[j]])
    if sm_busy > val and sm_busy > sm_threshold:
        return STREAM_MANAGER
    return name if val > saturation_threshold else None


class SimResult:
    """One configuration's evaluation result — lazily backed.

    ``mode="full"`` results hold the windowed metric trajectory in
    :attr:`samples` (the historical payload).  ``mode="summary"`` results
    hold only the on-device-computed summary pytree (:attr:`summary`);
    trajectory access through :attr:`samples` transparently *refetches* a
    full-mode run of the same (config, load, seed, backend) — bitwise what
    full mode would have returned, by the bucket-invariance contract — or
    raises :class:`TrajectoryUnavailable` when constructed without a
    refetch hook.  Scoring consumers (:attr:`achieved_ktps`,
    :meth:`bottleneck_node`) answer from the summary in both modes, so the
    two modes agree exactly; learning consumers (:meth:`to_metrics_store`)
    need the trajectory and trigger the refetch path.
    """

    def __init__(
        self,
        structure: SimStructure,
        params: SimParams,
        offered_ktps: np.ndarray,
        samples: dict | None = None,
        summary: dict | None = None,
        mode: str = "full",
        refetch=None,
    ) -> None:
        if samples is None and summary is None:
            raise ValueError("SimResult needs samples and/or summary")
        self.structure = structure
        self.params = params
        self.offered_ktps = offered_ktps
        self.mode = mode
        self._samples = samples
        self._summary = summary
        self._refetch = refetch
        self._achieved: float | None = None

    @property
    def samples(self) -> dict:
        """The windowed metric trajectory; summary-backed results refetch
        it lazily (one full-mode single-row kernel run, counted in
        :func:`transfer_info` as a ``refetch``)."""
        if self._samples is None:
            if self._refetch is None:
                raise TrajectoryUnavailable(
                    "summary-backed SimResult has no trajectory; re-evaluate "
                    "with samples='full' (or through a refetch-capable path)"
                )
            _TRANSFER_STATS["refetches"] += 1
            self._samples = self._refetch()
        return self._samples

    @property
    def summary(self) -> dict:
        """The :func:`_summarize_windowed` reductions (numpy, sliced to the
        real instance/container counts) — precomputed on device in summary
        mode, computed lazily from the trajectory in full mode via the
        *same* jitted reduction (so the modes agree bitwise)."""
        if self._summary is None:
            self._summary = _host_summary(
                self._samples, self.structure.is_source
            )
        return self._summary

    @property
    def achieved_ktps(self) -> float:
        """Steady-state delivered source rate (mean of second half).
        Memoized — policies read it repeatedly per step."""
        if self._achieved is None:
            self._achieved = float(
                self.summary["src_half_mean"] / self.params.dt
            )
        return self._achieved

    def bottleneck_node(
        self,
        saturation_threshold: float = 0.8,
        sm_threshold: float = 0.9,
    ) -> str | None:
        """Most saturated node (by mean caputil over the last half), or the
        stream manager when it dominates; ``None`` when nothing exceeds
        ``saturation_threshold`` (no bottleneck observed).

        The thresholds belong to the *caller's* control policy — an engine
        evaluator passes its own ``saturation_threshold`` here so policy
        guards and bottleneck attribution judge saturation by one number
        (defaults preserve the historical 0.8 / 0.9 cutoffs).  Answers
        from the summary reductions in both modes (no trajectory access).
        """
        s = self.summary
        sm_half = np.asarray(s["sm_half_mean"])
        sm_busy = float(sm_half.max()) if sm_half.size else 0.0
        return _bottleneck_from_reductions(
            self.structure.node_of,
            self.structure.node_names,
            s["caputil_half_mean"],
            sm_busy,
            saturation_threshold,
            sm_threshold,
        )

    def to_metrics_store(self) -> MetricsStore:
        """Package the trajectory as Heron-style metric timeseries.

        Column extraction is vectorized: each (samples, instances) metric
        matrix is transposed once into a contiguous (instances, samples)
        layout, so per-instance series are contiguous row views rather than
        I strided column slices, and the node-name / container lookups run
        as whole-array gathers instead of per-element Python conversions.
        Values are bitwise-identical to the historical per-column loop
        (transpose commutes with the elementwise rate division).  The SM
        rows share one read-only fill/zeros array across containers.
        """
        store = MetricsStore()
        st = self.structure
        dt = self.params.dt
        rows = {
            k: np.ascontiguousarray(np.asarray(self.samples[k]).T)
            for k in ("proc", "out", "cputil", "caputil", "mem", "gc", "bp")
        }
        proc = rows["proc"] / dt                           # ktps in
        out = rows["out"] / dt                             # ktps out
        names = [st.node_names[n] for n in st.node_of.tolist()]
        conts = st.cont_of.tolist()
        for i in range(st.n_inst):
            store.add(
                InstanceSamples(
                    node=names[i],
                    container=conts[i],
                    slot=i,
                    rate_in_ktps=proc[i],
                    rate_out_ktps=out[i],
                    cputil=rows["cputil"][i],
                    caputil=rows["caputil"][i],
                    memutil_mb=rows["mem"][i],
                    gctime=rows["gc"][i],
                    backpressure=rows["bp"][i],
                )
            )
        trav = np.ascontiguousarray(np.asarray(self.samples["sm_trav"]).T) / dt
        smc = np.ascontiguousarray(np.asarray(self.samples["sm_cpu"]).T)
        n_samples = trav.shape[1]
        sm_mem = np.full(n_samples, 256.0)
        sm_zero = np.zeros(n_samples)
        for c in range(st.n_cont):
            store.add(
                InstanceSamples(
                    node=STREAM_MANAGER,
                    container=c,
                    slot=-1,
                    rate_in_ktps=trav[c],
                    rate_out_ktps=trav[c],
                    cputil=smc[c],
                    caputil=smc[c],
                    memutil_mb=sm_mem,
                    gctime=sm_zero,
                    backpressure=sm_zero,
                )
            )
        return store


def is_scalar_load(x) -> bool:
    """True for a plain/0-d scalar offered load.  np.ndim would choke on a
    ragged list of mixed scalar and per-sample-trace loads (a supported
    shape), so never call it on the container."""
    return np.isscalar(x) or getattr(x, "ndim", None) == 0


def _per_tick_trace(offered_ktps, n_ticks: int, dt: float) -> np.ndarray:
    """Expand a scalar rate or a piecewise-constant trace to per-tick loads.

    A scalar holds for the whole run.  A 1-D trace of length ``L`` is
    treated as **piecewise-constant**: each entry is held for
    ``ceil(n_ticks / L)`` consecutive ticks (entry-wise repetition, not
    whole-sequence tiling), and the expansion is truncated to ``n_ticks``
    — so when ``L`` does not divide ``n_ticks`` the final entries get
    proportionally fewer ticks (a trace longer than ``n_ticks`` simply
    truncates).  An empty trace is ambiguous (there is no rate to hold)
    and raises.
    """
    offered = np.asarray(offered_ktps, np.float64)
    if offered.ndim == 0:
        return np.full(n_ticks, float(offered) * dt)
    if offered.shape[0] == 0:
        raise ValueError("offered_ktps trace is empty: no rate to hold")
    reps = int(np.ceil(n_ticks / offered.shape[0]))
    return np.repeat(offered, reps)[:n_ticks] * dt


# ---------------------------------------------------------------------------
# Cache-first evaluation: request canonicalization + in-batch dedup (Tier 1)
# and value-keyed result memoization (Tier 2)
# ---------------------------------------------------------------------------

#: Tier-1 accounting: rows submitted vs rows that actually reached the tick
#: kernel.  ``rows_in / rows_executed`` is the dedup/memoization factor a
#: fleet replan achieves (1,000 tenants over 8 archetypes ⇒ ≥ 125×).
_DEDUP_STATS = {"batches": 0, "rows_in": 0, "rows_unique": 0, "rows_executed": 0}


def dedup_info() -> dict:
    """In-batch request-dedup statistics for :func:`simulate_batch`.

    ``rows_in`` counts submitted rows, ``rows_unique`` the value-distinct
    rows after canonicalization, and ``rows_executed`` the rows that
    actually ran the tick kernel (unique rows minus result-cache hits).
    """
    return dict(_DEDUP_STATS)


def clear_dedup_stats() -> None:
    for k in _DEDUP_STATS:
        _DEDUP_STATS[k] = 0


def _canonical_load(offered) -> object:
    """Hashable value key for one offered-load entry: scalars collapse to
    ``float`` (quantization-to-exact — ``400`` and ``400.0`` are one
    request), per-sample traces to their float64 shape + bytes."""
    if is_scalar_load(offered):
        return float(offered)
    a = np.asarray(offered, np.float64)
    return ("trace", a.shape, a.tobytes())


def _result_nbytes(res: "SimResult") -> int:
    """Approximate resident bytes of one cached :class:`SimResult` (the
    sample arrays — or the ~100×-smaller summary pytree for summary-backed
    results, so the bytes-bounded LRU holds correspondingly more of them;
    the structure is shared through ``structure_for``)."""
    payload = res._samples if res._samples is not None else res._summary
    return int(
        sum(np.asarray(v).nbytes for v in payload.values())
        + np.asarray(res.offered_ktps).nbytes
    )


def simulate_batch(
    configs: Sequence[Configuration],
    offered_ktps,
    duration_s: float = 20.0,
    params: SimParams = SimParams(),
    seeds: Sequence[int] | None = None,
    min_inst_bucket: int = 0,
    min_cont_bucket: int = 0,
    devices: int | None = None,
    min_batch_bucket: int = 0,
    tick_kernel: str = "auto",
    min_edge_bucket: int = 0,
    min_degree_bucket: int = 0,
    resident: bool = False,
    samples: str = "full",
    dedup: bool = True,
    cache=None,
    cache_token=None,
    min_node_bucket: int = 0,
) -> list[SimResult]:
    """Evaluate N configurations in one vmapped (and device-sharded) call.

    ``samples`` picks the per-result payload (:data:`SAMPLES_MODES`):
    ``"full"`` (default, the historical behaviour) ships every row's whole
    windowed trajectory to the host — O(B·S·I) bytes; ``"summary"`` fuses
    the scoring reductions (:func:`_summarize_windowed`) into the kernel
    epilogue and transfers only the O(B·I) summary pytree, in ONE
    ``device_get`` for the whole batch.  Summary-backed results answer
    ``achieved_ktps`` / ``bottleneck_node`` exactly as full results do
    (the reductions are shared) and lazily refetch a full-mode run on
    trajectory access.  ``cache`` keys carry the mode, so summary and full
    entries never answer each other's lookups; :func:`transfer_info`
    reports the bytes moved per mode.

    ``offered_ktps`` is either one *scalar* load shared by every
    configuration or a sequence of per-configuration loads (each a scalar or
    a per-sample trace).  A bare 1-D array is always interpreted as
    per-configuration loads — to share one trace across every configuration
    pass ``[trace] * len(configs)``.  All configurations are padded to a
    common shape bucket; the
    ``min_*_bucket`` floors let a caller pin the bucket it already compiled
    (sticky bucketing — see :class:`repro.streams.engine.SimulatorEvaluator`).

    ``devices`` shards the batch: ``None`` (auto) splits it across local
    devices via ``pmap`` while every shard keeps at least two
    configurations (see :func:`shard_count`), an explicit count pins the
    shard count, and ``1`` forces the single-device vmap path.  The batch
    is padded to a multiple of the shard count by replicating the last
    configuration (replicas are dropped on unpack), so sharded results are
    bitwise-identical to the unsharded path.

    ``min_batch_bucket`` (> 0) additionally pads the *batch axis* up to the
    :data:`BATCH_LADDER` rung ≥ the floor, again by replicating the last
    configuration.  Shard counts are then derived from the bucketed batch,
    so fleet traces whose candidate counts fluctuate replan after replan
    keep hitting the same compiled kernel (see
    ``SimulatorEvaluator(sticky_batch=True)``).  Padding rows are data-
    parallel replicas sliced away on unpack — results stay bitwise-identical
    to the unbucketed call.

    ``tick_kernel`` selects the per-tick flow physics: ``"dense"`` (the
    (I, I) flow-matrix oracle), ``"sparse"`` (edge-list gathers + ELL
    segment sums, O(E) per tick — numerically equivalent to dense, to
    float tolerance), or ``"auto"`` (sparse when the batch's densest
    structure sits below :data:`SPARSE_DENSITY_THRESHOLD`; the decision
    uses unpadded counts, so bucket floors never flip it).  The sparse
    edge axis is padded to :data:`EDGE_LADDER` with the sticky
    ``min_edge_bucket`` floor, and the ELL row widths to
    :data:`DEGREE_LADDER` buckets with the sticky ``min_degree_bucket``
    floor; padded edges
    carry zero share and padded ELL slots gather an exact 0.0, so results
    are bitwise invariant to both buckets.  The dense node-pair table is
    padded to :data:`NODE_LADDER` with the sticky ``min_node_bucket``
    floor; its padded entries are never looked up, so results are bitwise
    invariant to it as well.

    ``resident=True`` caches the stacked, *device-resident* structure
    arrays keyed by (configs, params, buckets, backend, shard layout): a
    caller that re-submits the same candidate set — a fleet replan
    re-scoring its pruned ladder — skips ``np.stack`` and host→device
    staging entirely (see :func:`resident_cache_info`; per-tick loads and
    seeds are still staged fresh each call).  Resident structure buffers
    are excluded from XLA donation so they survive the call.

    ``dedup=True`` (Tier 1 of the cache-first evaluation path)
    canonicalizes each row to a value key — (configuration, offered load,
    seed) — collapses duplicates *before* padding/stacking, runs the tick
    kernel on the unique rows only, and scatters results back in
    submission order (duplicate rows share one :class:`SimResult` object).
    Rows on the vmapped batch axis are data-parallel and independent, so
    the outputs are bitwise-identical to the undeduped path;
    :func:`dedup_info` reports the collapse factor.  ``cache=`` (Tier 2)
    accepts a :class:`repro.streams.cache.ResultCache` (anything with
    ``get(key)`` / ``put(key, value, nbytes)``): unique rows are looked up
    and filled by full value key — (config, load, seed, params, tick
    count, resolved backend, ``cache_token``, platform) — so an identical
    resubmission across calls costs zero kernel executions.  The key
    carries the *resolved* backend and the platform (dense and sparse, and
    CPU and accelerator, agree only to float tolerance) but neither
    buckets nor device/residency layout: results
    are bitwise invariant to those (the bucketing contract), so an entry
    computed at any layout answers every layout.  ``cache_token`` is the
    caller's invalidation handle — the engine layer passes the learner's
    ``ModelStore.version``, so calibration/retrain makes stale entries
    unreachable.  ``dedup=False, cache=None`` is the escape hatch that
    preserves the historical path exactly (no canonicalization, no
    accounting, every submitted row reaches the kernel).
    """
    if samples not in SAMPLES_MODES:
        raise ValueError(f"samples={samples!r} not in {SAMPLES_MODES}")
    configs = list(configs)
    if not configs:
        return []
    B = len(configs)
    if is_scalar_load(offered_ktps):
        offered_list = [offered_ktps] * B
    else:
        offered_list = list(offered_ktps)
        if len(offered_list) != B:
            raise ValueError(
                f"offered_ktps has {len(offered_list)} entries for {B} configs"
            )
    if seeds is None:
        seeds = [params.seed] * B
    seeds = list(seeds)
    if len(seeds) != B:
        raise ValueError("seeds must match configs")
    n_ticks = int(duration_s / params.dt)
    n_ticks = (n_ticks // params.sample_every) * params.sample_every

    def run(rows: list[int], kernel_sel: str) -> list[SimResult]:
        return _run_batch(
            [configs[i] for i in rows],
            [offered_list[i] for i in rows],
            [seeds[i] for i in rows],
            n_ticks=n_ticks,
            params=params,
            min_inst_bucket=min_inst_bucket,
            min_cont_bucket=min_cont_bucket,
            devices=devices,
            min_batch_bucket=min_batch_bucket,
            tick_kernel=kernel_sel,
            min_edge_bucket=min_edge_bucket,
            min_degree_bucket=min_degree_bucket,
            resident=resident,
            samples_mode=samples,
            min_node_bucket=min_node_bucket,
        )

    if not dedup and cache is None:
        return run(list(range(B)), tick_kernel)

    with span(spans.DEDUP):
        # Tier 1: collapse value-identical rows before padding/stacking.
        row_keys = [
            (c, _canonical_load(o), int(s))
            for c, o, s in zip(configs, offered_list, seeds)
        ]
        if dedup:
            first: dict = {}
            uniq: list[int] = []
            row_of: list[int] = []
            for i, k in enumerate(row_keys):
                j = first.get(k)
                if j is None:
                    j = len(uniq)
                    first[k] = j
                    uniq.append(i)
                row_of.append(j)
        else:
            uniq = list(range(B))
            row_of = list(range(B))
        _DEDUP_STATS["batches"] += 1
        _DEDUP_STATS["rows_in"] += B
        _DEDUP_STATS["rows_unique"] += len(uniq)

        results_u: list = [None] * len(uniq)
        backend = tick_kernel
        full_keys = None
        if cache is not None:
            # the backend is resolved from the unique rows' unpadded maxima —
            # identical to the full set's (duplicates share structures) — and
            # pinned for the executed subset, so key-backend == run-backend
            # even when cache hits remove the densest row
            sts = [structure_for(configs[i], params) for i in uniq]
            backend = resolve_tick_kernel(
                max(st.n_inst for st in sts),
                max(st.n_edges for st in sts),
                tick_kernel,
            )
            # the key carries the payload mode: a summary entry must never
            # answer a full-mode lookup (nor vice versa) — the payloads differ;
            # and the platform: backends agree only to a tolerance, so a CPU
            # reference must never be answered from accelerator results
            platform = _platform()
            full_keys = [
                row_keys[i] + (params, n_ticks, backend, samples, cache_token,
                               platform)
                for i in uniq
            ]
            miss = []
            for j, key in enumerate(full_keys):
                hit = cache.get(key)
                if hit is None:
                    miss.append(j)
                else:
                    results_u[j] = hit
        else:
            miss = list(range(len(uniq)))

    _DEDUP_STATS["rows_executed"] += len(miss)
    if miss:
        rows = [uniq[j] for j in miss]
        # Cache state must never drive tick-kernel recompiles: hits make
        # the executed subset's size data-dependent, and every distinct
        # size is a fresh XLA compile.  With a cache in play, pad the
        # subset to its BATCH_LADDER rung — sticky via the cache (one
        # cache ≈ one evaluator ≈ one trace), capped by this call's own
        # deduped rung so one huge replan never inflates later small
        # calls.  Without a cache the executed set is deterministic per
        # submission, so only restore the deduped size.  Replicas of the
        # last missed row are dropped by the zip below; batch padding is
        # bitwise-invariant (the bucketing contract).
        pad_to = len(uniq)
        if cache is not None:
            floor = int(getattr(cache, "batch_floor", 0))
            pad_to = min(
                batch_bucket_size(len(rows), floor),
                batch_bucket_size(len(uniq)),
            )
            try:
                cache.batch_floor = max(floor, pad_to)
            except AttributeError:
                pass
        rows += [rows[-1]] * (pad_to - len(rows))
        executed = run(rows, backend)
        for j, res in zip(miss, executed):
            results_u[j] = res
            if cache is not None:
                cache.put(full_keys[j], res, _result_nbytes(res))
    return [results_u[j] for j in row_of]


def _make_refetch(config, offered, seed, n_ticks: int, params: SimParams,
                  backend: str):
    """Refetch hook for one summary-backed result: re-run THIS row alone in
    full-sample mode.  Pins the batch's *resolved* backend (dense and
    sparse agree only to float tolerance) and goes straight to
    :func:`_run_batch` — bypassing dedup/result caches, so cache hit-rate
    accounting never counts refetches — at default buckets on one device:
    by the bucket-invariance contract the trajectory is bitwise what full
    mode would have returned at batch time."""

    def refetch() -> dict:
        return _run_batch(
            [config], [offered], [seed],
            n_ticks=n_ticks, params=params,
            min_inst_bucket=0, min_cont_bucket=0, devices=1,
            min_batch_bucket=0, tick_kernel=backend,
            min_edge_bucket=0, min_degree_bucket=0, resident=False,
            samples_mode="full",
        )[0]._samples

    return refetch


def _run_batch(
    configs: list[Configuration],
    offered_list: list,
    seeds: list,
    n_ticks: int,
    params: SimParams,
    min_inst_bucket: int,
    min_cont_bucket: int,
    devices: int | None,
    min_batch_bucket: int,
    tick_kernel: str,
    min_edge_bucket: int,
    min_degree_bucket: int,
    resident: bool,
    samples_mode: str = "full",
    min_node_bucket: int = 0,
) -> list[SimResult]:
    """Execute one already-canonicalized batch (loads expanded per row,
    seeds resolved, tick count fixed): pad, stack, stage, and run the
    vmapped/sharded tick kernel.  This is the historical
    :func:`simulate_batch` body — the public entry point decides *which
    rows* reach it.  The whole output pytree (trajectories or summaries,
    per ``samples_mode``) comes back in ONE ``jax.device_get``, counted in
    :func:`transfer_info`."""
    B = len(configs)
    B_bucket = batch_bucket_size(B, min_batch_bucket) if min_batch_bucket else B
    n_dev = shard_count(B_bucket, devices)
    with span(spans.STAGE_STRUCTURE):
        structures = [structure_for(c, params) for c in configs]
    n_inst_b = bucket_size(max(st.n_inst for st in structures), min_inst_bucket)
    n_cont_b = bucket_size(max(st.n_cont for st in structures), min_cont_bucket)
    backend = resolve_tick_kernel(
        max(st.n_inst for st in structures),
        max(st.n_edges for st in structures),
        tick_kernel,
    )
    n_edge_b = d_out_b = d_in_b = n_node_b = None
    if backend == "dense":
        n_node_b = node_bucket_size(
            max(st.n_nodes for st in structures), min_node_bucket
        )
    else:
        n_edge_b = edge_bucket_size(
            max(st.n_edges for st in structures), min_edge_bucket
        )
        d_out_b = degree_bucket_size(
            max(st.d_out for st in structures), min_degree_bucket
        )
        d_in_b = degree_bucket_size(
            max(st.d_in for st in structures), min_degree_bucket
        )

    with span(spans.STAGE_STACK):
        per_tick = np.stack([_per_tick_trace(o, n_ticks, params.dt) for o in offered_list])

    # pad the batch axis: up to the batch bucket (if any), then to a multiple
    # of the shard count, by replicating the last row (replicas are sliced
    # away below); then add the device axis when sharded
    fill = (B_bucket - B) + ((-B_bucket) % n_dev)
    def shard(a: np.ndarray) -> np.ndarray:
        if fill:
            a = np.concatenate([a, np.repeat(a[-1:], fill, axis=0)])
        if n_dev > 1:
            a = a.reshape(n_dev, -1, *a.shape[1:])
        return a
    per_dev_B = (B + fill) // n_dev

    stage_key = None
    stacked_dev = None
    if resident:
        stage_key = (
            tuple(configs), params, n_inst_b, n_cont_b, n_edge_b, d_out_b,
            d_in_b, n_node_b, backend, n_dev, fill, _platform(),
        )
        hit = _RESIDENT_CACHE.get(stage_key)
        if hit is not None:
            _RESIDENT_STATS["hits"] += 1
            _RESIDENT_CACHE.move_to_end(stage_key)
            stacked_dev = hit[0]
        else:
            _RESIDENT_STATS["misses"] += 1
    if stacked_dev is None:
        with span(spans.STAGE_PAD):
            padded = [
                _padded_for(st, params, n_inst_b, n_cont_b, n_edge_b, d_out_b,
                            d_in_b, n_node_b)
                for st in structures
            ]
        with span(spans.STAGE_STACK):
            stacked = {k: np.stack([p[k] for p in padded]) for k in padded[0]}
            if fill or n_dev > 1:
                stacked = {k: shard(v) for k, v in stacked.items()}
        with span(spans.STAGE_H2D):
            if n_dev > 1:
                # place each shard on its pmap device up front — a resident
                # hit then re-enters pmap with zero host→device transfers
                mesh = Mesh(np.array(jax.local_devices()[:n_dev]), ("shard",))
                stacked_dev = jax.device_put(
                    stacked, NamedSharding(mesh, PartitionSpec("shard"))
                )
            else:
                stacked_dev = {k: jnp.asarray(v) for k, v in stacked.items()}
        _TRANSFER_STATS["bytes_h2d"] += sum(int(v.nbytes) for v in stacked.values())
        if stage_key is not None:
            _resident_put(stage_key, stacked_dev)
    _TRANSFER_STATS["staged_devices"] = max(
        _TRANSFER_STATS["staged_devices"],
        len(stacked_dev["busy_cost"].devices()),
    )

    with span(spans.STAGE_STACK):
        per_tick_in = np.asarray(per_tick, np.float32)
        seeds_in = np.asarray(seeds, np.int32)
        if fill or n_dev > 1:
            per_tick_in = shard(per_tick_in)
            seeds_in = shard(seeds_in)

    compiles = _CACHE_STATS["misses"]
    kernel = _get_batch_kernel(
        per_dev_B, n_inst_b, n_cont_b, n_ticks, params.sample_every, n_dev,
        backend, n_edge_b or 0, d_out_b or 0, d_in_b or 0,
        donate_batch=not resident, samples_mode=samples_mode,
        n_nodes=n_node_b or 0,
    )
    with span(spans.STAGE_H2D):
        per_tick_dev = jnp.asarray(per_tick_in)
        seeds_dev = jnp.asarray(seeds_in)
    _TRANSFER_STATS["bytes_h2d"] += int(per_tick_in.nbytes) + int(seeds_in.nbytes)
    # a call right after a compile-cache miss traces and compiles first
    with span(spans.KERNEL_COMPILE if _CACHE_STATS["misses"] > compiles
              else spans.KERNEL_DISPATCH):
        out = kernel(
            stacked_dev,
            per_tick_dev,
            seeds_dev,
            params.dt,
            params.noise_std,
            params.queue_high_ktuples,
            params.queue_low_ktuples,
            params.gc_heap_mb,
            params.gc_cost_frac,
            params.mem_alloc_mb_per_ktuple,
        )
    # waits no longer than the device_get below would: it splits the
    # kernel's device time from the transfer back
    with span(spans.KERNEL_WAIT):
        jax.block_until_ready(out)
    # ONE device→host transfer for the whole batch pytree — O(B·S·I) bytes
    # of trajectories in full mode, O(B·I) of summaries in summary mode
    with span(spans.STAGE_D2H):
        out = jax.device_get(out)
    _TRANSFER_STATS["batches"] += 1
    _TRANSFER_STATS[
        "bytes_summary" if samples_mode == "summary" else "bytes_full"
    ] += sum(int(v.nbytes) for v in jax.tree_util.tree_leaves(out))
    with span(spans.RESULTS):
        if n_dev > 1:
            # merge the device axis back and drop the fill replicas
            out = {k: v.reshape(-1, *v.shape[2:])[:B] for k, v in out.items()}
        else:
            out = {k: v[:B] for k, v in out.items()}

        n_samples = n_ticks // params.sample_every
        results: list[SimResult] = []
        for i, st in enumerate(structures):
            off = (
                per_tick[i, : n_samples * params.sample_every]
                .reshape(n_samples, -1)
                .mean(1)
                / params.dt
            )
            if samples_mode == "summary":
                summary = dict(
                    src_half_mean=out["src_half_mean"][i],
                    caputil_half_mean=out["caputil_half_mean"][i][: st.n_inst],
                    sm_half_mean=out["sm_half_mean"][i][: st.n_cont],
                    bp_half_mean=out["bp_half_mean"][i][: st.n_inst],
                    mem_peak=out["mem_peak"][i][: st.n_inst],
                    gate_final=out["gate_final"][i],
                )
                results.append(
                    SimResult(
                        structure=st, params=params, offered_ktps=off,
                        summary=summary, mode="summary",
                        refetch=_make_refetch(
                            configs[i], offered_list[i], seeds[i], n_ticks,
                            params, backend,
                        ),
                    )
                )
                continue
            si: dict = {}
            for k, v in out.items():
                vi = v[i]
                if vi.ndim == 1:                      # per-run scalar series (gate)
                    si[k] = vi
                elif k in ("sm_trav", "sm_cpu"):      # per-container series
                    si[k] = vi[:, : st.n_cont]
                else:                                 # per-instance series
                    si[k] = vi[:, : st.n_inst]
            results.append(
                SimResult(structure=st, params=params, offered_ktps=off, samples=si)
            )
        return results


def _grid_through_batch(evaluate_batch, configs, rates_ktps):
    """Shared config × rate grid driver: flatten the cross-product
    config-major onto the batch axis (config ``i`` at rate ``j`` lands at
    flat index ``i * R + j``), score it through one ``evaluate_batch``-
    shaped callable, and slice back to ``out[i][j]``.  Both the engine's
    ``evaluate_grid`` entry points and :func:`simulate_grid` route through
    here, so grid ordering and empty-input semantics have one home."""
    configs = list(configs)
    rates = [float(r) for r in rates_ktps]
    if not configs or not rates:
        return [[] for _ in configs]
    flat = evaluate_batch(
        [c for c in configs for _ in rates],
        [r for _ in configs for r in rates],
    )
    R = len(rates)
    return [flat[i * R : (i + 1) * R] for i in range(len(configs))]


def simulate_grid(
    configs: Sequence[Configuration],
    rates_ktps,
    duration_s: float = 20.0,
    params: SimParams = SimParams(),
    min_inst_bucket: int = 0,
    min_cont_bucket: int = 0,
    devices: int | None = None,
    min_batch_bucket: int = 0,
    tick_kernel: str = "auto",
    min_edge_bucket: int = 0,
    min_degree_bucket: int = 0,
    resident: bool = False,
    samples: str = "full",
    dedup: bool = True,
    cache=None,
    cache_token=None,
) -> list[list[SimResult]]:
    """Score C configurations × R offered rates in ONE batched kernel call.

    The cross-product rides the vmapped batch axis, so a predictive
    policy's whole horizon sweep — every candidate configuration at every
    forecast rate — shares a single compilation through the existing
    shape-bucket cache.  Returns ``out[i][j]`` for config ``i`` at
    ``rates_ktps[j]``; results are bitwise identical to evaluating each
    (config, rate) pair on its own (same bucket), because the batch axis is
    data-parallel.
    """

    def batch(flat_cfgs, flat_loads):
        return simulate_batch(
            flat_cfgs,
            flat_loads,
            duration_s=duration_s,
            params=params,
            min_inst_bucket=min_inst_bucket,
            min_cont_bucket=min_cont_bucket,
            devices=devices,
            min_batch_bucket=min_batch_bucket,
            tick_kernel=tick_kernel,
            min_edge_bucket=min_edge_bucket,
            min_degree_bucket=min_degree_bucket,
            resident=resident,
            samples=samples,
            dedup=dedup,
            cache=cache,
            cache_token=cache_token,
        )

    return _grid_through_batch(batch, configs, rates_ktps)


def simulate(
    config: Configuration,
    offered_ktps,
    duration_s: float = 20.0,
    params: SimParams = SimParams(),
    tick_kernel: str = "auto",
    samples: str = "full",
    cache=None,
    cache_token=None,
) -> SimResult:
    """Run ``config`` under ``offered_ktps`` (scalar or per-sample array).

    Routed through the batched, shape-bucketed kernel (batch of one), so
    repeated calls in the same bucket share a single XLA compilation.
    ``cache`` (optional :class:`repro.streams.cache.ResultCache`) memoizes
    the result by value across calls; ``samples="summary"`` keeps the
    trajectory on device — see :func:`simulate_batch`.
    """
    return simulate_batch(
        [config], [offered_ktps], duration_s, params, seeds=[params.seed],
        tick_kernel=tick_kernel, samples=samples, cache=cache,
        cache_token=cache_token,
    )[0]


def measure_capacity(
    config: Configuration,
    params: SimParams = SimParams(),
    duration_s: float = 20.0,
    overload_ktps: float = 1e6,
    tick_kernel: str = "auto",
    samples: str = "summary",
    cache=None,
    cache_token=None,
) -> float:
    """The 'measured rate' of a configuration: offered load far above capacity,
    backpressure gating throttles spouts, steady-state admission = capacity.

    A capacity probe consumes one scalar, so it defaults to the summary
    payload (no trajectory transfer; the value is exactly the full-mode
    one).  A ``cache`` makes repeated capacity probes of the same
    configuration — calibration sweeps, fleet feasibility checks —
    cross-call lookups."""
    return simulate(
        config, overload_ktps, duration_s, params, tick_kernel=tick_kernel,
        samples=samples, cache=cache, cache_token=cache_token,
    ).achieved_ktps


def training_sweep(
    config: Configuration,
    rates_ktps,
    params: SimParams = SimParams(),
    seconds_per_rate: float = 10.0,
    tick_kernel: str = "auto",
    cache=None,
    cache_token=None,
) -> MetricsStore:
    """The paper's profiling procedure (§5.1): sweep a throttled producer over
    a range of rates with hold times, collect metrics at each level.

    The whole rate ladder is evaluated as ONE batched kernel call (the
    structure is identical at every rung, so it shares a single compilation
    and the rungs run data-parallel under ``vmap``).  Profiling *is* the
    trajectory consumer, so this path pins ``samples="full"`` — the learned
    models train on whole metric timeseries, not summaries.
    """
    rates = [float(r) for r in rates_ktps]
    seeds = [params.seed + 1000 + i for i in range(len(rates))]
    results = simulate_batch(
        [config] * len(rates), rates, duration_s=seconds_per_rate,
        params=params, seeds=seeds, tick_kernel=tick_kernel, samples="full",
        cache=cache, cache_token=cache_token,
    )
    store = MetricsStore()
    for res in results:
        store.extend(res.to_metrics_store())
    return store
