"""Engine abstraction for configuration evaluation.

Every control layer (allocator candidate scoring, the Dhalion-style reactive
baseline, autoscaler calibration, benchmarks) asks the same question: *what
rate does this configuration achieve, and what limits it?*  This module
defines the :class:`ConfigEvaluator` protocol that answers it, plus two
backends:

* :class:`SimulatorEvaluator` — the discrete-time cluster simulator, with
  batched (vmapped) candidate sweeps and **sticky shape buckets**: once a
  bucket has been compiled, smaller configurations keep padding up to it, so
  a whole autoscaling trace re-uses one or two XLA compilations of the tick
  kernel.
* :class:`ExecutorEvaluator` — the real-JAX executor: operator bodies are
  timed on this host (:func:`repro.streams.executor.calibrate_dag`), and the
  calibrated costs feed the LP flow solver.  ``evaluate_batch`` is serial
  (real deployments cannot be vmapped), which is exactly why the protocol
  exists: control layers stay agnostic to how bulk evaluation happens.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Protocol, Sequence, runtime_checkable

import numpy as np

from ..core.dag import Configuration, DagSpec
from ..core.flow_solver import solve_flow
from ..core.metrics import STREAM_MANAGER
from ..core.node_model import oracle_models
from . import spans
from .cache import ResultCache
from .spans import span
from .simulator import (
    SAMPLES_MODES,
    SimParams,
    SimResult,
    _grid_through_batch,
    batch_bucket_size,
    bucket_size,
    degree_bucket_size,
    edge_bucket_size,
    is_scalar_load,
    node_bucket_size,
    resolve_tick_kernel,
    simulate_batch,
    structure_for,
)

#: A multi-job evaluation request: one candidate-configuration list per job.
JobGroups = Sequence[Sequence[Configuration]]

#: Offered load far above any realistic capacity: backpressure gating
#: throttles the spouts and the achieved rate *is* the capacity.
OVERLOAD_KTPS = 1e6


class PerCandidateLoads(tuple):
    """A per-*candidate* offered-load entry for one ``evaluate_jobs`` group.

    A plain per-job load (scalar or per-sample trace) applies to every
    candidate of that job's group.  Wrapping a sequence of scalars in
    ``PerCandidateLoads`` instead gives each candidate its *own* offered
    load — the fleet scheduler uses this to score one forecast-window rate
    across a whole candidate set whose members sit on different-speed hosts
    (each candidate is driven at ``rate / its_min_host_speed`` in
    reference-host units), still inside one batched call.  The wrapper is
    the disambiguator: a bare sequence keeps meaning a shared per-sample
    trace."""

    __slots__ = ()


@dataclasses.dataclass(frozen=True)
class EvalResult:
    """One configuration's evaluation: achieved rate + limiting component."""

    config: Configuration
    achieved_ktps: float
    bottleneck: str | None            # node name, STREAM_MANAGER, or None
    sim: SimResult | None = None      # backend detail (simulator only)


@runtime_checkable
class ConfigEvaluator(Protocol):
    """What a configuration-evaluation backend must provide.

    All four entry points answer the same question at different shapes:
    *what rate does this configuration achieve under this offered load, and
    which component limits it?*  Control layers depend only on this
    protocol; how bulk evaluation happens (vmapped simulation, serial LP
    scoring of a real deployment, a caching wrapper...) is the backend's
    business.  Backends written before the multi-job/grid entry points
    existed keep working through :func:`evaluate_jobs_with` /
    :func:`evaluate_grid_with`.
    """

    def evaluate(
        self, config: Configuration, offered_ktps: float = OVERLOAD_KTPS
    ) -> EvalResult:
        """Score one configuration.

        Args:
            config: the physical configuration to score.
            offered_ktps: offered source load — a scalar rate or a
                per-sample trace.  The default :data:`OVERLOAD_KTPS` is far
                above any realistic capacity, so the achieved rate *is* the
                configuration's capacity (a capacity probe).

        Returns:
            An :class:`EvalResult` with the achieved rate and the limiting
            component (a node name, :data:`~repro.core.metrics
            .STREAM_MANAGER`, or None when unsaturated).
        """
        ...

    def evaluate_batch(
        self, configs: Sequence[Configuration], offered_ktps=OVERLOAD_KTPS
    ) -> list[EvalResult]:
        """Score N configurations in one call.

        Args:
            configs: the candidate configurations.
            offered_ktps: a shared scalar, or one load per *config* (each a
                scalar or per-sample trace).

        Returns:
            One :class:`EvalResult` per config, in input order.  Batching
            backends answer this with a single kernel dispatch; serial
            backends loop — callers must not assume either.
        """
        ...

    def evaluate_jobs(
        self, groups: JobGroups, offered_ktps=OVERLOAD_KTPS
    ) -> list[list[EvalResult]]:
        """Score candidate sets for N independent jobs in one call.

        Args:
            groups: ``groups[j]`` holds job ``j``'s candidate
                configurations — jobs may be entirely different DAGs.
            offered_ktps: a shared scalar, or one entry per *job*: a scalar
                or per-sample trace applied to every candidate of that
                job's group, or a :class:`PerCandidateLoads` giving each
                candidate its own load (the fleet scheduler's
                candidate-set shape).

        Returns:
            Per-job lists of :class:`EvalResult`, mirroring ``groups``'
            shape.  This is the fleet scheduler's joint-scoring primitive:
            every tenant's candidate set and forecast window costs one
            batched (device-sharded) evaluation.
        """
        ...

    def evaluate_grid(
        self, configs: Sequence[Configuration], rates_ktps
    ) -> list[list[EvalResult]]:
        """Score the configs × rates cross-product in one call.

        Args:
            configs: C candidate configurations.
            rates_ktps: R offered rates (scalars).

        Returns:
            ``out[i][j]`` scores config ``i`` at rate ``j``.  Predictive
            policies use this to check a candidate ladder against a whole
            forecast window; on batching backends the grid rides the
            vmapped batch axis in a single dispatch.
        """
        ...


def evaluate_grid_with(
    evaluator, configs: Sequence[Configuration], rates_ktps
) -> list["list[EvalResult]"]:
    """``evaluate_grid`` on *any* evaluator, including backends written
    before the grid entry point existed: those fall back to one flattened
    ``evaluate_batch`` over the cross-product — still a single batched call
    on batching backends.  Predictive policies call through this shim so
    old evaluators (counting/caching wrappers) keep working."""
    fn = getattr(evaluator, "evaluate_grid", None)
    if fn is not None:
        return fn(configs, rates_ktps)
    return _grid_through_batch(evaluator.evaluate_batch, configs, rates_ktps)


def _expand_job_loads(groups: list[list[Configuration]], offered_ktps):
    """Per-job offered loads → one per-config flat list.

    A scalar is shared by every config of every job; a per-job entry is a
    scalar or per-sample trace shared by that job's candidates, or a
    :class:`PerCandidateLoads` giving each candidate its own scalar load."""
    if is_scalar_load(offered_ktps):
        return [offered_ktps for g in groups for _ in g]
    loads = list(offered_ktps)
    if len(loads) != len(groups):
        raise ValueError(
            f"offered_ktps has {len(loads)} entries for {len(groups)} jobs"
        )
    flat = []
    for g, o in zip(groups, loads):
        if isinstance(o, PerCandidateLoads):
            if len(o) != len(g):
                raise ValueError(
                    f"PerCandidateLoads has {len(o)} entries for a "
                    f"{len(g)}-candidate group"
                )
            flat.extend(float(x) for x in o)
        else:
            flat.extend(o for _ in g)
    return flat


def _regroup(flat: list, groups: list[list]) -> list[list]:
    """Undo the flattening: slice per-config results back into job groups."""
    out: list[list] = []
    i = 0
    for g in groups:
        out.append(flat[i : i + len(g)])
        i += len(g)
    return out


def evaluate_jobs_with(
    evaluator, groups: JobGroups, offered_ktps=OVERLOAD_KTPS
) -> list["list[EvalResult]"]:
    """``evaluate_jobs`` on *any* evaluator, including backends written
    against the pre-multi-job protocol (``evaluate``/``evaluate_batch``
    only, e.g. counting/caching wrappers): those fall back to one flattened
    ``evaluate_batch`` call with the same grouping semantics.  The fleet
    layer calls through this shim so old evaluators keep working."""
    fn = getattr(evaluator, "evaluate_jobs", None)
    if fn is not None:
        return fn(groups, offered_ktps)
    groups = [list(g) for g in groups]
    flat = [c for g in groups for c in g]
    if not flat:
        return [[] for _ in groups]
    loads = _expand_job_loads(groups, offered_ktps)
    return _regroup(evaluator.evaluate_batch(flat, loads), groups)


class SimulatorEvaluator:
    """Batched simulator backend with sticky shape buckets.

    ``duration_s`` trades fidelity for speed (8 s reaches steady state for
    the bundled workloads).  With ``sticky_buckets`` every call pads at least
    to the largest bucket seen so far, so bucket growth — not call count —
    determines the number of XLA compilations.  ``devices`` is forwarded to
    :func:`~repro.streams.simulator.simulate_batch`: ``None`` (auto) shards
    large batches across every local device, ``1`` pins single-device vmap.

    ``sticky_batch`` extends the same idea to the *batch axis*: batch sizes
    pad up to a sticky :data:`~repro.streams.simulator.BATCH_LADDER` rung
    (replicating the last configuration; replicas are dropped on unpack), so
    a fleet trace whose per-replan candidate count fluctuates keeps hitting
    one compiled kernel and a stable device-shard count.  Off by default:
    for one-shot batches the padding is pure overhead.

    ``tick_kernel`` picks the flow-physics backend (``"dense"``,
    ``"sparse"``, or ``"auto"``).  ``"auto"`` is resolved ONCE, from the
    first batch seen, and then pinned — a per-call decision could flip the
    backend as candidate sets fluctuate and recompile.  The sparse edge
    bucket is sticky like the shape buckets.  ``resident_batches`` turns on
    the device-resident staging cache in :func:`simulate_batch` — the fleet
    scheduler re-scores largely identical candidate sets every replan, so
    repeated submissions skip ``np.stack`` + host→device transfer (results
    stay bitwise identical).  ``saturation_threshold`` is forwarded to
    :meth:`SimResult.bottleneck_node` when labelling the limiting component.

    ``dedup`` / ``cache`` turn on the cache-first evaluation path
    (:func:`~repro.streams.simulator.simulate_batch` Tiers 1 and 2):
    value-identical rows in one batch collapse to one kernel row, and
    unique rows are memoized across calls in a per-evaluator
    :class:`~repro.streams.cache.ResultCache` (``cache=True`` builds one;
    pass an instance to share it, ``False`` to disable).  Both tiers are
    bitwise-transparent — ``SimulatorEvaluator(dedup=False, cache=False)``
    is the escape hatch reproducing the uncached path exactly.
    ``version_source`` is the invalidation hook: any object exposing a
    ``version`` attribute (a :class:`~repro.control.learning.ModelStore`,
    or the fleet loop's aggregate clock) is folded into every cache key,
    so calibration/retrain bumps make stale entries unreachable.  The
    control/fleet loops wire it automatically when left unset.

    ``samples`` picks the per-result payload forwarded to
    :func:`~repro.streams.simulator.simulate_batch`.  The default
    ``"summary"`` keeps trajectories on device — every scoring consumer of
    an :class:`EvalResult` (``achieved_ktps`` + ``bottleneck``) is answered
    from the on-device reductions, with values exactly equal to full mode —
    and the rare trajectory consumer (a control loop pooling
    ``sim.to_metrics_store()`` on saturation) transparently refetches.
    ``samples="full"`` restores the historical O(B·S·I) transfers.
    """

    def __init__(
        self,
        params: SimParams = SimParams(),
        duration_s: float = 8.0,
        sticky_buckets: bool = True,
        devices: int | None = None,
        sticky_batch: bool = False,
        tick_kernel: str = "auto",
        resident_batches: bool = True,
        saturation_threshold: float = 0.8,
        dedup: bool = True,
        cache: "bool | ResultCache" = True,
        version_source=None,
        samples: str = "summary",
    ) -> None:
        if samples not in SAMPLES_MODES:
            raise ValueError(f"samples={samples!r} not in {SAMPLES_MODES}")
        self.samples = samples
        self.params = params
        self.duration_s = duration_s
        self.sticky_buckets = sticky_buckets
        self.devices = devices
        self.sticky_batch = sticky_batch
        self.tick_kernel = tick_kernel
        self.resident_batches = resident_batches
        self.saturation_threshold = saturation_threshold
        self.dedup = dedup
        if cache is True:
            cache = ResultCache(name="simulator")
        # identity test, not truthiness: an *empty* ResultCache is len() 0
        self.result_cache: ResultCache | None = (
            cache if isinstance(cache, ResultCache) else None
        )
        self.version_source = version_source
        self._inst_floor = 0
        self._cont_floor = 0
        self._batch_floor = 0
        self._edge_floor = 0
        self._degree_floor = 0
        self._node_floor = 0
        self._backend: str | None = None if tick_kernel == "auto" else tick_kernel
        # shape-scan memo: flat config tuple (by identity) -> bucket inputs;
        # the fleet scheduler re-submits largely identical candidate lists
        # every replan, so the O(total instances) packing scan runs once per
        # distinct layout.  Values hold the configs, keeping the ids valid.
        self._layout_memo: OrderedDict[tuple, tuple] = OrderedDict()

    def presize(
        self, n_inst: int, n_cont: int, n_batch: int = 0, n_edges: int = 0,
        max_degree: int = 0,
    ) -> None:
        """Pin bucket floors for the largest configuration (and optionally
        batch size / sparse edge count / ELL row width) expected —
        guarantees a single compilation up front."""
        self._inst_floor = max(self._inst_floor, bucket_size(n_inst))
        self._cont_floor = max(self._cont_floor, bucket_size(n_cont))
        if n_batch:
            self._batch_floor = max(self._batch_floor, batch_bucket_size(n_batch))
        if n_edges:
            self._edge_floor = max(self._edge_floor, edge_bucket_size(n_edges))
        if max_degree:
            self._degree_floor = max(
                self._degree_floor, degree_bucket_size(max_degree)
            )

    def _layout(
        self, configs: list[Configuration]
    ) -> tuple[int, int, int, int, int]:
        """Max (instances, containers, edges, in-/out-degree, DAG nodes)
        across ``configs`` — memoized on the identity signature of the batch
        so repeated submissions of the same candidate layout (fleet
        replans) skip the packing re-scan.  Edge counts and degrees are the
        structures' closed forms: no host (I, I) routing is built."""
        sig = tuple(id(c) for c in configs)
        hit = self._layout_memo.get(sig)
        if hit is not None:
            self._layout_memo.move_to_end(sig)
            return hit[1:]
        n_inst = max(sum(len(p) for p in c.packing) for c in configs)
        n_cont = max(c.n_containers for c in configs)
        # structure_for is value-memoized, so this warms the same cache
        # simulate_batch reads — no duplicate structure builds
        with span(spans.STAGE_STRUCTURE):
            sts = [structure_for(c, self.params) for c in configs]
        n_edges = max(st.n_edges for st in sts)
        d_max = max(max(st.d_out, st.d_in) for st in sts)
        n_nodes = max(st.n_nodes for st in sts)
        layout = (n_inst, n_cont, n_edges, d_max, n_nodes)
        self._layout_memo[sig] = (tuple(configs), *layout)
        if len(self._layout_memo) > 128:
            self._layout_memo.popitem(last=False)
        return layout

    def _cache_token(self):
        """Invalidation token folded into every result-cache key: the
        ``version`` of :attr:`version_source` (``None`` when unwired —
        cached entries then live until LRU eviction)."""
        vs = self.version_source
        if vs is None:
            return None
        return ("models", getattr(vs, "version", None))

    def evaluate(
        self, config: Configuration, offered_ktps: float = OVERLOAD_KTPS
    ) -> EvalResult:
        return self.evaluate_batch([config], offered_ktps)[0]

    def evaluate_batch(
        self, configs: Sequence[Configuration], offered_ktps=OVERLOAD_KTPS
    ) -> list[EvalResult]:
        with span(spans.EVALUATE):
            configs = list(configs)
            if not configs:
                return []
            if self.sticky_buckets:
                n_inst, n_cont, n_edges, d_max, n_nodes = self._layout(configs)
                self._inst_floor = max(self._inst_floor, bucket_size(n_inst))
                self._cont_floor = max(self._cont_floor, bucket_size(n_cont))
                if self._backend is None:
                    # pin "auto" on first contact so later batches with different
                    # densities never flip the backend (and recompile)
                    self._backend = resolve_tick_kernel(n_inst, n_edges, "auto")
                if self._backend == "sparse":
                    self._edge_floor = max(
                        self._edge_floor, edge_bucket_size(n_edges)
                    )
                    self._degree_floor = max(
                        self._degree_floor, degree_bucket_size(d_max)
                    )
                else:
                    self._node_floor = max(
                        self._node_floor, node_bucket_size(n_nodes)
                    )
            if self.sticky_batch:
                self._batch_floor = max(
                    self._batch_floor, batch_bucket_size(len(configs))
                )
            results = simulate_batch(
                configs,
                offered_ktps,
                duration_s=self.duration_s,
                params=self.params,
                min_inst_bucket=self._inst_floor,
                min_cont_bucket=self._cont_floor,
                devices=self.devices,
                min_batch_bucket=self._batch_floor,
                tick_kernel=self._backend if self._backend else self.tick_kernel,
                min_edge_bucket=self._edge_floor,
                min_degree_bucket=self._degree_floor,
                min_node_bucket=self._node_floor,
                resident=self.resident_batches,
                samples=self.samples,
                dedup=self.dedup,
                cache=self.result_cache,
                cache_token=self._cache_token(),
            )
            with span(spans.RESULTS):
                return [
                    EvalResult(
                        config=c,
                        achieved_ktps=r.achieved_ktps,
                        bottleneck=r.bottleneck_node(self.saturation_threshold),
                        sim=r,
                    )
                    for c, r in zip(configs, results)
                ]

    def evaluate_jobs(
        self, groups: JobGroups, offered_ktps=OVERLOAD_KTPS
    ) -> list[list[EvalResult]]:
        """Score candidate sets for N independent jobs in ONE sharded kernel
        call.

        ``groups[j]`` holds job ``j``'s candidate configurations (the jobs
        may be entirely different DAGs — padding buckets them together);
        ``offered_ktps`` is a shared scalar or one load per *job* (scalar or
        per-sample trace, applied to every candidate of that job).  This is
        the fleet scheduler's joint-scoring primitive: all tenants' candidate
        allocations cost one batched (device-sharded) evaluation.
        """
        groups = [list(g) for g in groups]
        flat = [c for g in groups for c in g]
        if not flat:
            return [[] for _ in groups]
        loads = _expand_job_loads(groups, offered_ktps)
        return _regroup(self.evaluate_batch(flat, loads), groups)

    def evaluate_grid(
        self, configs: Sequence[Configuration], rates_ktps
    ) -> list[list[EvalResult]]:
        """Candidate-configs × horizon-rates in ONE vmapped kernel call —
        the rates ride the batch axis (config-major cross-product), so a
        predictive policy's whole window sweep reuses the sticky shape
        buckets and costs no extra compilations beyond its batch shape."""
        return _grid_through_batch(self.evaluate_batch, configs, rates_ktps)


class ExecutorEvaluator:
    """Real-JAX executor backend.

    Operator bodies are run and timed once per DAG (cached); a configuration
    is then scored by the LP flow solver under the calibrated per-node costs.
    The bottleneck is the most-saturated component at the solved rates,
    mirroring :meth:`SimResult.bottleneck_node` semantics.

    ``cache`` memoizes whole :class:`EvalResult`\\ s by value across calls
    (Tier 2 of the cache-first path, same contract as
    :class:`SimulatorEvaluator`): the key is the calibration identity
    (DagSpec value + operator-body ids), the configuration, the offered
    load, the scoring thresholds, and the ``version_source`` token — so a
    fleet step that re-scores an unchanged candidate set skips the LP
    entirely, and any model/calibration version bump invalidates.

    ``samples`` is accepted for constructor symmetry with
    :class:`SimulatorEvaluator` (callers swap backends without branching);
    the LP scoring path has no trajectories to ship, so every result is
    already summary-shaped and the value only validates.
    """

    def __init__(
        self,
        n_batches: int = 5,
        floor_ktps: float = 50.0,
        sm_cost_per_ktuple: float = SimParams.sm_cost_per_ktuple,
        saturation_threshold: float = 0.8,
        cache: "bool | ResultCache" = True,
        version_source=None,
        samples: str = "summary",
    ) -> None:
        if samples not in SAMPLES_MODES:
            raise ValueError(f"samples={samples!r} not in {SAMPLES_MODES}")
        self.samples = samples
        self.n_batches = n_batches
        self.floor_ktps = floor_ktps
        self.sm_cost_per_ktuple = sm_cost_per_ktuple
        self.saturation_threshold = saturation_threshold
        if cache is True:
            # EvalResults are tiny (no sim payload): bound by entries
            cache = ResultCache(
                name="executor", max_entries=65536, max_bytes=1 << 24
            )
        self.result_cache: ResultCache | None = (
            cache if isinstance(cache, ResultCache) else None
        )
        self.version_source = version_source
        # keyed by the DagSpec *value* plus its operator-body identities:
        # DagSpec equality excludes NodeSpec.fn (compare=False), but fn is
        # exactly what this backend times — two DAGs with identical declared
        # specs and different real operators must not alias each other's
        # measured costs (nor may a spec and its recalibrated namesake)
        self._calibrated: dict[tuple, DagSpec] = {}
        # identity signatures of DAG batches already validated+calibrated:
        # repeated ``evaluate_jobs``/``evaluate_batch`` calls over an
        # unchanged group layout (every fleet step) skip the per-config
        # ``_cache_key`` hashing sweep.  Values hold the dags so the ids in
        # the key stay valid.
        self._groups_seen: OrderedDict[tuple, tuple] = OrderedDict()

    def _precalibrate_once(self, dags: Sequence[DagSpec]) -> None:
        sig = tuple(id(d) for d in dags)
        if sig in self._groups_seen:
            self._groups_seen.move_to_end(sig)
            return
        self.precalibrate(dags)
        self._groups_seen[sig] = tuple(dags)
        if len(self._groups_seen) > 128:
            self._groups_seen.popitem(last=False)

    @staticmethod
    def _cache_key(dag: DagSpec) -> tuple:
        # id() of each fn is stable while the dag (kept alive in the cache
        # key) holds a reference to it
        return (dag, tuple(id(n.fn) for n in dag.nodes))

    def _dag_for(self, dag: DagSpec) -> DagSpec:
        key = self._cache_key(dag)
        cal = self._calibrated.get(key)
        if cal is None:
            from .executor import calibrate_dag

            cal = calibrate_dag(
                dag, n_batches=self.n_batches, floor_ktps=self.floor_ktps
            )
            self._calibrated[key] = cal
        return cal

    def precalibrate(self, dags: Sequence[DagSpec]) -> None:
        """Time each *distinct* DAG's operator bodies exactly once — called
        up front by the batch entry points so a batch over N configurations
        of k DAGs costs k timing runs, not N."""
        for dag in dags:
            self._dag_for(dag)

    def calibrated_dag(self, dag: DagSpec) -> DagSpec:
        """The DAG with this host's measured per-ktuple costs (cached) —
        consumed by :func:`repro.control.learning.fold_executor_timings` to
        re-parameterize the simulator's physical truth."""
        return self._dag_for(dag)

    def _eval_key(self, config: Configuration, offered: float):
        token = None
        if self.version_source is not None:
            token = getattr(self.version_source, "version", None)
        return (
            self._cache_key(config.dag), config, float(offered),
            self.saturation_threshold, self.sm_cost_per_ktuple, token,
        )

    def evaluate(
        self, config: Configuration, offered_ktps: float = OVERLOAD_KTPS
    ) -> EvalResult:
        key = None
        if self.result_cache is not None and is_scalar_load(offered_ktps):
            key = self._eval_key(config, float(offered_ktps))
            hit = self.result_cache.get(key)
            if hit is not None:
                return hit
        result = self._evaluate_uncached(config, offered_ktps)
        if key is not None:
            # frozen EvalResult without a sim payload: nominal footprint
            self.result_cache.put(key, result, nbytes=128)
        return result

    def _evaluate_uncached(
        self, config: Configuration, offered_ktps: float
    ) -> EvalResult:
        dag2 = self._dag_for(config.dag)
        cfg2 = Configuration(dag2, config.packing, config.dims)
        models = oracle_models(dag2, self.sm_cost_per_ktuple)
        sol = solve_flow(cfg2, models)
        if not sol.feasible:
            return EvalResult(config=config, achieved_ktps=0.0, bottleneck=None)
        achieved = min(float(sol.rate_ktps), float(offered_ktps))
        # saturation per node at the solved instance rates
        per_node: dict[str, float] = {}
        for (nm, _c, _s), rate in sol.instance_rates.items():
            util = rate * models[nm].cap.slope
            per_node[nm] = max(per_node.get(nm, 0.0), util)
        sm_util = max(
            (t * self.sm_cost_per_ktuple for t in sol.sm_traversals.values()),
            default=0.0,
        )
        bottleneck: str | None = None
        if per_node:
            name, val = max(per_node.items(), key=lambda kv: kv[1])
            if sm_util > val and sm_util > 0.9:
                bottleneck = STREAM_MANAGER
            elif val > self.saturation_threshold:
                bottleneck = name
        return EvalResult(config=config, achieved_ktps=achieved, bottleneck=bottleneck)

    def evaluate_batch(
        self, configs: Sequence[Configuration], offered_ktps=OVERLOAD_KTPS
    ) -> list[EvalResult]:
        if is_scalar_load(offered_ktps):
            offered = [float(offered_ktps)] * len(configs)
        else:
            offered = [float(np.max(o)) for o in offered_ktps]
            if len(offered) != len(configs):
                raise ValueError(
                    f"offered_ktps has {len(offered)} entries for "
                    f"{len(configs)} configs"
                )
        self._precalibrate_once([c.dag for c in configs])
        return [self.evaluate(c, o) for c, o in zip(configs, offered)]

    def evaluate_jobs(
        self, groups: JobGroups, offered_ktps=OVERLOAD_KTPS
    ) -> list[list[EvalResult]]:
        """Multi-job scoring on the real-executor backend: every distinct
        DAG across all jobs is timed once, then candidates score serially
        through the calibrated LP flow solver."""
        groups = [list(g) for g in groups]
        loads = _expand_job_loads(groups, offered_ktps)
        self._precalibrate_once([c.dag for g in groups for c in g])
        # the flow solver answers a single-rate question: a per-sample trace
        # reduces to its peak (the capacity the job must sustain)
        flat = [
            self.evaluate(c, float(np.max(o)))
            for c, o in zip((c for g in groups for c in g), loads)
        ]
        return _regroup(flat, groups)

    def evaluate_grid(
        self, configs: Sequence[Configuration], rates_ktps
    ) -> list[list[EvalResult]]:
        """Grid scoring on the real-executor backend: each distinct DAG is
        timed once, then the (config, rate) pairs score serially through
        the calibrated LP flow solver."""

        def batch(flat_cfgs, flat_loads):
            self.precalibrate([c.dag for c in flat_cfgs])
            return [self.evaluate(c, o) for c, o in zip(flat_cfgs, flat_loads)]

        return _grid_through_batch(batch, configs, rates_ktps)
