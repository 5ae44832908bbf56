"""What the drivers share: the configuration file's DAG and packings in the
program's types, the program's evaluator, and its counters.  The program
under test is imported here and in ``drivers/`` and nowhere else in the
benchmark.
"""
from __future__ import annotations


def annotate(name: str):
    """A host span the trace reduction attributes idle time to."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def program_dag(name: str, spec: dict):
    """The configuration file's DAG as the program's ``DagSpec``."""
    from repro.core.dag import DagSpec, EdgeSpec, Grouping, NodeSpec

    has_input = {e["dst"] for e in spec["edges"]}
    nodes = tuple(
        NodeSpec(n["name"], cpu_cost_per_ktuple=1.0 / n["peak_ktps"],
                 gamma=n.get("gamma", 1.0), mem_mb_base=n["mem_mb_base"],
                 mem_mb_per_ktps=n.get("mem_mb_per_ktps", 0.0),
                 io_fraction=n.get("io_fraction", 0.0),
                 is_source=n["name"] not in has_input)
        for n in spec["nodes"])
    edges = tuple(EdgeSpec(e["src"], e["dst"], Grouping(e["grouping"]))
                  for e in spec["edges"])
    return DagSpec(name, nodes=nodes, edges=edges)


def round_robin(names: list[str], parallelism: tuple, n_cont: int) -> tuple:
    """Instances of each node, in DAG order, dealt in turn onto containers."""
    packs: list[list[str]] = [[] for _ in range(n_cont)]
    i = 0
    for name, p in zip(names, parallelism):
        for _ in range(p):
            packs[i % n_cont].append(name)
            i += 1
    return tuple(tuple(p) for p in packs)


def evaluator(config: dict, cls=None, **kw):
    """The program's evaluator as the configuration states it."""
    from repro.streams import SimParams, SimulatorEvaluator

    return (cls or SimulatorEvaluator)(
        params=SimParams(**config["physics"]), duration_s=config["horizon_s"],
        samples=config["samples"],
        saturation_threshold=config["saturation_threshold"], **kw)


def recording_evaluator(config: dict):
    """The evaluator, keeping what each ``evaluate_batch`` call returned
    while ``calls`` is a list: every other entry point goes through it."""
    from repro.streams import OVERLOAD_KTPS, SimulatorEvaluator

    class Recording(SimulatorEvaluator):
        calls: list | None = None

        def evaluate_batch(self, configs, offered_ktps=OVERLOAD_KTPS):
            res = super().evaluate_batch(configs, offered_ktps)
            if self.calls is not None:
                self.calls.append((list(configs), offered_ktps, res))
            return res

    return evaluator(config, cls=Recording)


def counters() -> dict:
    """The program's cache counters the per-layer metrics read."""
    from repro.streams import cache_stats

    s = cache_stats()
    return dict(kernel_compiles=s["kernel"]["misses"],
                result_hits=s["result"]["hits"],
                result_misses=s["result"]["misses"],
                rows_executed=s["dedup"]["rows_executed"])


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}
