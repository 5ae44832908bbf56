"""Stream-processing substrate: operators, workloads, load sources, the
batched discrete-time cluster simulator, the real JAX executor, and the
engine abstraction that lets control layers evaluate configurations without
knowing which backend answers."""

from .workloads import (
    WORKLOADS,
    adanalytics,
    deep_pipeline,
    diamond,
    mobile_analytics,
    wordcount,
)
from .simulator import (
    DEGREE_LADDER,
    EDGE_LADDER,
    NODE_LADDER,
    SAMPLES_MODES,
    SimParams,
    SimResult,
    TrajectoryUnavailable,
    batch_bucket_size,
    bucket_size,
    clear_dedup_stats,
    clear_kernel_cache,
    clear_resident_cache,
    clear_structure_cache,
    clear_transfer_stats,
    dedup_info,
    degree_bucket_size,
    edge_bucket_size,
    kernel_cache_info,
    measure_capacity,
    node_bucket_size,
    pad_structure,
    resident_cache_info,
    resolve_tick_kernel,
    shard_count,
    simulate,
    simulate_batch,
    simulate_grid,
    structure_cache_info,
    training_sweep,
    transfer_info,
)
from .cache import (
    ResultCache,
    cache_stats,
    clear_result_caches,
    result_cache_info,
)
from .engine import (
    OVERLOAD_KTPS,
    ConfigEvaluator,
    EvalResult,
    ExecutorEvaluator,
    PerCandidateLoads,
    SimulatorEvaluator,
    evaluate_grid_with,
    evaluate_jobs_with,
)
from . import sources

__all__ = [
    "DEGREE_LADDER",
    "EDGE_LADDER", "NODE_LADDER", "SAMPLES_MODES", "WORKLOADS", "ConfigEvaluator",
    "EvalResult",
    "ExecutorEvaluator",
    "OVERLOAD_KTPS", "PerCandidateLoads", "ResultCache", "SimParams",
    "SimResult",
    "SimulatorEvaluator", "TrajectoryUnavailable",
    "adanalytics", "batch_bucket_size", "bucket_size", "cache_stats",
    "clear_dedup_stats", "clear_kernel_cache",
    "clear_resident_cache", "clear_result_caches", "clear_structure_cache",
    "clear_transfer_stats",
    "dedup_info", "deep_pipeline",
    "degree_bucket_size",
    "diamond", "edge_bucket_size", "evaluate_grid_with", "evaluate_jobs_with",
    "kernel_cache_info", "measure_capacity", "mobile_analytics",
    "node_bucket_size", "pad_structure", "resident_cache_info",
    "resolve_tick_kernel",
    "result_cache_info",
    "shard_count", "simulate", "simulate_batch",
    "simulate_grid", "sources", "structure_cache_info", "training_sweep",
    "transfer_info",
    "wordcount",
]
