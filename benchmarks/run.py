"""Benchmark harness: one module per paper table/figure (+ system extras).

Prints ``name,us_per_call,derived`` CSV rows (comment lines start with '#').

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table2 speed
"""
from __future__ import annotations

import sys
import time

from .common import dump_json, enable_compile_cache

BENCHES = [
    ("table2", "bench_table2", "Paper Table 2 — WordCount sensitivity + prediction"),
    ("fig4", "bench_fig4", "Paper Fig. 4 — AdAnalytics heatmap / efficiency gap"),
    ("models", "bench_models", "Paper Fig. 8 + Table 4 — node-model fits"),
    ("prediction", "bench_prediction", "Paper Fig. 13 — learned-model accuracy"),
    ("allocator", "bench_allocator", "Paper Fig. 14 — allocator efficiency"),
    ("reactive", "bench_reactive", "Paper §2.3/§6 — Dhalion baseline vs one-shot"),
    ("forecast", "bench_forecast", "Predictive layer — forecast accuracy + horizon sweeps"),
    ("fleet", "bench_fleet", "Fleet layer — sharded sweeps + joint scheduling"),
    ("fleet_scale", "bench_fleet_scale", "Fleet layer — tenant-count scaling curve (incremental vs full)"),
    ("failover", "bench_failover", "Fleet layer — host/rack failure: time-to-refit + breach steps, N+1 on vs off"),
    ("speed", "bench_speed", "Paper §4/§5 — predict/allocate latency + LP bench"),
    ("kernels", "bench_kernels", "Pallas kernels vs jnp oracles"),
    ("tick", "bench_tick", "Tick kernel — dense vs sparse ELL flow physics + batch staging"),
    ("eval_cache", "bench_eval_cache", "Cache-first evaluation path — dedup factor + memoization hit rate"),
    ("summary", "bench_summary", "Summary mode — on-device reduction vs full-trajectory transfer"),
]


def main() -> None:
    selected = set(sys.argv[1:])
    enable_compile_cache()
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    for key, module, desc in BENCHES:
        if selected and key not in selected:
            continue
        print(f"# === {desc} ===")
        mod = __import__(f"benchmarks.{module}", fromlist=["run"])
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001 — keep the harness going
            print(f"{key}_FAILED,0,{type(e).__name__}:{e}")
            raise
    print(f"# total wall time: {time.perf_counter() - t0:.1f}s")
    dump_json()  # BENCH JSON artifact when $BENCH_JSON is set


if __name__ == "__main__":
    main()
