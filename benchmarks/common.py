"""Shared benchmark utilities: timing, CSV emission, and the BENCH JSON
artifact (every emitted row is also collected so a run can be dumped as one
machine-readable file — the perf-trajectory record CI uploads)."""
from __future__ import annotations

import json
import os
import time
from pathlib import Path

#: Where the persistent compilation cache lives when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset.  A fixed path inside the checkout
#: (git-ignored): the directory is part of what a later run must find again.
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[1] / ".jax_cache"

#: Every emit() row of the current process, in order.
RESULTS: list[dict] = []

#: Free-form structured payloads keyed by bench name — e.g. the fleet
#: scheduler's per-phase wall-time breakdown — shipped alongside the rows
#: in the BENCH JSON artifact.
EXTRAS: dict = {}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is used
    as JAX already reads it; otherwise :data:`DEFAULT_COMPILE_CACHE`.
    Called by the entry-point scripts only, never at import, so the test
    suite compiles without a persistent cache."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def timed(fn, *args, repeats: int = 3, warmup: int = 1, **kw):
    for _ in range(warmup):
        out = fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args, **kw)
    us = (time.perf_counter() - t0) / repeats * 1e6
    return out, us


def emit(name: str, us_per_call: float, derived: str) -> None:
    RESULTS.append(
        {"name": name, "us_per_call": round(us_per_call, 1), "derived": derived}
    )
    print(f"{name},{us_per_call:.1f},{derived}")


def _kernel_cache_snapshot() -> dict | None:
    """Tick-kernel compile/hit counts for the run, so the perf trajectory
    tracks recompiles (a perf regression can hide behind warm wall time)."""
    try:
        from repro.streams import kernel_cache_info

        return dict(kernel_cache_info())
    except Exception:
        return None


def _cache_stats_snapshot() -> dict | None:
    """Unified cache hierarchy counters (kernel / structure / resident /
    result / dedup) for the run — the cache-first evaluation path's whole
    story in one place, so hit-rate regressions show up next to wall time."""
    try:
        from repro.streams import cache_stats

        return cache_stats()
    except Exception:
        return None


def dump_json(path: str | None = None) -> str | None:
    """Write the collected rows as BENCH JSON.  ``path`` defaults to the
    ``BENCH_JSON`` environment variable; no-op when neither is set."""
    path = path or os.environ.get("BENCH_JSON")
    if not path:
        return None
    payload = {
        "schema": "bench.v1",
        "generated_unix": int(time.time()),
        "results": RESULTS,
        "kernel_cache": _kernel_cache_snapshot(),
        "caches": _cache_stats_snapshot(),
        "extras": EXTRAS,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# BENCH JSON -> {path} ({len(RESULTS)} rows)")
    return path
