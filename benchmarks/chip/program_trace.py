"""Run one cell traced, and read the program's own spans in its window.

    python benchmarks/chip/program_trace.py --workload <cell> --seed <n> --seconds <s> [--out <file>]

The run is ``run.py --trace 1``'s, and its result line is printed the same
way; the trace it reads also keeps the program's host spans (``trevor.*``,
``chipbench/program_spans.py``), which ``run.py``'s reduction never sees.
A second JSON line, on standard error and in ``--out`` where given, holds
per span name its seconds, count and self seconds in the window; the
device's idle time charged to the innermost span open over it, and the
share of it left to the benchmark's own call span; the host-staging and
control-loop numbers per call; and the window's calls on the profiler's
clock.  It fails, printing no result, where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import run as bench


def profile(spec: dict, seed: int, seconds: float, devices=None) -> tuple[dict, dict]:
    """``run.run`` traced, with the program's spans read from the same trace:
    the result object and the program's readings."""
    import numpy as np

    from chipbench import program, program_spans, trace

    seen: dict = {}
    extract, counters = trace.extract, program.counters

    def widened(trace_dir, names):
        seen["extract"] = ex = program_spans.split(
            extract(trace_dir, program_spans.Names(names)))
        return dict(devices=ex["devices"], spans=ex["spans"])

    h2d: list = []

    def counted():
        from repro.streams import transfer_info

        h2d.append(transfer_info().get("bytes_h2d"))
        return counters()

    trace.extract, program.counters = widened, counted
    try:
        result = bench.run(spec, seed, seconds, True, devices)
    finally:
        trace.extract, program.counters = extract, counters
    ex = seen["extract"]
    summary = program_spans.reduce(ex)
    lo, hi = next((a, b) for n, a, b in ex["spans"] if n == "window")
    calls = sorted((a, b) for n, a, b in ex["spans"]
                   if n != "window" and b > lo and a < hi)
    window_s = (calls[-1][1] - calls[0][0]) * 1e-9
    run = dict(trace=summary, counters=dict(
        calls=len(calls),
        h2d_bytes=None if None in h2d[:1] + h2d[-1:] else h2d[-1] - h2d[0]))
    idle = dict(summary["idle_gaps"])
    readings = dict(
        workload=spec["cell"]["name"], seed=seed,
        program=summary["program"], idle_gaps=summary["idle_gaps"],
        call_idle_share=sum(t for n, t in idle.items() if not n.startswith(
            program_spans.PREFIX) and n != "window") / max(sum(idle.values()), 1e-12),
        stage_ms_per_call=program_spans.stage_ms_per_call(run),
        staged_mb_per_call=program_spans.staged_mb_per_call(run),
        loop_self_ms_per_call=program_spans.loop_self_ms_per_call(run),
        calls=len(calls),
        call_ms=window_s * 1e3 / len(calls),
        call_p90_ms=float(np.percentile([(b - a) * 1e-6 for a, b in calls], 90)),
        attempted_per_s=result["attempted"] / window_s,
    )
    return result, readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    spec = bench.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(bench.HERE))
    sys.path.insert(0, str(bench.ROOT / "src"))
    try:
        devices = bench.chips(spec["cell"]["chips"])
    except bench.NoChip as e:
        print(f"program_trace.py: {e}; this run measures nothing", file=sys.stderr)
        return 3
    bench.enable_compile_cache()
    result, readings = profile(spec, args.seed, args.seconds, devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    line = json.dumps(readings)
    print(f"program: {line}", file=sys.stderr, flush=True)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(line + "\n")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
