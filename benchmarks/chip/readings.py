"""Readings that set the limits of ``chipbench/check.py``: for each seed, one
set-up and a short window of the cell at its own load, then the compared
numbers twice over the same sample, once against the float32 reference
(the program's reading) and once against the control, the reference with
its routing and flow matrices in bfloat16.  One JSON line per seed.

    python benchmarks/chip/readings.py --workload <cell> --seeds 1,2,3 --seconds 10

Run on the chip the cell asks for; not part of the benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = bench.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(bench.ROOT / "src"))
    bench.chips(spec["cell"]["chips"])
    bench.enable_compile_cache()
    import jax.numpy as jnp

    kind = bench.driver_module(spec["mix"]["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        driver = kind.Driver(spec["config"], spec["mix"], seed)
        win = driver.window(args.seconds)
        driver.release()
        line = dict(seed=seed, e2e=win["e2e"], attempted=win["attempted"])
        for label, dtype in (("program", jnp.float32), ("control", jnp.bfloat16)):
            line[label] = {n: v for n, v, _limit in kind.check(
                driver, spec["config"], spec["mix"], seed, dtype)}
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
