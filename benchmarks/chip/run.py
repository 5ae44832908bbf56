"""Run one cell of the benchmark once, on the chips of this machine.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the root of the checkout; its
configuration, traffic mix, the driver of the mix's kind and its per-layer
metric readers are files under this directory, found by name.  Set-up
(imports, compile, traffic from the seed, warm-up) is timed as ``setup_s``;
then the window runs for ``--seconds``, finishing the call in flight.
Afterwards a seeded sample of what the window returned is scored again by
the plain reference, each compared number is printed beside its limit as
the last lines of standard error, and the last line of standard output is
the result as one JSON object.  With ``--trace 1`` the window runs under
the profiler and the result carries the per-layer metrics instead of the
end-to-end ones.

It fails, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the persistent compile cache, at a fixed path inside the checkout
COMPILE_CACHE = HERE / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell, its configuration and mix, and its metric entries."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return dict(cell=cell, config=config, mix=mix, end_to_end=e2e,
                per_layer=per_layer)


def _load(folder: str, name: str):
    """The module ``<folder>/<name>.py`` under this directory."""
    path = HERE / folder / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{folder}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """``read(run) -> float | None`` from ``metrics/<name>.py``."""
    return _load("metrics", name).read


def driver_module(kind: str):
    """``drivers/<kind>.py``: its ``Driver`` (set-up, ``window``,
    ``release``) and its ``check``, for the mixes of that kind."""
    return _load("drivers", kind)


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR`` where
    that is set, else at :data:`COMPILE_CACHE`; every program is written,
    however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips(n: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips and JAX found {len(devices)}")
    return devices


def run(spec: dict, seed: int, seconds: float, trace: bool,
        devices: list | None = None, flows_dtype=None) -> dict:
    """One run of one cell; returns the result object.  ``devices`` is the
    chips to report on (all of JAX's where None)."""
    import jax
    import jax.numpy as jnp

    from chipbench import trace as tracing

    devices = devices if devices is not None else jax.devices()
    kind_module = driver_module(spec["mix"]["kind"])
    driver = kind_module.Driver(spec["config"], spec["mix"], seed)
    gc.collect()
    setup_s = time.perf_counter() - T_START
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tmp:
        if trace:
            # host spans come from the TraceMe host tracer; the Python
            # tracer would slow every host-bound call it records
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation("window"):
            win = driver.window(seconds)
        if trace:
            jax.profiler.stop_trace()
            t_read = time.perf_counter()
            summary = tracing.reduce(tracing.extract(tmp, ("window", kind_module.SPAN)))
            t_read = time.perf_counter() - t_read
    memory = [d.memory_stats() or {} for d in devices]
    peak = max(m.get("peak_bytes_in_use", 0) for m in memory)
    driver.release()
    gc.collect()
    checks = kind_module.check(driver, spec["config"], spec["mix"], seed,
                               flows_dtype or jnp.float32)
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices), memory_peak_bytes=int(peak))
    result = dict(correct=all(v <= limit for _n, v, limit in checks),
                  attempted=int(win["attempted"]), failed=0)
    if trace:
        if summary is None:
            raise RuntimeError("the traced window holds no device operation")
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        print(f"trace: {summary['devices']} device(s), read in {t_read:.3f} s, "
              f"modules {summary['modules']}", file=sys.stderr)
        for a, b, c in summary["calls"][:12]:
            print(f"call: host before first op {a:.6f} s, device {b:.6f} s, "
                  f"after last op {c:.6f} s", file=sys.stderr)
        ctx = dict(trace=summary, counters=win["counters"])
        metrics = {}
        for m in spec["per_layer"]:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=float(value), unit=m["unit"])
        result["breakdown"] = dict(device_ops=summary["device_ops"],
                                   idle_gaps=summary["idle_gaps"])
    else:
        # a metric named ``<number>.<qualifier>`` (``candidates_per_s.x4``)
        # reports the window's ``<number>`` in the cells it lists
        values = dict(win["e2e"], setup_s=setup_s)
        metrics = {m["name"]: dict(value=float(values[m["name"].split(".")[0]]),
                                   unit=m["unit"])
                   for m in spec["end_to_end"]}
    result.update(metrics=metrics, device=device)
    result["checks"] = {n: dict(value=float(v), limit=float(limit))
                        for n, v, limit in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devices = chips(spec["cell"]["chips"])
    except NoChip as e:
        print(f"run.py: {e}; this run measures nothing", file=sys.stderr)
        return 3
    enable_compile_cache()
    result = run(spec, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
