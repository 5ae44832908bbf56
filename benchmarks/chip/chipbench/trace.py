"""From a profiler trace to numbers.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote into plain
lists: per device, the operations that ran (name, start, end in ns); on the
host, the benchmark's own spans.  ``reduce`` turns those lists into the
numbers the per-layer metrics and the result's breakdown read.  Only
``extract`` knows the profiler's format, so ``reduce`` is tested on a small
recorded extract.
"""
from __future__ import annotations

import glob
import os

#: the tick kernel's XLA modules: ``jit__simulate_core`` on one chip;
#: ``jit_call_wrapped`` where ``pmap`` shards it over several (the program
#: pmaps nothing else)
KERNEL_MODULES = ("_simulate_core", "jit_call_wrapped")
#: control-flow ops: they hold other ops and are left out of the ranking
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``fusion.61`` from an op event's name, which on a TPU is the whole
    HLO instruction (``%fusion.61 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def extract(trace_dir: str, spans: tuple[str, ...]) -> dict:
    """Device operations and modules, and the host spans named in ``spans``
    (the measured window and the driver's calls inside it), of one trace."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, found = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            ops = lines.get("XLA Ops")
            mods = lines.get("XLA Modules")
            if ops is None and mods is None:
                continue
            devices[plane.name] = dict(
                ops=[(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                     for e in (ops.events if ops is not None else ())],
                modules=[(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in (mods.events if mods is not None else ())])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                found += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name in spans]
    return dict(devices=devices, spans=found)


def is_kernel(module: str) -> bool:
    """Whether an XLA module's name is the tick kernel's."""
    return any(k in module for k in KERNEL_MODULES)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def reduce(ex: dict, top: int = 10) -> dict | None:
    """Numbers of the traced window, or None where no device ran anything
    inside it.

    * ``window_s``: the ``window`` span's length;
    * ``busy_s``: per device, the union of its operations inside the window,
      averaged over the devices that ran any;
    * ``kernel_s``: time of the tick kernel's modules inside the window,
      summed over devices;
    * ``device_ops``: the operations that took most time, summed over
      devices, in seconds (control-flow ops, which hold the others, left
      out);
    * ``idle_gaps``: time no operation ran, averaged over devices, by the
      innermost benchmark span the host was in (``window`` when in none);
    * ``calls``: per call span, seconds from its start to its first device
      operation, from there to its last, and from there to its end, on the
      first device.
    """
    windows = [(a, b) for name, a, b in ex["spans"] if name == "window"]
    if not windows:
        return None
    lo, hi = windows[0]
    calls = sorted((a, b, name) for name, a, b in ex["spans"]
                   if name != "window" and b > lo and a < hi)
    busy, kernel, per_op, gaps = [], 0.0, {}, {}
    first_device = None
    for dev in sorted(ex["devices"]):
        d = ex["devices"][dev]
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in d["ops"] if b > lo and a < hi]
        if not ops:
            continue
        first_device = first_device or [(a, b) for _n, a, b in ops]
        merged = _union([(a, b) for _n, a, b in ops])
        busy.append(sum(b - a for a, b in merged))
        for n, a, b in ops:
            if not n.startswith(CONTAINERS):
                per_op[n] = per_op.get(n, 0.0) + (b - a)
        kernel += sum(b - a for n, a, b in d["modules"]
                      for a, b in _clip([(a, b)], lo, hi) if is_kernel(n))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                _attribute(a, b, calls, gaps)
    if not busy:
        return None
    n_dev = len(busy)
    ns = 1e-9
    modules: dict[str, float] = {}
    for d in ex["devices"].values():
        for n, a, b in d["modules"]:
            for a, b in _clip([(a, b)], lo, hi):
                modules[n] = modules.get(n, 0.0) + (b - a)
    return dict(
        modules=sorted(([n, t * ns] for n, t in modules.items()),
                       key=lambda x: -x[1])[:top],
        window_s=(hi - lo) * ns,
        busy_s=sum(busy) / n_dev * ns,
        devices=n_dev,
        kernel_s=kernel * ns,
        device_ops=sorted(([n, t * ns] for n, t in per_op.items()),
                          key=lambda x: -x[1])[:top],
        idle_gaps=sorted(([n, t / n_dev * ns] for n, t in gaps.items()),
                         key=lambda x: -x[1])[:top],
        calls=[_split(a, b, first_device) for a, b, _n in calls],
    )


def _attribute(a: float, b: float, calls, gaps: dict) -> None:
    """Charge the idle interval [a, b) to the call spans covering it."""
    t = a
    for s, e, name in calls:
        if e <= t or s >= b:
            continue
        if s > t:
            gaps["window"] = gaps.get("window", 0.0) + (s - t)
            t = s
        end = min(e, b)
        gaps[name] = gaps.get(name, 0.0) + (end - t)
        t = end
        if t >= b:
            return
    if b > t:
        gaps["window"] = gaps.get("window", 0.0) + (b - t)


def _split(a: float, b: float, ops) -> list[float]:
    inside = [(x, y) for x, y in ops if y > a and x < b]
    if not inside:
        return [(b - a) * 1e-9, 0.0, 0.0]
    first = max(min(x for x, _y in inside), a)
    last = min(max(y for _x, y in inside), b)
    return [(first - a) * 1e-9, (last - first) * 1e-9, (b - last) * 1e-9]
