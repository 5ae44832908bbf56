"""The program's own host spans in a trace, and the numbers they give.

The program names its host spans ``trevor.*`` (``repro.streams.spans``):
the evaluator's host phases (structure, pad, stack, host→device, kernel
call and wait, device→host, results) and the control loop's (forecast,
plan, measure, learn), plus ``trevor.gc`` for full garbage collections.
``trace.extract`` keeps only the names it is given; :class:`Names` widens
them to the program's, and :func:`split` parts the two kinds again, so
``trace.reduce`` sees what it always saw.  :func:`reduce` gives, per span
name, its seconds inside the window, its count and its self seconds (its
time less that of the spans directly inside it), and the device's idle time
charged to the innermost span open over it: a program span, else the
benchmark's own call span, else ``window``.  The functions at the end are the
host-staging and control-loop numbers a run's reduction and counters give.
"""
from __future__ import annotations

from .trace import _union

#: the prefix of every span the program emits
PREFIX = "trevor."
NS = 1e-9


class Names:
    """The span names ``trace.extract`` keeps: the given ones, and every
    name under :data:`PREFIX`."""

    def __init__(self, names) -> None:
        self.names = tuple(names)

    def __contains__(self, name: str) -> bool:
        return name in self.names or name.startswith(PREFIX)


def split(ex: dict) -> dict:
    """The extract with the program's spans moved from ``spans`` to
    ``program_spans``."""
    ours = [s for s in ex["spans"] if s[0].startswith(PREFIX)]
    theirs = [s for s in ex["spans"] if not s[0].startswith(PREFIX)]
    return dict(ex, spans=theirs, program_spans=ours)


def _nested(spans) -> list[tuple]:
    """``(start, end, name)`` sorted so that a span comes before the spans
    it holds."""
    return sorted(((a, b, n) for n, a, b in spans), key=lambda s: (s[0], -s[1]))


def _table(spans: list[tuple], lo: float, hi: float) -> dict:
    """Per name: seconds inside ``[lo, hi)``, count, self seconds."""
    out: dict[str, list] = {}
    stack: list[tuple] = []            # (end, name) of the open spans
    for a, b, name in spans:
        inside = max(0.0, min(b, hi) - max(a, lo))
        while stack and stack[-1][0] <= a:
            stack.pop()
        row = out.setdefault(name, [0.0, 0, 0.0])
        row[0] += inside
        row[1] += 1
        row[2] += inside
        if stack and stack[-1][0] >= b:
            out[stack[-1][1]][2] -= inside
        stack.append((b, name))
    return {n: [t * NS, c, s * NS] for n, (t, c, s) in out.items()}


def _charge(idle: list[tuple], spans: list[tuple], gaps: dict) -> None:
    """Charge each idle interval to the innermost span open over it
    (``window`` where none is)."""
    stack: list[tuple] = []
    k = 0
    for a, b in idle:
        t = a
        while t < b:
            while k < len(spans) and spans[k][0] <= t:
                if spans[k][1] > t:
                    stack.append(spans[k])
                k += 1
            while stack and stack[-1][1] <= t:
                stack.pop()
            end = b
            if stack:
                end = min(end, stack[-1][1])
            if k < len(spans):
                end = min(end, spans[k][0])
            name = stack[-1][2] if stack else "window"
            gaps[name] = gaps.get(name, 0.0) + (end - t)
            t = end


def reduce(ex: dict) -> dict | None:
    """The program's spans in the traced window, or None where the extract
    has no window or no device ran anything inside it.

    * ``program``: per span name, ``[seconds inside the window, count,
      self seconds]``;
    * ``idle_gaps``: time no operation ran, averaged over devices, by the
      innermost span open over it; the values sum to the window less its
      busy time, as ``trace.reduce``'s do.
    """
    windows = [(a, b) for name, a, b in ex["spans"] if name == "window"]
    if not windows:
        return None
    lo, hi = windows[0]
    ours = _nested(s for s in ex.get("program_spans", []) if s[2] > lo and s[1] < hi)
    calls = _nested(s for s in ex["spans"] if s[0] != "window" and s[2] > lo and s[1] < hi)
    spans = sorted(calls + ours, key=lambda s: (s[0], -s[1]))
    gaps: dict[str, float] = {}
    n_dev = 0
    for dev in sorted(ex["devices"]):
        ops = [(max(a, lo), min(b, hi)) for _n, a, b in ex["devices"][dev]["ops"]
               if b > lo and a < hi]
        if not ops:
            continue
        n_dev += 1
        edges = [lo] + [x for ab in _union(ops) for x in ab] + [hi]
        _charge([(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a], spans, gaps)
    if not n_dev:
        return None
    return dict(
        program=_table(ours, lo, hi),
        idle_gaps=sorted(([n, t / n_dev * NS] for n, t in gaps.items()),
                         key=lambda x: -x[1]),
    )


def _seconds(run: dict, prefix: str, column: int) -> float | None:
    program = (run["trace"] or {}).get("program") or {}
    rows = [row[column] for name, row in program.items() if name.startswith(prefix)]
    return sum(rows) if rows else None


def stage_ms_per_call(run: dict) -> float | None:
    """Host staging per call, in milliseconds: the ``trevor.stage.*``
    spans' seconds in the window over the calls it held."""
    s, calls = _seconds(run, PREFIX + "stage.", 0), run["counters"]["calls"]
    return None if s is None or not calls else s * 1e3 / calls


def staged_mb_per_call(run: dict) -> float | None:
    """Bytes staged host→device per call, in megabytes."""
    c = run["counters"]
    if c.get("h2d_bytes") is None or not c["calls"]:
        return None
    return c["h2d_bytes"] / 1e6 / c["calls"]


def loop_self_ms_per_call(run: dict) -> float | None:
    """The control loop's own host time per step, in milliseconds: the self
    seconds of the ``trevor.control.*`` spans over the steps."""
    s, calls = _seconds(run, PREFIX + "control.", 2), run["counters"]["calls"]
    return None if s is None or not calls else s * 1e3 / calls
