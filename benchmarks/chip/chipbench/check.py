"""What the drivers' checks share: scoring the window's answers again with
the plain reference (``reference.py``) and comparing.  Each kind of decision
compares its own numbers in ``drivers/<kind>.py``; each number has its limit
there or here, and ``PERF.md`` gives the readings each was set from.  A
number at or under its limit passes.
"""
from __future__ import annotations

import math

from . import reference

#: widest relative gap between the program's achieved rate and the
#: reference's (set between the program's and the bfloat16 control's
#: readings on the chip; see PERF.md)
GAP_LIMIT = 1e-4
#: a bottleneck whose compared quantities lie within this relative margin of
#: each other (in the reference) is a tie and is not compared
TIE_MARGIN = 0.01


def score(config: dict, rows: list, flows_dtype) -> list[dict]:
    """The reference's scores of ``rows`` under the configuration."""
    return reference.score(config["dags"], rows, config["physics"],
                           config["horizon_s"], config["saturation_threshold"],
                           config["sm_threshold"], flows_dtype=flows_dtype)


def gap(got: float, want: float) -> float:
    """Relative gap; infinite where either side is not a finite number."""
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-12)


def compare_scores(config: dict, pairs: list, flows_dtype) -> tuple[float, int]:
    """Widest achieved-rate gap, and bottlenecks that differ where the
    reference's decision is no tie."""
    refs = score(config, [row for row, _got in pairs], flows_dtype)
    widest = max((gap(got["achieved"], ref["achieved"])
                  for (_row, got), ref in zip(pairs, refs)), default=0.0)
    wrong = sum(1 for (_row, got), ref in zip(pairs, refs)
                if "bottleneck" in got and ref["margin"] >= TIE_MARGIN
                and got["bottleneck"] != ref["bottleneck"])
    return widest, wrong
