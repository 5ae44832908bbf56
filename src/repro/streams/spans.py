"""Named host spans on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``: with a profiler session
running, its start and end land in the trace's host plane beside the device
operations, so an idle gap on the device can be put down to the host phase
that held it; with none running it costs well under a microsecond.  Spans
wrap whole phases (a few per evaluator call, a few per control step), never
single rows, and carry no value the program reads.

:data:`SPANS` names every span the program emits.  A generation-2 garbage
collection shows up as :data:`GC`, through a ``gc.callbacks`` hook
registered when this module is first imported.
"""
from __future__ import annotations

import gc

import jax

EVALUATE = "trevor.evaluate"            # SimulatorEvaluator.evaluate_batch
DEDUP = "trevor.dedup"                  # simulate_batch: Tier-1 dedup, result-cache lookup
STAGE_STRUCTURE = "trevor.stage.structure"  # structure_for over the rows
STAGE_PAD = "trevor.stage.pad"          # _padded_for over the rows
STAGE_STACK = "trevor.stage.stack"      # np.stack of the padded arrays and loads, shard
STAGE_H2D = "trevor.stage.h2d"          # host -> device of the stacked arrays, loads, seeds
KERNEL_COMPILE = "trevor.kernel.compile"  # the kernel call right after a compile-cache miss
KERNEL_DISPATCH = "trevor.kernel.dispatch"  # the kernel call
KERNEL_WAIT = "trevor.kernel.wait"      # waiting for the kernel's outputs
STAGE_D2H = "trevor.stage.d2h"          # device -> host of the outputs
RESULTS = "trevor.results"              # SimResult / EvalResult building
CONTROL_STEP = "trevor.control.step"    # ControlLoop.step
CONTROL_FORECAST = "trevor.control.forecast"  # sense, forecast, tracker, guards
CONTROL_PLAN = "trevor.control.plan"    # policy.plan
CONTROL_MEASURE = "trevor.control.measure"  # ControlLoop._measure
CONTROL_LEARN = "trevor.control.learn"  # ControlLoop._learn
GC = "trevor.gc"                        # a generation-2 garbage collection

SPANS = (
    EVALUATE, DEDUP, STAGE_STRUCTURE, STAGE_PAD, STAGE_STACK, STAGE_H2D,
    KERNEL_COMPILE, KERNEL_DISPATCH, KERNEL_WAIT, STAGE_D2H, RESULTS,
    CONTROL_STEP, CONTROL_FORECAST, CONTROL_PLAN, CONTROL_MEASURE,
    CONTROL_LEARN, GC,
)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of :data:`SPANS`), used as ``with``."""
    return jax.profiler.TraceAnnotation(name)


_open_gc: list = []


def _gc_span(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        s = span(GC)
        s.__enter__()
        _open_gc.append(s)
    elif _open_gc:
        _open_gc.pop().__exit__(None, None, None)


gc.callbacks.append(_gc_span)
