"""Tick-kernel compiles inside the window (the evaluator's kernel-cache
misses)."""


def read(run):
    return run["counters"]["kernel_compiles"]
