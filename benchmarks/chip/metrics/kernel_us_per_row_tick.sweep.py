"""Device time of the tick kernel per row and tick, in microseconds: the
kernel modules' time in the traced window, summed over chips, over the rows
the kernel ran times the ticks of each row."""


def read(run):
    trace, c = run["trace"], run["counters"]
    if trace is None or not trace["kernel_s"] or not c["rows_executed"]:
        return None
    return trace["kernel_s"] * 1e6 / (c["rows_executed"] * c["n_ticks"])
