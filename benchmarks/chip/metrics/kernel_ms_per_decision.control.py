"""Device time of the tick kernel per control step, in milliseconds: the
kernel modules' time in the traced window over the steps it held."""


def read(run):
    trace, c = run["trace"], run["counters"]
    if trace is None or not trace["kernel_s"] or not c["calls"]:
        return None
    return trace["kernel_s"] * 1e3 / c["calls"]
