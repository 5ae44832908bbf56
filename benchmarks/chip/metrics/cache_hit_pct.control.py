"""Result-cache hits over lookups in the window, in percent."""


def read(run):
    c = run["counters"]
    lookups = c["result_hits"] + c["result_misses"]
    if not lookups:
        return None
    return 100.0 * c["result_hits"] / lookups
