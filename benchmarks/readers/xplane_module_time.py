"""Device time, in ms per request, of the runs of the named XLA modules
inside the traced window, from the profiler's trace (first chip that
ran any).

args: {"modules": [name prefixes]}
"""

from .. import xplane


def read(args: dict, sources: dict):
    trace = sources["trace"]
    if trace is None or not trace.requests:
        return None
    lo, hi = trace.window()
    for rows in trace.modules.values():
        ns = xplane.module_ns(rows, args["modules"], lo, hi)
        if ns > 0:
            return ns / 1e6 / len(trace.requests)
    return None
