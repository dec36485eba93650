"""The device's idle share of the traced window, in per cent: 1 minus
the union of the device operations' intervals over the window, from the
profiler's trace.  No argument."""

from .. import xplane


def read(args: dict, sources: dict):
    reduced = sources["trace_reduced"]
    if reduced is None:
        return None
    return xplane.idle_share(reduced["busy_s"], reduced["window_s"])
