"""Median, in ms, of the per-request difference of two clock readings
the run took (samples of the window, in seconds): what the outer one
holds beyond the inner one.

args: {"outer": "<sample name>", "inner": "<sample name>"}
"""

from .. import stats


def read(args: dict, sources: dict):
    outer = sources["samples"].get(args["outer"], [])
    inner = sources["samples"].get(args["inner"], [])
    if not outer or len(outer) != len(inner):
        return None  # not one inner reading per request: nothing to pair
    return 1e3 * stats.median([o - i for o, i in zip(outer, inner)])
