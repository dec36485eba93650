"""The chip's idle time inside requests, in ms per request, by what the
host was doing meanwhile: each idle gap inside a ``bench.request`` is cut
at the edges of the program's spans (mirrored into the profile by
utils/tracing, so on the profiler's clock and from every thread) and
given to the FIRST class of CLASSES that covers the piece.  The four
classes add up to the idle time inside requests.

args: {"class": "verifier_host" | "validation" | "device_wait" | "handoff"}
"""

from .. import profile_rows

# in order of precedence; "handoff" is what none of them covers: the
# scheduler's queue, thread wake-ups, futures
CLASSES = {
    "verifier_host": ["verify.uncached_assemble", "verify.slab_fill",
                      "verify.h2d_dispatch", "verify.blame_unpack"],
    "validation": ["commit.assemble", "commit.judge"],
    # the host only waits: launch, transfer and fetch latency
    "device_wait": ["verify.device_wait"],
}
REST = "handoff"


def read(args: dict, sources: dict):
    trace = sources["trace"]
    if trace is None or not trace.requests:
        return None
    rows = profile_rows.of(sources)
    if rows is None:
        return None
    if "idle_by_class" not in rows.memo:
        lo, hi = trace.window()
        rows.memo["idle_by_class"] = None
        for ops in trace.ops.values():
            if _ran_in(ops, lo, hi):
                rows.memo["idle_by_class"] = profile_rows.idle_by_class(
                    ops, trace.requests, rows.annotations,
                    list(CLASSES.values()), lo, hi,
                )
                break
    by_class = rows.memo["idle_by_class"]
    if by_class is None:
        return None
    at = list(CLASSES) + [REST]
    return by_class[at.index(args["class"])] / 1e6 / len(trace.requests)


def _ran_in(ops, lo, hi) -> bool:
    """Whether this chip ran anything in the window (xplane.reduce reads
    the first such chip)."""
    return any(min(e, hi) > max(s, lo) for s, e, _ in ops)
