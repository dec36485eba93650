"""From a jax.profiler trace (.xplane.pb) to the numbers the benchmark
reports: device busy time, idle share, idle gaps by what the host was
doing, device time per XLA module, and the operations that took most.

Which planes and lines are read (looked at by hand on a v5e trace, see
PERF.md section 3): a plane named ``/device:TPU:<n>`` is one chip; its
line ``XLA Ops`` holds one event per operation that ran on the chip and
its line ``XLA Modules`` one event per run of a jitted program, named
``<module>(<fingerprint>)``.  The benchmark's own request annotations
(jax.profiler.TraceAnnotation, REQUEST) are events of the host plane
``/host:CPU``, on the same clock.

Everything below the loader works on plain rows ``(start_ns, end_ns,
name)``, so the arithmetic is tested on hand-written rows.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")  # one chip's own plane
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
REQUEST = "bench.request"

Row = tuple[float, float, str]


class TraceError(Exception):
    """The trace does not hold what a traced run must show."""


@dataclass
class Trace:
    """One profile, reduced to rows.  ``ops`` and ``modules`` are keyed
    by device plane name."""

    ops: dict[str, list[Row]] = field(default_factory=dict)
    modules: dict[str, list[Row]] = field(default_factory=dict)
    requests: list[Row] = field(default_factory=list)

    def window(self) -> tuple[float, float]:
        """From the first request's start to the last one's end."""
        if not self.requests:
            raise TraceError("the trace holds no request annotation")
        return (min(r[0] for r in self.requests),
                max(r[1] for r in self.requests))


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    trace.ops[plane.name] = _rows(line)
                elif line.name == MODULES_LINE:
                    trace.modules[plane.name] = _rows(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                trace.requests += [
                    r for r in _rows(line) if r[2] == REQUEST
                ]
    trace.requests.sort()
    return trace


def _rows(line) -> list[Row]:
    return [
        (e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events
    ]


def describe(path: str) -> list[str]:
    """Planes, lines, event counts and a few names: what one looks at
    by hand before trusting the selectors above
    (``python3 -m benchmarks.xplane <trace dir>``)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names: dict[str, int] = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:6]
            span = (
                (min(e.start_ns for e in events),
                 max(e.start_ns + e.duration_ns for e in events))
                if events else None
            )
            out.append(
                f"  line {line.name!r}: {len(events)} events, span {span}, "
                f"top {top}"
            )
    return out


# ------------------------------------------------------------ arithmetic


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as disjoint
    sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out = []
    at = lo
    for s, e in merge(intervals, lo, hi):
        if s > at:
            out.append((at, s))
        at = e
    if hi > at:
        out.append((at, hi))
    return out


def idle_share(busy: float, window: float) -> float:
    """Idle share of the window in per cent."""
    if window <= 0:
        raise ValueError("empty window")
    return 100.0 * (1.0 - busy / window)


def split_gaps(idle, requests, lo: float, hi: float) -> dict[str, list[float]]:
    """Each idle gap cut at the requests' edges: the pieces under a
    request annotation are ``inside_request`` (the host was in the call
    under test: assembling, handing over, waiting, judging), the rest
    ``between_requests`` (the benchmark's own loop).  Lengths in ns."""
    inside = merge(requests, lo, hi)
    out: dict[str, list[float]] = {"inside_request": [], "between_requests": []}
    for gs, ge in idle:
        covered = merge(inside, gs, ge)
        out["inside_request"] += [e - s for s, e in covered]
        out["between_requests"] += [e - s for s, e in gaps(covered, gs, ge)]
    return out


def module_ns(modules, prefixes, lo: float, hi: float) -> float:
    """Device time of the runs of the modules whose name starts with one
    of ``prefixes``, clipped to [lo, hi]."""
    return sum(
        min(e, hi) - max(s, lo)
        for s, e, name in modules
        if name.startswith(tuple(prefixes)) and min(e, hi) > max(s, lo)
    )


def top_names(rows, lo: float, hi: float, k: int = 10) -> list[list]:
    """[[name, seconds], ...] of the k names with most time in [lo, hi].
    An operation's event is named by its whole HLO line, ``%while.9955 =
    (s32[]...) while(...)``: the name is what stands before `` = ``."""
    total: dict[str, float] = {}
    for s, e, name in rows:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            name = name.split(" = ", 1)[0]
            total[name] = total.get(name, 0.0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce(trace: Trace) -> dict:
    """What the harness prints from one trace: busy and window seconds
    (averaged over the chips that ran anything), the breakdown, and the
    window itself for the readers."""
    lo, hi = trace.window()
    planes = [p for p, rows in trace.ops.items() if busy_ns(rows, lo, hi) > 0]
    if not planes:
        raise TraceError("no operation ran on a device inside the traced window")
    busy = sum(busy_ns(trace.ops[p], lo, hi) for p in planes) / len(planes)
    first = trace.ops[planes[0]]
    pieces = split_gaps(gaps(first, lo, hi), trace.requests, lo, hi)
    idle_rows = []
    for where, lengths in pieces.items():
        if lengths:
            idle_rows.append([where + ".total", sum(lengths) / 1e9])
            idle_rows.append([where + ".longest", max(lengths) / 1e9])
    return {
        "busy_s": busy / 1e9,
        "window_s": (hi - lo) / 1e9,
        "requests": len(trace.requests),
        "device_ops": top_names(first, lo, hi),
        "idle_gaps": idle_rows,
    }


if __name__ == "__main__":
    import sys

    print("\n".join(describe(find_xplane(sys.argv[1]))))
