"""The chip's idle time inside requests, in ms per request, by the span
of the request's path that covers it, read off the program's span ring
(utils/tracing) rather than the profile's mirrored annotations: the ring
also holds what the profile cannot, the intervals whose start was
stamped earlier or on another thread (queue waits, wake-ups), and each
span's parent, its trace id and whether it is a wait.

1. The ring is placed on the profile's clock by the clock anchors
   (``verify.clock_anchor``): each is a ring event and a mirrored
   annotation, so a pair gives the offset between the two clocks.
2. The request's path: the spans of the thread that runs the requests
   inside each ``bench.request``, and every span sharing a trace id with
   one of them.  Spans of other threads without such a trace id
   (background binds on ``comb-build``) are not on it.
3. Each idle gap of the chip inside a request is cut at the edges of
   those spans.  On each thread the innermost span over a piece (the
   latest-started: a thread's spans nest) says what that thread was
   doing, so a span whose child waits is not at work meanwhile.  The
   piece goes to the latest-started of those that is not a wait (arg
   ``wait``); where all are waits, to the latest-started wait; where
   nothing covers it, to no span.

The pieces add up to the idle time inside requests, the same total
that ``xplane_idle_under_spans`` splits four ways.

args: {"spans": [names]}  ms a request under those spans
      {"unnamed": true}   ms a request under no span of the path
"""

from __future__ import annotations

import sys
from bisect import bisect_left

from .. import profile_rows, stats, xplane

ANCHOR = "verify.clock_anchor"
# the two readings of one anchor differ from the clocks' offset by less
PAIR_NS = 100_000.0
# a ring span is held to a mirrored annotation of its name this close
MIRROR_NS = 1_000_000.0


def read(args: dict, sources: dict):
    trace = sources["trace"]
    if trace is None or not trace.requests:
        return None
    rows = profile_rows.of(sources)
    if rows is None:
        return None
    if "ring_idle_by_span" not in rows.memo:
        by_span = reduce(trace, rows.annotations, sources["spans"])
        rows.memo["ring_idle_by_span"] = by_span
        if by_span is not None:
            _log(by_span, len(trace.requests))
    by_span = rows.memo["ring_idle_by_span"]
    if by_span is None:
        return None
    if args.get("unnamed"):
        ns = by_span["idle"].get(None, 0.0)
    else:
        ns = sum(by_span["idle"].get(n, 0.0) for n in args["spans"])
    return ns / 1e6 / len(trace.requests)


# ------------------------------------------------------------ the clock


def anchor_pairs(ring: list[dict], annotations) -> list[tuple[float, float]]:
    """[(ring ns, profile ns), ...] of the clock anchors found on both
    sides, in order.  Either side may hold an anchor the other lacks
    (one written as the profiler started or stopped); of the offsets a
    ring anchor and a profile anchor give, the one the most anchors
    agree on wins, and of those the one under which the ring's spans
    lie closest to their mirrored annotations."""
    mine = sorted(e["ts"] * 1e3 for e in ring if e["name"] == ANCHOR)
    theirs = sorted(s for s, _, name in annotations if name == ANCHOR)
    found = {
        tuple((a, b) for a in mine for b in theirs
              if abs(a - b - (r - p)) < PAIR_NS)
        for r in mine for p in theirs
    }
    most = max(map(len, found), default=0)
    tied = [list(pairs) for pairs in found if len(pairs) == most]
    if len(tied) < 2:
        return tied[0] if tied else []
    return min(tied, key=lambda pairs: _spread(ring, annotations, pairs))


def to_profile(pairs, ring_ns: float) -> float:
    """A ring time on the profile's clock, by the nearest anchor."""
    at = bisect_left(pairs, (ring_ns,))
    r, p = min(pairs[max(at - 1, 0):at + 1], key=lambda q: abs(q[0] - ring_ns))
    return ring_ns - r + p


def residuals(spans, annotations) -> list[float]:
    """|mapped ring start - mirrored annotation start| in ns, for each
    of ``spans`` ((start, end, event) on the profile's clock) that has
    an annotation of its name within MIRROR_NS."""
    starts: dict[str, list[float]] = {}
    for s, _, name in annotations:
        starts.setdefault(name, []).append(s)
    for v in starts.values():
        v.sort()
    out = []
    for s, _, e in spans:
        mirrored = starts.get(e["name"])
        if mirrored:
            at = bisect_left(mirrored, s)
            d = min(abs(s - m) for m in mirrored[max(at - 1, 0):at + 1])
            if d < MIRROR_NS:
                out.append(d)
    return out


def _spread(ring, annotations, pairs) -> float:
    """The median residual of the ring's spans that ``pairs`` places
    inside the profile; infinite where it places none there."""
    lo = min((s for s, _, _ in annotations), default=0.0)
    hi = max((t for _, t, _ in annotations), default=0.0)
    spans = []
    for e in ring:
        if e["name"] != ANCHOR:
            s = to_profile(pairs, e["ts"] * 1e3)
            if lo <= s <= hi:
                spans.append((s, s, e))
    got = residuals(spans, annotations)
    return stats.median(got) if got else float("inf")


# ------------------------------------------------------------- the path


def _args(e: dict) -> dict:
    return e.get("args") or {}


def on_path(spans, requests) -> list[tuple[float, float, str, bool, int]]:
    """(start, end, name, wait, thread) of those of ``spans`` ((start,
    end, event) on the profile's clock) that lie on the requests' path
    (module docstring, step 2)."""
    inside = xplane.merge(requests, float("-inf"), float("inf"))

    def request_at(s: float, t: float):
        """The request [s, t] reaches into, if any."""
        at = bisect_left(inside, (s, float("inf"))) - 1
        for r in inside[max(at, 0):at + 2]:
            if min(r[1], t) >= max(r[0], s):
                return r
        return None

    # the thread that runs the requests: the one whose spans without a
    # parent lie inside requests for the longest time
    roots: dict = {}
    for s, t, e in spans:
        r = request_at(s, t)
        if "parent" not in _args(e) and r is not None and r[0] <= s and t <= r[1]:
            roots[e["tid"]] = roots.get(e["tid"], 0.0) + (t - s)
    if not roots:
        return []
    caller = max(roots, key=roots.get)
    mine = [e for s, t, e in spans
            if e["tid"] == caller and request_at(s, t) is not None]
    traces = {_args(e).get("trace_id") for e in mine} - {None}
    return [
        (s, t, e["name"], bool(_args(e).get("wait")), e["tid"])
        for s, t, e in spans
        if (e["tid"] == caller and request_at(s, t) is not None)
        or _args(e).get("trace_id") in traces
    ]


def owners(path) -> list[tuple[float, float, str]]:
    """The time the path's spans cover, cut at their edges, each segment
    with the name of its owner: of each thread's innermost span over it,
    the latest-started that is not a wait, else the latest-started."""
    starting: dict[float, list] = {}
    for sp in path:
        if sp[1] > sp[0]:
            starting.setdefault(sp[0], []).append(sp)
    for same in starting.values():
        same.sort(key=lambda sp: -sp[1])  # of one start, the inner last
    points = sorted({p for s, t, *_ in path if t > s for p in (s, t)})
    active: list = []
    out = []
    for a, b in zip(points, points[1:]):
        active = [sp for sp in active if sp[1] > a] + starting.get(a, [])
        if active:
            inner: dict = {}
            for sp in active:  # in order of start: the innermost last
                inner[sp[4]] = sp
            work = [sp for sp in inner.values() if not sp[3]]
            out.append((a, b, max(work or inner.values(),
                                  key=lambda sp: sp[0])[2]))
    return out


def split(idle, segments) -> dict:
    """ns of the idle pieces under each owner, and under None what no
    segment covers.  Both lists are sorted and disjoint."""
    out: dict = {}
    covered = 0.0
    j = 0
    for s, t in idle:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < t:
            a, b, name = segments[k]
            d = min(b, t) - max(a, s)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                covered += d
            k += 1
    out[None] = out.get(None, 0.0) + profile_rows.total(idle) - covered
    return out


def reduce(trace: xplane.Trace, annotations, ring: list[dict]) -> dict | None:
    """{"idle": {span name or None: ns}, "total": ns, "residual_ns":
    [...], "anchors": n}; None where the profile holds no anchor of the
    ring (a program that writes none) or the chip ran nothing."""
    pairs = anchor_pairs(ring, annotations)
    if not pairs:
        return None
    lo, hi = trace.window()
    ops = next((o for o in trace.ops.values()
                if any(min(e, hi) > max(s, lo) for s, e, _ in o)), None)
    if ops is None:
        return None
    spans = []  # on the profile's clock, those that reach into the window
    for e in ring:
        if e["name"] != ANCHOR:
            s = to_profile(pairs, e["ts"] * 1e3)
            t = s + e["dur"] * 1e3
            if t > lo and s < hi:
                spans.append((s, t, e))
    idle = profile_rows.intersect(xplane.gaps(ops, lo, hi),
                                  xplane.merge(trace.requests, lo, hi))
    return {
        "idle": split(idle, owners(on_path(spans, trace.requests))),
        "total": profile_rows.total(idle),
        "residual_ns": residuals(
            [sp for sp in spans if lo <= sp[0] <= hi], annotations),
        "anchors": len(pairs),
    }


def _log(by_span: dict, requests: int) -> None:
    """What PERF.md's breakdown reads: the whole partition and how well
    the clocks agree (stderr; the result line keeps its metrics)."""
    res = by_span["residual_ns"]
    clock = (f"median {stats.median(res) / 1e3:.3f} us, max "
             f"{max(res) / 1e3:.3f} us over {len(res)} mirrored spans"
             if res else "no mirrored span to hold it to")
    per = {str(k): round(v / 1e6 / requests, 4)
           for k, v in sorted(by_span["idle"].items(), key=lambda kv: -kv[1])}
    print(f"bench: ring clock: {by_span['anchors']} anchors, residual "
          f"{clock}; idle inside requests "
          f"{by_span['total'] / 1e6 / requests:.4f} ms a request, by span: "
          f"{per}", file=sys.stderr, flush=True)
