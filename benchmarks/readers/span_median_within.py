"""Median over the named spans that lie inside a span of another name on
the same thread, either of their time in ms or, with ``count``, of how
many lie inside each such outer span.  ``where`` keeps the spans whose
labels have these values: the outer spans' if ``within`` is given, else
the named spans' own.

args: {"spans": [names], "within": "<name>", "where": {label: value},
       "count": bool}
"""

from bisect import bisect_right

from .. import stats


def _has(e: dict, where: dict) -> bool:
    args = e.get("args") or {}
    return all(args.get(k) == v for k, v in where.items())


def read(args: dict, sources: dict):
    names, where = set(args["spans"]), args.get("where", {})
    inner = [e for e in sources["spans"] if e["name"] in names]
    if "within" not in args:
        values = [e["dur"] for e in inner if _has(e, where)]
        return stats.median(values) / 1e3 if values else None
    outer: dict = {}  # thread -> its outer spans
    for e in sources["spans"]:
        if e["name"] == args["within"] and _has(e, where):
            outer.setdefault(e["tid"], []).append(e)
    starts = {}
    for tid, spans in outer.items():
        spans.sort(key=lambda e: e["ts"])
        starts[tid] = [o["ts"] for o in spans]
    inside = {id(o): [] for spans in outer.values() for o in spans}
    for e in inner:
        spans = outer.get(e["tid"])
        if not spans:
            continue
        o = spans[bisect_right(starts[e["tid"]], e["ts"]) - 1]
        if o["ts"] <= e["ts"] and e["ts"] + e["dur"] <= o["ts"] + o["dur"]:
            inside[id(o)].append(e["dur"])
    if args.get("count"):
        counts = [len(v) for v in inside.values()]
        return float(stats.median(counts)) if counts else None
    values = [d for v in inside.values() for d in v]
    return stats.median(values) / 1e3 if values else None  # the ring's us
