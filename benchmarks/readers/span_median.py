"""Median, in ms, of the named spans' time: per batch (the spans of one
batch share a trace id; their durations are added) or per span.

args: {"spans": [names], "per": "trace_id" | "span"}
"""

from .. import stats


def read(args: dict, sources: dict):
    names = set(args["spans"])
    per_batch: dict[str, float] = {}
    singles: list[float] = []
    for e in sources["spans"]:
        if e["name"] not in names:
            continue
        if args.get("per", "span") == "trace_id":
            tid = (e.get("args") or {}).get("trace_id")
            if tid is not None:
                per_batch[tid] = per_batch.get(tid, 0.0) + e["dur"]
        else:
            singles.append(e["dur"])
    values = list(per_batch.values()) or singles
    if not values:
        return None
    return stats.median(values) / 1e3  # the ring's durations are in us
