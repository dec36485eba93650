#!/usr/bin/env python
"""Diff two bench rounds and flag regressions.

    python scripts/bench_compare.py old.json new.json
    python scripts/bench_compare.py --threshold 0.05 --json old.json new.json

Each input is a driver round wrapper (``{"n", "cmd", "rc", "tail",
"parsed": {...}}``) or a bare bench JSON line (the ``parsed`` object
itself).  Degraded/wedge rounds are EXCLUDED from comparison rather
than compared as if they were numbers: a round with a nonzero ``rc``,
a null headline ``value``, or an ``error`` key measured the failure
mode, not the code under test.

What gets diffed:

- the headline metric (``value``, lower-is-better ms): percent delta,
  regression when the new round is slower by more than ``--threshold``
  (a fraction, default 0.10);
- per-lane p50/p95 (``classes`` from ``BENCH_WORKLOAD=mixed``), each
  lane held to the same threshold;
- phase wall-share shifts (``phase_attribution[phase].share_of_wall``),
  reported in percentage points — attribution drift is a smell, not a
  gate, so shares never trip the exit code;
- the proofs sweep (``sweep`` from ``BENCH_WORKLOAD=proofs``): per
  query-count tpu/host p50/p95, each held to the threshold like the
  headline; the multiproof dedup factor is reported-only (it is a
  property of the query shape, not a latency);
- ``vs_baseline`` (speedup vs the Go CPU baseline), reported only.

Exit codes: 0 compared, within threshold; 1 regression above
threshold; 2 not comparable (degraded round, metric mismatch,
unreadable input).
"""

from __future__ import annotations

import argparse
import json
import sys


def load_round(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object")
    return doc


def classify(doc: dict, label: str) -> tuple[dict | None, str | None]:
    """(parsed bench object, exclusion reason).  Exactly one is None."""
    if "parsed" in doc or "rc" in doc:  # driver round wrapper
        rc = doc.get("rc", 0)
        if rc != 0:
            return None, f"{label}: rc={rc} (bench process failed)"
        parsed = doc.get("parsed")
        if not isinstance(parsed, dict):
            return None, f"{label}: no parsed bench line (wedged run)"
    else:
        parsed = doc
    if parsed.get("error"):
        return None, f"{label}: degraded round: {parsed['error']}"
    if parsed.get("value") is None:
        return None, f"{label}: headline value is null"
    return parsed, None


def _pct(old: float, new: float) -> float | None:
    if not old:
        return None
    return (new - old) / old


def compare(old: dict, new: dict, threshold: float) -> dict:
    """Diff two valid parsed rounds.  ``regressions`` lists every series
    that got slower than ``threshold`` allows (lower-is-better ms)."""
    report: dict = {
        "metric": old.get("metric"),
        "threshold": threshold,
        "regressions": [],
    }
    if old.get("metric") != new.get("metric"):
        report["error"] = (
            f"metric mismatch: {old.get('metric')!r} vs {new.get('metric')!r}"
        )
        return report

    d = _pct(old["value"], new["value"])
    report["headline"] = {
        "old_ms": old["value"],
        "new_ms": new["value"],
        "delta_pct": None if d is None else round(d * 100, 2),
    }
    if d is not None and d > threshold:
        report["regressions"].append(
            f"{old.get('metric')}: {old['value']} -> {new['value']} ms "
            f"({d * +100:+.1f}%)"
        )

    if old.get("vs_baseline") is not None and new.get("vs_baseline") is not None:
        report["vs_baseline"] = {
            "old": old["vs_baseline"],
            "new": new["vs_baseline"],
            "delta": round(new["vs_baseline"] - old["vs_baseline"], 3),
        }

    lanes: dict = {}
    oc, nc = old.get("classes") or {}, new.get("classes") or {}
    for lane in sorted(set(oc) & set(nc)):
        row: dict = {}
        for q in ("p50_ms", "p95_ms"):
            ov, nv = oc[lane].get(q), nc[lane].get(q)
            if ov is None or nv is None:
                continue
            dq = _pct(ov, nv)
            row[q] = {
                "old": ov,
                "new": nv,
                "delta_pct": None if dq is None else round(dq * 100, 2),
            }
            if dq is not None and dq > threshold:
                report["regressions"].append(
                    f"lane {lane} {q}: {ov} -> {nv} ({dq * 100:+.1f}%)"
                )
        if row:
            lanes[lane] = row
    if lanes:
        report["lanes"] = lanes

    if old.get("workload") == "proofs" and new.get("workload") == "proofs":
        sweep: dict = {}
        os_, ns_ = old.get("sweep") or {}, new.get("sweep") or {}
        for size in sorted(set(os_) & set(ns_), key=lambda s: int(s)):
            row = {}
            for q in ("tpu_p50_ms", "tpu_p95_ms", "host_p50_ms", "host_p95_ms"):
                ov, nv = os_[size].get(q), ns_[size].get(q)
                if ov is None or nv is None:
                    continue
                dq = _pct(ov, nv)
                row[q] = {
                    "old": ov,
                    "new": nv,
                    "delta_pct": None if dq is None else round(dq * 100, 2),
                }
                if dq is not None and dq > threshold:
                    report["regressions"].append(
                        f"proofs K={size} {q}: {ov} -> {nv} ({dq * 100:+.1f}%)"
                    )
            ov = os_[size].get("multiproof_dedup_factor")
            nv = ns_[size].get("multiproof_dedup_factor")
            if ov is not None and nv is not None:
                row["multiproof_dedup_factor"] = {
                    "old": ov, "new": nv, "delta": round(nv - ov, 2),
                }
            if row:
                sweep[size] = row
        if sweep:
            report["proofs_sweep"] = sweep

    shares: dict = {}
    oa, na = old.get("phase_attribution") or {}, new.get("phase_attribution") or {}
    for phase in sorted(set(oa) & set(na)):
        ov = (oa[phase] or {}).get("share_of_wall")
        nv = (na[phase] or {}).get("share_of_wall")
        if ov is None or nv is None:
            continue
        shares[phase] = {
            "old": ov,
            "new": nv,
            "shift_pp": round((nv - ov) * 100, 2),
        }
    if shares:
        report["phase_shares"] = shares
    return report


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="diff two bench rounds and flag regressions"
    )
    p.add_argument("old", help="baseline round (BENCH_*.json)")
    p.add_argument("new", help="candidate round (BENCH_*.json)")
    p.add_argument(
        "--threshold", type=float, default=0.10,
        help="regression threshold as a fraction (default 0.10 = 10%%)",
    )
    p.add_argument("--json", action="store_true",
                   help="print the comparison report as JSON")
    args = p.parse_args(argv)

    parsed: list[dict] = []
    for path in (args.old, args.new):
        try:
            doc = load_round(path)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"bench_compare: {path}: {e}", file=sys.stderr)
            return 2
        obj, reason = classify(doc, path)
        if obj is None:
            print(f"bench_compare: excluded: {reason}", file=sys.stderr)
            return 2
        parsed.append(obj)

    report = compare(parsed[0], parsed[1], args.threshold)
    if "error" in report:
        print(f"bench_compare: {report['error']}", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(report, indent=1))
    else:
        h = report["headline"]
        print(
            f"{report['metric']}: {h['old_ms']} -> {h['new_ms']} ms "
            f"({h['delta_pct']:+.2f}%)"
            if h["delta_pct"] is not None
            else f"{report['metric']}: {h['old_ms']} -> {h['new_ms']} ms"
        )
        if "vs_baseline" in report:
            vb = report["vs_baseline"]
            print(f"vs_baseline: {vb['old']} -> {vb['new']} ({vb['delta']:+})")
        for lane, row in report.get("lanes", {}).items():
            for q, cell in row.items():
                print(
                    f"lane {lane:>10} {q}: {cell['old']} -> {cell['new']} "
                    f"({cell['delta_pct']:+.2f}%)"
                )
        for size, row in report.get("proofs_sweep", {}).items():
            for q, cell in row.items():
                if q == "multiproof_dedup_factor":
                    print(
                        f"proofs K={size:>5} dedup: {cell['old']} -> "
                        f"{cell['new']} ({cell['delta']:+})"
                    )
                elif cell["delta_pct"] is not None:
                    print(
                        f"proofs K={size:>5} {q}: {cell['old']} -> "
                        f"{cell['new']} ({cell['delta_pct']:+.2f}%)"
                    )
        for phase, cell in report.get("phase_shares", {}).items():
            print(
                f"phase {phase:>14} share: {cell['old']:.3f} -> "
                f"{cell['new']:.3f} ({cell['shift_pp']:+.2f} pp)"
            )
        for r in report["regressions"]:
            print(f"REGRESSION: {r}", file=sys.stderr)
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
