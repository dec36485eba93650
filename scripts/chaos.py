#!/usr/bin/env python
"""Chaos scenario driver: run the named e2e fault scenarios
(cometbft_tpu/e2e/scenarios.py) and emit a machine-readable pass/fail
artifact per scenario.

    python scripts/chaos.py                      # the 5 full scenarios
    python scripts/chaos.py --scenario wedge --scenario double_sign
    python scripts/chaos.py --smoke              # fast single-node smoke
    python scripts/chaos.py --json out/chaos.json --out out/artifacts
    python scripts/chaos.py --repeat 3 --seed 42 # deterministic cycling
    python scripts/chaos.py --list

Exit status: 0 iff every selected scenario passed; 1 when one or more
scenarios ran and FAILED their assertions; 3 when one or more scenarios
CRASHED (raised — a harness/environment breakage, not a chaos verdict).
The distinction lets a driver (the soak harness, CI retry logic) treat
"the network forked" differently from "the runner threw".

``--repeat N`` runs the selected scenario list N times (ports offset
per iteration so iterations never collide) and ``--seed`` pins the
deterministic load-round numbering — together they make scenarios
reusable as repeated mid-soak fault injections.  ``--json`` writes
``{"ok": bool, "scenarios": [ScenarioResult...]}``; each scenario also
leaves a per-node artifact directory (flight-recorder dump, health
snapshot, verify-service stats, node logs) under ``--out`` so a failed
run is diagnosable without a rerun.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str] | None = None) -> int:
    # Persistent XLA compile cache for every node the scenarios spawn
    # (children inherit the env, and jax reads JAX_COMPILATION_CACHE_DIR
    # by itself): repeated chaos runs stop paying the kernel recompiles.
    # setdefault — an operator's own placement always wins.  The dir is
    # harness-private (not tests/.jax_cache): these scenarios kill -9
    # nodes mid-flight, and a write torn by a kill must never be able to
    # corrupt the tier-1 suite's cache (a corrupt entry can crash jax's
    # cache read path).
    from cometbft_tpu.utils import compilecache

    os.environ.setdefault(compilecache.ENV_VAR, compilecache.HARNESS_DIR)
    from cometbft_tpu.e2e import scenarios as sc

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--scenario", action="append", default=[],
        help="scenario name (repeatable); default: the 5 full scenarios",
    )
    p.add_argument(
        "--smoke", action="store_true",
        help="run only the fast single-node wedge_smoke",
    )
    p.add_argument("--list", action="store_true", help="list scenarios and exit")
    p.add_argument("--json", default="", help="write the machine-readable verdict here")
    p.add_argument("--out", default="", help="artifact directory (default: a tmp dir)")
    p.add_argument(
        "--base-port", type=int, default=0,
        help="override the per-scenario default port ranges",
    )
    p.add_argument(
        "--repeat", type=int, default=1,
        help="run the selected scenario list N times (ports offset per "
             "iteration); the mid-soak fault-injection shape",
    )
    p.add_argument(
        "--seed", type=int, default=None,
        help="deterministic load-round numbering (repeat runs submit "
             "identical tx streams)",
    )
    args = p.parse_args(argv)

    if args.list:
        for name in sc.SCENARIOS:
            print(name)
        return 0

    names = args.scenario or (
        ["wedge_smoke"] if args.smoke else list(sc.DEFAULT_SCENARIOS)
    )
    unknown = [n for n in names if n not in sc.SCENARIOS]
    if unknown:
        print(f"unknown scenario(s): {', '.join(unknown)}", file=sys.stderr)
        print(f"known: {', '.join(sc.SCENARIOS)}", file=sys.stderr)
        return 2
    if args.repeat < 1:
        print(f"--repeat must be >= 1, got {args.repeat}", file=sys.stderr)
        return 2

    out_dir = args.out or tempfile.mkdtemp(prefix="cometbft-chaos-")
    os.makedirs(out_dir, exist_ok=True)

    results = []
    t0 = time.monotonic()
    for rep in range(args.repeat):
        rep_dir = (
            out_dir if args.repeat == 1
            else os.path.join(out_dir, f"rep{rep}")
        )
        for i, name in enumerate(names):
            # each (iteration, scenario) slot gets its own port range so
            # a lingering listener from a previous run never collides.
            # Without --base-port the scenarios' built-in defaults are
            # already disjoint within one rep, but reps would reuse
            # them — so repeats anchor above the built-in ranges.
            slot = rep * len(names) + i
            anchor = args.base_port or (27400 if args.repeat > 1 else None)
            base_port = (anchor + slot * 200) if anchor else None
            res = sc.run_scenario(
                name, rep_dir, base_port=base_port, seed=args.seed
            )
            if args.repeat > 1:
                res.details["repeat"] = rep
            results.append(res)
            print(json.dumps(res.to_dict()), flush=True)  # one line each

    verdict = {
        "ok": all(r.ok for r in results),
        "crashed": any(r.crashed for r in results),
        "repeat": args.repeat,
        "seed": args.seed,
        "elapsed_s": round(time.monotonic() - t0, 1),
        "artifact_dir": out_dir,
        "scenarios": [r.to_dict() for r in results],
    }
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(verdict, f, indent=1)
    print(
        f"chaos: {sum(r.ok for r in results)}/{len(results)} scenarios passed "
        f"in {verdict['elapsed_s']}s (artifacts: {out_dir})",
        file=sys.stderr,
    )
    if verdict["ok"]:
        return 0
    # crash (scenario raised) vs failure (assertions failed): distinct
    # exit codes so drivers can tell a broken harness from a bad verdict
    return 3 if verdict["crashed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
