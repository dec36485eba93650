#!/usr/bin/env python
"""Multi-tenant verify-plane soak driver: sustained mixed load from M
in-process chains over ONE shared verify service, a rogue tenant's
mempool flood, and mid-soak fault injections (device-wedge failover
cycles; optionally a full chaos scenario — node crash + WAL replay — as
a concurrent subprocess), with a machine-readable per-tenant SLO
artifact asserting no starvation, quota isolation, no leak, no drift,
and fault endurance (cometbft_tpu/e2e/soak.py).

    python scripts/soak.py                              # 5 min, 3 tenants
    python scripts/soak.py --duration 3600 --tenants 8  # the long haul
    python scripts/soak.py --duration 30 --no-chaos --json out/soak.json
    python scripts/soak.py --smoke                      # tier-1 shape, ~10 s
    python scripts/soak.py --remote-plane               # out-of-process
                                                        # verifyd, kill -9'd
                                                        # and revived mid-soak

``--remote-plane`` spawns a verifyd subprocess and routes every
tenant's batches over the RPC surface (verifysvc/remote.py): quotas
are enforced server-side, each mid-soak fault cycle kill -9s the plane
with batches in flight (breaker trip -> host fallback -> restart ->
probation restore), and the default concurrent chaos scenario becomes
``plane_crash`` — REAL node processes sharing their own verifyd that
dies and returns mid-height.

Exit status: 0 iff every SLO assertion held.  ``--json`` (default
``out/soak.json``) writes the full report; the assertions block is also
printed to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str] | None = None) -> int:
    # CPU determinism + warm compile cache for any real-plane run and
    # for the chaos subprocess's nodes (same reasoning as chaos.py:
    # setdefault so an operator's environment always wins; harness-
    # private cache dir so a kill -9-torn write can't corrupt tier-1's)
    from cometbft_tpu.utils import compilecache

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault(compilecache.ENV_VAR, compilecache.HARNESS_DIR)
    from cometbft_tpu.e2e.soak import SoakConfig, run_soak

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tenants", type=int, default=3)
    p.add_argument("--validators", type=int, default=16,
                   help="validator-set size per chain (commit width)")
    p.add_argument("--duration", type=float, default=300.0,
                   help="soak length in seconds (default 300 = 5 min)")
    p.add_argument("--seed", type=int, default=7,
                   help="deterministic workload seed (keys, tamper pattern)")
    p.add_argument("--rogue", default="",
                   help="tenant that floods (default: the last chain)")
    p.add_argument("--flood-senders", type=int, default=3)
    p.add_argument("--flood-batch-sigs", type=int, default=8)
    p.add_argument("--quota", type=int, default=128,
                   help="per-(tenant, class) signature quota")
    p.add_argument("--wedge-cycles", type=int, default=2,
                   help="mid-soak device-wedge failover cycles")
    p.add_argument("--plane", choices=("fake", "real"), default="fake",
                   help="data plane: fake = deterministic CPU device "
                        "(production scheduling, host crypto), real = "
                        "the jitted kernels")
    p.add_argument("--chaos-scenario", action="append", default=[],
                   help="chaos scenario(s) to run as concurrent "
                        "subprocesses mid-soak (repeatable); default "
                        "crash_replay unless --no-chaos")
    p.add_argument("--no-chaos", action="store_true",
                   help="skip the concurrent chaos subprocess")
    p.add_argument("--starvation-factor", type=float, default=2.0)
    p.add_argument("--starvation-floor-ms", type=float, default=0.0)
    p.add_argument("--json", default="out/soak.json",
                   help="SLO artifact path ('' disables)")
    p.add_argument("--out", default="",
                   help="artifact dir for forensics/chaos (default: tmp)")
    p.add_argument("--base-port", type=int, default=29400,
                   help="base port for the chaos subprocess's nodes")
    p.add_argument("--smoke", action="store_true",
                   help="the fast tier-1 shape: 2 tenants, ~10 s, one "
                        "wedge cycle, no chaos subprocess")
    p.add_argument("--remote-plane", action="store_true",
                   help="spawn a verifyd subprocess and run the soak "
                        "over the RPC surface; fault cycles kill -9 the "
                        "plane instead of wedging a fake device")
    p.add_argument("--verifyd-port", type=int, default=29900,
                   help="port the spawned verifyd listens on (0 = "
                        "ephemeral)")
    args = p.parse_args(argv)

    if args.smoke:
        cfg = SoakConfig(
            tenants=2, validators_per_chain=4, duration_s=10.0,
            seed=args.seed, flood_senders=2, flood_batch_sigs=8,
            tenant_quota=48, wedge_cycles=1, wedge_hold_s=1.0,
            probation_ok=2, probe_period_s=0.1, batch_deadline_s=0.5,
            starvation_floor_ms=max(args.starvation_floor_ms, 250.0),
            leak_check=False, commit_pause_s=0.02, checktx_period_s=0.1,
            artifact_dir=args.out, json_path=args.json,
            remote_plane=args.remote_plane, verifyd_port=args.verifyd_port,
        )
    else:
        chaos = tuple(args.chaos_scenario) or (
            () if args.no_chaos
            else (("plane_crash",) if args.remote_plane else ("crash_replay",))
        )
        cfg = SoakConfig(
            tenants=args.tenants,
            validators_per_chain=args.validators,
            duration_s=args.duration,
            seed=args.seed,
            rogue=args.rogue,
            flood_senders=args.flood_senders,
            flood_batch_sigs=args.flood_batch_sigs,
            tenant_quota=args.quota,
            wedge_cycles=args.wedge_cycles,
            data_plane=args.plane,
            starvation_factor=args.starvation_factor,
            starvation_floor_ms=args.starvation_floor_ms,
            chaos_scenarios=chaos,
            chaos_base_port=args.base_port,
            artifact_dir=args.out,
            json_path=args.json,
            remote_plane=args.remote_plane,
            verifyd_port=args.verifyd_port,
        )

    report = run_soak(cfg)
    print(json.dumps(
        {"ok": report["ok"], "duration_s": report["duration_s"],
         "assertions": report["assertions"]},
        indent=1, default=str,
    ))
    if args.json:
        print(f"soak: full SLO artifact at {args.json}", file=sys.stderr)
    print(
        f"soak: {'PASS' if report['ok'] else 'FAIL'} "
        f"({report['duration_s']}s, {cfg.tenants} tenants, "
        f"{len(report['assertions'])} assertions)",
        file=sys.stderr,
    )
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
