#!/usr/bin/env python
"""Repo linter entry point — the `go vet` of this codebase.

    python scripts/lint.py [paths...] [--json] [--list-checks]
                           [--check ID ...]
    python scripts/lint.py regen-fingerprints
    python scripts/lint.py regen-shardings
    python scripts/lint.py regen-ranges

Runs every check in cometbft_tpu/analysis over the given paths (default:
the cometbft_tpu package), filters through the checked-in allowlist
(cometbft_tpu/analysis/allowlist.txt), and exits non-zero when any
non-allowlisted finding remains.  Stale allowlist entries are reported
on stderr (and under "stale_allowlist" in --json) but don't fail the
run.

--check restricts the run to the named check id(s).  The special id
``kernel`` selects the kernel contract gate: the three kernel-plane AST
checks (untracked-jit, host-sync-in-hot-path, weak-type-literal) PLUS
the kernelcheck trace pass — every manifest kernel abstract-interpreted
under JAX_PLATFORMS=cpu and diffed against the checked-in fingerprints
(docs/kernel_contracts.md).  ``regen-fingerprints`` re-traces everything
and rewrites cometbft_tpu/analysis/kernel_fingerprints.json after a
DELIBERATE kernel change (contract violations still refuse).

The special id ``sharding`` selects the sharded-program contract gate
(docs/sharding_contracts.md): the donated-read-after-dispatch AST check
PLUS the shardcheck trace pass — every mesh-parameterized kernel traced
under a REAL 8-way CPU mesh in a forced-environment subprocess
(XLA_FLAGS=--xla_force_host_platform_device_count=8, JAX_PLATFORMS=cpu,
works on CPU-only hosts) and held to its declared shardings, collective
census, compile-cost budgets, donation discipline, and the checked-in
cometbft_tpu/analysis/shard_fingerprints.json goldens.
``regen-shardings`` re-traces and rewrites the goldens; open contract
findings refuse regeneration — blessing drift never blesses a broken
contract.

The special id ``range`` selects the limb-range contract gate
(docs/limb_headroom.md): the unchecked-shift-width AST check PLUS the
rangecheck interval pass — every manifest kernel abstract-interpreted
over declared input ranges, every intermediate held to its dtype's safe
range (int32 magnitude, the 2^24 f32-exact threshold), declared output
ranges enforced, and the result diffed against the checked-in
cometbft_tpu/analysis/range_fingerprints.json certificates.
``regen-ranges`` re-interprets and rewrites the certificates; open
overflow findings refuse regeneration.

The special id ``taint`` selects the Byzantine-input contract gate
(docs/byzantine_inputs.md): the unbounded-wire-length AST check PLUS
the taintcheck dataflow pass — every decode surface diffed against
taint_manifest.DECODE_SITES in both directions, and every declared
source abstract-interpreted over a taint lattice to prove no untrusted
value reaches a consensus/state/store/dispatch sink without a declared
sanitizer on the path.

Check toggles live in pyproject.toml:

    [tool.cometbft-tpu-lint]
    disable = ["check-id", ...]
    allowlist = "cometbft_tpu/analysis/allowlist.txt"

The gate test (tests/test_static_analysis.py) runs the same machinery,
so a finding that would fail this script also fails the tier-1 suite.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tomllib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cometbft_tpu.analysis import linter  # noqa: E402


def load_config(pyproject: str) -> dict:
    """The [tool.cometbft-tpu-lint] table, {} when absent."""
    try:
        with open(pyproject, "rb") as f:
            data = tomllib.load(f)
    except (FileNotFoundError, ValueError):
        return {}
    table = data.get("tool", {}).get("cometbft-tpu-lint")
    return table if isinstance(table, dict) else {}


def regen_fingerprints() -> int:
    """Re-trace every manifest kernel and rewrite the golden file."""
    from cometbft_tpu.analysis import kernelcheck

    findings, traces = kernelcheck.regenerate()
    for f in findings:
        print(f.render())
    if findings:
        print(
            f"\n{len(findings)} contract finding(s) — regeneration only "
            "blesses drift, never a broken contract; goldens NOT written",
            file=sys.stderr,
        )
        return 1
    print(
        f"traced {len(traces)} kernels -> {kernelcheck.FINGERPRINTS_PATH}"
    )
    return 0


def regen_shardings() -> int:
    """Re-trace every sharded manifest kernel in the forced 8-device
    child and rewrite the shard goldens."""
    from cometbft_tpu.analysis import shardcheck

    findings, data = shardcheck.run_subprocess(regen=True)
    for f in findings:
        print(f.render())
    if findings or not data.get("regen_written"):
        print(
            f"\n{len(findings)} contract finding(s) — regeneration only "
            "blesses drift, never a broken contract; shard goldens NOT "
            "written",
            file=sys.stderr,
        )
        return 1
    print(
        f"traced {len(data.get('kernels', {}))} sharded kernels on "
        f"{data.get('device_count')} devices -> "
        f"{shardcheck.SHARD_FINGERPRINTS_PATH}"
    )
    return 0


def regen_ranges() -> int:
    """Re-interpret every manifest kernel and rewrite the range
    certificates."""
    from cometbft_tpu.analysis import rangecheck

    findings, reports = rangecheck.regenerate()
    for f in findings:
        print(f.render())
    if findings:
        print(
            f"\n{len(findings)} range finding(s) — regeneration only "
            "blesses drift, never an open overflow; certificates NOT "
            "written",
            file=sys.stderr,
        )
        return 1
    print(
        f"interpreted {len(reports)} kernels -> "
        f"{rangecheck.RANGE_FINGERPRINTS_PATH}"
    )
    return 0


def main(argv: list[str] | None = None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "regen-fingerprints":
        return regen_fingerprints()
    if argv and argv[0] == "regen-shardings":
        return regen_shardings()
    if argv and argv[0] == "regen-ranges":
        return regen_ranges()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=None)
    ap.add_argument("--json", action="store_true", help="machine output")
    ap.add_argument("--list-checks", action="store_true")
    ap.add_argument(
        "--check",
        action="append",
        metavar="ID",
        help="restrict to the given check id(s); 'kernel' = the three "
        "kernel-plane AST checks + the kernelcheck trace/fingerprint gate; "
        "'sharding' = the 8-device shardcheck gate; 'range' = the "
        "unchecked-shift-width AST check + the rangecheck interval gate; "
        "'taint' = the unbounded-wire-length AST check + the taintcheck "
        "Byzantine-input dataflow gate",
    )
    ap.add_argument(
        "--config",
        default=os.path.join(repo_root, "pyproject.toml"),
        help="pyproject.toml with [tool.cometbft-tpu-lint]",
    )
    ap.add_argument(
        "--allowlist",
        default=None,
        help="override the allowlist path (config/default otherwise)",
    )
    args = ap.parse_args(argv)

    checks = linter.all_checks()
    all_ids = set(checks)
    if args.list_checks:
        for cid, m in checks.items():
            print(f"{cid}: {m.SUMMARY}")
        print("kernel: the kernel contract gate (kernel AST checks + "
              "kernelcheck trace/fingerprint pass)")
        print("sharding: the sharded-program contract gate (donated-read "
              "AST check + 8-device shardcheck trace/golden pass)")
        print("range: the limb-range contract gate (unchecked-shift-width "
              "AST check + rangecheck interval/certificate pass)")
        print("taint: the Byzantine-input contract gate (unbounded-wire-"
              "length AST check + taintcheck decode-surface/dataflow pass)")
        return 0

    run_trace = False
    run_shard_trace = False
    run_range_trace = False
    run_taint_trace = False
    if args.check:
        ids: list[str] = []
        for c in args.check:
            if c == "kernel":
                run_trace = True
                ids.extend(linter.KERNEL_CHECK_IDS)
            elif c == "sharding":
                run_shard_trace = True
                ids.extend(linter.SHARDING_CHECK_IDS)
            elif c == "range":
                run_range_trace = True
                ids.extend(linter.RANGE_CHECK_IDS)
            elif c == "taint":
                run_taint_trace = True
                ids.extend(linter.TAINT_CHECK_IDS)
            else:
                ids.append(c)
        unknown_ids = set(ids) - set(checks)
        if unknown_ids:
            print(f"unknown check(s): {sorted(unknown_ids)}", file=sys.stderr)
            return 2
        checks = {cid: m for cid, m in checks.items() if cid in set(ids)}

    cfg = load_config(args.config)
    disable = set(cfg.get("disable", ()))
    unknown = disable - all_ids  # not the --check-restricted subset
    if unknown:
        print(f"config disables unknown check(s): {sorted(unknown)}",
              file=sys.stderr)
        return 2
    allowlist_path = args.allowlist or cfg.get(
        "allowlist", linter.default_allowlist_path()
    )
    if not os.path.isabs(allowlist_path) and not os.path.exists(allowlist_path):
        allowlist_path = os.path.join(repo_root, allowlist_path)

    paths = args.paths or [os.path.join(repo_root, "cometbft_tpu")]
    allowlist = linter.Allowlist.load(allowlist_path)
    try:
        findings, stale = linter.lint_paths(
            paths, checks=checks, allowlist=allowlist, disable=disable
        )
    except FileNotFoundError as e:
        # a typo'd path linting zero files must not read as a clean pass
        print(str(e), file=sys.stderr)
        return 2

    kernel_summary = None
    if run_trace:
        from cometbft_tpu.analysis import kernelcheck

        kfindings, traces = kernelcheck.run_check()
        kfindings = [f for f in kfindings if not allowlist.suppresses(f)]
        findings = findings + kfindings
        kernel_summary = kernelcheck.summary(kfindings, traces)
        stale = allowlist.unused()  # kernel findings may have used entries

    range_summary = None
    if run_range_trace:
        from cometbft_tpu.analysis import rangecheck

        rfindings, reports = rangecheck.run_check()
        rfindings = [f for f in rfindings if not allowlist.suppresses(f)]
        findings = findings + rfindings
        range_summary = rangecheck.summary(rfindings, reports)
        stale = allowlist.unused()

    taint_summary = None
    if run_taint_trace:
        from cometbft_tpu.analysis import taintcheck

        tfindings, treport = taintcheck.run_check()
        tfindings = [f for f in tfindings if not allowlist.suppresses(f)]
        findings = findings + tfindings
        taint_summary = taintcheck.summary(tfindings, treport)
        stale = allowlist.unused()

    shard_summary = None
    if run_shard_trace:
        from cometbft_tpu.analysis import shardcheck

        # the trace runs in a forced-environment child (8 CPU devices)
        # so this works on CPU-only hosts and never touches a real
        # accelerator; the child reports RAW findings and the
        # allowlist — including an --allowlist/--config override — is
        # applied here only, so used/stale entry bookkeeping stays exact
        sfindings, shard_summary = shardcheck.run_subprocess()
        sfindings = [f for f in sfindings if not allowlist.suppresses(f)]
        findings = findings + sfindings
        # the child's "ok" predates the allowlist; recompute both fields
        # post-filter so a blessed state reads green here too
        shard_summary = {
            **shard_summary, "ok": not sfindings, "findings": len(sfindings),
        }
        stale = allowlist.unused()

    if args.check:
        # a restricted run must not call entries for checks that never
        # ran "stale" — only full runs can prove an entry matches nothing
        enabled_ids = set(checks)
        if run_trace:
            from cometbft_tpu.analysis import kernelcheck

            enabled_ids |= set(kernelcheck.FINDING_CHECK_IDS)
        if run_shard_trace:
            from cometbft_tpu.analysis import shardcheck

            enabled_ids |= set(shardcheck.FINDING_CHECK_IDS)
        if run_range_trace:
            from cometbft_tpu.analysis import rangecheck

            enabled_ids |= set(rangecheck.FINDING_CHECK_IDS)
        if run_taint_trace:
            from cometbft_tpu.analysis import taintcheck

            enabled_ids |= set(taintcheck.FINDING_CHECK_IDS)
        stale = [e for e in stale if e.check in enabled_ids]

    if args.json:
        print(json.dumps(
            {
                "findings": [
                    {
                        "check": f.check, "path": f.path, "line": f.line,
                        "col": f.col, "message": f.message,
                    }
                    for f in findings
                ],
                "stale_allowlist": [
                    {"check": e.check, "path": e.path, "line": e.line,
                     "allowlist_line": e.lineno}
                    for e in stale
                ],
                "ok": not findings,
                **({"kernel": kernel_summary} if kernel_summary else {}),
                **({"sharding": shard_summary} if shard_summary else {}),
                **({"range": range_summary} if range_summary else {}),
                **({"taint": taint_summary} if taint_summary else {}),
            },
            indent=2,
        ))
    else:
        for f in findings:
            print(f.render())
        for e in stale:
            print(
                f"stale allowlist entry (line {e.lineno}): {e.check} "
                f"{e.path}{':' + str(e.line) if e.line else ''} — "
                "matched nothing; remove it",
                file=sys.stderr,
            )
        if findings:
            print(f"\n{len(findings)} finding(s)", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
