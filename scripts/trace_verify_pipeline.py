"""Run a synthetic VerifyCommit load with span tracing ON and write a
Chrome trace-event JSON that opens in Perfetto (ui.perfetto.dev) or
chrome://tracing — the quickest way to SEE the verification pipeline
(slab fill / H2D+dispatch / device wait / collect, caller vs staging
thread) instead of inferring it from aggregate timings.

Usage:
    JAX_PLATFORMS=cpu python scripts/trace_verify_pipeline.py \
        [--validators 64] [--iters 4] [--out verify_pipeline.trace.json]

The load goes through the real seam — crypto/batch.create_batch_verifier
with the validator set's pubkeys, so large-enough sets route to the
comb-cached verifier and its pipelined submit()/collect() — exactly the
path consensus and blocksync replay drive.  tests/test_tracing.py
smoke-runs run() at a tiny scale so tier-1 catches tracer regressions.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _enable_compile_cache() -> None:
    """Share the repo's persistent XLA compile cache (same recipe as
    chip_smoke.py): cold comb/Straus compiles are minutes on a 1-core box; a
    warm cache makes the synthetic load I/O-bound instead."""
    try:
        from __graft_entry__ import _enable_compile_cache as enable

        enable()
    except Exception:  # noqa: BLE001 — cache is an optimization only
        pass


def run(
    n_validators: int = 64,
    iters: int = 4,
    out_path: str = "verify_pipeline.trace.json",
) -> dict:
    """Build one validator set, verify `iters` synthetic commits through
    the batch-verifier seam with tracing on, export the trace.  Returns
    {"path", "events", "phases"} (phases = distinct span/instant names).
    Callers that want the comb path at small scale set
    COMETBFT_TPU_COMB_MIN / COMETBFT_TPU_DEVICE_BATCH_MIN first."""
    _enable_compile_cache()
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519 as host
    from cometbft_tpu.utils import tracing

    tracing.set_enabled(True)
    tracing.reset()

    keys = [
        host.PrivKey.from_seed(bytes([40 + (i % 200)]) * 31 + bytes([i // 200]))
        for i in range(n_validators)
    ]
    pubs = [k.pub_key().data for k in keys]

    with tracing.span("trace_script.table_build"):
        crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)

    for it in range(iters):
        bv = crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
        with tracing.span("trace_script.add_loop", {"iter": it}):
            for i, sk in enumerate(keys):
                msg = b"trace-%d-%d" % (it, i)
                bv.add(pubs[i], msg, sk.sign(msg))
        ok, per_sig = bv.verify()
        assert ok and len(per_sig) == n_validators, "synthetic commit must verify"

    n_events = tracing.export_chrome_trace(out_path)
    with open(out_path) as f:
        events = json.load(f)["traceEvents"]
    phases = sorted({e["name"] for e in events if e["ph"] in ("X", "i")})
    return {"path": out_path, "events": n_events, "phases": phases}


def main(argv: list[str]) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--validators", type=int, default=64)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--out", default="verify_pipeline.trace.json")
    args = ap.parse_args(argv)
    res = run(args.validators, args.iters, args.out)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
