"""Benchmark of record: VerifyCommit over a 10,000-validator Commit.

Measures the BatchVerifier path the engine actually uses for commit
verification (types/validation.py -> crypto/batch.create_batch_verifier):
the validator-set-keyed comb-table cache (models/comb_verifier.py).  The
timed region is one full verification call — host batch assembly
(vectorized numpy; the SHA-512 challenge digests are computed on device)
plus the device comb kernel (ops/comb.verify_cached: no doublings, no
pubkey decompression) — i.e. the same work the reference does on CPU via
curve25519-voi in verifyCommitBatch (types/validation.go:265,
crypto/ed25519/ed25519.go:220), with the expanded-key cache warm on both
sides (ed25519.go:43,68 <-> the resident comb tables, built once per
validator set outside the timed region and reported in table_build_s).

Prints ONE JSON line; exits 0 only when the run measured what it set out
to measure:
  {"metric": "verify_commit_p50_10k_ms", "value": <p50 ms>, "unit": "ms",
   "platform": "tpu", "device_kind": "...", "device_count": 1,
   "vs_baseline": <Go-CPU-baseline / ours, i.e. speedup>, "phases": {...},
   "phase_attribution": {phase: {"p50_ms", "share_of_wall"}, ...}}
Every line names the device it ran on, as JAX reports it.  The script
never changes platform by itself: a caller's JAX_PLATFORMS=cpu (or
BENCH_MULTICHIP_CPU=1) is an explicit request, honored and stamped as
what it is, and a number from such a run is a CPU-backend number.
phase_attribution is the per-phase median over ALL timed iterations,
keyed verbatim by the last_timings keys models/comb_verifier.py records
per call (assembly_ms / h2d_dispatch_ms / staging_wait_ms /
device_wait_ms / submit_ms / kernel_ms); BENCH_TRACE=<path> additionally
exports a Chrome trace of the timed region (utils/tracing) and sets
"traced": true so traced values are never compared against untraced
baselines.
On any failure — a failed probe, a watchdog expiry, an exception — the
line still prints, carrying "error" plus whatever phases completed, and
the exit code is non-zero: there is no host-path round under a device
metric's name.  The backend is probed BEFORE the expensive table build
(utils/healthmon.probe_devices: in a throwaway subprocess with a hard
timeout, because this process has not attached yet and an accelerator
can hang backend init instead of erroring; the child exits, freeing the
chip, before this process attaches).  The probe passes only if it finds
the platform the caller expects: a TPU, unless JAX_PLATFORMS names
another.  When the probe fails the line additionally carries
"kernelcheck": the CPU-only static contract pass over every manifest
kernel (analysis/kernelcheck) — a backend-less round still certifies
that the verify plane's shapes, dtypes, and jaxpr fingerprints hold —
and "shardcheck": the sharded-plane contract pass (analysis/shardcheck)
traced under a forced 8-device CPU mesh in a subprocess, certifying
shardings, collective census, compile-cost budgets, and donation
discipline — and "rangecheck": per-kernel overflow headroom from the
checked-in range certificates (analysis/rangecheck) with a live
interval spot-check over the fast hash-plane kernels (BENCH_RANGECHECK=0
opts out, like the other two).  Those blocks are static analysis, not
measurements.

BENCH_WORKLOAD=multichip sweeps the same verify over device counts
(default 1/2/4/8) and reports per-count p50 scaling plus
cold-start-to-first-verify from an empty comb cache — the ROADMAP item 1
capture (see _run_multichip); BENCH_WORKLOAD=mixed drives concurrent
consensus + mempool CheckTx load through the verify service;
BENCH_WORKLOAD=bls sweeps validator-set sizes comparing ed25519-batch
vs BLS-aggregate-commit p50 and reports the crossover set size
(see _run_bls); BENCH_WORKLOAD=secp sweeps batch sizes comparing the
TPU-batched secp256k1/ECDSA lane vs the pure-host lane and drives a
mixed ed25519+secp CheckTx ingest round with per-key-type per-class
latency (see _run_secp); BENCH_WORKLOAD=proofs sweeps coalesced
Merkle-proof query counts comparing the one-dispatch TPU proof kernel
(ops/merkle.proofs_from_leaves) against the host
proofs_from_byte_slices oracle — bit-identity is asserted on every
swept size, and the multiproof shared-node dedup factor rides in the
same line (see _run_proofs).

Baseline: curve25519-voi batch verify ~27.5 us/sig/core on the QA CPUs
(BASELINE.md: 50-60 us single, ~2x batch gain) -> 275 ms for 10k sigs.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# The ONE hang-proof device probe lives in the library — the health
# sentinel runs it periodically on live nodes, this bench runs it before
# the expensive table build.  utils/healthmon imports no jax, so this
# process probes in a child (it holds no chip yet) and the "jax not yet
# imported" contract the kernelcheck block relies on still holds.
from cometbft_tpu.utils import healthmon as _healthmon

GO_CPU_US_PER_SIG = 27.5

# The bench measures the WARM comb path; the async background build
# (crypto/batch.comb_async_min) would route the timed calls through the
# uncached fallback while tables warm — force synchronous builds.
os.environ.setdefault("COMETBFT_TPU_COMB_ASYNC_MIN", str(1 << 30))


def _probe_timeout_s() -> int:
    try:
        return int(os.environ.get("BENCH_PROBE_TIMEOUT", "240") or 240)
    except ValueError:
        return 240

REPORT: dict = {
    "metric": "verify_commit_p50_10k_ms",
    "value": None,
    "unit": "ms",
    # the device this line's numbers come from, as JAX reports it
    # (_stamp_device); None = the run never reached one
    "platform": None,
    "device_kind": None,
    "device_count": None,
    "vs_baseline": None,
    "verifier": "comb-cached",
    "phases": {},
}


def emit_and_exit() -> None:
    """Print the one line; exit 0 only if it carries no "error"."""
    print(json.dumps(REPORT))
    raise SystemExit(1 if "error" in REPORT else 0)


def _stamp_device() -> None:
    """Name the device on the line, as this process's JAX reports it."""
    import jax

    devs = jax.devices()
    REPORT["platform"] = devs[0].platform
    REPORT["device_kind"] = devs[0].device_kind
    REPORT["device_count"] = len(devs)


def _arm_run_watchdog() -> None:
    """Guarantee ONE structured JSON line even if the run hangs AFTER
    the probe passed (an accelerator can stop answering mid-benchmark).
    A daemon timer prints the report with an error and hard-exits
    non-zero; BENCH_HARD_TIMEOUT seconds, default 2400 (enough for a
    cold 10k table build + 12 timed iterations), 0 disables."""
    import threading

    try:
        budget = int(os.environ.get("BENCH_HARD_TIMEOUT", "2400") or 0)
    except ValueError:
        budget = 2400
    if budget <= 0:
        return

    def fire():
        REPORT["error"] = f"bench wedged: no result within {budget}s"
        print(json.dumps(REPORT), flush=True)
        os._exit(1)

    t = threading.Timer(budget, fire)
    t.daemon = True
    t.start()


def probe_backend() -> None:
    """Probe the backend before anything expensive; return only if it
    is the platform this run expects (healthmon.expected_platform: a
    TPU, unless the caller's JAX_PLATFORMS names another).  Anything
    else — a hang, a crash, a JAX that quietly fell back to ``cpu`` —
    prints the one structured line, with the probe's own account and the
    static contract blocks, and exits non-zero.  BENCH_SKIP_PROBE=1
    skips the probe (the device is still stamped after attach)."""
    if os.environ.get("BENCH_SKIP_PROBE") == "1":
        return
    res = _healthmon.probe_devices(_probe_timeout_s())
    REPORT["probe"] = res.to_dict()
    REPORT["platform"] = res.platform
    REPORT["device_kind"] = res.device_kind
    REPORT["device_count"] = res.device_count
    if res.ok:
        return
    REPORT["error"] = "backend-unavailable: " + res.detail
    if os.environ.get("BENCH_KERNELCHECK", "1").lower() not in (
        "0", "false", "no", "off"
    ):
        REPORT["kernelcheck"] = _kernelcheck_report()
    if os.environ.get("BENCH_SHARDCHECK", "1").lower() not in (
        "0", "false", "no", "off"
    ):
        REPORT["shardcheck"] = _shardcheck_report()
    if os.environ.get("BENCH_RANGECHECK", "1").lower() not in (
        "0", "false", "no", "off"
    ):
        REPORT["rangecheck"] = _rangecheck_report()
    emit_and_exit()


def _kernelcheck_report() -> dict:
    """The CPU-only kernel contract pass (analysis/kernelcheck): traces
    every manifest kernel under JAX_PLATFORMS=cpu and diffs against the
    checked-in fingerprints.  Run when the device backend is unavailable
    so the failed round still reports a meaningful verify-plane signal —
    the kernels' numeric contract holding is worth recording even when
    their wall clock is unmeasurable.  ~2-3 min of CPU tracing, well
    inside the run watchdog; BENCH_KERNELCHECK=0 skips it (the
    bench-harness tests do, to stay inside their own subprocess timeout).

    jax has NOT been imported in this process yet (the probe ran in a
    throwaway subprocess), so JAX_PLATFORMS is forced to cpu HERE, before
    the first import: the pass is abstract tracing, CPU-only by
    definition, and must never touch the accelerator the probe just
    declared unusable.  The round has already failed; nothing measured
    follows."""
    try:
        if "jax" in sys.modules:  # can't re-pin an already-initialized jax
            return {"ok": False, "error": "jax already imported pre-probe"}
        os.environ["JAX_PLATFORMS"] = "cpu"
        t0 = time.monotonic()
        from cometbft_tpu.analysis import kernelcheck

        # honor justified allowlist entries so this report agrees with
        # `scripts/lint.py --check kernel` on what counts as green
        findings, traces = kernelcheck.run_check(
            allowlist=kernelcheck.default_allowlist()
        )
        return {
            **kernelcheck.summary(findings, traces),
            "elapsed_s": round(time.monotonic() - t0, 1),
        }
    except BaseException as e:  # noqa: BLE001 — the JSON line must still emit
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def _shardcheck_report() -> dict:
    """The sharded-plane contract pass (analysis/shardcheck): every
    mesh-parameterized kernel traced under a REAL 8-way CPU mesh and
    held to its declared shardings, collective census, compile-cost
    budgets, and donation discipline — so a backend-less round still
    carries sharded-plane signal, the same pattern as the "kernelcheck"
    field above.  Runs entirely in a forced-environment SUBPROCESS
    (JAX_PLATFORMS=cpu + xla_force_host_platform_device_count=8 exported
    before the child's first jax import), so this process's jax state
    and the accelerator are both untouched.  ~40s; the child timeout is
    capped at 300s so that the probe + kernelcheck + this pass still
    land the structured JSON line inside the driver's patience — a hung trace
    child becomes a timeout finding in the summary, not a lost round.
    BENCH_SHARDCHECK=0 skips it (the bench-harness tests do, to stay
    inside their subprocess timeout)."""
    try:
        t0 = time.monotonic()
        from cometbft_tpu.analysis import kernelcheck, shardcheck

        findings, data = shardcheck.run_subprocess(timeout=300)
        allow = kernelcheck.default_allowlist()
        findings = [f for f in findings if not allow.suppresses(f)]
        censuses = {
            name: k.get("collectives", {})
            for name, k in data.get("kernels", {}).items()
        }
        return {
            "ok": not findings,
            "findings": len(findings),
            "kernels": {
                name: k.get("eqns")
                for name, k in data.get("kernels", {}).items()
            },
            # the stage-handoff claim, machine-checkable next to the
            # perf numbers: a sharding_constraint in any census is a
            # resharding copy between pipelined stages
            "collectives": censuses,
            "resharding_free": all(
                "sharding_constraint" not in c for c in censuses.values()
            ) if censuses else None,
            "device_count": data.get("device_count"),
            "elapsed_s": round(time.monotonic() - t0, 1),
        }
    except BaseException as e:  # noqa: BLE001 — the JSON line must still emit
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def _rangecheck_report() -> dict:
    """The limb-range contract pass (analysis/rangecheck): per-kernel
    overflow headroom from the checked-in range certificates, plus a
    live interval spot-check over the fast hash-plane kernels diffed
    against those certificates — the same failed-round pattern as the
    "kernelcheck"/"shardcheck" fields above.  The FULL interval pass is
    minutes of CPU (the curve walks dominate), so the certificates carry
    the field-kernel headroom and the spot subset keeps the round honest
    about drift.  Runs under the cpu pin the kernelcheck report already
    forced; BENCH_RANGECHECK=0 skips it (the bench-harness tests do, to
    stay inside their subprocess timeout)."""
    try:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        t0 = time.monotonic()
        from cometbft_tpu.analysis import rangecheck

        return {
            **rangecheck.bench_summary(),
            "elapsed_s": round(time.monotonic() - t0, 1),
        }
    except BaseException as e:  # noqa: BLE001 — the JSON line must still emit
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def _run_mixed() -> None:
    """BENCH_WORKLOAD=mixed: consensus commit verification and mempool
    CheckTx signature checks driven CONCURRENTLY through the unified
    verify service (verifysvc/), to show the scheduler's class
    separation under contention.  The JSON line carries per-class
    latency percentiles plus the service's flush/queue tallies — the
    claim to check is that consensus p50 under mempool load stays near
    its unloaded value while mempool requests coalesce into wide
    deadline-flushed batches.

    Sizes: BENCH_N commit signatures (default 10000), BENCH_MIXED_SECONDS
    of concurrent load (default 20), BENCH_MIXED_SENDERS CheckTx threads
    (default 8)."""
    import threading

    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519 as host
    from cometbft_tpu.utils import heightline
    from cometbft_tpu.verifysvc import checktx
    from cometbft_tpu.verifysvc.service import global_service

    N = int(os.environ.get("BENCH_N", "10000"))
    seconds = float(os.environ.get("BENCH_MIXED_SECONDS", "20"))
    senders = int(os.environ.get("BENCH_MIXED_SENDERS", "8"))
    REPORT["metric"] = "verify_mixed_consensus_p50_ms"
    REPORT["workload"] = "mixed"
    REPORT["n_sigs"] = N
    REPORT["mixed_seconds"] = seconds
    REPORT["mixed_senders"] = senders

    rng = np.random.default_rng(11)
    keys = [host.PrivKey.from_seed(rng.bytes(32)) for _ in range(N)]
    pubs = [k.pub_key().data for k in keys]
    items = []
    for i, sk in enumerate(keys):
        msg = b"\x08\x02\x10\x01\x18\x05" + i.to_bytes(8, "big") + b"|chain-mixed"
        items.append((pubs[i], msg, sk.sign(msg)))

    t0 = time.perf_counter()
    crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
    REPORT["phases"]["table_build_s"] = round(time.perf_counter() - t0, 1)

    tx_keys = [host.PrivKey.from_seed(rng.bytes(32)) for _ in range(64)]
    txs = [
        checktx.make_signed_tx(k, b"mixed-payload-%d" % i)
        for i, k in enumerate(tx_keys)
    ]

    stop = threading.Event()
    lat: dict[str, list[float]] = {"consensus": [], "mempool": []}
    lat_mtx = threading.Lock()
    errors: list[str] = []

    # each consensus round below is one synthetic "height": the bench
    # surfaces the same per-height ledger a node serves on
    # /height_timeline, with the commit verify attributed per height
    hl = heightline.HeightlineRegistry(capacity=128, enabled=True)

    def consensus_loop():
        try:
            height = 0
            while not stop.is_set():
                height += 1
                hl.set_current(height)
                hl.mark(height, "start", _record=False)
                v = crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
                t = time.perf_counter()
                for pub, msg, sig in items:
                    v.add(pub, msg, sig)
                ok, per = v.verify()
                dt = (time.perf_counter() - t) * 1e3
                assert ok and len(per) == N
                hl.mark(height, "commit", _record=False)
                hl.note_verify(N, dt / 1e3, height=height)
                with lat_mtx:
                    lat["consensus"].append(dt)
        except BaseException as e:  # noqa: BLE001 — report, don't hang the bench
            errors.append(f"consensus: {type(e).__name__}: {e}")
            stop.set()

    def mempool_loop(i: int):
        try:
            j = i
            while not stop.is_set():
                t = time.perf_counter()
                ok = checktx.verify_tx_signature(txs[j % len(txs)])
                dt = (time.perf_counter() - t) * 1e3
                assert ok is True
                with lat_mtx:
                    lat["mempool"].append(dt)
                j += 1
        except BaseException as e:  # noqa: BLE001
            errors.append(f"mempool-{i}: {type(e).__name__}: {e}")
            stop.set()

    threads = [threading.Thread(target=consensus_loop, name="bench-consensus")]
    threads += [
        threading.Thread(target=mempool_loop, args=(i,), name=f"bench-mempool-{i}")
        for i in range(senders)
    ]
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join(timeout=120)

    def pct(vals, q):
        if not vals:
            return None
        s = sorted(vals)
        return round(s[min(len(s) - 1, int(q * len(s)))], 3)

    stats = global_service().stats()
    REPORT["value"] = pct(lat["consensus"], 0.5)
    REPORT["classes"] = {
        k: {
            "count": len(v),
            "p50_ms": pct(v, 0.5),
            "p95_ms": pct(v, 0.95),
        }
        for k, v in lat.items()
    }
    REPORT["scheduler"] = {
        "dispatched_batches": stats["dispatched_batches"],
        "rejected": stats["rejected"],
        "batch_max": stats["batch_max"],
        "deadline_ms": stats["deadline_ms"],
    }
    snap = hl.snapshot(limit=10)
    REPORT["height_timeline"] = {
        "heights_total": hl.current,
        "newest": [
            {
                "height": h["height"],
                "commit_s": h["phase_seconds"].get("commit"),
                "verify": h["verify"],
            }
            for h in snap["heights"]
        ],
    }
    if errors:
        REPORT["error"] = "; ".join(errors[:4])
    emit_and_exit()


def _run_bls() -> None:
    """BENCH_WORKLOAD=bls: the ed25519-vs-BLS cost-model crossover
    capture (ROADMAP item 2 / PAPERS.md arXiv:2302.00418).  Sweeps
    validator-set sizes (BENCH_BLS_SIZES, default 64,256,1024,4096)
    and measures, per size:

      * ed25519-batch: N individually signed rows through the
        production batch path (crypto/batch.create_batch_verifier,
        comb-cached) — cost grows ~linearly in N;
      * BLS-aggregate: ONE aggregate commit (N validators, one shared
        message, one aggregate G2 signature replicated per row) through
        the BLS lane (models/bls_verifier behind the verify service) —
        one pairing-product check plus a data-parallel pubkey sum, so
        cost is ~flat in N once the validated-pubkey cache is warm
        (steady state: the validator set outlives the commit, exactly
        like the resident ed25519 comb tables).

    The JSON line carries per-size p50 for both schemes and
    ``crossover_validators``: the interpolated set size where the BLS
    aggregate becomes cheaper than the ed25519 batch (null when the
    sweep never crosses).  Setup uses small secret scalars (pk/sig
    scalar mults dominate setup wall clock; verification cost is
    independent of scalar size), distinct per validator.
    """
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import bls12381 as host_bls
    from cometbft_tpu.crypto import ed25519 as host_ed
    from cometbft_tpu.models import bls_verifier

    sizes = [
        int(x) for x in
        os.environ.get("BENCH_BLS_SIZES", "64,256,1024,4096").split(",")
        if x.strip()
    ]
    iters = int(os.environ.get("BENCH_BLS_ITERS", "5"))
    REPORT["metric"] = "verify_bls_crossover_validators"
    REPORT["workload"] = "bls"
    REPORT["sizes"] = sizes
    REPORT["iters"] = iters

    rng = np.random.default_rng(17)

    def p50(fn):
        runs = sorted(fn() for _ in range(iters))
        return runs[len(runs) // 2]

    sweep: dict[str, dict] = {}
    n_max = max(sizes)
    # one key universe per scheme, sliced per size (setup dominates the
    # sweep's wall clock; the timed regions only ever see warm caches)
    ed_keys = [host_ed.PrivKey.from_seed(rng.bytes(32)) for _ in range(n_max)]
    ed_pubs = [k.pub_key().data for k in ed_keys]
    # distinct small scalars: verification cost is scalar-size-blind
    sks = rng.choice(1 << 30, size=n_max, replace=False) + 2
    bls_keys = [host_bls.PrivKey(int(sk)) for sk in sks]
    bls_pubs = [k.pub_key().data for k in bls_keys]

    for n in sizes:
        row: dict = {}
        # ---- ed25519 batch: N rows, per-validator sign bytes
        pubs = ed_pubs[:n]
        items = []
        for i, sk in enumerate(ed_keys[:n]):
            msg = b"\x08\x02\x10\x01\x18\x05" + i.to_bytes(8, "big") + b"|chain-bls-bench"
            items.append((pubs[i], msg, sk.sign(msg)))
        crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)  # warm tables

        def run_ed():
            v = crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
            t0 = time.perf_counter()
            for pub, msg, sig in items:
                v.add(pub, msg, sig)
            ok, per = v.verify()
            dt = (time.perf_counter() - t0) * 1e3
            assert ok and len(per) == n
            return dt

        run_ed()  # warmup (bucket compile / cache warm)
        row["ed25519_p50_ms"] = round(p50(run_ed), 3)

        # ---- BLS aggregate commit: one message, one aggregate sig
        msg = b"\x08\x02\x10\x01\x18\x05|bls-agg-commit|%d" % n
        agg_sig = host_bls.aggregate_signatures(
            [k.sign(msg) for k in bls_keys[:n]]
        )
        bpubs = bls_pubs[:n]

        def run_bls():
            v = crypto_batch.create_batch_verifier("bls12_381", pubkeys=bpubs)
            t0 = time.perf_counter()
            for pub in bpubs:
                v.add(pub, msg, agg_sig)
            ok, per = v.verify()
            dt = (time.perf_counter() - t0) * 1e3
            assert ok and len(per) == n
            return dt

        # genuinely cold first verify per size: the key universe is
        # sliced, so without the reset the n=256 round would find the
        # first 64 keys already validated by the n=64 round
        bls_verifier.reset_caches()
        t0 = time.perf_counter()
        run_bls()  # warmup: pays pubkey validation once (cache fill)
        row["bls_first_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        row["bls_p50_ms"] = round(p50(run_bls), 3)
        sweep[str(n)] = row

    REPORT["sweep"] = sweep

    # crossover: smallest swept size where the aggregate wins, with a
    # log-linear interpolation between the straddling sizes
    crossover = None
    prev = None
    for n in sizes:
        row = sweep[str(n)]
        d = row["bls_p50_ms"] - row["ed25519_p50_ms"]
        if d <= 0:
            if prev is None:
                crossover = n
            else:
                pn, pd = prev
                # linear interpolation of the (bls - ed) gap in log2(N)
                import math

                f = pd / (pd - d) if pd != d else 0.0
                crossover = int(round(
                    2 ** (math.log2(pn) + f * (math.log2(n) - math.log2(pn)))
                ))
            break
        prev = (n, d)
    REPORT["value"] = REPORT["crossover_validators"] = crossover
    REPORT["unit"] = "validators"
    emit_and_exit()


def _run_secp() -> None:
    """BENCH_WORKLOAD=secp: the batched-ECDSA capture of ROADMAP item 4
    (PAPERS.md arXiv:2112.02229).  Two measurements in one JSON line:

    * **batch-size sweep** (BENCH_SECP_SIZES, default 64,256,1024,4096):
      per size, p50 of the TPU-batched lane (models/secp_verifier ->
      ops/secp256k1: range checks, Montgomery batch inversion, Shamir
      double-scalar — one fused dispatch) vs the pure-host ECDSA lane.
      The host path is pure-Python bigint ECDSA (~tens of ms per
      signature), so it is measured on min(n, BENCH_SECP_HOST_CAP
      [default 64]) rows and reported per-signature plus extrapolated
      (``host_extrapolated`` carries the flag AND the cap AND the
      measured-subset size — an extrapolated number is never passed
      off as a measured one, and the JSON line alone says how much was
      actually measured).
    * **phase attribution** (BENCH_SECP_PHASES=0 to skip): the
      top-size dispatch split into hash / decode / assembly / h2d /
      kernel / fetch (models/secp_verifier.LAST_PHASES), captured for
      the default shape (GLV + fused on-device hashing) AND the PR-15
      witness (noglv + host hashing, BENCH_SECP_PHASE_WITNESS=0 to
      skip its extra compile) — the GLV and hashing-residency deltas
      ride in the same JSON line as the sweep.
    * **mixed ingest round** (BENCH_SECP_MIXED_SECONDS, default 10):
      concurrent ed25519-commit consensus load plus TWO mempool CheckTx
      sender pools — ed25519 (v1 envelopes, MODE_PLAIN) and secp256k1
      (key-typed v2 envelopes, MODE_SECP) — through one verify service,
      reporting per-key-type per-class latency percentiles: the
      Ethereum-shaped ingest claim next to the scheduler's class
      separation.
    """
    import threading

    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519 as host_ed
    from cometbft_tpu.crypto import secp256k1 as host_secp
    from cometbft_tpu.models import secp_verifier as mv
    from cometbft_tpu.verifysvc import checktx
    from cometbft_tpu.verifysvc.service import global_service

    sizes = [
        int(x) for x in
        os.environ.get("BENCH_SECP_SIZES", "64,256,1024,4096").split(",")
        if x.strip()
    ]
    iters = int(os.environ.get("BENCH_SECP_ITERS", "5"))
    host_cap = int(os.environ.get("BENCH_SECP_HOST_CAP", "64"))
    REPORT["metric"] = "verify_secp_tpu_batch_p50_ms"
    REPORT["workload"] = "secp"
    REPORT["verifier"] = "secp-batched"
    REPORT["sizes"] = sizes
    REPORT["iters"] = iters

    rng = np.random.default_rng(23)
    n_max = max(sizes)
    keys = [host_secp.PrivKey.from_seed(rng.bytes(32)) for _ in range(n_max)]
    pubs = [k.pub_key().data for k in keys]
    items = []
    for i, sk in enumerate(keys):
        msg = b"\x08\x02\x10\x01\x18\x05" + i.to_bytes(8, "big") + b"|chain-secp"
        items.append((pubs[i], msg, sk.sign(msg)))

    def p50(fn):
        runs = sorted(fn() for _ in range(iters))
        return runs[len(runs) // 2]

    sweep: dict[str, dict] = {}
    for n in sizes:
        row: dict = {}
        batch = items[:n]

        def run_tpu(batch=batch, n=n):
            v = mv.TpuSecpBatchVerifier()
            t0 = time.perf_counter()
            for it in batch:
                v.add(*it)
            ok, per = v.verify()
            dt = (time.perf_counter() - t0) * 1e3
            assert ok and len(per) == n
            return dt

        run_tpu()  # warmup: bucket-shape compile / cache hit
        row["tpu_p50_ms"] = round(p50(run_tpu), 3)

        hn = min(n, host_cap)
        hbatch = batch[:hn]

        def run_host(hbatch=hbatch, hn=hn):
            v = mv.CpuSecpBatchVerifier()
            t0 = time.perf_counter()
            for it in hbatch:
                v.add(*it)
            ok, per = v.verify()
            dt = (time.perf_counter() - t0) * 1e3
            assert ok and len(per) == hn
            return dt

        host_ms = p50(run_host)
        row["host_p50_ms_per_sig"] = round(host_ms / hn, 3)
        row["host_p50_ms"] = round(
            host_ms if hn == n else host_ms / hn * n, 3
        )
        row["host_extrapolated"] = {
            "extrapolated": hn != n,
            "cap": host_cap,
            "measured_rows": hn,
        }
        row["tpu_speedup_vs_host"] = round(
            row["host_p50_ms"] / row["tpu_p50_ms"], 2
        ) if row["tpu_p50_ms"] else None
        sweep[str(n)] = row
    REPORT["sweep"] = sweep
    top = sweep[str(max(sizes))]
    REPORT["value"] = top["tpu_p50_ms"]

    # ---- phase attribution of the top-size dispatch: default shape
    # (GLV + fused hashing) vs the PR-15 witness (noglv + host
    # hashing) — the same LAST_PHASES capture scripts/
    # profile_secp_phases.py prints, embedded in the JSON line
    if os.environ.get("BENCH_SECP_PHASES", "1") != "0":
        import statistics

        phase_keys = ("hash_ms", "decode_ms", "assembly_ms",
                      "h2d_ms", "kernel_ms", "fetch_ms")
        cfgs: dict[str, dict[str, str]] = {"glv_fused": {}}
        if os.environ.get("BENCH_SECP_PHASE_WITNESS", "1") != "0":
            cfgs["noglv_host"] = {
                "COMETBFT_TPU_SECP_GLV": "0",
                "COMETBFT_TPU_SECP_HASH_DEVICE_MIN": "0",
            }
        pbatch = items[:max(sizes)]
        attribution: dict[str, dict] = {}
        for cname, cenv in cfgs.items():
            saved = {k: os.environ.get(k) for k in cenv}
            os.environ.update(cenv)
            try:
                mv._verify_items(pbatch, use_device=True)  # warm variant
                samples: dict[str, list[float]] = {k: [] for k in phase_keys}
                walls = []
                for _ in range(iters):
                    t0 = time.perf_counter()
                    mv._verify_items(pbatch, use_device=True)
                    walls.append((time.perf_counter() - t0) * 1e3)
                    for k in phase_keys:
                        samples[k].append(mv.LAST_PHASES.get(k, 0.0))
                wall = statistics.median(walls)
                attribution[cname] = {
                    "wall_p50_ms": round(wall, 3),
                    "hash_device": bool(mv.LAST_PHASES.get("hash_device")),
                    **{k: {
                        "p50_ms": round(statistics.median(samples[k]), 3),
                        "share_of_wall": round(
                            statistics.median(samples[k]) / wall, 3
                        ) if wall else 0.0,
                    } for k in phase_keys},
                }
            finally:
                for k, old in saved.items():
                    if old is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = old
        REPORT["phase_attribution"] = attribution

    # ---- mixed ed25519 + secp256k1 ingest round
    seconds = float(os.environ.get("BENCH_SECP_MIXED_SECONDS", "10"))
    senders = int(os.environ.get("BENCH_SECP_MIXED_SENDERS", "4"))
    n_commit = int(os.environ.get("BENCH_SECP_COMMIT_N", "1000"))
    ed_keys = [host_ed.PrivKey.from_seed(rng.bytes(32)) for _ in range(n_commit)]
    ed_pubs = [k.pub_key().data for k in ed_keys]
    commit_items = []
    for i, sk in enumerate(ed_keys):
        msg = b"\x08\x02\x10\x01\x18\x05" + i.to_bytes(8, "big") + b"|mixed-commit"
        commit_items.append((ed_pubs[i], msg, sk.sign(msg)))
    crypto_batch.create_batch_verifier("ed25519", pubkeys=ed_pubs)

    ed_txs = [
        checktx.make_signed_tx(host_ed.PrivKey.from_seed(rng.bytes(32)),
                               b"mixed-ed-%d" % i)
        for i in range(32)
    ]
    secp_txs = [
        checktx.make_signed_tx(host_secp.PrivKey.from_seed(rng.bytes(32)),
                               b"mixed-secp-%d" % i)
        for i in range(32)
    ]

    stop = threading.Event()
    lat: dict[str, list[float]] = {
        "consensus_ed25519": [], "mempool_ed25519": [], "mempool_secp256k1": [],
    }
    lat_mtx = threading.Lock()
    errors: list[str] = []

    def consensus_loop():
        try:
            while not stop.is_set():
                v = crypto_batch.create_batch_verifier("ed25519", pubkeys=ed_pubs)
                t = time.perf_counter()
                for it in commit_items:
                    v.add(*it)
                ok, per = v.verify()
                dt = (time.perf_counter() - t) * 1e3
                assert ok and len(per) == n_commit
                with lat_mtx:
                    lat["consensus_ed25519"].append(dt)
        except BaseException as e:  # noqa: BLE001 — report, don't hang the bench
            errors.append(f"consensus: {type(e).__name__}: {e}")
            stop.set()

    def mempool_loop(i: int, txs, key):
        try:
            j = i
            while not stop.is_set():
                t = time.perf_counter()
                ok = checktx.verify_tx_signature(txs[j % len(txs)])
                dt = (time.perf_counter() - t) * 1e3
                assert ok is True
                with lat_mtx:
                    lat[key].append(dt)
                j += 1
        except BaseException as e:  # noqa: BLE001
            errors.append(f"{key}-{i}: {type(e).__name__}: {e}")
            stop.set()

    threads = [threading.Thread(target=consensus_loop, name="bench-consensus")]
    threads += [
        threading.Thread(target=mempool_loop, args=(i, ed_txs, "mempool_ed25519"),
                         name=f"bench-mp-ed-{i}")
        for i in range(senders)
    ]
    threads += [
        threading.Thread(
            target=mempool_loop, args=(i, secp_txs, "mempool_secp256k1"),
            name=f"bench-mp-secp-{i}")
        for i in range(senders)
    ]
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join(timeout=120)

    def pct(vals, q):
        if not vals:
            return None
        s = sorted(vals)
        return round(s[min(len(s) - 1, int(q * len(s)))], 3)

    stats = global_service().stats()
    REPORT["mixed"] = {
        "seconds": seconds,
        "senders_per_key_type": senders,
        "commit_n": n_commit,
        "classes": {
            k: {"count": len(v), "p50_ms": pct(v, 0.5), "p95_ms": pct(v, 0.95)}
            for k, v in lat.items()
        },
        "scheduler": {
            "dispatched_batches": stats["dispatched_batches"],
            "rejected": stats["rejected"],
        },
    }
    if errors:
        REPORT["error"] = "; ".join(errors[:4])
    emit_and_exit()


def _run_proofs() -> None:
    """BENCH_WORKLOAD=proofs: the TPU proof-serving-plane capture.
    Sweeps coalesced query counts (BENCH_PROOF_SIZES, default
    64,256,1024,4096 — the top size is the >=1k-coalesced-queries
    acceptance point) and measures, per count K over a K-leaf tree:

      * tpu: crypto/merkle.device_proofs_from_byte_slices — host plans
        sibling coordinates, ONE device dispatch retains every interior
        level and one-hot-gathers all K audit paths;
      * host: crypto/merkle.proofs_from_byte_slices — the pure-host
        oracle that DEFINES the proof bytes (every degraded service
        route funnels to it).

    Bit-identity between the two is asserted on every swept size — a
    fast proof plane that serves different bytes is a bug, not a win —
    and each row carries the multiproof shared-node dedup factor
    (crypto/merkle.multiproof_plan: naive path-node slots over deduped
    unique nodes) for the same K.  p50 AND p95 ride per lane: proof
    fan-out is a latency-sensitive read path, so the tail is part of
    the claim."""
    from cometbft_tpu.crypto import merkle as cmerkle

    sizes = [
        int(x) for x in
        os.environ.get("BENCH_PROOF_SIZES", "64,256,1024,4096").split(",")
        if x.strip()
    ]
    iters = int(os.environ.get("BENCH_PROOF_ITERS", "5"))
    REPORT["metric"] = "proof_gen_tpu_batch_p50_ms"
    REPORT["workload"] = "proofs"
    REPORT["verifier"] = "merkle-proof-batched"
    REPORT["sizes"] = sizes
    REPORT["iters"] = iters

    def pct(vals, q):
        s = sorted(vals)
        return round(s[min(len(s) - 1, int(q * len(s)))], 3)

    rng = np.random.default_rng(29)
    sweep: dict[str, dict] = {}
    for n in sizes:
        row: dict = {}
        leaves = [rng.bytes(64) for _ in range(n)]
        idxs = list(range(n))  # every leaf queried: worst-case coalesce

        def run_tpu(leaves=leaves, idxs=idxs):
            t0 = time.perf_counter()
            root, proofs = cmerkle.device_proofs_from_byte_slices(leaves, idxs)
            dt = (time.perf_counter() - t0) * 1e3
            assert len(proofs) == len(idxs)
            return dt, root, proofs

        def run_host(leaves=leaves, idxs=idxs):
            t0 = time.perf_counter()
            root, all_proofs = cmerkle.proofs_from_byte_slices(leaves)
            proofs = [all_proofs[i] for i in idxs]
            dt = (time.perf_counter() - t0) * 1e3
            return dt, root, proofs

        _, d_root, d_proofs = run_tpu()  # warmup: shape compile / cache hit
        _, h_root, h_proofs = run_host()
        # the contract, asserted in the bench itself: same root, same
        # proof bytes, row for row
        assert d_root == h_root
        assert all(
            dp.total == hp.total and dp.index == hp.index
            and dp.leaf_hash == hp.leaf_hash and dp.aunts == hp.aunts
            for dp, hp in zip(d_proofs, h_proofs)
        ), f"device/host proof divergence at n={n}"

        tpu_runs = [run_tpu()[0] for _ in range(iters)]
        host_runs = [run_host()[0] for _ in range(iters)]
        row["tpu_p50_ms"] = pct(tpu_runs, 0.5)
        row["tpu_p95_ms"] = pct(tpu_runs, 0.95)
        row["host_p50_ms"] = pct(host_runs, 0.5)
        row["host_p95_ms"] = pct(host_runs, 0.95)
        row["tpu_speedup_vs_host"] = round(
            row["host_p50_ms"] / row["tpu_p50_ms"], 2
        ) if row["tpu_p50_ms"] else None
        _, _, coords, naive = cmerkle.multiproof_plan(n, idxs)
        row["multiproof_dedup_factor"] = round(
            naive / len(coords), 2
        ) if coords else None
        row["bit_identical"] = True  # the asserts above did not fire
        sweep[str(n)] = row
    REPORT["sweep"] = sweep
    top = sweep[str(max(sizes))]
    REPORT["value"] = top["tpu_p50_ms"]
    REPORT["unit"] = "ms"
    emit_and_exit()


def _run_multichip() -> None:
    """BENCH_WORKLOAD=multichip: the 8-device scaling capture of ROADMAP
    item 1.  Sweeps the comb-cached commit verify over device counts
    (BENCH_MULTICHIP_DEVICES, default "1,2,4,8", clamped to what the
    host exposes) and reports, per count:

      - p50 of the warm verify (BENCH_MULTICHIP_ITERS, default 5), and
      - COLD-START-TO-FIRST-VERIFY: wall clock from an EMPTY comb cache
        to the first completed verify — table build (host-precomputed
        under COMB_HOST_BUILD_MAX, jitted beyond) + sharded placement +
        program compile-or-cache-hit + dispatch + fetch.  With the
        persistent XLA compile cache warm this is the <30s ROADMAP
        target; the pre-PR-11 table build alone compiled for 2m34s.

    BENCH_MULTICHIP_CPU=1 forces a virtual CPU mesh (the dryrun's
    _force_cpu_mesh pattern) so backend-less hosts can run the sweep —
    pair it with BENCH_SKIP_PROBE=1.  The JSON line also embeds the
    shardcheck collective censuses ("shardcheck.resharding_free") so
    the no-inter-stage-resharding claim rides next to the numbers.
    """
    N = int(os.environ.get("BENCH_N", "10000"))
    iters = int(os.environ.get("BENCH_MULTICHIP_ITERS", "5"))
    want = [
        int(x) for x in
        os.environ.get("BENCH_MULTICHIP_DEVICES", "1,2,4,8").split(",")
        if x.strip()
    ]
    if os.environ.get("BENCH_MULTICHIP_CPU") == "1":
        # an explicit request for a virtual CPU mesh; the line is
        # stamped platform "cpu" like any other CPU-backend run
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={max(want)}"
            ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    _stamp_device()
    have = len(jax.devices())
    devices = [d for d in want if d <= have]
    REPORT["metric"] = f"verify_commit_multichip_p50_{N}_ms"
    REPORT["workload"] = "multichip"
    REPORT["n_sigs"] = N
    REPORT["device_counts"] = devices

    from cometbft_tpu.crypto import ed25519 as host
    from cometbft_tpu.models import comb_verifier as cv
    from cometbft_tpu.parallel import make_mesh

    rng = np.random.default_rng(7)
    keys = [host.PrivKey.from_seed(rng.bytes(32)) for _ in range(N)]
    pubs = [k.pub_key().data for k in keys]
    items = []
    for i, sk in enumerate(keys):
        msg = b"\x08\x02\x10\x01\x18\x05" + i.to_bytes(8, "big") + b"|chain-mc"
        items.append((pubs[i], msg, sk.sign(msg)))

    def one_verify(entry):
        bv = cv.CombBatchVerifier(entry)
        t0 = time.perf_counter()
        for pub, msg, sig in items:
            bv.add(pub, msg, sig)
        ok, per = bv.verify()
        dt = (time.perf_counter() - t0) * 1e3
        assert ok and len(per) == N
        return dt, getattr(bv, "last_timings", {})

    from cometbft_tpu.ops import comb as comb_ops

    scaling: dict[str, dict] = {}
    try:
        for d in devices:
            cv.set_active_mesh(make_mesh(d) if d > 1 else None)
            cache = cv.ValsetCombCache()
            # per-count cold start must be COLD: drop the process-global
            # comb state the previous count warmed (the jitted build's
            # traced wrapper, the 24 MB basepoint constant) so every row
            # pays its own trace + table construction and rows are
            # comparable — only the PERSISTENT compile cache stays warm,
            # which is exactly the warm-pod-restart scenario the <30s
            # target is stated against
            comb_ops._BUILD_A_JIT = None
            comb_ops._B_TABLES = None
            t0 = time.perf_counter()
            entry = cache.ensure(pubs)  # EMPTY cache: the real cold start
            build_s = time.perf_counter() - t0
            first_ms, _ = one_verify(entry)  # first verify pays the compile
            cold_s = time.perf_counter() - t0
            runs = sorted(one_verify(entry) for _ in range(iters))
            p50, timings = runs[len(runs) // 2]
            scaling[str(d)] = {
                "p50_ms": round(p50, 3),
                "cold_start_to_first_verify_s": round(cold_s, 1),
                "table_build_s": round(build_s, 1),
                "first_verify_ms": round(first_ms, 3),
                "phases": {k: round(v, 2) for k, v in timings.items()},
            }
    finally:
        cv.set_active_mesh(None)

    REPORT["scaling"] = scaling
    top = scaling.get(str(devices[-1])) if devices else None
    if top:
        REPORT["value"] = top["p50_ms"]
        REPORT["vs_baseline"] = round(
            GO_CPU_US_PER_SIG * N / 1e3 / top["p50_ms"], 2
        )
        REPORT["phases"]["table_build_s"] = top["table_build_s"]
        base = scaling.get(str(devices[0]))
        if base and len(devices) > 1:
            # keyed by the ACTUAL base count — a sweep starting at 2
            # devices must not label its ratios "vs_1dev"
            REPORT[f"speedup_vs_{devices[0]}dev"] = {
                k: round(base["p50_ms"] / v["p50_ms"], 2)
                for k, v in scaling.items()
                if v["p50_ms"]
            }
    if os.environ.get("BENCH_SHARDCHECK", "1").lower() not in (
        "0", "false", "no", "off"
    ):
        REPORT["shardcheck"] = _shardcheck_report()
    emit_and_exit()


def main() -> None:
    _arm_run_watchdog()
    probe_backend()
    from cometbft_tpu.utils import compilecache

    compilecache.enable()

    if os.environ.get("BENCH_WORKLOAD", "") == "multichip":
        _run_multichip()  # may pin a virtual CPU mesh before attaching
    _stamp_device()
    if os.environ.get("BENCH_WORKLOAD", "") == "mixed":
        _run_mixed()
    if os.environ.get("BENCH_WORKLOAD", "") == "bls":
        _run_bls()
    if os.environ.get("BENCH_WORKLOAD", "") == "secp":
        _run_secp()
    if os.environ.get("BENCH_WORKLOAD", "") == "proofs":
        _run_proofs()

    N = int(os.environ.get("BENCH_N", "10000"))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    iters = int(os.environ.get("BENCH_ITERS", "10"))
    baseline_ms = GO_CPU_US_PER_SIG * N / 1e3
    if N != 10_000:  # don't mislabel off-scale smoke runs
        REPORT["metric"] = f"verify_commit_p50_{N}_ms"
    REPORT["n_sigs"] = N

    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519 as host

    # One validator set, one commit: distinct keys, per-validator sign-bytes.
    rng = np.random.default_rng(7)
    keys = [host.PrivKey.from_seed(rng.bytes(32)) for _ in range(N)]
    pubs = [k.pub_key().data for k in keys]
    items = []
    for i, sk in enumerate(keys):
        msg = b"\x08\x02\x10\x01\x18\x05" + i.to_bytes(8, "big") + b"|chain-bench"
        items.append((pubs[i], msg, sk.sign(msg)))

    # one-time per validator set: comb tables built + kept device-resident
    # (host-precomputed + device_put under COMB_HOST_BUILD_MAX, jitted
    # beyond — scripts/profile_comb_phases.py breaks the phase down)
    t0 = time.perf_counter()
    crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
    table_build_s = time.perf_counter() - t0
    REPORT["phases"]["table_build_s"] = round(table_build_s, 1)

    def run_once():
        v = crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
        t0 = time.perf_counter()
        for pub, msg, sig in items:
            v.add(pub, msg, sig)
        ok, per_sig = v.verify()
        dt = (time.perf_counter() - t0) * 1e3
        assert ok and len(per_sig) == N
        return dt, getattr(v, "last_timings", {})

    for _ in range(warmup):
        run_once()

    # BENCH_TRACE=/path.trace.json captures the TIMED iterations with the
    # span tracer on and exports a Chrome trace (open in Perfetto) —
    # enabled only after warmup so compile/cold-cache spans neither show
    # up in the artifact nor evict timed-region events from the ring.
    trace_path = os.environ.get("BENCH_TRACE", "")
    if trace_path:
        from cometbft_tpu.utils import tracing

        tracing.set_enabled(True)
        tracing.reset()
        # traced iterations pay per-span clock reads inside the timed
        # region: flag the artifact so regression tracking never compares
        # a traced "value" against untraced baselines
        REPORT["traced"] = True

    runs = sorted((run_once() for _ in range(iters)), key=lambda r: r[0])
    p50, timings = runs[len(runs) // 2]
    REPORT["value"] = round(p50, 3)
    REPORT["vs_baseline"] = round(baseline_ms / p50, 2)
    for k, v in timings.items():
        REPORT["phases"][k] = round(v, 2)

    # Phase attribution: per-phase medians across ALL timed iterations
    # (the pipeline phases — assembly, h2d_dispatch, device_wait — run on
    # the staging thread and OVERLAP the caller-visible wall time, so
    # shares are each phase's own duration over the p50 wall clock and
    # need not sum to 1).
    phase_samples: dict[str, list[float]] = {}
    for _, t in runs:
        for k, v in t.items():
            phase_samples.setdefault(k, []).append(v)
    REPORT["phase_attribution"] = {
        k: {
            "p50_ms": round(sorted(vs)[len(vs) // 2], 3),
            "share_of_wall": round(sorted(vs)[len(vs) // 2] / p50, 3),
        }
        for k, vs in sorted(phase_samples.items())
    }
    # the cold-start cost is attributable too: one-time (per validator
    # set), so it carries no share_of_wall — amortization depends on how
    # many commits verify against the set
    REPORT["phase_attribution"]["table_build"] = {
        "p50_ms": round(table_build_s * 1e3, 1),
        "one_time": True,
    }

    if trace_path:
        REPORT["trace_events"] = tracing.export_chrome_trace(trace_path)
        REPORT["trace"] = trace_path
    emit_and_exit()


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException as e:  # noqa: BLE001 — the one JSON line must emit
        REPORT["error"] = f"{type(e).__name__}: {e}"
        emit_and_exit()
