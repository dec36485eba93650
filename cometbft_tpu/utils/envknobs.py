"""Central registry of every COMETBFT_TPU_* environment knob.

Each knob is declared exactly once — name, type, default, and a one-line
doc — and read through the typed getters below.  Reading a knob that was
never declared raises ``KeyError`` loudly: the registry IS the inventory,
and the static linter (analysis/raw_env) rejects any
``os.environ``/``getenv`` read of a ``COMETBFT_TPU_*`` name outside this
module, so a knob cannot exist without documentation.

``docs/knobs.md`` is generated from this registry
(``python -m cometbft_tpu.utils.envknobs``); a test asserts the checked-in
copy matches, so the doc cannot drift.

Parsing is deliberately forgiving (malformed values fall back to the
declared default) because knobs are operator input read on hot-path
module imports — a typo must degrade to the default, never crash a node.
This module imports only the stdlib so every subsystem (logging included)
can depend on it without cycles.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

_TRUE = frozenset({"1", "true", "yes", "on"})
_FALSE = frozenset({"0", "false", "no", "off"})


@dataclass(frozen=True)
class Knob:
    name: str
    type: str  # "str" | "int" | "bool" | "int?"
    default: object
    doc: str


_REGISTRY: dict[str, Knob] = {}


def _declare(name: str, type_: str, default, doc: str) -> str:
    if name in _REGISTRY:
        raise ValueError(f"knob {name!r} declared twice")
    _REGISTRY[name] = Knob(name, type_, default, doc)
    return name


# --------------------------------------------------------------- knobs
# (grouped by subsystem; order is the order docs/knobs.md renders)

# crypto / verification plane
CRYPTO_BACKEND = _declare(
    "COMETBFT_TPU_CRYPTO_BACKEND", "str", "auto",
    "Batch-verifier backend: `tpu` | `cpu` | `auto` "
    "(auto = accelerator kernel whenever JAX is importable).",
)
COMB_MIN = _declare(
    "COMETBFT_TPU_COMB_MIN", "int", 32,
    "Minimum size of a validator set the caller names for the "
    "device-resident comb-table path.  The default is "
    "`COMETBFT_TPU_DEVICE_BATCH_MIN`'s: every set the device serves at "
    "all is served from resident tables (152 KB a lane, lanes in "
    "buckets of 128), and a narrower batch is verified on the host "
    "anyway.",
)
COMB_ASYNC_MIN = _declare(
    "COMETBFT_TPU_COMB_ASYNC_MIN", "int", 2048,
    "Set size at/above which a missing comb table builds in the background "
    "while verification proceeds through the uncached kernel.",
)
COMB_HOST_BUILD_MAX = _declare(
    "COMETBFT_TPU_COMB_HOST_BUILD_MAX", "int", 2048,
    "Largest validator-set (or churn-bucket) size whose comb A-tables "
    "are precomputed on HOST (exact bigint, bit-identical to the jitted "
    "kernel, ~10 ms/validator, NO XLA program) and `device_put` straight "
    "into their sharded layout — a cold pod never pays the table-build "
    "compile.  Bigger builds use the scan-rolled jitted kernel (persistent "
    "compile cache amortizes it).  0 = always the device kernel.",
)
BTAB_CACHE = _declare(
    "COMETBFT_TPU_BTAB_CACHE", "str", "",
    "Path (`.npy` appended if missing) disk-caching the constant "
    "basepoint comb tables across processes.",
)
MESH = _declare(
    "COMETBFT_TPU_MESH", "int", 0,
    "Shard comb tables + signature rows over the first N devices (N > 1); "
    "unset/0/1 keeps the single-device program.",
)
DEVICE_BATCH_MIN = _declare(
    "COMETBFT_TPU_DEVICE_BATCH_MIN", "int", 32,
    "Batch width at/above which signatures route to the device kernels; "
    "narrower batches verify on the host.",
)
BLS_DEVICE = _declare(
    "COMETBFT_TPU_BLS_DEVICE", "bool", False,
    "`1` tree-reduces BLS pubkey aggregation on the accelerator "
    "(ops/bls381); pairings always run on host.",
)
BLS_VALIDATE_DEVICE_MIN = _declare(
    "COMETBFT_TPU_BLS_VALIDATE_DEVICE_MIN", "int", 8,
    "Minimum count of not-yet-cached BLS pubkeys for which the batched "
    "on-curve/subgroup validation runs on the accelerator "
    "(ops/bls381.validate_g1); below it the ~4 ms/key host check wins "
    "over dispatch overhead.  The verdict is bit-identical either way.",
)
BLS_AGG_DEVICE_MIN = _declare(
    "COMETBFT_TPU_BLS_AGG_DEVICE_MIN", "int", 256,
    "Minimum pubkey count per aggregate unit for which the tree-reduced "
    "G1 sum runs on the accelerator (ops/bls381.aggregate_g1); smaller "
    "units sum on host.  The aggregate point is identical either way.",
)
BLS_PUBKEY_CACHE = _declare(
    "COMETBFT_TPU_BLS_PUBKEY_CACHE", "int", 65536,
    "Entries in the validated-BLS-pubkey cache (models/bls_verifier): "
    "decompression + subgroup membership are per-key facts, so a "
    "validator set pays validation once, not once per commit.  0 "
    "disables caching.",
)
SECP_DEVICE_MIN = _declare(
    "COMETBFT_TPU_SECP_DEVICE_MIN", "int", 8,
    "Minimum batch width at/above which secp256k1 ECDSA batches run on "
    "the accelerator (ops/secp256k1.verify_batch: Shamir double-scalar "
    "kernels + Montgomery batch inversion); below it the per-row host "
    "verify wins over dispatch overhead.  The verdict is bit-identical "
    "either way (models/secp_verifier).",
)
SECP_PUBKEY_CACHE = _declare(
    "COMETBFT_TPU_SECP_PUBKEY_CACHE", "int", 65536,
    "Entries in the decoded-secp256k1-pubkey cache "
    "(models/secp_verifier): decompressing a 33-byte key costs a field "
    "square root, and CheckTx ingest repeats senders, so decode is "
    "paid once per key, not once per transaction.  0 disables caching.",
)
SECP_GLV = _declare(
    "COMETBFT_TPU_SECP_GLV", "bool", True,
    "`0` selects the plain 66-window Shamir double-scalar walk (the "
    "bit-exactness witness path) instead of the GLV endomorphism "
    "quad-scalar walk over 33 windows in ops/secp256k1.verify_batch.  "
    "The verdict is bit-identical either way (tests/test_secp_glv.py "
    "pins it); GLV roughly halves the shared doubling chain that "
    "dominates the kernel.",
)
SECP_HASH_DEVICE_MIN = _declare(
    "COMETBFT_TPU_SECP_HASH_DEVICE_MIN", "int", 64,
    "Minimum secp batch width at/above which message hashing (SHA-256 "
    "for cosmos rows, Keccak-256 for eth/ecrecover rows) fuses into "
    "the device dispatch (ops/secp256k1.hash_verify_batch) instead of "
    "running as a per-row host loop; 0 disables the fused path.  Only "
    "batches whose every message fits COMETBFT_TPU_SECP_HASH_MAX_LEN "
    "take it — the verdict is bit-identical either way.",
)
SECP_HASH_MAX_LEN = _declare(
    "COMETBFT_TPU_SECP_HASH_MAX_LEN", "int", 119,
    "Longest message (bytes) eligible for on-device hashing in the "
    "fused secp dispatch: 119 keeps every row inside one Keccak rate "
    "block (136 - pad) and two SHA-256 blocks — the CheckTx envelope "
    "shape.  A batch with any longer message hashes on host.",
)
SECP_FIREHOSE_TXS = _declare(
    "COMETBFT_TPU_SECP_FIREHOSE_TXS", "int", 100000,
    "Signed-tx count scripts/firehose_soak.py drives through the "
    "CheckTx secp firehose (>= 100k is the acceptance shape).",
)
SECP_FIREHOSE_SENDERS = _declare(
    "COMETBFT_TPU_SECP_FIREHOSE_SENDERS", "int", 32,
    "Distinct repeat senders per key type in the firehose pool — small "
    "enough that the decoded-pubkey cache must earn its > 0.9 hit-rate "
    "SLO, large enough to exercise eviction-free steady state.",
)

# verify service (verifysvc/ — priority-scheduled device batching)
VERIFYSVC_BATCH_MAX = _declare(
    "COMETBFT_TPU_VERIFYSVC_BATCH_MAX", "int", 4096,
    "Verify-service batch width: a class's queue flushes as `full` once "
    "this many signatures are pending (clamped to >= 1).",
)
VERIFYSVC_QUEUE_MAX = _declare(
    "COMETBFT_TPU_VERIFYSVC_QUEUE_MAX", "int", 16384,
    "Per-class queue bound in signatures; a submit beyond it is rejected "
    "with backpressure and the caller falls back to host verification.",
)
VERIFYSVC_DEADLINE_CONSENSUS_MS = _declare(
    "COMETBFT_TPU_VERIFYSVC_DEADLINE_CONSENSUS_MS", "int", 0,
    "Flush deadline (ms) for the consensus class: 0 = dispatch the "
    "moment the scheduler sees a request.",
)
VERIFYSVC_DEADLINE_BLOCKSYNC_MS = _declare(
    "COMETBFT_TPU_VERIFYSVC_DEADLINE_BLOCKSYNC_MS", "int", 2,
    "Flush deadline (ms) for the blocksync class.",
)
VERIFYSVC_DEADLINE_MEMPOOL_MS = _declare(
    "COMETBFT_TPU_VERIFYSVC_DEADLINE_MEMPOOL_MS", "int", 5,
    "Flush deadline (ms) for the mempool class — the coalescing window "
    "that merges per-tx CheckTx signature checks from concurrent "
    "senders into one device batch.",
)
VERIFYSVC_DEADLINE_BACKGROUND_MS = _declare(
    "COMETBFT_TPU_VERIFYSVC_DEADLINE_BACKGROUND_MS", "int", 25,
    "Flush deadline (ms) for the background class (light client, "
    "evidence).",
)
VERIFYSVC_WEIGHTS = _declare(
    "COMETBFT_TPU_VERIFYSVC_WEIGHTS", "str", "",
    "Optional weighted interleave of READY classes, e.g. "
    "`consensus=8,blocksync=4,mempool=2,background=1`; empty/malformed "
    "= strict priority (consensus > blocksync > mempool > background).",
)
VERIFYSVC_CHECKTX = _declare(
    "COMETBFT_TPU_VERIFYSVC_CHECKTX", "bool", True,
    "`0` disables the mempool CheckTx ed25519 envelope gate "
    "(verifysvc/checktx); unsigned txs always pass through untouched.",
)
VERIFYSVC_TENANT = _declare(
    "COMETBFT_TPU_VERIFYSVC_TENANT", "str", "default",
    "Tenant id this process submits verify-service work under (how a "
    "chain claims its slice of a shared multi-tenant verify plane).  "
    "Single-chain deployments keep the `default` tenant and see no "
    "behavior change.",
)
VERIFYSVC_TENANT_QUOTA = _declare(
    "COMETBFT_TPU_VERIFYSVC_TENANT_QUOTA", "int", 0,
    "Per-(tenant, class) bound on OUTSTANDING signatures (queued + "
    "dispatched-but-unsettled, released when each request's ticket "
    "settles) — one tenant's mempool flood hits ITS quota and "
    "backpressures while other tenants stay admissible, no matter how "
    "fast the scheduler drains the queue into the device or wire "
    "pipeline.  0 (default) = the class-wide "
    "COMETBFT_TPU_VERIFYSVC_QUEUE_MAX, i.e. no extra per-tenant bound.",
)
VERIFYSVC_TENANT_WEIGHTS = _declare(
    "COMETBFT_TPU_VERIFYSVC_TENANT_WEIGHTS", "str", "",
    "Weighted-fair interleave of READY tenants within one priority "
    "class, e.g. `chain-a=4,chain-b=1`; unlisted tenants weigh 1.  "
    "Classes still dispatch in strict priority (consensus first) — "
    "weights only order tenants competing inside the same class.",
)
VERIFYSVC_TENANT_LABEL_MAX = _declare(
    "COMETBFT_TPU_VERIFYSVC_TENANT_LABEL_MAX", "int", 32,
    "Bound on distinct tenant label values the metrics hub exposes "
    "(utils/metrics.LabelGuard); tenants beyond it aggregate under the "
    "`__overflow__` label so an unbounded tenant-id stream cannot blow "
    "up the /metrics exposition.",
)
VERIFYSVC_COLLECT_TIMEOUT_MS = _declare(
    "COMETBFT_TPU_VERIFYSVC_COLLECT_TIMEOUT_MS", "int", 120000,
    "Deadline (ms) a verify-service client waits in Ticket.collect() "
    "before declaring the scheduler stuck: the wait is abandoned with "
    "stall forensics and the client verifies its own batch inline on "
    "the host (first-wins ticket settlement discards the late device "
    "result).  Time the batch waits for a first-shape XLA compile of "
    "its program — minutes on a cold cache — is not charged, up to 600 s "
    "of it: that is work, not a stuck scheduler.  0 = wait forever (the "
    "pre-PR-12 contract).",
)

# out-of-process verify plane (verifysvc/server.py + remote.py + verifyd)
VERIFYRPC_ADDR = _declare(
    "COMETBFT_TPU_VERIFYRPC_ADDR", "str", "",
    "host:port of a shared out-of-process verify plane (verifyd, "
    "`scripts/verifyd.py`).  When set, the local verify service routes "
    "every batch over the wire instead of to a local device verifier "
    "(comb binds are bypassed — device-resident state is the plane's), "
    "falling back to the in-process host path whenever the circuit "
    "breaker is open.  Empty (default) = the in-process plane.",
)
VERIFYRPC_BUDGET_MS = _declare(
    "COMETBFT_TPU_VERIFYRPC_BUDGET_MS", "int", 10000,
    "Per-request deadline budget (ms) for remote verify RPCs.  The "
    "REMAINING budget — never a wall-clock deadline — crosses the wire "
    "on every send and idempotent resend; a request that exhausts its "
    "budget is a deadline breach, which trips the circuit breaker.",
)
VERIFYRPC_CONNECT_TIMEOUT_MS = _declare(
    "COMETBFT_TPU_VERIFYRPC_CONNECT_TIMEOUT_MS", "int", 2000,
    "TCP connect timeout (ms) for the remote verify plane (dials and "
    "probation probes).",
)
VERIFYRPC_RETRY_MAX = _declare(
    "COMETBFT_TPU_VERIFYRPC_RETRY_MAX", "int", 4,
    "Max send attempts per remote verify request (first send + "
    "idempotent resends after reconnects); beyond it the request fails "
    "and the batch is re-verified on the host path.",
)
VERIFYRPC_BREAKER_FAILS = _declare(
    "COMETBFT_TPU_VERIFYRPC_BREAKER_FAILS", "int", 3,
    "Consecutive connection-level failures (connect/send/recv) that "
    "trip the remote-plane circuit breaker to the in-process host "
    "path.  A request deadline breach trips it immediately.",
)
VERIFYRPC_BACKOFF_MS = _declare(
    "COMETBFT_TPU_VERIFYRPC_BACKOFF_MS", "int", 50,
    "Initial reconnect backoff (ms) toward the remote verify plane; "
    "jittered exponential, capped at 40x.",
)
VERIFYRPC_PROBE_PERIOD_MS = _declare(
    "COMETBFT_TPU_VERIFYRPC_PROBE_PERIOD_MS", "int", 1000,
    "Probation probe period (ms) while the remote-plane breaker is "
    "open: one ping round-trip per period.",
)
VERIFYRPC_PROBATION_OK = _declare(
    "COMETBFT_TPU_VERIFYRPC_PROBATION_OK", "int", 2,
    "Consecutive successful probation pings required before the "
    "remote-plane breaker closes and batches route remotely again.",
)
VERIFYRPC_DEDUP_WINDOW_S = _declare(
    "COMETBFT_TPU_VERIFYRPC_DEDUP_WINDOW_S", "int", 120,
    "Server-side idempotency window (seconds): verifyd remembers "
    "(request_id, digest) -> response this long, so a retried batch is "
    "answered from cache — never re-verified into a different blame "
    "order — and a retry racing the original attaches to the in-flight "
    "verification instead of duplicating it.",
)

# proof serving plane (models/proof_server.py + verifysvc PROOF class)
PROOF_DEADLINE_MS = _declare(
    "COMETBFT_TPU_PROOF_DEADLINE_MS", "int", 5,
    "PROOF-class coalescing window (ms): how long the verify-service "
    "scheduler holds a proof request open for more light-client queries "
    "before dispatching the batch.  Proof traffic is read-only fan-out, "
    "so it tolerates a longer window than consensus work in exchange "
    "for wider device batches.  0 = dispatch immediately.",
)
PROOF_QUEUE_MAX = _declare(
    "COMETBFT_TPU_PROOF_QUEUE_MAX", "int", 8192,
    "PROOF-class queue bound (queries) in the verify service, separate "
    "from COMETBFT_TPU_VERIFYSVC_QUEUE_MAX: light-client fan-out is the "
    "one workload expected to arrive thousands-wide, and its backlog "
    "must backpressure without consuming the signature classes' "
    "headroom.  0 = use the class-wide queue bound.",
)
PROOF_DEVICE_MIN = _declare(
    "COMETBFT_TPU_PROOF_DEVICE_MIN", "int", 64,
    "Below this many coalesced queries against one tree the proof "
    "prover answers on host (crypto/merkle.proofs_from_byte_slices — "
    "bit-identical by construction); at or above it the batched one-hot "
    "gather kernel takes the dispatch.",
)
PROOF_TREE_CACHE = _declare(
    "COMETBFT_TPU_PROOF_TREE_CACHE", "int", 256,
    "Entries in the proof server's digest -> leaves tree cache "
    "(models/proof_server).  Proof queries reference trees by digest; "
    "a query against an evicted/unknown digest gets a None row (typed "
    "miss), never a wrong proof.  LRU, bounded.",
)
PROOF_QUERY_MAX = _declare(
    "COMETBFT_TPU_PROOF_QUERY_MAX", "int", 1024,
    "Per-request index cap on the merkle_proof RPC route: one JSON-RPC "
    "call may ask for at most this many leaf indices (invalid-params "
    "error beyond it), bounding what a single client can pin into one "
    "PROOF-class submit.",
)

# verify-service degraded-mode failover (verifysvc/service.py)
FAILOVER = _declare(
    "COMETBFT_TPU_FAILOVER", "bool", True,
    "`0` disables automatic TPU->CPU verify-plane failover: a wedged "
    "device then strands in-flight batches instead of tripping the "
    "service to host verification.",
)
FAILOVER_BATCH_DEADLINE_MS = _declare(
    "COMETBFT_TPU_FAILOVER_BATCH_DEADLINE_MS", "int", 30000,
    "An in-flight batch older than this while dispatched to (or "
    "awaiting results from) the device trips the verify service to CPU "
    "mode; host-side submit work (cold compiles) is exempt.",
)
FAILOVER_PROBATION_OK = _declare(
    "COMETBFT_TPU_FAILOVER_PROBATION_OK", "int", 2,
    "Consecutive successful probation probes required before a tripped "
    "verify service restores TPU mode.",
)
FAILOVER_PROBE_PERIOD_MS = _declare(
    "COMETBFT_TPU_FAILOVER_PROBE_PERIOD_MS", "int", 15000,
    "Probation probe period (ms) while the verify service is in CPU "
    "fallback mode.",
)
FAILOVER_PROBE_TIMEOUT_MS = _declare(
    "COMETBFT_TPU_FAILOVER_PROBE_TIMEOUT_MS", "int", 10000,
    "Hard deadline (ms) for one probation probe (the hang-proof "
    "probe, utils/healthmon.probe_devices).",
)

# fault injection registry (utils/fail.py; chaos harness only — never
# set in production)
FAULT_WEDGE_DEVICE = _declare(
    "COMETBFT_TPU_FAULT_WEDGE_DEVICE", "str", "",
    "Non-empty arms the `wedge_device` fault at process start: device "
    "result waits block and the accelerator probe reports a hang until "
    "the fault is cleared.",
)
FAULT_SLOW_COLLECT = _declare(
    "COMETBFT_TPU_FAULT_SLOW_COLLECT", "str", "",
    "Arms the `slow_collect` fault: device result waits take an extra "
    "<value> seconds.",
)
FAULT_FAIL_DISPATCH = _declare(
    "COMETBFT_TPU_FAULT_FAIL_DISPATCH", "str", "",
    "Arms the `fail_dispatch` fault: verify-service dispatches raise "
    "InjectedFault (failover re-verifies the batch on host).",
)
FAULT_DROP_P2P_PCT = _declare(
    "COMETBFT_TPU_FAULT_DROP_P2P_PCT", "str", "",
    "Arms the `drop_p2p_pct` fault: <value> percent of outbound p2p "
    "messages are silently dropped at the MConnection send seam.",
)
FAULT_DELAY_P2P_MS = _declare(
    "COMETBFT_TPU_FAULT_DELAY_P2P_MS", "str", "",
    "Arms the `delay_p2p_ms` fault: outbound p2p writes are delayed "
    "<value> ms (±50% jitter) at the MConnection send routine — a "
    "laggy link, composable with `drop_p2p_pct` for flaky-network "
    "soaks.",
)
FAULT_DOUBLE_SIGN = _declare(
    "COMETBFT_TPU_FAULT_DOUBLE_SIGN", "str", "",
    "Arms the `double_sign` fault: the next <value> signed non-nil "
    "prevotes are accompanied by a conflicting broadcast-only vote "
    "(byzantine equivocation feeding the evidence pool).",
)
FAULT_PLANE_CRASH = _declare(
    "COMETBFT_TPU_FAULT_PLANE_CRASH", "str", "",
    "Arms the `plane_crash` fault in a verifyd process: the <value>'th "
    "verify request SIGKILLs the plane mid-batch (no response, no "
    "cleanup) — the deterministic kill -9-with-batches-in-flight.",
)
FAULT_PLANE_STALL = _declare(
    "COMETBFT_TPU_FAULT_PLANE_STALL", "str", "",
    "Arms the `plane_stall` fault in a verifyd process: the <value>'th "
    "verify request SIGSTOPs the plane mid-batch (connections stay "
    "open, nothing answers) until an external SIGCONT.",
)
FAULT_RPC_DELAY_MS = _declare(
    "COMETBFT_TPU_FAULT_RPC_DELAY_MS", "str", "",
    "Arms the `rpc_delay_ms` fault: verifyd delays every response "
    "<value> ms (±50% jitter) before the socket write.",
)
FAULT_RPC_DROP_PCT = _declare(
    "COMETBFT_TPU_FAULT_RPC_DROP_PCT", "str", "",
    "Arms the `rpc_drop_pct` fault: verifyd silently drops <value> "
    "percent of responses (the batch WAS verified; the client's "
    "deadline machinery must recover).",
)
FAULT_RPC = _declare(
    "COMETBFT_TPU_FAULT_RPC", "bool", False,
    "`1` exposes the `arm_fault` / `clear_fault` RPC routes so the "
    "chaos harness can inject faults into a live node; off (the "
    "default) those routes reject.",
)

# blocksync
VERIFY_AHEAD = _declare(
    "COMETBFT_TPU_VERIFY_AHEAD", "int?", None,
    "Blocksync verify-ahead pipeline depth; unset = "
    "BlocksyncReactor.VERIFY_AHEAD_DEPTH (2).  Clamped to >= 1.",
)

# observability
LOG_LEVEL = _declare(
    "COMETBFT_TPU_LOG_LEVEL", "str", "INFO",
    "Root level for the `cometbft_tpu` logger tree.",
)
TRACE = _declare(
    "COMETBFT_TPU_TRACE", "str", "",
    "Span tracer switch: any truthy value records; a path value "
    "(contains the os separator or ends in `.json`) also auto-exports "
    "Chrome trace JSON at interpreter exit.",
)
TRACE_RING = _declare(
    "COMETBFT_TPU_TRACE_RING", "int", 65536,
    "Tracer ring capacity in events (clamped to >= 1).",
)
TRACE_CTX = _declare(
    "COMETBFT_TPU_TRACE_CTX", "bool", True,
    "`0` disables span-context propagation: no trace_id/span_id args on "
    "recorded events and no traceparent field on verify-plane RPC "
    "requests (the per-process tracer itself stays governed by "
    "COMETBFT_TPU_TRACE).",
)
FLIGHTREC = _declare(
    "COMETBFT_TPU_FLIGHTREC", "int", 1024,
    "Consensus flight-recorder ring capacity (clamped to >= 1).",
)
HEIGHTLINE_CAP = _declare(
    "COMETBFT_TPU_HEIGHTLINE_CAP", "int", 512,
    "Per-height consensus timeline ledger capacity in heights (clamped "
    "to >= 8); the oldest heights are evicted as new ones commit.",
)
HEIGHTLINE = _declare(
    "COMETBFT_TPU_HEIGHTLINE", "bool", True,
    "`0` disables the per-height timeline ledger (utils/heightline): "
    "no phase recording, an empty `/height_timeline` RPC answer, and "
    "no `consensus_height_phase_seconds` observations.",
)

# health sentinel (utils/healthmon)
HEALTH = _declare(
    "COMETBFT_TPU_HEALTH", "bool", False,
    "`1` starts the node health sentinel (utils/healthmon) at node "
    "start: periodic hang-proof accelerator probes, heartbeat audits of "
    "the long-lived loops, and automatic stall forensics.  Off = "
    "`healthmon.beat()` stays a zero-overhead no-op.",
)
HEALTH_PERIOD_MS = _declare(
    "COMETBFT_TPU_HEALTH_PERIOD_MS", "int", 60000,
    "Sentinel probe period (ms): how often the accelerator is probed "
    "(utils/healthmon.probe_devices: in-process in a process that holds "
    "the chip).",
)
HEALTH_PROBE_TIMEOUT_MS = _declare(
    "COMETBFT_TPU_HEALTH_PROBE_TIMEOUT_MS", "int", 20000,
    "Hard deadline (ms) for one sentinel probe; a probe past it is "
    "abandoned (a child probe is SIGKILLed, whole process group) and "
    "counted as a failure.",
)
HEALTH_WEDGE_AFTER = _declare(
    "COMETBFT_TPU_HEALTH_WEDGE_AFTER", "int", 2,
    "Consecutive probe failures at/above which the health state is "
    "`wedged` (below it: `degraded`); a success snaps back to `ok`.",
)
HEALTH_ARTIFACT_MIN_INTERVAL_MS = _declare(
    "COMETBFT_TPU_HEALTH_ARTIFACT_MIN_INTERVAL_MS", "int", 300000,
    "Floor (ms) between two stall-forensics artifacts: one artifact is "
    "captured per incident, and never more often than this however the "
    "state flaps.",
)
HEALTH_DIR = _declare(
    "COMETBFT_TPU_HEALTH_DIR", "str", "",
    "Directory for stall-forensics artifacts; empty = `$TMPDIR`.",
)

# analysis / correctness tooling
LOCKCHECK = _declare(
    "COMETBFT_TPU_LOCKCHECK", "bool", False,
    "`1` installs the runtime lock-order witness "
    "(analysis/lockwitness): lock acquisitions build an order graph and "
    "inversions/blocking-while-locked are reported with both stacks.  "
    "The special value `raise` additionally raises in the acquiring "
    "thread (read raw by `maybe_install`, not via `get_bool`, which "
    "treats it as unset).  The test conftest turns the witness on for "
    "every suite run.",
)

# test-only
TEST_LATENCY_MS = _declare(
    "COMETBFT_TPU_TEST_LATENCY_MS", "str", "",
    "Inject `delay` or `delay:jitter` milliseconds on every p2p "
    "connection (e2e perturbation harness only; never set in production).",
)


# -------------------------------------------------------------- getters

def knob(name: str) -> Knob:
    return _REGISTRY[name]


def all_knobs() -> list[Knob]:
    return list(_REGISTRY.values())


def raw(name: str) -> str | None:
    """The raw env value, or None when unset.  For the rare reader whose
    semantics don't fit the typed getters (e.g. the tracer's
    truthy-or-path switch); the knob must still be declared."""
    _REGISTRY[name]  # undeclared knob = programming error
    return os.environ.get(name)


def get_str(name: str) -> str:
    k = _REGISTRY[name]
    v = os.environ.get(name)
    return v if v is not None else k.default


def get_int(name: str) -> int:
    k = _REGISTRY[name]
    v = os.environ.get(name, "")
    if v:
        try:
            return int(v)
        except ValueError:
            pass
    return k.default


def get_opt_int(name: str) -> int | None:
    """None when unset/empty/malformed — the caller owns the fallback
    (used for knobs whose default is computed, not constant)."""
    _REGISTRY[name]
    v = os.environ.get(name, "")
    if v:
        try:
            return int(v)
        except ValueError:
            pass
    return None


def get_bool(name: str) -> bool:
    k = _REGISTRY[name]
    v = os.environ.get(name)
    if v is None or not v.strip():
        # set-but-empty (`KNOB= cmd` shell idiom) means "default", not
        # False — flipping a kernel-path knob on an empty string would
        # silently select a different compiled program
        return k.default
    s = v.strip().lower()
    if s in _TRUE:
        return True
    if s in _FALSE:
        return False
    return k.default


# --------------------------------------------------------- doc generation

def to_markdown() -> str:
    """Render docs/knobs.md — regenerate with
    ``python -m cometbft_tpu.utils.envknobs > docs/knobs.md``."""
    lines = [
        "# Environment knobs",
        "",
        "Generated from `cometbft_tpu/utils/envknobs.py` — do not edit by "
        "hand; regenerate with `python -m cometbft_tpu.utils.envknobs > "
        "docs/knobs.md`.  Every `COMETBFT_TPU_*` knob is declared in that "
        "registry and read through its typed getters; the static linter "
        "(`scripts/lint.py`, check `raw-env-read`) rejects reads anywhere "
        f"else, so this table of {len(all_knobs())} knobs is the complete "
        "inventory.",
        "",
        "| Knob | Type | Default | Description |",
        "|---|---|---|---|",
    ]
    for k in all_knobs():
        default = "*(unset)*" if k.default is None else f"`{k.default!r}`"
        doc = k.doc.replace("|", "\\|")
        lines.append(f"| `{k.name}` | {k.type} | {default} | {doc} |")
    lines.append("")
    return "\n".join(lines)


if __name__ == "__main__":
    print(to_markdown(), end="")
