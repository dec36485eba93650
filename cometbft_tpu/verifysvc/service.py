"""The unified verify service: one priority-scheduled seam in front of
the device verify pipeline.

Every signature-verification workload in the node — consensus
VerifyCommit, blocksync verify-ahead, the uncached fallback during comb
table warming, and mempool CheckTx — submits through this service
instead of driving the device verifiers (models/verifier.py,
models/comb_verifier.py) directly.  The service owns:

  * **Priority classes** (consensus > blocksync > mempool > background):
    a strict-priority scheduler dispatches ready consensus batches
    before anything else, so a flood of mempool CheckTx traffic can
    never delay a commit verification behind it.  An optional weighted
    mode (``COMETBFT_TPU_VERIFYSVC_WEIGHTS``) trades strictness for
    proportional interleave when starvation of low classes matters more
    than worst-case consensus latency.
  * **Adaptive batch formation**: a class's queue flushes when the
    pending signature count reaches the batch width
    (``COMETBFT_TPU_VERIFYSVC_BATCH_MAX``, reason=``full``) or when its
    oldest request has waited the class's flush deadline
    (``COMETBFT_TPU_VERIFYSVC_DEADLINE_<CLASS>_MS``, reason=
    ``deadline``), whichever comes first.  Consensus's deadline is 0 —
    its batches dispatch the moment the scheduler sees them — while
    mempool's small deadline is the coalescing window that merges per-tx
    CheckTx signature checks from concurrent senders into one device
    batch (the batch-width lever of arXiv:2302.00418; the
    tx-offload argument of arXiv:2112.02229).
  * **Bounded queues + backpressure**: each class's queue admits at most
    ``COMETBFT_TPU_VERIFYSVC_QUEUE_MAX`` signatures; a submit beyond
    that raises :class:`VerifyServiceBackpressure` (counted in
    ``verify_svc_rejected_total{class}``, flight-recorded) and the
    caller falls back to host verification — admission control, not an
    unbounded latency cliff.

Requests within one class that carry no validator-set binding coalesce
into shared batches; comb-bound requests (a whole commit against a
cached validator set) dispatch solo, because the comb program scatters
one row per validator.  Per-request blame order is preserved exactly:
each ticket's per-signature list follows its own add() order however
batches were merged or completed.

**Multi-tenant scheduling** (ROADMAP item 5: N independent chains
consolidated onto one shared verify plane): every request carries a
*tenant* id — ``COMETBFT_TPU_VERIFYSVC_TENANT`` names the tenant a
process submits under, defaulting to ``default`` so every single-chain
caller is untouched — and the scheduler keys its queues by
**(tenant, class)**:

  * classes still dispatch in strict global priority (one tenant's
    ready consensus batch outranks every tenant's mempool work);
  * WITHIN a class, ready tenants interleave weighted-fair
    (``COMETBFT_TPU_VERIFYSVC_TENANT_WEIGHTS``, default weight 1 each,
    rotating round-robin so no tenant owns the tie-break) — a rogue
    tenant's mempool flood cannot monopolize the class's dispatch slots;
  * each (tenant, class) is additionally bounded by
    ``COMETBFT_TPU_VERIFYSVC_TENANT_QUOTA`` OUTSTANDING signatures —
    queued plus dispatched-but-unsettled, released at ticket
    settlement, so a fast drain into the device/wire pipeline cannot
    launder a flood past admission (0 = the class-wide bound) — and
    backpressure lands on the flooding tenant:
    :class:`VerifyServiceBackpressure` carries ``tenant`` and ``scope``
    (which bound was hit) while other tenants keep admitting;
  * batches never mix tenants: coalescing happens inside one
    (tenant, class) queue, so per-tenant latency/flush/reject
    accounting stays exact (the ``verify_svc_tenant_*`` metrics, with
    the tenant label set bounded by utils/metrics.LabelGuard).

The sustained-load proof of these properties is the soak harness
(``scripts/soak.py`` driving e2e/soak.py): M in-process chains
(e2e/tenants.py) share one service for minutes-to-hours while faults
fire, with per-tenant SLOs asserting no starvation, no leak, no drift.

The scheduler thread only *dispatches* (the underlying submit() seam is
asynchronous — payload staging runs on the comb staging thread); a
separate collector thread drains results in dispatch order and resolves
tickets, so the scheduler is free to form the next batch while the
device runs the previous one.  Batches whose submit() does real inline
work — host-routed verifies below the device threshold, demoted comb
batches, and the uncached path's assembly/compile — go to a dedicated
host worker draining a CLASS-PRIORITY queue instead: that compute on
the scheduler thread would delay a consensus dispatch behind a mempool
batch, the inversion the class system exists to prevent, and the
priority queue bounds a queued consensus batch's extra wait to at most
one in-flight lower-class task.

**Degraded-mode failover** (the failure guarded against is an
accelerator that hangs instead of erroring, which the health sentinel
only *detects*): the service runs in one of two backend modes,
``tpu`` or ``cpu_fallback``.  A dedicated failover watchdog thread —
never the scheduler, which must stay free to dispatch — trips the
service to CPU mode when an in-flight batch has been dispatched to (or
awaiting results from) the device longer than
``COMETBFT_TPU_FAILOVER_BATCH_DEADLINE_MS``, or when the health
sentinel (utils/healthmon) reports the accelerator ``wedged``.  A trip:

  * re-verifies every stranded in-flight batch on the host path, each
    request's per-signature blame in its OWN add() order (ticket
    resolution is first-wins, so the wedged device wait completing
    later — or never — cannot double-resolve or overwrite verdicts);
  * respawns the collector/host workers under a new generation (the old
    ones may be parked inside a wedged device wait forever; stale
    generations exit as soon as they unblock instead of double-draining);
  * routes every subsequent batch host-side — comb table binds are
    bypassed in ``_make_verifier`` here and in ``client.resolve_mode``
    (a table build is device work: it would hang with the device);
  * emits a flight-recorder ``verifysvc_failover`` event, flips the
    ``verify_svc_backend_mode`` gauge, and writes ONE forensics
    artifact (utils/debugdump.stall_report) per trip.

While tripped, the watchdog runs a **probation loop**: the hang-proof
probe (utils/healthmon.probe_devices — in-process, on a worker thread
judged against a hard deadline, because this process holds the chip; it
honors the ``wedge_device`` injected fault) every
``COMETBFT_TPU_FAILOVER_PROBE_PERIOD_MS``; after
``COMETBFT_TPU_FAILOVER_PROBATION_OK`` consecutive successes the
service restores TPU mode.  Dispatch/collect *errors* (as opposed to
hangs) don't flip the mode: the failed batch is re-verified on host
with identical verdicts and the service keeps serving — the
``fail_dispatch`` injected fault exercises exactly that path.

**Out-of-process verify plane** (``COMETBFT_TPU_VERIFYRPC_ADDR``):
when a remote plane is configured, the service routes every batch over
the wire to a shared verifyd (verifysvc/server.py) through
verifysvc/remote.py's crash-tolerant client instead of a local device
verifier.  The scheduler/collector/ticket plumbing is unchanged — a
RemoteBatchVerifier is just another BatchVerifier at the dispatch seam
— which is exactly how the PR-8 guarantees extend across the process
boundary: a plane death surfaces as a collect/submit error or deadline
breach, the remote client's circuit breaker trips to the in-process
HOST path (comb binds are bypassed — device-resident tables belong to
the plane), stranded batches host-re-verify with per-signature blame
in each request's own add() order, first-wins settlement discards any
late remote answer, and probation pings restore the remote path once
the plane returns.  Remote batches are tracked in flight as
``where="remote"`` and exempt from the LOCAL failover batch deadline:
the remote client owns its own deadline, and a slow plane must not be
conflated with a wedged local accelerator.
"""

from __future__ import annotations

import functools
import itertools
import queue
import threading
import time
from enum import IntEnum

from ..utils import envknobs, fail, healthmon, tracing
from ..utils.flightrec import recorder as _flightrec
from ..utils.log import get_logger
from ..utils.metrics import hub as _mhub

MODE_TPU = "tpu"
MODE_CPU_FALLBACK = "cpu_fallback"
_MODE_CODE = {MODE_TPU: 0, MODE_CPU_FALLBACK: 1}


class Klass(IntEnum):
    """Priority classes, highest first (lower value = dispatched first)."""

    CONSENSUS = 0
    BLOCKSYNC = 1
    MEMPOOL = 2
    BACKGROUND = 3
    # read-only proof serving (light-client fan-out): LOWEST priority by
    # construction — the scheduler is strict-priority across classes, so
    # however wide the proof backlog grows it can never delay a queued
    # CONSENSUS (or any signature-class) dispatch
    PROOF = 4

    @property
    def label(self) -> str:
        return self.name.lower()


_DEADLINE_KNOBS = {
    Klass.CONSENSUS: envknobs.VERIFYSVC_DEADLINE_CONSENSUS_MS,
    Klass.BLOCKSYNC: envknobs.VERIFYSVC_DEADLINE_BLOCKSYNC_MS,
    Klass.MEMPOOL: envknobs.VERIFYSVC_DEADLINE_MEMPOOL_MS,
    Klass.BACKGROUND: envknobs.VERIFYSVC_DEADLINE_BACKGROUND_MS,
    Klass.PROOF: envknobs.PROOF_DEADLINE_MS,
}

# request modes: how the dispatcher binds a batch to a device program.
# ("plain",)        -> uncached ed25519 kernel (power-of-two bucket
#                      shapes); coalescible with other plain requests of
#                      the class
# ("comb", entry)   -> comb-cached program bound to a valset cache entry
#                      (models/comb_verifier); dispatches solo — the
#                      scatter is one row per validator, so two commits
#                      against the same set cannot share a program call
# ("bls",)          -> BLS12-381 aggregate verifier (models/bls_verifier:
#                      device pubkey validation + G1 aggregation, host
#                      pairing); dispatches solo — a batch is an
#                      aggregate-commit claim, and mixing it with
#                      ed25519 rows would hand one verifier two key
#                      types.  Selected off the validator key type by
#                      crypto/batch.create_batch_verifier / client
#                      .resolve_mode.
# ("secp",)         -> batched secp256k1 ECDSA verifier
#                      (models/secp_verifier; Cosmos 33-byte and
#                      Ethereum 65-byte wire shapes in one lane);
#                      rows are independent, so secp requests COALESCE
#                      with other secp requests of the class exactly
#                      like plain ones — but never with a different
#                      mode, which would hand one verifier two key
#                      types.
# ("proof",)        -> batched Merkle proof GENERATION
#                      (models/proof_server): items are
#                      (tree_digest, index, b"") query triples, results
#                      are crypto/merkle.Proof rows.  Coalescible — each
#                      query's proof is independent, and coalescing is
#                      the whole point: a light-client swarm's queries
#                      merge into one one-hot-gather dispatch.
MODE_PLAIN = ("plain",)
MODE_BLS = ("bls",)
MODE_SECP = ("secp",)
MODE_PROOF = ("proof",)

# modes whose requests may merge into one batch (same mode only):
# per-row-independent verdicts with one shared data plane
_COALESCIBLE_MODES = frozenset({"plain", "secp", "proof"})

# the wire spelling of each mode's key type (verifysvc/wire.VerifyRequest
# .key_type); "" rides as ed25519 for back-compat with pre-BLS planes
_MODE_KEY_TYPE = {
    "plain": "ed25519",
    "comb": "ed25519",
    "bls": "bls12_381",
    "secp": "secp256k1",
    # proofs never ride a VerifyRequest — they have their own wire shape
    # (wire.ProofRequest).  The label exists for metrics/spans only, and
    # is deliberately ABSENT from _KEY_TYPE_MODE: a VerifyRequest
    # claiming key_type "proof" is a bad_request, not a proof query.
    "proof": "proof",
}
_KEY_TYPE_MODE = {
    "": MODE_PLAIN,
    "ed25519": MODE_PLAIN,
    "bls12_381": MODE_BLS,
    # all three secp wire formats share the MODE_SECP lane: the
    # verifier tells rows apart by pubkey length, like the host crypto
    # modules (20-byte "pubkey" = ecrecover sender address)
    "secp256k1": MODE_SECP,
    "secp256k1eth": MODE_SECP,
    "ecrecover": MODE_SECP,
}


def mode_key_type(mode) -> str:
    return _MODE_KEY_TYPE.get(mode[0], "ed25519")


def mode_for_key_type(key_type: str):
    """Wire key_type -> dispatch mode, or None for an unknown type (the
    server answers bad_request — never a silently-wrong verifier)."""
    return _KEY_TYPE_MODE.get(key_type)

# host-queue shutdown sentinel: sorts after every real class so queued
# work settles before the worker exits
_HOST_SENTINEL_PRIO = 1 << 30

# the tenant every single-chain caller lands on when none is claimed
DEFAULT_TENANT = "default"


def default_tenant() -> str:
    """The tenant id this process submits under — how a chain claims
    its slice of a shared verify plane (COMETBFT_TPU_VERIFYSVC_TENANT);
    empty/unset = ``default``."""
    t = envknobs.get_str(envknobs.VERIFYSVC_TENANT).strip()
    return t or DEFAULT_TENANT


def collect_timeout_s() -> float | None:
    """The client-side Ticket.collect() deadline
    (COMETBFT_TPU_VERIFYSVC_COLLECT_TIMEOUT_MS); None = wait forever."""
    ms = envknobs.get_int(envknobs.VERIFYSVC_COLLECT_TIMEOUT_MS)
    return None if ms <= 0 else ms / 1e3


def remote_plane_configured() -> bool:
    """Whether this process points at a shared out-of-process verify
    plane (COMETBFT_TPU_VERIFYRPC_ADDR).  Routing gates (crypto/batch,
    checktx, node startup) use this alongside device_capable(): a node
    with no local accelerator still consumes the remote plane."""
    return bool(envknobs.get_str(envknobs.VERIFYRPC_ADDR).strip())


class VerifyServiceBackpressure(Exception):
    """A signature bound was hit; the caller must fall back to host
    verification (or shed the request).  ``scope`` says which bound:
    ``tenant`` (this tenant's per-class quota on OUTSTANDING sigs —
    queued + in flight, released at settlement; other tenants are
    still admissible) or ``class`` (the class-wide queue bound)."""

    def __init__(
        self,
        klass: Klass,
        queued: int,
        limit: int,
        tenant: str = DEFAULT_TENANT,
        scope: str = "class",
    ):
        super().__init__(
            f"verify service backpressure: {scope} bound, class "
            f"{klass.label} tenant {tenant} has {queued} signatures "
            f"outstanding (limit {limit})"
        )
        self.klass = klass
        self.queued = queued
        self.limit = limit
        self.tenant = tenant
        self.scope = scope


# The longest a program's compile may keep its batch off the clocks
# (Ticket.compiling): past it the compile is taken for hung, and the
# collect timeout and the failover deadline run again.  Cold first-shape
# compiles on the v5e took 110-170 s (PERF.md section 5).
COMPILE_BOUND_S = 600.0


class Ticket:
    """Handle for one submitted request; collect() blocks for
    (all_ok, per_signature) in the request's own add() order, or raises
    whatever the dispatch/collect path raised."""

    __slots__ = ("_ev", "_mtx", "_result", "_exc", "nsigs", "resolved_ns",
                 "_on_settle", "compiling_since")

    def __init__(self, nsigs: int):
        self._ev = threading.Event()
        self._mtx = threading.Lock()
        self._result: tuple[bool, list[bool]] | None = None
        self._exc: BaseException | None = None
        self.nsigs = nsigs
        # perf_counter_ns of the winning resolution, stamped while tracing
        # is on: where the caller's wake-up interval starts
        self.resolved_ns = 0
        # fired exactly once, on whichever resolution wins — the
        # service's outstanding-quota release hook (submit() sets it)
        self._on_settle = None
        # when the batch's verifier said its program started compiling
        # (VerifyService._note_compile); None when it is not compiling
        self.compiling_since: float | None = None

    def compiling(self) -> bool:
        """Whether the batch is waiting for its program to compile — a
        first-shape XLA compile takes minutes on a cold cache and is
        work, not a stuck scheduler or a hung device, so neither
        collect()'s timeout nor the failover deadline counts it.  Only
        the compile: assembly, transfers and the dispatch are charged.
        And only for COMPILE_BOUND_S."""
        since = self.compiling_since
        return since is not None and time.monotonic() - since < COMPILE_BOUND_S

    def _settled(self) -> None:
        cb, self._on_settle = self._on_settle, None
        if cb is not None:
            cb()

    def _resolve(self, result) -> bool:
        """First resolution wins: a failover host re-verify races the
        wedged device collect it replaced, and whichever settles a
        ticket first is authoritative — the loser's late answer is
        discarded, never overwritten onto an already-read result."""
        with self._mtx:
            if self._ev.is_set():
                return False
            self._result = result
            self.resolved_ns = tracing.stamp()
            self._ev.set()
        self._settled()
        return True

    def _fail(self, exc: BaseException) -> bool:
        with self._mtx:
            if self._ev.is_set():
                return False
            self._exc = exc
            self._ev.set()
        self._settled()
        return True

    def done(self) -> bool:
        return self._ev.is_set()

    def collect(self, timeout: float | None = None) -> tuple[bool, list[bool]]:
        """``timeout`` bounds the wait.  It is spent in slices of at
        most a second, and a slice that ends while the batch's program
        compiles (``compiling``) is not charged."""
        left = timeout
        while not self._ev.is_set():
            if left is not None and left <= 0:
                raise TimeoutError("verify service ticket not resolved in time")
            t0 = time.monotonic()
            if self._ev.wait(None if left is None else min(left, 1.0)):
                break
            if not self.compiling():
                left -= time.monotonic() - t0
        if self._exc is not None:
            raise self._exc
        return self._result


class _Request:
    __slots__ = ("items", "klass", "mode", "ticket", "enq", "enq_ns", "tenant",
                 "ctx")

    def __init__(self, items, klass: Klass, mode, tenant: str = DEFAULT_TENANT):
        self.items = items
        self.klass = klass
        self.mode = mode
        self.tenant = tenant
        self.ticket = Ticket(len(items))
        self.enq = time.monotonic()
        # where the interval verify.svc.queued starts (tracing on only)
        self.enq_ns = tracing.stamp()
        # the submitter's span context: the scheduler/worker/collector
        # threads re-install it around their spans, so every hop of this
        # request — including the remote plane's, the context rides the
        # wire — shares the submitter's trace_id, and their spans name
        # the submitter's span as their parent
        self.ctx = (
            tracing.handoff()
            if tracing.propagation_enabled() else None
        )


def _batch_ctx(batch: list["_Request"]):
    """The span context a coalesced batch's spans run under: the first
    member's (consensus batches are single-request; a coalesced mempool
    batch's members joined one dispatch, so one trace naming it is the
    honest attribution)."""
    for r in batch:
        if r.ctx is not None:
            return r.ctx
    return None


def _parse_weights(spec: str) -> dict[Klass, int]:
    """``"consensus=8,blocksync=4,mempool=2,background=1"`` -> weights.
    Forgiving like the rest of the knob layer: malformed entries are
    dropped, an empty result means strict priority."""
    out: dict[Klass, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        name, _, val = part.partition("=")
        try:
            k = Klass[name.strip().upper()]
            w = int(val)
        except (KeyError, ValueError):
            continue
        if w >= 1:
            out[k] = w
    return out


def _parse_tenant_weights(spec: str) -> dict[str, int]:
    """``"chain-a=4,chain-b=1"`` -> per-tenant fair-share weights
    (unlisted tenants weigh 1).  Same forgiving parse as the class
    weights: malformed entries drop, empty = equal shares."""
    out: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        name, _, val = part.partition("=")
        name = name.strip()
        try:
            w = int(val)
        except ValueError:
            continue
        if name and w >= 1:
            out[name] = w
    return out


def cpu_verifier_for_mode(mode):
    """The mode's pure-host data plane (CpuEd25519BatchVerifier for the
    ed25519 modes, CpuBlsBatchVerifier for MODE_BLS,
    CpuSecpBatchVerifier for MODE_SECP) — the ONE selection point every
    fallback path shares, so a new key type cannot be added to one
    fallback and missed in another."""
    if mode[0] == "bls":
        from ..models.bls_verifier import CpuBlsBatchVerifier

        return CpuBlsBatchVerifier()
    if mode[0] == "secp":
        from ..models.secp_verifier import CpuSecpBatchVerifier

        return CpuSecpBatchVerifier()
    if mode[0] == "proof":
        from ..models.proof_server import CpuProofProver

        return CpuProofProver()
    from ..models.verifier import CpuEd25519BatchVerifier

    return CpuEd25519BatchVerifier()


class _HostBatchVerifier:
    """The degraded-mode data plane: the exact BatchVerifier seam shape
    the device verifiers expose, wrapping the MODE's pure-host verifier
    (:func:`cpu_verifier_for_mode` — each the ONE source of its
    host-verdict semantics, bit-identical to its kernels) behind a
    sync-ticket submit().  ``_entry = None`` routes its submit() through
    the class-priority host worker (``_submit_is_offloaded``), so a
    mempool batch's host verification still cannot delay a queued
    consensus dispatch while the service is tripped."""

    _entry = None
    _fallback = None

    def __init__(self, mode=MODE_PLAIN):
        self._cpu = cpu_verifier_for_mode(mode)

    def add(self, pub_key: bytes, msg: bytes, sig: bytes) -> None:
        self._cpu.add(pub_key, msg, sig)

    def add_items_unchecked(self, items) -> None:
        """Re-verify seam: take the items as-is, bypassing add()'s
        shape validation.  The error paths re-verify batches whose
        dispatch ALREADY failed — possibly on exactly that validation —
        and a raise here would escape into the scheduler/host-worker
        loop; the cpu verifiers instead judge malformed rows False."""
        self._cpu._items = list(items)

    def submit(self):
        return ("sync", self._cpu.verify())

    def collect(self, ticket) -> tuple[bool, list[bool]]:
        return ticket[1]


def _host_verify_items(items, mode=MODE_PLAIN) -> tuple[bool, list[bool]]:
    """The one host-path verdict every fallback resolves to — delegates
    to the mode's cpu verifier so the semantics cannot drift from the
    cpu backend (the blame-order tests pin service results against
    exactly this)."""
    cpu = cpu_verifier_for_mode(mode)
    cpu._items = list(items)
    return cpu.verify()


class VerifyService:
    """Priority-scheduled batching front of the device verify pipeline.

    Construction reads the ``COMETBFT_TPU_VERIFYSVC_*`` knobs once;
    explicit constructor arguments override them (tests).  Threads start
    lazily on first submit and are daemons; :meth:`stop` tears them down
    (in-flight tickets are failed, not leaked).
    """

    def __init__(
        self,
        batch_max: int | None = None,
        queue_max: int | None = None,
        deadlines_ms: dict[Klass, float] | None = None,
        weights: dict[Klass, int] | None = None,
        tenant_quota: int | None = None,
        tenant_weights: dict[str, int] | None = None,
        failover: bool | None = None,
        batch_deadline_s: float | None = None,
        probation_ok: int | None = None,
        probe_fn=None,
        probe_period_s: float | None = None,
        probe_timeout_s: float | None = None,
        failover_tick_s: float = 0.25,
        artifact_dir: str | None = None,
        remote_addr: str | None = None,
        remote_opts: dict | None = None,
    ):
        self.batch_max = max(
            1, batch_max if batch_max is not None
            else envknobs.get_int(envknobs.VERIFYSVC_BATCH_MAX)
        )
        self.queue_max = max(
            1, queue_max if queue_max is not None
            else envknobs.get_int(envknobs.VERIFYSVC_QUEUE_MAX)
        )
        # PROOF gets its own (usually wider) queue bound: light-client
        # fan-out arrives thousands of queries at a time and must be
        # able to backlog without that backlog counting against — or
        # being counted against — the signature classes' bound.  0 =
        # inherit the class-wide bound.
        pq = envknobs.get_int(envknobs.PROOF_QUEUE_MAX)
        self._proof_queue_max = pq if pq and pq > 0 else self.queue_max
        if deadlines_ms is None:
            deadlines_ms = {
                k: max(0, envknobs.get_int(knob))
                for k, knob in _DEADLINE_KNOBS.items()
            }
        self._deadline_s = {
            k: float(deadlines_ms.get(k, 0)) / 1e3 for k in Klass
        }
        self._weights = (
            dict(weights) if weights is not None
            else _parse_weights(envknobs.get_str(envknobs.VERIFYSVC_WEIGHTS))
        )
        self._credits: dict[Klass, int] = {}
        # ---- (tenant, class) scheduling state.  Queues are keyed
        # class-first (strict global priority), then by tenant (the
        # weighted-fair interleave within the class).  Tenant sub-dicts
        # are created on first submit and REMOVED when drained, so an
        # unbounded tenant-id stream never grows the scheduler state.
        q = tenant_quota if tenant_quota is not None else envknobs.get_int(
            envknobs.VERIFYSVC_TENANT_QUOTA
        )
        self.tenant_quota = q if q and q > 0 else self.queue_max
        self._tenant_weights = (
            dict(tenant_weights) if tenant_weights is not None
            else _parse_tenant_weights(
                envknobs.get_str(envknobs.VERIFYSVC_TENANT_WEIGHTS)
            )
        )
        self._queues: dict[Klass, dict[str, list[_Request]]] = {
            k: {} for k in Klass
        }
        self._queued_sigs: dict[Klass, dict[str, int]] = {k: {} for k in Klass}
        self._class_sigs: dict[Klass, int] = {k: 0 for k in Klass}
        # per-(class, tenant) OUTSTANDING signatures — submitted and not
        # yet settled.  This, not queue depth, is what the tenant quota
        # admits against: the scheduler hands batches to the device's
        # (or the wire's) async pipeline almost instantly, so a queue
        # bound alone would let one tenant park unbounded work in
        # flight.  Released exactly once per request via the ticket's
        # first-wins settle hook.  Own lock, nested inside _cond on the
        # submit path; the release path takes only this lock, so a
        # ticket resolved under any other service lock cannot deadlock.
        self._outstanding_sigs: dict[Klass, dict[str, int]] = {
            k: {} for k in Klass
        }
        self._out_mtx = threading.Lock()
        # weighted round-robin position + credits per class; credits are
        # rebuilt from the READY tenant set at each replenish, so tenants
        # that drained and left the queue dict are pruned for free
        self._tenant_credits: dict[Klass, dict[str, int]] = {k: {} for k in Klass}
        self._last_tenant: dict[Klass, str | None] = {k: None for k in Klass}
        self._cond = threading.Condition()
        self._collectq: queue.Queue = queue.Queue()
        # class-priority queue for batches whose submit() runs real work
        # inline (host routes, uncached assembly, cold-shape compiles):
        # entries (klass_value, seq, (bv, batch)); lower tuples first so
        # a queued consensus batch always overtakes queued mempool work
        self._hostq: queue.PriorityQueue = queue.PriorityQueue()
        # thread-safe sequence (scheduler, collector, AND the failover
        # error path all enqueue): equal (prio, seq) tuples would make
        # PriorityQueue compare the unorderable payloads
        self._hostseq = itertools.count(1)
        # batches handed to the device/host but not yet settled, keyed by
        # id(batch): the health sentinel's forensics read their ages to
        # say HOW LONG a wedged dispatch has been in flight
        self._inflight: dict[int, dict] = {}
        self._inflight_mtx = threading.Lock()
        self._running = False
        self._threads: list[threading.Thread] = []
        self._start_once = threading.Lock()
        self.logger = get_logger("verifysvc")
        # service-local tallies mirrored to hub metrics; the RPC status
        # endpoint reads these without scraping /metrics
        self._dispatched: dict[str, int] = {k.label: 0 for k in Klass}
        self._rejected: dict[str, int] = {k.label: 0 for k in Klass}
        # per-tenant tallies for stats()/soak SLOs, keyed by the hub's
        # BOUNDED tenant label (LabelGuard) so a tenant-id flood can't
        # grow this dict without bound either
        self._tenant_tallies: dict[str, dict[str, int]] = {}
        self._tally_mtx = threading.Lock()

        # ---- degraded-mode failover (module docstring, "failover")
        self.failover_enabled = (
            envknobs.get_bool(envknobs.FAILOVER) if failover is None
            else failover
        )
        self.batch_deadline_s = (
            batch_deadline_s if batch_deadline_s is not None
            else max(1, envknobs.get_int(envknobs.FAILOVER_BATCH_DEADLINE_MS))
            / 1e3
        )
        self.probation_ok = max(
            1, probation_ok if probation_ok is not None
            else envknobs.get_int(envknobs.FAILOVER_PROBATION_OK)
        )
        self.probe_period_s = (
            probe_period_s if probe_period_s is not None
            else max(1, envknobs.get_int(envknobs.FAILOVER_PROBE_PERIOD_MS))
            / 1e3
        )
        self.probe_timeout_s = (
            probe_timeout_s if probe_timeout_s is not None
            else max(1, envknobs.get_int(envknobs.FAILOVER_PROBE_TIMEOUT_MS))
            / 1e3
        )
        self._probe_fn = (
            probe_fn if probe_fn is not None else healthmon.probe_devices
        )
        self.failover_tick_s = max(0.01, failover_tick_s)
        self.artifact_dir = artifact_dir
        # ---- out-of-process verify plane (module docstring, "remote").
        # The client (and its io thread) is created at _ensure_started,
        # so merely constructing a service never dials a plane.
        self.remote_addr = (
            remote_addr if remote_addr is not None
            else envknobs.get_str(envknobs.VERIFYRPC_ADDR).strip()
        ) or None
        self._remote_opts = dict(remote_opts or {})
        self._remote = None
        # mode state, guarded by _failover_mtx (never held across
        # blocking work); _gen tags worker threads so a trip can respawn
        # the collector/host workers while the wedged old generation is
        # still parked inside a device wait
        self._failover_mtx = threading.Lock()
        self._backend_mode = MODE_TPU
        self._gen = 0
        self._trips = 0
        self._restores = 0
        self._probation_consec_ok = 0
        self._next_probation_probe = 0.0
        self._last_restore_at: float | None = None
        self._last_trip_reason: str | None = None
        self._last_artifact: str | None = None
        self._stop_ev = threading.Event()

    # ------------------------------------------------------------ lifecycle

    def _ensure_started(self) -> None:
        if self._running:
            return
        with self._start_once:
            if self._running:
                return
            self._running = True
            # restart path (stop() then a later submit): a stale stop
            # signal would make every bounded wait in the failover loop
            # return immediately — a busy spin firing back-to-back
            # probes
            self._stop_ev.clear()
            if self.remote_addr and self._remote is None:
                from . import remote

                self._remote = remote.RemotePlaneClient(
                    self.remote_addr,
                    artifact_dir=self.artifact_dir,
                    **self._remote_opts,
                )
            self._threads = [
                threading.Thread(
                    target=self._sched_loop, name="verifysvc-sched",
                    daemon=True,
                ),
            ]
            if self.failover_enabled:
                self._threads.append(
                    threading.Thread(
                        target=self._failover_loop,
                        name="verifysvc-failover", daemon=True,
                    )
                )
            for t in self._threads:
                t.start()
            self._threads += self._spawn_workers(self._gen)

    def _spawn_workers(self, gen: int) -> list[threading.Thread]:
        """Start a collector + host worker tagged with ``gen``.  A
        failover trip bumps the generation and calls this again: the
        old workers may be parked forever inside a wedged device wait,
        and a stale generation exits (without retiring its heartbeat —
        the fresh worker owns the name now) as soon as it unblocks."""
        ts = [
            threading.Thread(
                target=self._collect_loop, args=(gen,),
                name="verifysvc-collect", daemon=True,
            ),
            threading.Thread(
                target=self._host_loop, args=(gen,),
                name="verifysvc-host", daemon=True,
            ),
        ]
        for t in ts:
            t.start()
        return ts

    def stop(self) -> None:
        """Tear down the scheduler/collector (tests).  Queued requests
        are failed with backpressure so no caller blocks forever."""
        with self._cond:
            if not self._running:
                return
            self._running = False
            stranded = [
                r
                for tenant_queues in self._queues.values()
                for q in tenant_queues.values()
                for r in q
            ]
            for k in Klass:
                self._queues[k] = {}
                self._queued_sigs[k] = {}
                self._class_sigs[k] = 0
            self._cond.notify_all()
        self._stop_ev.set()
        # close the remote client FIRST: its pending requests settle
        # with errors and their deferred-collect callbacks enqueue the
        # batches onto the collect queue, so the drain below fails those
        # tickets too — stop() must never leave a remote-in-flight
        # caller parked until its own collect timeout
        if self._remote is not None:
            self._remote.close()
            self._remote = None
        self._collectq.put(None)
        self._hostq.put((_HOST_SENTINEL_PRIO, 0, None))
        for r in stranded:
            r.ticket._fail(
                VerifyServiceBackpressure(r.klass, 0, self.queue_max)
            )
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []
        # a dispatch racing the sentinels can land its batch AFTER a
        # worker exited: fail those tickets too — stop() must never
        # leave a caller parked in collect() forever
        def _fail_batch(batch):
            for r in batch:
                r.ticket._fail(
                    VerifyServiceBackpressure(r.klass, 0, self.queue_max)
                )

        while True:
            try:
                item = self._collectq.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                _fail_batch(item[2])
        while True:
            try:
                _, _, payload = self._hostq.get_nowait()
            except queue.Empty:
                break
            if payload is not None:
                _fail_batch(payload[1])
        with self._inflight_mtx:
            self._inflight.clear()

    # ------------------------------------------------------------- submit

    def submit(
        self, items, klass: Klass, mode=MODE_PLAIN, tenant: str | None = None
    ) -> Ticket:
        """Enqueue one verification request (a list of
        (pubkey, msg, sig) triples, verified as a unit) under
        ``tenant`` (None = this process's default tenant) and return
        its ticket.  Raises :class:`VerifyServiceBackpressure` when the
        tenant's per-class quota or the class-wide queue bound is hit."""
        items = list(items)
        if tenant is None:
            tenant = default_tenant()
        if not items:
            t = Ticket(0)
            t._resolve((False, []))  # empty-batch contract of the verifiers
            return t
        self._ensure_started()
        n = len(items)
        m = _mhub()
        tlabel = m.tenant_labels.bound(tenant)
        with self._cond:
            if not self._running:
                # stop() won the race after _ensure_started: enqueueing
                # onto a dead scheduler would park the caller forever —
                # reject so they take their host fallback instead
                raise VerifyServiceBackpressure(
                    klass, 0, self.queue_max, tenant=tenant
                )
            class_q = self._class_sigs[klass]
            ten_q = self._queued_sigs[klass].get(tenant, 0)
            qmax = (
                self._proof_queue_max if klass is Klass.PROOF
                else self.queue_max
            )
            with self._out_mtx:
                ten_out = self._outstanding_sigs[klass].get(tenant, 0)
                if ten_out + n > self.tenant_quota < self.queue_max:
                    # the flooding tenant's OWN quota — on OUTSTANDING
                    # sigs (queued + dispatched-unsettled), so a fast
                    # drain into the device/wire pipeline can't launder
                    # a flood past admission: backpressure confined to
                    # the offender, the class stays admissible for
                    # others.  With no extra per-tenant bound configured
                    # (quota == queue_max) the class bound below owns
                    # the attribution: scope="tenant" must only ever
                    # point an operator at a quota knob that is
                    # actually the binding constraint.
                    queued, limit, scope = (
                        ten_out, self.tenant_quota, "tenant"
                    )
                elif class_q + n > qmax:
                    queued, limit, scope = class_q, qmax, "class"
                else:
                    queued = limit = 0
                    scope = None
                    self._outstanding_sigs[klass][tenant] = ten_out + n
            if scope is not None:
                self._rejected[klass.label] += 1
            else:
                req = _Request(items, klass, mode, tenant=tenant)
                req.ticket._on_settle = functools.partial(
                    self._release_outstanding, klass, tenant, n
                )
                self._queues[klass].setdefault(tenant, []).append(req)
                self._queued_sigs[klass][tenant] = ten_q + n
                self._class_sigs[klass] = class_q + n
                depth = class_q + n
                tdepth = ten_q + n
                self._cond.notify()
        if scope is not None:
            # admission control: count it, flight-record it, and push the
            # decision back to the caller (host fallback / shed)
            self._tally_tenant(tlabel, "rejected")
            m.verify_svc_rejected.inc(**{"class": klass.label})
            m.verify_svc_tenant_rejected.inc(
                **{"tenant": tlabel, "class": klass.label, "scope": scope}
            )
            _flightrec().record(
                "verifysvc_backpressure",
                klass=klass.label, tenant=tenant, scope=scope,
                queued=queued, sigs=n, limit=limit,
            )
            tracing.instant(
                "verify.sched.reject",
                {"class": klass.label, "tenant": tenant, "scope": scope,
                 "queued": queued, "sigs": n}
                if tracing.enabled() else None,
            )
            raise VerifyServiceBackpressure(
                klass, queued, limit, tenant=tenant, scope=scope
            )
        m.verify_svc_queue_depth.set(depth, **{"class": klass.label})
        m.verify_svc_tenant_queue_depth.set(
            tdepth, **{"tenant": tlabel, "class": klass.label}
        )
        return req.ticket

    def _release_outstanding(self, klass: Klass, tenant: str, n: int) -> None:
        """Return ``n`` signatures of ``tenant``'s quota — the ticket's
        settle hook, fired exactly once per admitted request no matter
        which path (collect, host re-verify, failure, stop) wins."""
        with self._out_mtx:
            d = self._outstanding_sigs[klass]
            left = d.get(tenant, 0) - n
            if left > 0:
                d[tenant] = left
            else:
                d.pop(tenant, None)

    def _tally_tenant(self, tlabel: str, key: str, n: int = 1) -> None:
        """Bump a per-tenant tally (keyed by the BOUNDED label).  Its
        own small lock: the reject path holds the scheduler cond, the
        dispatch path holds nothing — order is always cond -> tally."""
        with self._tally_mtx:
            t = self._tenant_tallies.get(tlabel)
            if t is None:
                t = self._tenant_tallies[tlabel] = {
                    "dispatched_batches": 0, "dispatched_sigs": 0,
                    "rejected": 0,
                }
            t[key] = t.get(key, 0) + n

    def verify(
        self, items, klass: Klass, mode=MODE_PLAIN, tenant: str | None = None
    ) -> tuple[bool, list[bool]]:
        """submit() + collect() in one call (synchronous callers)."""
        return self.submit(items, klass, mode, tenant=tenant).collect()

    # ---------------------------------------------------------- scheduler

    def _tenant_ready_locked(self, klass: Klass, tenant: str, now: float) -> bool:
        q = self._queues[klass].get(tenant)
        if not q:
            return False
        if q[0].mode[0] not in _COALESCIBLE_MODES:
            # the head dispatches solo (comb- or bls-bound): the class's
            # flush deadline is a window for coalescing, and nothing can
            # join this request however long it waits
            return True
        if self._queued_sigs[klass].get(tenant, 0) >= self.batch_max:
            return True
        return (now - q[0].enq) >= self._deadline_s[klass]

    def _ready_locked(self, klass: Klass, now: float) -> bool:
        """A class is ready when ANY of its tenants is ready (width or
        deadline) — strict class priority is decided first, the tenant
        interleave second."""
        return any(
            self._tenant_ready_locked(klass, t, now)
            for t in self._queues[klass]
        )

    def _next_deadline_locked(self, now: float) -> float | None:
        """Seconds until the earliest not-yet-ready (class, tenant)
        queue flushes, or None when every queue is empty."""
        best = None
        for k in Klass:
            for q in self._queues[k].values():
                if not q:
                    continue
                remain = self._deadline_s[k] - (now - q[0].enq)
                if best is None or remain < best:
                    best = remain
        return best

    def _pick_class_locked(self, now: float) -> Klass | None:
        ready = [k for k in Klass if self._ready_locked(k, now)]
        if not ready:
            return None
        if not self._weights:
            return ready[0]  # strict priority: Klass order
        # weighted interleave: spend per-class credits in priority order,
        # replenish when every ready class is out
        for k in ready:
            if self._credits.get(k, 0) > 0:
                self._credits[k] -= 1
                return k
        for k in Klass:
            self._credits[k] = self._weights.get(k, 1)
        self._credits[ready[0]] -= 1
        return ready[0]

    def _pick_tenant_locked(self, klass: Klass, now: float) -> str:
        """Weighted-fair interleave of the class's READY tenants: spend
        per-tenant credits in rotating round-robin order (starting after
        the last dispatched tenant, so no tenant owns the tie-break);
        when every ready tenant is out of credits, replenish each to its
        configured weight.  A tenant with weight w gets w dispatch slots
        per round — a flooding tenant's surplus queue depth buys it
        nothing beyond its share."""
        ready = sorted(
            t for t in self._queues[klass]
            if self._tenant_ready_locked(klass, t, now)
        )
        if len(ready) == 1:
            self._last_tenant[klass] = ready[0]
            return ready[0]
        last = self._last_tenant[klass]
        if last in ready:
            i = ready.index(last)
            order = ready[i + 1 :] + ready[: i + 1]
        else:
            order = ready
        creds = self._tenant_credits[klass]
        for t in order:
            if creds.get(t, 0) > 0:
                creds[t] -= 1
                self._last_tenant[klass] = t
                return t
        # replenish — rebuilt from the ready set, which prunes tenants
        # that drained and left the queue dict since the last round
        self._tenant_credits[klass] = creds = {
            t: self._tenant_weights.get(t, 1) for t in ready
        }
        t = order[0]
        creds[t] -= 1
        self._last_tenant[klass] = t
        return t

    def _form_batch_locked(
        self, klass: Klass, tenant: str
    ) -> tuple[list[_Request], str]:
        """Pop the head batch of a ready (class, tenant) queue.  Only
        coalescible modes (plain ed25519, secp) merge — and only with
        the SAME mode, up to the batch width: a coalesced batch has
        exactly one verifier, and one verifier serves one key type.
        Comb- and bls-bound requests go solo (each binds its own device
        program / aggregate claim).  Batches never mix tenants —
        per-tenant latency and blame accounting stay exact."""
        q = self._queues[klass][tenant]
        # the flush reason is what made the queue ready, decided before
        # popping: a width-triggered flush whose head dispatches solo
        # (comb) must not read as a deadline expiry on the dashboards
        was_full = self._queued_sigs[klass].get(tenant, 0) >= self.batch_max
        head = q.pop(0)
        batch = [head]
        total = len(head.items)
        kind = head.mode[0]
        if kind in _COALESCIBLE_MODES:
            while q and q[0].mode[0] == kind and total < self.batch_max:
                nxt = q.pop(0)
                batch.append(nxt)
                total += len(nxt.items)
        remaining = self._queued_sigs[klass].get(tenant, 0) - total
        if q:
            self._queued_sigs[klass][tenant] = remaining
        else:
            # drained: drop the tenant's entries so scheduler state stays
            # bounded however many tenant ids ever appeared
            del self._queues[klass][tenant]
            self._queued_sigs[klass].pop(tenant, None)
        self._class_sigs[klass] -= total
        if was_full or total >= self.batch_max:
            reason = "full"
        else:
            reason = "deadline" if kind in _COALESCIBLE_MODES else "solo"
        return batch, reason

    def _track_inflight(self, batch: list[_Request], where: str) -> None:
        now = time.monotonic()
        with self._inflight_mtx:
            self._inflight[id(batch)] = {
                "class": batch[0].klass.label,
                "tenant": batch[0].tenant,
                "sigs": sum(len(r.items) for r in batch),
                "requests": len(batch),
                "where": where,
                "since": now,
                # when the batch ENTERED the device-bound phase — the
                # clock the failover deadline runs on (``_device_age``).
                # A host-tracked batch starts it only at the
                # host->device relabel: host-worker time must never
                # count toward the trip.  A batch waiting for its
                # program to compile is off it, and starts it anew when
                # the program is there (``_note_compile``)
                "device_since": now if where == "device" else None,
                # the requests themselves, so a failover trip can
                # re-verify stranded work on host (never serialized:
                # stats() copies the display fields only)
                "batch": batch,
            }

    def _relabel_inflight(self, batch: list[_Request], where: str) -> None:
        with self._inflight_mtx:
            rec = self._inflight.get(id(batch))
            if rec is not None:
                if where == "remote":
                    # remote batches never start the LOCAL failover
                    # deadline clock (device_since stays None): the
                    # remote client owns its own deadline + breaker,
                    # and a slow plane is not a wedged local device
                    rec["remote"] = True
                rec["where"] = where
                if (
                    where in ("device", "collect")
                    and not rec.get("remote")
                    and rec.get("device_since") is None
                ):
                    rec["device_since"] = time.monotonic()

    def _untrack_inflight(self, batch: list[_Request]) -> None:
        with self._inflight_mtx:
            self._inflight.pop(id(batch), None)

    def _note_compile(self, batch: list[_Request], on: bool) -> None:
        """The batch's verifier says the batch waits for its program to
        compile (``on``), or has it (models/verifier.program_for).  Both
        clocks stand still in between (``Ticket.compiling``), and the
        failover deadline's starts anew: the dispatch is made only now."""
        since = time.monotonic() if on else None
        for r in batch:
            r.ticket.compiling_since = since
        if not on:
            with self._inflight_mtx:
                rec = self._inflight.get(id(batch))
                if rec is not None and rec.get("device_since") is not None:
                    rec["device_since"] = time.monotonic()

    @staticmethod
    def _device_age(rec: dict, now: float) -> float | None:
        """Seconds an in-flight record has been on the failover
        deadline's clock; None while it is off it (host-tracked, remote,
        or waiting for its program to compile)."""
        since = rec.get("device_since")
        if since is None or rec["batch"][0].ticket.compiling():
            return None
        return now - since

    def _sched_loop(self) -> None:
        m = _mhub()
        while True:
            healthmon.beat("verifysvc-sched")
            with self._cond:
                if not self._running:
                    healthmon.retire("verifysvc-sched")
                    return
                now = time.monotonic()
                klass = self._pick_class_locked(now)
                if klass is None:
                    remain = self._next_deadline_locked(now)
                    # bounded wait (never a bare wait(): new submissions
                    # notify, deadlines cap the sleep, and an idle tick
                    # keeps shutdown prompt)
                    self._cond.wait(
                        0.5 if remain is None else max(0.0, min(remain, 0.5))
                    )
                    continue
                tenant = self._pick_tenant_locked(klass, now)
                batch, reason = self._form_batch_locked(klass, tenant)
                depth = self._class_sigs[klass]
                tdepth = self._queued_sigs[klass].get(tenant, 0)
            m.verify_svc_queue_depth.set(depth, **{"class": klass.label})
            m.verify_svc_tenant_queue_depth.set(
                tdepth,
                **{"tenant": m.tenant_labels.bound(tenant),
                   "class": klass.label},
            )
            self._dispatch(klass, batch, reason)

    def _make_verifier(self, mode):
        """Bind a batch to a data-plane verifier.  The ONLY constructor
        seam — tests monkeypatch this to observe dispatch order without
        touching a real kernel.  With a remote plane configured, every
        batch routes over the wire while the breaker is closed and to
        the in-process HOST path while it is open (never a local device:
        a node consuming a shared plane may not even have one, and the
        host path is the bit-identical verdict source either way).  In
        CPU fallback mode EVERY batch — comb-bound or not — gets the
        host verifier: a comb entry is device-resident state, and
        touching it while the device is wedged is exactly the hang the
        trip escaped."""
        rem = self._remote  # one read: stop() nulls it concurrently
        if mode[0] == "proof":
            # proofs have their own wire shape and their own device
            # prover; every degraded arm lands on _HostBatchVerifier
            # over CpuProofProver -> proofs_from_byte_slices, the
            # bit-identity oracle
            if rem is not None:
                if rem.available():
                    from .remote import RemoteProofVerifier

                    return RemoteProofVerifier(rem)
                return _HostBatchVerifier(mode)
            if self._backend_mode == MODE_CPU_FALLBACK:
                return _HostBatchVerifier(mode)
            from ..models.proof_server import TpuProofProver

            return TpuProofProver()
        if rem is not None:
            if rem.available():
                from .remote import RemoteBatchVerifier

                return RemoteBatchVerifier(rem, key_type=mode_key_type(mode))
            return _HostBatchVerifier(mode)
        if self._backend_mode == MODE_CPU_FALLBACK:
            return _HostBatchVerifier(mode)
        if mode[0] == "bls":
            from ..models.bls_verifier import BlsAggregateVerifier

            return BlsAggregateVerifier()
        if mode[0] == "secp":
            from ..models.secp_verifier import TpuSecpBatchVerifier

            return TpuSecpBatchVerifier()
        if mode[0] == "comb":
            from ..models.comb_verifier import CombBatchVerifier

            return CombBatchVerifier(mode[1])
        from ..models.verifier import TpuEd25519BatchVerifier

        return TpuEd25519BatchVerifier()

    @staticmethod
    def _submit_is_offloaded(bv, nsigs: int) -> bool:
        """Whether bv.submit() must run on the host worker instead of
        the scheduler thread.  Only the comb-cached staging path is
        genuinely cheap at submit time (the slab fill + H2D + dispatch
        run on the comb staging thread): everything else does real work
        inline — sub-threshold batches verify on host, demoted comb
        batches resolve their fallback synchronously, and the uncached
        device path runs host assembly plus, at a new bucket shape, the
        XLA compile.  Any of those on the scheduler thread would delay
        a consensus dispatch behind lower-class work."""
        if getattr(bv, "_entry", None) is None:  # plain/uncached path
            return True
        if getattr(bv, "_fallback", None) is not None:  # demoted comb
            return True
        from ..models.verifier import _device_batch_min

        return nsigs < _device_batch_min()  # comb submit host-routes

    def _dispatch(self, klass: Klass, batch: list[_Request], reason: str) -> None:
        m = _mhub()
        nsigs = sum(len(r.items) for r in batch)
        tlabel = m.tenant_labels.bound(batch[0].tenant)
        now = time.monotonic()
        for r in batch:
            m.verify_svc_queue_wait.observe(
                now - r.enq, **{"class": klass.label}
            )
        m.verify_svc_flush.inc(**{"class": klass.label, "reason": reason})
        m.verify_svc_tenant_dispatched.inc(
            **{"tenant": tlabel, "class": klass.label}
        )
        self._dispatched[klass.label] += 1
        self._tally_tenant(tlabel, "dispatched_batches")
        self._tally_tenant(tlabel, "dispatched_sigs", nsigs)
        labels = None
        if tracing.enabled():
            labels = {"class": klass.label, "tenant": batch[0].tenant,
                      "reason": reason, "sigs": nsigs, "requests": len(batch)}
            t_ns = tracing.stamp()
            for r in batch:
                with tracing.context_scope(r.ctx):
                    tracing.interval("verify.svc.queued", r.enq_ns, t_ns)
        bv = None
        with tracing.context_scope(_batch_ctx(batch)), \
                tracing.span("verify.sched.dispatch", labels):
            try:
                if fail.armed("fail_dispatch") is not None:
                    raise fail.InjectedFault("injected fault: fail_dispatch")
                bv = self._make_verifier(batch[0].mode)
                bind = getattr(bv, "bind_request", None)
                if bind is not None:
                    # remote verifiers carry (tenant, class) on the wire
                    # — the plane schedules remote submitters server-side
                    bind(klass, batch[0].tenant)
                with tracing.span("verify.sched.add"):
                    for r in batch:
                        for pub, msg, sig in r.items:
                            bv.add(pub, msg, sig)
                if hasattr(bv, "on_compile"):
                    # the device verifiers say when the batch waits for
                    # a first-shape compile, wherever it runs
                    bv.on_compile = functools.partial(
                        self._note_compile, batch
                    )
                if self._submit_is_offloaded(bv, nsigs):
                    # real submit-time work: hand it to the host worker
                    # (class-priority queue) so the scheduler stays free
                    # to dispatch the next, possibly higher-class, batch
                    self._track_inflight(batch, "host")
                    self._hostq.put(
                        (int(klass), next(self._hostseq),
                         (bv, batch, tracing.stamp()))
                    )
                    return
                ticket = bv.submit()  # comb staging seam: cheap dispatch
            except BaseException as e:  # noqa: BLE001 — settle the tickets, keep scheduling
                self.logger.error(
                    f"dispatch failed (class={klass.label}, sigs={nsigs}): {e!r}"
                )
                self._fail_or_reverify(
                    batch, e, cause="dispatch_error", bv=bv
                )
                return
        self._track_inflight(batch, "device")
        self._collectq.put((bv, ticket, batch))

    def _host_loop(self, gen: int = 0) -> None:
        """Drain submit-time work in class-priority order: queued
        consensus batches overtake queued lower-class ones (the worker
        can't preempt an in-flight verify/compile, so the worst-case
        consensus delay is ONE lower-class task, not a whole backlog).
        ``gen`` retires this worker after a failover trip respawned a
        fresh one (a stale worker processes at most the item it already
        held — harmless, settlement is first-wins — then exits without
        retiring the heartbeat the fresh worker now owns)."""
        while True:
            if gen != self._gen:
                return
            if not self._running:
                healthmon.retire("verifysvc-host")
                return
            healthmon.beat("verifysvc-host")
            try:
                _, _, payload = self._hostq.get(timeout=0.5)
            except queue.Empty:
                continue
            if payload is None:
                healthmon.retire("verifysvc-host")
                return
            bv, batch, queued_ns = payload
            if all(r.ticket.done() for r in batch):
                # a failover trip already host-re-verified this batch
                # while it sat queued: submitting its stale device-bound
                # verifier now could park THIS worker in the same wedge
                self._untrack_inflight(batch)
                continue
            if (
                self._backend_mode == MODE_CPU_FALLBACK
                and not isinstance(bv, _HostBatchVerifier)
            ):
                # pending batch whose payload was bound to a DEVICE
                # verifier pre-trip (raced the mode flip): its submit()
                # would dispatch to the wedged device — rebuild it on
                # the host path instead (unchecked: a malformed row must
                # judge False, not raise out of this worker loop)
                hbv = _HostBatchVerifier(batch[0].mode)
                hbv.add_items_unchecked(
                    [it for r in batch for it in r.items]
                )
                bv = hbv
            klass = batch[0].klass
            labels = (
                {"class": klass.label, "requests": len(batch)}
                if tracing.enabled() else None
            )
            with tracing.context_scope(_batch_ctx(batch)):
                tracing.interval("verify.host.queued", queued_ns)
            with tracing.context_scope(_batch_ctx(batch)), \
                    tracing.span("verify.sched.hostwork", labels):
                try:
                    ticket = bv.submit()  # the inline work happens here
                except BaseException as e:  # noqa: BLE001 — settle the tickets, keep serving
                    self.logger.error(
                        f"host-route verify failed (class={klass.label}): {e!r}"
                    )
                    self._untrack_inflight(batch)
                    self._fail_or_reverify(
                        batch, e, cause="submit_error", bv=bv
                    )
                    continue
            if ticket[0] == "sync":
                self._settle(bv, ticket, batch)  # resolved already
            else:
                # device/remote ticket (uncached path): the collector
                # owns the blocking result wait, freeing this worker
                # immediately.  Relabel the in-flight record (same
                # entry, age keeps accruing) so a wedge during the
                # collect blames the device wait, not the finished host
                # work — remote batches keep their own label and stay
                # off the local failover clock
                self._relabel_inflight(
                    batch, getattr(bv, "inflight_where", "device")
                )
                defer = getattr(bv, "defer_collect", None)
                if defer is not None:
                    # remote batches reach the collector only once their
                    # response/expiry has SETTLED them: the plane answers
                    # out of dispatch order (it schedules by class), and
                    # a FIFO blocking collect would park a consensus
                    # settle behind every in-flight mempool response
                    defer(
                        ticket,
                        lambda bv=bv, t=ticket, b=batch:
                        self._collectq.put((bv, t, b)),
                    )
                else:
                    self._collectq.put((bv, ticket, batch))

    # ---------------------------------------------------------- collector

    def _collect_loop(self, gen: int = 0) -> None:
        while True:
            if gen != self._gen:
                return  # superseded by a failover trip's fresh worker
            if not self._running:
                healthmon.retire("verifysvc-collect")
                return
            healthmon.beat("verifysvc-collect")
            try:
                item = self._collectq.get(timeout=0.5)
            except queue.Empty:
                continue
            if item is None:
                healthmon.retire("verifysvc-collect")
                return
            self._settle(*item)

    def _settle(self, bv, ticket, batch: list[_Request]) -> None:
        """Resolve a dispatched batch's tickets from its verifier
        ticket, splitting the result vector back per request.  The batch
        stays in the in-flight table until it resolves either way — the
        blocking collect() below is exactly the wait whose age the
        failover watchdog and the health forensics read when a device
        wedges mid-batch."""
        if all(r.ticket.done() for r in batch):
            # a failover trip already host-re-verified this batch while
            # it sat queued behind a wedged collect: touching the device
            # ticket now would park THIS worker in the same wedge
            self._untrack_inflight(batch)
            return
        self._relabel_inflight(batch, "collect")
        try:
            self._settle_inner(bv, ticket, batch)
        finally:
            self._untrack_inflight(batch)

    def _settle_inner(self, bv, ticket, batch: list[_Request]) -> None:
        labels = (
            {"class": batch[0].klass.label,
             "requests": len(batch)}
            if tracing.enabled() else None
        )
        t_collect = time.monotonic()
        with tracing.context_scope(_batch_ctx(batch)), \
                tracing.span("verify.sched.collect", labels, wait=True):
            try:
                if not (isinstance(ticket, tuple) and ticket and ticket[0] == "sync"):
                    # injected-fault seams, in the same place a real
                    # device wedge/stall bites: the blocking DEVICE
                    # result wait.  Sync tickets are host-verified
                    # results — a wedged device never blocks them, so
                    # neither do the faults (post-trip CPU-mode batches
                    # must keep settling while the wedge is armed)
                    slow = fail.armed("slow_collect")
                    if slow is not None:
                        time.sleep(slow)
                    fail.wedge_wait("wedge_device")
                _, res = bv.collect(ticket)
            except BaseException as e:  # noqa: BLE001 — settle the tickets, keep draining
                self.logger.error(
                    f"collect failed (class={batch[0].klass.label}): {e!r}"
                )
                self._fail_or_reverify(
                    batch, e, cause="collect_error", bv=bv
                )
                return
        total = sum(len(r.items) for r in batch)
        if batch[0].klass == Klass.CONSENSUS:
            # height-timeline verify attribution: tickets don't carry
            # heights, so the batch lands on the ledger's current one
            from ..utils.heightline import registry as _hl_registry

            _hl_registry().note_verify(total, time.monotonic() - t_collect)
        if len(res) != total:
            err = RuntimeError(
                f"verifier returned {len(res)} results for {total} "
                "submitted signatures"
            )
            for r in batch:
                r.ticket._fail(err)
            return
        off = 0
        with tracing.context_scope(_batch_ctx(batch)), \
                tracing.span("verify.sched.split"):
            for r in batch:
                part = list(res[off : off + len(r.items)])
                off += len(r.items)
                # per-request verdict: the whole-batch all_ok is useless
                # once requests are coalesced — recompute from the slice
                # (matches the verifiers' own all(res) and bool(res))
                r.ticket._resolve((all(part) and bool(part), part))

    # ----------------------------------------------------------- failover

    @property
    def backend_mode(self) -> str:
        """``tpu`` | ``cpu_fallback`` (atomic str read; clients check
        this before binding comb tables)."""
        return self._backend_mode

    def _fail_or_reverify(
        self, batch: list[_Request], exc, cause: str, bv=None
    ) -> None:
        """A dispatch/submit/collect ERROR (not a hang): with failover
        enabled the batch re-verifies on host — identical verdicts, no
        mode change, the service keeps serving — instead of failing the
        callers' tickets and pushing every one of them onto their own
        inline fallback.  The re-verification is requeued onto the
        class-priority host worker, NEVER run on the caller: a big
        lower-class batch erroring at dispatch must not park the
        scheduler (or the collector's FIFO) behind seconds of
        sequential host verifies, and the single worker bounds
        concurrency while keeping consensus re-verifies ahead of
        mempool ones.  If the HOST path itself errored (``bv`` already
        a :class:`_HostBatchVerifier`) the tickets fail — requeueing
        would loop."""
        if isinstance(exc, VerifyServiceBackpressure):
            # a REMOTE plane's server-side admission control said no:
            # the same contract as a local reject — the tickets fail
            # with the backpressure (tenant/scope intact) and the
            # CALLER owns the host fallback; re-verifying here would
            # defeat the plane's admission control by doing the work
            # locally on its behalf
            for r in batch:
                r.ticket._fail(exc)
            return
        if not self.failover_enabled or isinstance(bv, _HostBatchVerifier):
            for r in batch:
                r.ticket._fail(exc)
            return
        _mhub().verify_svc_host_reverify.inc(cause=cause)
        # unchecked fill: the dispatch may have failed on add()'s own
        # shape validation (e.g. a remote batch whose items don't match
        # its key_type) — re-raising here would escape into the
        # scheduler/worker loop and wedge the plane; the cpu verifiers
        # judge malformed rows False instead
        hbv = _HostBatchVerifier(batch[0].mode)
        hbv.add_items_unchecked([it for r in batch for it in r.items])
        # (re-)track as host work; on the collect_error path the outer
        # _settle finally pops this entry while the requeue is pending —
        # a brief stats gap, settlement itself is unaffected
        self._track_inflight(batch, "host")
        self._hostq.put(
            (int(batch[0].klass), next(self._hostseq),
             (hbv, batch, tracing.stamp()))
        )

    def _reverify_batches(self, batches: list[list[_Request]]) -> None:
        """Host-verify every request of every batch, per-signature blame
        in each request's OWN add() order, resolving tickets first-wins
        (a wedged device wait completing later is discarded)."""
        for batch in batches:
            for r in batch:
                if r.ticket.done():
                    continue
                with tracing.context_scope(r.ctx), tracing.span(
                    "verify.failover.reverify",
                    {"class": r.klass.label, "sigs": len(r.items)}
                    if tracing.enabled() else None,
                ):
                    r.ticket._resolve(_host_verify_items(r.items, r.mode))

    def _failover_loop(self) -> None:
        """The failover watchdog: a dedicated thread — NEVER the
        scheduler — so a wedged scheduler/collector can't take the trip
        decision down with it, and the probation probe (deadline-
        bounded) has somewhere safe to block."""
        while self._running:
            if self._backend_mode == MODE_TPU:
                healthmon.beat("verifysvc-failover")
                reason = self._trip_reason()
                if reason is not None:
                    self._trip_to_cpu(reason)
                else:
                    self._stop_ev.wait(self.failover_tick_s)
                continue
            # ---- CPU mode: sweep stranded work every tick, probe
            # toward restoration every probe period
            self._stop_ev.wait(self.failover_tick_s)
            if not self._running:
                return
            healthmon.beat("verifysvc-failover")
            if self._backend_mode != MODE_CPU_FALLBACK:
                continue
            self._sweep_stranded()
            now = time.monotonic()
            if now < self._next_probation_probe:
                continue
            self._next_probation_probe = now + self.probe_period_s
            try:
                res = self._probe_fn(self.probe_timeout_s)
                ok = bool(res.ok)
                detail = res.detail
            except BaseException as e:  # noqa: BLE001 — a probe bug is a failed probe
                ok, detail = False, f"probe raised {type(e).__name__}: {e}"
            with self._failover_mtx:
                self._probation_consec_ok = (
                    self._probation_consec_ok + 1 if ok else 0
                )
                consec = self._probation_consec_ok
            self.logger.info(
                f"failover probation probe: ok={ok} ({detail}) "
                f"[{consec}/{self.probation_ok}]"
            )
            if consec >= self.probation_ok:
                self._restore_tpu()

    def _sweep_stranded(self) -> None:
        """Close the trip/dispatch race: the scheduler reads the mode
        (tpu) in _make_verifier BEFORE tracking the batch, so a batch
        bound to a device verifier concurrently with the trip can miss
        the stranded-batch snapshot and park the fresh collector in the
        same wedge.  In CPU mode, any tracked batch overdue on the
        device deadline is host-re-verified — first-wins settlement
        makes repeats no-ops, and its callers unblock no matter how the
        race interleaved."""
        now = time.monotonic()
        with self._inflight_mtx:
            overdue = [
                rec["batch"] for rec in self._inflight.values()
                if (self._device_age(rec, now) or 0.0) > self.batch_deadline_s
            ]
        overdue = [
            b for b in overdue if not all(r.ticket.done() for r in b)
        ]
        if not overdue:
            return
        _mhub().verify_svc_host_reverify.inc(len(overdue), cause="wedge")
        self.logger.warning(
            f"cpu-fallback sweep: {len(overdue)} batch(es) stranded past "
            "the device deadline after the trip; re-verifying on host"
        )
        # untrack BEFORE the off-thread re-verify: the parked worker's
        # own finally may never run (that is the wedge), a stale
        # ever-aging entry would re-trip the service the moment
        # probation restores, and the next tick must not re-select the
        # work this spawn is already doing.  Off-thread like the trip's
        # _recover: the watchdog must go straight back to watching (and
        # to probation probes), not serialize behind a big host verify.
        for b in overdue:
            self._untrack_inflight(b)
        threading.Thread(
            target=self._reverify_batches, args=(overdue,),
            name="verifysvc-reverify", daemon=True,
        ).start()

    def _trip_reason(self) -> str | None:
        """Why the service should trip NOW, or None.  Two signals:
        an in-flight batch stuck dispatched-to/awaiting the device past
        the batch deadline (``where`` device/collect; ``host`` is exempt,
        and so is a batch waiting for its program to compile — a
        first-shape XLA compile is legitimate minutes-long work), or the
        health sentinel judging the accelerator wedged."""
        now = time.monotonic()
        with self._inflight_mtx:
            worst = max(
                (
                    self._device_age(rec, now) or 0.0
                    for rec in self._inflight.values()
                ),
                default=0.0,
            )
        if worst > self.batch_deadline_s:
            return (
                f"in-flight batch {worst:.1f}s past the "
                f"{self.batch_deadline_s:g}s device deadline"
            )
        mon = healthmon.monitor()
        if mon is not None and mon.state == healthmon.STATE_WEDGED:
            # ignore a wedged verdict the sentinel formed BEFORE our
            # probation restored: the sentinel probes far less often
            # (60s default vs probation's 15s), and its stale state
            # would re-trip a just-restored service every watchdog tick
            # until its next probe — duplicate artifacts and to_cpu
            # events for one incident.  Once it re-probes and still
            # says wedged, the trip is legitimate.
            probe_at = getattr(mon, "last_probe_at", None)
            if (
                self._last_restore_at is None
                or probe_at is None
                or probe_at > self._last_restore_at
            ):
                return "health sentinel reports accelerator wedged"
        return None

    def trip_to_cpu(self, reason: str) -> bool:
        """Public trip entry (operators; tests).  Returns False when
        already tripped."""
        return self._trip_to_cpu(reason)

    def _trip_to_cpu(self, reason: str) -> bool:
        with self._failover_mtx:
            if self._backend_mode == MODE_CPU_FALLBACK:
                return False
            self._backend_mode = MODE_CPU_FALLBACK
            self._trips += 1
            self._probation_consec_ok = 0
            self._last_trip_reason = reason
            self._next_probation_probe = time.monotonic() + self.probe_period_s
            self._gen += 1
            gen = self._gen
        with self._inflight_mtx:
            stranded = [rec["batch"] for rec in self._inflight.values()]
        stranded_sigs = sum(
            len(r.items) for batch in stranded for r in batch
        )
        m = _mhub()
        m.verify_svc_backend_mode.set(_MODE_CODE[MODE_CPU_FALLBACK])
        m.verify_svc_failover.inc(direction="to_cpu")
        m.verify_svc_host_reverify.inc(len(stranded), cause="wedge")
        _flightrec().record(
            "verifysvc_failover",
            direction="to_cpu",
            reason=reason,
            stranded_batches=len(stranded),
            stranded_sigs=stranded_sigs,
        )
        tracing.instant(
            "verify.failover",
            {"direction": "to_cpu", "stranded": len(stranded)}
            if tracing.enabled() else None,
        )
        self.logger.error(
            f"verify service TRIPPED to CPU fallback: {reason} "
            f"({len(stranded)} in-flight batches / {stranded_sigs} sigs "
            "re-verifying on host)"
        )
        # a pre-trip stats snapshot (in-flight ages still visible) for
        # the forensics artifact, taken before re-verification resolves
        # and untracks the stranded entries
        snapshot = self.stats(lock_timeout=0.5)
        # fresh workers: the old generation may be parked inside the
        # wedged wait forever (that is the failure being survived)
        workers = self._spawn_workers(gen)
        self._threads = [
            t for t in self._threads
            if t.name not in ("verifysvc-collect", "verifysvc-host")
        ] + workers
        # re-verify stranded work off-thread: the watchdog must go
        # straight back to watching, and forensics IO must not delay
        # the re-verification that restores consensus liveness
        def _recover():
            # untrack FIRST: the stranded batches are already past the
            # device deadline, and leaving them in the table would make
            # the watchdog's very next sweep re-select them — double
            # counting and re-verifying work this thread is about to do
            # (the forensics snapshot above already preserved them)
            for batch in stranded:
                self._untrack_inflight(batch)
            self._reverify_batches(stranded)
            path = self._capture_failover_forensics(reason, snapshot)
            with self._failover_mtx:
                self._last_artifact = path

        threading.Thread(
            target=_recover, name="verifysvc-reverify", daemon=True
        ).start()
        return True

    def _restore_tpu(self) -> None:
        with self._failover_mtx:
            if self._backend_mode != MODE_CPU_FALLBACK:
                return
            self._backend_mode = MODE_TPU
            self._restores += 1
            self._probation_consec_ok = 0
            self._last_restore_at = time.monotonic()
        m = _mhub()
        m.verify_svc_backend_mode.set(_MODE_CODE[MODE_TPU])
        m.verify_svc_failover.inc(direction="to_tpu")
        _flightrec().record("verifysvc_failover", direction="to_tpu")
        tracing.instant(
            "verify.failover",
            {"direction": "to_tpu"} if tracing.enabled() else None,
        )
        self.logger.warning(
            "verify service restored to TPU mode "
            f"({self.probation_ok} consecutive probation probes ok)"
        )

    def _capture_failover_forensics(self, reason: str, snapshot: dict) -> str | None:
        """ONE diagnosis artifact per trip (debugdump.stall_report:
        verifysvc stats with the stranded in-flight ages, flight
        recorder, all-thread stacks).  Must never raise — it runs while
        the node is already degraded."""
        import json as _json

        from ..utils import debugdump

        try:
            sections = [
                ("verify service (at trip)",
                 _json.dumps(snapshot, indent=1, default=str)),
            ]
            if tracing.enabled():
                events = tracing.chrome_trace_events()[-256:]
                sections.append(
                    ("trace ring (newest 256)",
                     _json.dumps(events, default=str))
                )
            path = debugdump.stall_report(
                f"verify-service failover to cpu_fallback: {reason}",
                sections,
                directory=self.artifact_dir,
            )
            _mhub().health_forensics.inc()
            self.logger.warning(f"failover forensics written to {path}")
            return path
        except Exception as e:  # noqa: BLE001 — forensics must never hurt the node
            self.logger.warning(f"failover forensics capture failed: {e!r}")
            return None

    # ------------------------------------------------------------- status

    def stats(self, lock_timeout: float | None = None) -> dict:
        """Snapshot for the /verify_svc_status RPC, bench reporting, and
        the health sentinel's stall forensics.  ``lock_timeout`` bounds
        the wait for the scheduler lock (the sentinel passes a small
        value: a diagnosis of a wedged node must not block on the wedge
        it is diagnosing); on timeout the queue section reads
        ``lock_busy`` and the lock-free tallies still report."""
        now = time.monotonic()
        with self._inflight_mtx:
            in_flight = [
                {
                    "class": rec["class"],
                    "tenant": rec.get("tenant", DEFAULT_TENANT),
                    "sigs": rec["sigs"],
                    "requests": rec["requests"],
                    "where": rec["where"],
                    "age_s": round(now - rec["since"], 3),
                    # the failover deadline's clock (None while off it:
                    # host-worker submit, or waiting for a compile)
                    "device_age_s": (
                        None if (age := self._device_age(rec, now)) is None
                        else round(age, 3)
                    ),
                }
                for rec in self._inflight.values()
            ]
        if lock_timeout is None:
            acquired = self._cond.acquire()
        else:
            acquired = self._cond.acquire(timeout=lock_timeout)
        if acquired:
            try:
                queued = {
                    k.label: {
                        "requests": sum(
                            len(q) for q in self._queues[k].values()
                        ),
                        "sigs": self._class_sigs[k],
                        "by_tenant": {
                            t: n for t, n in self._queued_sigs[k].items()
                        },
                    }
                    for k in Klass
                }
                dispatched = dict(self._dispatched)
                rejected = dict(self._rejected)
            finally:
                self._cond.release()
        else:
            queued = {"lock_busy": True}
            dispatched = dict(self._dispatched)
            rejected = dict(self._rejected)
        with self._tally_mtx:
            tenants = {t: dict(v) for t, v in self._tenant_tallies.items()}
        rem = self._remote  # one read: stop() nulls it concurrently
        remote = rem.stats() if rem is not None else None
        with self._failover_mtx:
            failover = {
                "enabled": self.failover_enabled,
                "backend_mode": self._backend_mode,
                "trips": self._trips,
                "restores": self._restores,
                "probation_consec_ok": self._probation_consec_ok,
                "probation_ok_needed": self.probation_ok,
                "batch_deadline_ms": self.batch_deadline_s * 1e3,
                "last_trip_reason": self._last_trip_reason,
                "last_artifact": self._last_artifact,
            }
        return {
            "in_flight": in_flight,
            "running": self._running,
            "backend_mode": failover["backend_mode"],
            "failover": failover,
            "remote": remote,
            "batch_max": self.batch_max,
            "queue_max": self.queue_max,
            "tenant_quota": self.tenant_quota,
            "tenant_weights": dict(self._tenant_weights),
            "deadline_ms": {
                k.label: self._deadline_s[k] * 1e3 for k in Klass
            },
            "weights": {k.label: w for k, w in self._weights.items()},
            "queued": queued,
            "dispatched_batches": dispatched,
            "rejected": rejected,
            "tenants": tenants,
        }


# ---- client-side collect-stall forensics (the bounded Ticket.collect
# contract): rate-limit the heavyweight artifact so a storm of timed-out
# callers produces ONE report per window, not one per caller
_STALL_MTX = threading.Lock()
_LAST_STALL_REPORT = 0.0
_STALL_REPORT_MIN_INTERVAL_S = 60.0


def _reset_stall_gate() -> None:
    """Tests only: re-arm the stall-report rate limiter."""
    global _LAST_STALL_REPORT
    with _STALL_MTX:
        _LAST_STALL_REPORT = 0.0


def report_collect_stall(
    klass: Klass,
    tenant: str,
    nsigs: int,
    waited_s: float,
    service: "VerifyService | None" = None,
    artifact_dir: str | None = None,
) -> str | None:
    """A client's bounded Ticket.collect() expired: the scheduler is
    alive enough to accept submits but did not resolve this ticket in
    time.  Count it, flight-record it, and (rate-limited) write a stall
    forensics artifact naming the stuck class/tenant with the service's
    own view of its queues and in-flight ages — the caller then degrades
    to an inline host verification instead of parking forever.  Returns
    the artifact path, or None when rate-limited/failed."""
    m = _mhub()
    m.verify_svc_collect_timeout.inc(**{"class": klass.label})
    _flightrec().record(
        "verifysvc_collect_stall",
        klass=klass.label, tenant=tenant, sigs=nsigs,
        waited_s=round(waited_s, 3),
    )
    tracing.instant(
        "verify.collect_stall",
        {"class": klass.label, "tenant": tenant, "sigs": nsigs}
        if tracing.enabled() else None,
    )
    global _LAST_STALL_REPORT
    now = time.monotonic()
    with _STALL_MTX:
        if now - _LAST_STALL_REPORT < _STALL_REPORT_MIN_INTERVAL_S:
            return None
        _LAST_STALL_REPORT = now
    import json as _json

    from ..utils import debugdump

    svc = service if service is not None else _GLOBAL
    sections = []
    if svc is not None:
        # bounded lock wait: the stats of a stuck scheduler must not
        # park the very diagnosis of its stall
        sections.append(
            ("verify service (at stall)",
             _json.dumps(svc.stats(lock_timeout=0.5), indent=1, default=str))
        )
    try:
        path = debugdump.stall_report(
            f"verify-service collect() deadline expired: class="
            f"{klass.label} tenant={tenant} sigs={nsigs} after "
            f"{waited_s:.1f}s (caller degrading to inline host verify)",
            sections,
            directory=artifact_dir,
        )
        m.health_forensics.inc()
        get_logger("verifysvc").error(
            f"collect stall forensics written to {path}"
        )
        return path
    except Exception as e:  # noqa: BLE001 — forensics must never hurt the caller
        get_logger("verifysvc").warning(
            f"collect stall forensics capture failed: {e!r}"
        )
        return None


_GLOBAL: VerifyService | None = None
_GLOBAL_MTX = threading.Lock()


def global_service() -> VerifyService:
    """The process-wide service every production consumer shares — one
    scheduler means one priority order across subsystems."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_MTX:
            if _GLOBAL is None:
                _GLOBAL = VerifyService()
    return _GLOBAL


def reset_global_service() -> None:
    """Stop and drop the global service (tests re-reading knobs)."""
    global _GLOBAL
    with _GLOBAL_MTX:
        svc, _GLOBAL = _GLOBAL, None
    if svc is not None:
        svc.stop()
