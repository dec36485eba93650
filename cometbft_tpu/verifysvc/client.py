"""Client-side adapters for the verify service.

:class:`ServiceBatchVerifier` implements the BatchVerifier contract
(crypto/crypto.go:47-55) — add() accumulates, verify()/submit()/collect()
resolve — but routes the batch through the process-global
:class:`~cometbft_tpu.verifysvc.service.VerifyService` instead of driving
a device verifier directly.  crypto/batch.create_batch_verifier returns
one of these whenever the device backend is selectable, so every legacy
call site (types/validation, blocksync, light, evidence) became a verify
-service client without changing its own shape.

Backpressure handling lives here, on the caller's side of the seam: a
rejected submit degrades to an inline host verification
(`verify.svc_fallback` span) — correct results, no device batching, and
the rejection is already counted/flight-recorded by the service.
"""

from __future__ import annotations

import time

from ..utils import tracing
from ..utils.metrics import hub as _metrics_hub
from .service import (
    MODE_BLS,
    MODE_PLAIN,
    MODE_SECP,
    Klass,
    VerifyService,
    VerifyServiceBackpressure,
    collect_timeout_s,
    default_tenant,
    global_service,
    report_collect_stall,
)


def resolve_mode(pubkeys: list[bytes] | None, key_type: str = "ed25519"):
    """Bind a request to its device program up front, in the CALLER's
    thread — exactly where the comb-table ensure()/ensure_async() cost
    landed before the service existed (a 10k-validator table build must
    never run on, and block, the shared scheduler thread).

    Mirrors the pre-service routing of crypto/batch.create_batch_verifier:
    BLS validator sets take the aggregate lane (MODE_BLS — no comb
    tables; the BLS plane owns its own pubkey-validation cache), secp
    sets (both the Cosmos and Ethereum wire formats) the batched ECDSA
    lane (MODE_SECP — the Shamir G table is a process-resident
    device_put constant, nothing to bind per set), known ed25519 sets
    of crypto/batch.comb_min() keys or more use the comb-cached program
    (tables built here at first sight; from comb_async_min() up in the
    background, uncached while warming), everything else (no set named,
    or a set narrower than the device serves) the uncached kernel."""
    if key_type == "bls12_381":
        return MODE_BLS
    if key_type in ("secp256k1", "secp256k1eth", "ecrecover"):
        return MODE_SECP
    if pubkeys is None:
        return MODE_PLAIN
    from .service import _GLOBAL, remote_plane_configured

    if remote_plane_configured():
        # a remote-bound process must not build a local table it will
        # never use — checked against the ENV, not just the installed
        # service's binding: a service constructed before the knob was
        # set would otherwise kick a background table build (minutes of
        # compile) for a plane that owns its own device-resident tables
        return MODE_PLAIN
    if _GLOBAL is not None:
        if _GLOBAL.backend_mode != "tpu" or _GLOBAL.remote_addr:
            # degraded mode: comb table binds are bypassed entirely — an
            # ensure()/ensure_async() is DEVICE work (table build + H2D),
            # exactly the hang the failover trip escaped.  Same with a
            # remote plane configured: device-resident tables belong to
            # the PLANE's process, not this one.  Peek the module
            # global, never global_service(): resolving a mode must not
            # construct and install a fresh scheduler.
            return MODE_PLAIN
    from ..crypto import batch as crypto_batch

    if len(pubkeys) < crypto_batch.comb_min():
        return MODE_PLAIN
    from ..models.comb_verifier import active_mesh, global_cache, lane_count

    if len(pubkeys) >= crypto_batch.comb_async_min():
        entry = global_cache().ensure_async(list(pubkeys))
        if entry is None:
            _metrics_hub().comb_warming.inc(
                lanes=str(lane_count(len(pubkeys), active_mesh())))
            return MODE_PLAIN  # tables still warming: uncached kernel
        return ("comb", entry)
    return ("comb", global_cache().ensure(list(pubkeys)))


# What add() holds a row to, by the mode's lane: the name in the refusal,
# the pubkey and signature lengths, and the bound on the message.
_ED25519_ROWS = (
    "ed25519", frozenset({32}), frozenset({64}),
    1 << 24,  # the comb payload's mlen field is 3 bytes (models/comb_verifier)
)
_ROW_RULES = {
    # 48-byte compressed G1 pubkey, 96-byte compressed G2 sig
    "bls": ("bls12-381", frozenset({48}), frozenset({96}), None),
    # 33-byte compressed (cosmos, 64-byte r||s), 65-byte uncompressed
    # (eth, 65-byte R||S||V), or 20-byte sender address (ecrecover,
    # 65-byte R||S||V) wire shapes
    "secp": ("secp256k1", frozenset({20, 33, 65}), frozenset({64, 65}), None),
}


class ServiceBatchVerifier:
    """BatchVerifier bound to a priority class of the verify service.

    Exposes the same async submit()/collect() seam as the device
    verifiers it replaced, so pipelined callers (blocksync verify-ahead,
    types/validation.submit_verify_commit_light) work unchanged."""

    def __init__(
        self,
        klass: Klass = Klass.CONSENSUS,
        mode=MODE_PLAIN,
        service: VerifyService | None = None,
        tenant: str | None = None,
    ):
        self._klass = klass
        self._mode = mode
        self._svc = service
        self._tenant = tenant if tenant is not None else default_tenant()
        self._items: list[tuple[bytes, bytes, bytes]] = []
        # this batch's span context, minted at submit(): the service
        # request inherits it (and carries it to a remote plane), and
        # the host-fallback / collect-stall paths re-install it so a
        # degraded batch's spans still share one trace_id
        self._ctx = None

    def __len__(self) -> int:
        return len(self._items)

    @property
    def klass(self) -> Klass:
        return self._klass

    @property
    def tenant(self) -> str:
        return self._tenant

    def add(self, pub_key: bytes, msg: bytes, sig: bytes) -> None:
        name, pub_lens, sig_lens, msg_bound = _ROW_RULES.get(
            self._mode[0], _ED25519_ROWS
        )
        if len(pub_key) not in pub_lens or len(sig) not in sig_lens:
            raise ValueError(f"malformed {name} pubkey or signature")
        if msg_bound is not None and len(msg) >= msg_bound:
            # raise at add() time like CombBatchVerifier did, not as a
            # deferred dispatch failure
            raise ValueError("message too large for batch verification")
        self._items.append((pub_key, msg, sig))

    def add_many(
        self, pub_keys: list[bytes], msgs: list[bytes], sigs: list[bytes]
    ) -> None:
        """add() for a whole batch handed over as three columns of one
        length: the same checks, the rows kept in the columns' order,
        and a ValueError for the first row add() would have refused
        (the rows before it are kept, as after that many add() calls)."""
        _, pub_lens, sig_lens, msg_bound = _ROW_RULES.get(
            self._mode[0], _ED25519_ROWS
        )
        rows = zip(pub_keys, msgs, sigs, strict=True)
        if (
            set(map(len, pub_keys)) <= pub_lens
            and set(map(len, sigs)) <= sig_lens
            and (msg_bound is None
                 or max(map(len, msgs), default=0) < msg_bound)
        ):
            self._items.extend(rows)
            return
        for row in rows:  # some row is malformed: find it as add() does
            self.add(*row)

    def _service(self) -> VerifyService:
        if self._svc is None:
            self._svc = global_service()
        return self._svc

    def _host_fallback(self, span_name: str) -> tuple[bool, list[bool]]:
        """Inline host verification of OUR retained items — correct
        verdicts in our own add() order, shared by the backpressure and
        collect-stall paths.  Mode-aware: a BLS batch degrades to the
        pure-host BLS verifier (bit-identical verdict procedure), never
        the ed25519 one."""
        from .service import cpu_verifier_for_mode

        cpu = cpu_verifier_for_mode(self._mode)
        cpu._items = list(self._items)
        with tracing.context_scope(self._ctx), tracing.span(
            span_name,
            {"class": self._klass.label, "sigs": len(cpu._items)}
            if tracing.enabled() else None,
        ):
            return cpu.verify()

    def submit(self):
        """Enqueue with the service and return an opaque ticket for
        collect().  On backpressure the batch is verified inline on the
        host — the caller-side fallback of the admission-control loop."""
        if not self._items:
            return ("sync", (False, []))
        if tracing.propagation_enabled() and self._ctx is None:
            # root of this batch's trace — unless the caller already
            # installed one (e.g. an RPC-served verify), which we join
            self._ctx = tracing.current_context() or tracing.new_context()
        try:
            with tracing.context_scope(self._ctx), \
                    tracing.span("verify.svc.submit"):
                return ("svc", self._service().submit(
                    list(self._items), self._klass, self._mode,
                    tenant=self._tenant,
                ))
        except VerifyServiceBackpressure:
            return ("sync", self._host_fallback("verify.svc_fallback"))

    def collect(self, ticket) -> tuple[bool, list[bool]]:
        kind, payload = ticket
        if kind == "sync":
            return payload
        # bounded wait: a live-but-stuck scheduler (accepted the submit,
        # never resolved the ticket) must not park a consensus or
        # blocksync caller forever.  (A first-shape compile of the
        # batch's program is work and is not charged, up to a bound:
        # Ticket.compiling.)  On expiry: stall forensics, then the
        # host fallback — first-wins ticket settlement discards the
        # service's late answer if it ever comes.
        timeout = collect_timeout_s()
        t0 = time.monotonic()
        try:
            result = payload.collect(timeout)
        except VerifyServiceBackpressure:
            # a REMOTE plane's server-side quota rejected the batch
            # after local admission (the reject rides the response and
            # fails the ticket): same contract as a local reject —
            # verify inline on host; the service never does it for us
            return self._host_fallback("verify.svc_fallback")
        except TimeoutError:
            report_collect_stall(
                self._klass, self._tenant, len(self._items),
                time.monotonic() - t0, service=self._svc,
            )
            return self._host_fallback("verify.collect_stall_fallback")
        if payload.resolved_ns:
            # from the collector's resolution to this thread running again
            with tracing.context_scope(self._ctx):
                tracing.interval("verify.svc.wake", payload.resolved_ns)
        return result

    def verify(self) -> tuple[bool, list[bool]]:
        return self.collect(self.submit())
