"""verifyd — the out-of-process verify plane's server half.

One :class:`VerifyServer` hosts one :class:`~.service.VerifyService`
behind the varint-delimited protobuf surface of ``verifysvc/wire.py``
(`scripts/verifyd.py` is the process entry point, equivalent to
``python -m cometbft_tpu.verifysvc.server``).  Remote submitters are
scheduled exactly like local ones: requests carry (tenant, class), so
the service's strict class priority, weighted-fair tenant interleave,
and per-(tenant, class) quotas are enforced **server-side** — a rogue
node flooding the shared plane is backpressured at the plane, and the
rejection (with the tenant/scope that bit) crosses the wire back to it.

Crash-tolerance contract (the client half is ``verifysvc/remote.py``):

  * **Deadline propagation** — requests carry their REMAINING budget in
    ms (never a wall-clock deadline: clock skew must not stretch or
    strangle a request).  The server derives its own absolute deadline
    at decode time; a request whose budget is already spent — or whose
    verification outlives it — answers ``STATUS_DEADLINE`` instead of
    parking the connection.
  * **Idempotent retry / dedup window** — every request carries
    (request_id UUID, batch digest).  The server remembers the pair →
    response for ``COMETBFT_TPU_VERIFYRPC_DEDUP_WINDOW_S``; a retried
    batch (the client resends after a connection death it cannot
    distinguish from a server death) is answered from the window, and a
    retry racing the ORIGINAL verification attaches to the in-flight
    ticket instead of re-submitting — the same batch is never verified
    twice into a different blame order.  Same id with a different
    digest is a protocol violation (``STATUS_BAD_REQUEST``).
  * **Liveness vs readiness** — ping answers whenever the socket is
    alive (liveness: don't reap the process); status reports the
    scheduler's own stats incl. ``running`` (readiness: route traffic).

Fault seams (utils/fail, armed via ``COMETBFT_TPU_FAULT_*`` env at
verifyd start or over the wire when ``COMETBFT_TPU_FAULT_RPC=1``):
``plane_crash`` / ``plane_stall`` fire on the Nth verify request —
SIGKILL/SIGSTOP with that exact batch in flight — and ``rpc_delay_ms``
/ ``rpc_drop_pct`` shape the response path at the socket.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

from ..utils import envknobs, fail, tracing
from ..utils.log import get_logger
from ..utils.netutil import close_socket
from . import wire
from .service import (
    MODE_PROOF,
    Klass,
    VerifyService,
    VerifyServiceBackpressure,
    mode_for_key_type,
)

_READY_PREFIX = "VERIFYD READY addr="


class _DedupWindow:
    """(request_id -> digest, pending-event, response) with TTL + size
    bounds.  ``begin`` registers or joins; ``finish`` publishes."""

    def __init__(self, ttl_s: float, max_entries: int = 8192):
        self.ttl_s = max(1.0, ttl_s)
        self.max_entries = max_entries
        self._mtx = threading.Lock()
        self._entries: dict[bytes, dict] = {}

    def begin(self, rid: bytes, digest: bytes):
        """Returns ("new", entry) for a first-seen id (caller must
        finish() or abort()), ("dup", entry) for a retry (wait its event,
        read its response), or ("mismatch", None) when the id is reused
        with different content."""
        now = time.monotonic()
        with self._mtx:
            self._prune_locked(now)
            e = self._entries.get(rid)
            if e is not None:
                if e["digest"] != digest:
                    return "mismatch", None
                return "dup", e
            e = {
                "digest": digest,
                "event": threading.Event(),
                "response": None,
                "ts": now,
            }
            self._entries[rid] = e
            return "new", e

    def finish(self, rid: bytes, response) -> None:
        with self._mtx:
            e = self._entries.get(rid)
            if e is None:
                return
            e["response"] = response
            e["ts"] = time.monotonic()
        e["event"].set()

    def abort(self, rid: bytes) -> None:
        """Drop a pending entry whose verification never produced a
        cacheable answer (so a later retry gets a fresh run)."""
        with self._mtx:
            e = self._entries.pop(rid, None)
        if e is not None:
            e["event"].set()

    def _prune_locked(self, now: float) -> None:
        if len(self._entries) <= self.max_entries:
            stale = [
                rid for rid, e in self._entries.items()
                if e["response"] is not None and now - e["ts"] > self.ttl_s
            ]
        else:
            # over the size bound: shed oldest finished entries first
            finished = sorted(
                (
                    (e["ts"], rid) for rid, e in self._entries.items()
                    if e["response"] is not None
                ),
            )
            stale = [rid for _ts, rid in finished[: len(self._entries) // 2]]
        for rid in stale:
            del self._entries[rid]

    def __len__(self) -> int:
        with self._mtx:
            return len(self._entries)


class VerifyServer:
    """The verifyd listener: accept loop + per-connection reader
    threads; each verify request is handled on its own worker thread so
    one long verification never head-of-line-blocks a connection's
    later (possibly higher-class) requests — the service's scheduler,
    not socket order, decides priority."""

    def __init__(
        self,
        addr: str = "127.0.0.1:0",
        service: VerifyService | None = None,
        dedup_window_s: float | None = None,
        idle_timeout_s: float = 1.0,
        max_inflight_requests: int = 256,
    ):
        host, _, port = addr.rpartition(":")
        self._host = host or "127.0.0.1"
        self._port = int(port)
        # remote_addr pinned EMPTY for the default service: the plane IS
        # the remote end — inheriting COMETBFT_TPU_VERIFYRPC_ADDR from
        # the operator's environment would forward every batch back over
        # the wire (to itself, typically), each hop under a fresh
        # request_id so the dedup window never breaks the loop
        self.svc = (
            service if service is not None else VerifyService(remote_addr="")
        )
        self.dedup = _DedupWindow(
            dedup_window_s if dedup_window_s is not None
            else float(envknobs.get_int(envknobs.VERIFYRPC_DEDUP_WINDOW_S))
        )
        self.idle_timeout_s = idle_timeout_s
        # one worker THREAD per verify request (so the scheduler, not
        # socket order, decides priority) — but bounded: the signature
        # quota admits outstanding sigs, not request COUNT, so without
        # this cap a flood of tiny requests (or dup-retries parked in
        # the dedup window's wait) could exhaust plane threads before
        # admission control ever runs.  Over the cap answers
        # STATUS_BACKPRESSURE scope="server" immediately.
        self._req_sem = threading.BoundedSemaphore(
            max(1, max_inflight_requests)
        )
        self.logger = get_logger("verifyd")
        self._listener: socket.socket | None = None
        self._stopped = threading.Event()
        self._conns: list[socket.socket] = []
        self._conns_mtx = threading.Lock()
        self._stats_mtx = threading.Lock()
        self._requests = 0
        self._deduped = 0
        self._rejected = 0
        self._errors = 0
        self._started_unix = 0.0

    # ---------------------------------------------------------- lifecycle

    @property
    def addr(self) -> str:
        return f"{self._host}:{self._port}"

    def start(self) -> None:
        self._listener = socket.create_server((self._host, self._port))
        # accept with a poll timeout: stop() flips the event and the
        # loop exits within one tick — no blocking-accept teardown race
        self._listener.settimeout(0.5)
        self._port = self._listener.getsockname()[1]
        self._started_unix = time.time()
        threading.Thread(
            target=self._accept_loop, name="verifyd-accept", daemon=True
        ).start()
        self.logger.info(f"verifyd serving on {self.addr}")

    def stop(self) -> None:
        self._stopped.set()
        close_socket(self._listener)
        with self._conns_mtx:
            conns, self._conns = self._conns, []
        for c in conns:
            close_socket(c)
        self.svc.stop()

    # ------------------------------------------------------------- serving

    def _accept_loop(self) -> None:
        while not self._stopped.is_set():
            try:
                conn, peer = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            conn.settimeout(self.idle_timeout_s)
            with self._conns_mtx:
                self._conns.append(conn)
            threading.Thread(
                target=self._serve_conn, args=(conn, peer),
                name=f"verifyd-conn-{peer[1]}", daemon=True,
            ).start()

    def _serve_conn(self, conn: socket.socket, peer) -> None:
        reader = wire.FrameReader(conn)
        wmtx = threading.Lock()  # response writes interleave across workers
        try:
            while not self._stopped.is_set():
                try:
                    msg = reader.read()
                except socket.timeout:
                    continue  # idle poll: re-check the stop flag
                if msg is None:
                    return  # clean EOF
                self._dispatch(msg, conn, wmtx)
        except (OSError, ValueError) as e:
            # conn death mid-frame or a desynced stream: drop the conn,
            # the client's reconnect/retry machinery owns recovery
            if not self._stopped.is_set():
                self.logger.info(f"verifyd conn {peer} dropped: {e!r}")
        finally:
            close_socket(conn)
            with self._conns_mtx:
                try:
                    self._conns.remove(conn)
                except ValueError:
                    self.logger.debug(
                        f"verifyd conn {peer} already removed at teardown"
                    )

    def _dispatch(self, msg: wire.PlaneMessage, conn, wmtx) -> None:
        which = msg.which()
        if which == "verify_request":
            # own worker per request: the service's scheduler decides
            # order, not the socket — and a plane_stall/crash seam firing
            # in a worker can never desync this connection's reader
            req = msg.verify_request
            if not self._req_sem.acquire(blocking=False):
                with self._stats_mtx:
                    self._rejected += 1
                self._send(conn, wmtx, wire.PlaneMessage(
                    verify_response=wire.VerifyResponse(
                        request_id=req.request_id,
                        status=wire.STATUS_BACKPRESSURE,
                        error="plane at max in-flight requests",
                        scope="server",
                    )
                ))
                return
            threading.Thread(
                target=self._handle_verify_guarded, args=(req, conn, wmtx),
                name="verifyd-req", daemon=True,
            ).start()
        elif which == "proof_request":
            # same worker-per-request + inflight-cap shape as verify:
            # proof batches are scheduled by the service (PROOF class),
            # never by socket order
            req = msg.proof_request
            if not self._req_sem.acquire(blocking=False):
                with self._stats_mtx:
                    self._rejected += 1
                self._send(conn, wmtx, wire.PlaneMessage(
                    proof_response=wire.ProofResponse(
                        request_id=req.request_id,
                        status=wire.STATUS_BACKPRESSURE,
                        error="plane at max in-flight requests",
                        scope="server",
                    )
                ))
                return
            threading.Thread(
                target=self._handle_proof_guarded, args=(req, conn, wmtx),
                name="verifyd-proof", daemon=True,
            ).start()
        elif which == "ping_request":
            self._send(
                conn, wmtx,
                wire.PlaneMessage(ping_response=wire.PingResponse()),
            )
        elif which == "status_request":
            self._send(
                conn, wmtx,
                wire.PlaneMessage(
                    status_response=wire.StatusResponse(
                        json=json.dumps(self.stats(), default=str)
                    )
                ),
            )
        elif which == "arm_fault_request":
            self._handle_arm(msg.arm_fault_request, conn, wmtx)
        else:
            self.logger.warning(f"verifyd: unsupported message {which!r}")

    def _handle_arm(self, req: wire.ArmFaultRequest, conn, wmtx) -> None:
        resp = wire.ArmFaultResponse(ok=True)
        if not envknobs.get_bool(envknobs.FAULT_RPC):
            resp = wire.ArmFaultResponse(
                ok=False,
                error="fault injection disabled: set COMETBFT_TPU_FAULT_RPC=1",
            )
        else:
            try:
                if req.clear:
                    fail.clear(req.name) if req.name else fail.clear_all()
                else:
                    fail.arm(req.name, req.value if req.value else 1.0)
                self.logger.warning(
                    f"verifyd fault {'cleared' if req.clear else 'armed'} "
                    f"over the wire: {req.name or 'ALL'}={req.value}"
                )
            except ValueError as e:
                resp = wire.ArmFaultResponse(ok=False, error=str(e))
        self._send(conn, wmtx, wire.PlaneMessage(arm_fault_response=resp))

    def _handle_verify_guarded(self, req: wire.VerifyRequest, conn, wmtx) -> None:
        try:
            self._handle_verify(req, conn, wmtx)
        finally:
            self._req_sem.release()

    def _handle_verify(self, req: wire.VerifyRequest, conn, wmtx) -> None:
        deadline = time.monotonic() + max(0, req.budget_ms) / 1e3
        with self._stats_mtx:
            self._requests += 1
        # chaos seams: the Nth request crashes/stalls the plane with THIS
        # batch in flight — consume() counts down; the final shot fires
        for name, sig in (("plane_crash", signal.SIGKILL),
                          ("plane_stall", signal.SIGSTOP)):
            shots = fail.consume(name)
            if shots is not None and shots <= 1.0:
                self.logger.error(
                    f"verifyd: injected {name} firing (rid="
                    f"{req.request_id.hex()[:12]})"
                )
                os.kill(os.getpid(), sig)
        # adopt the client's span context (a CHILD of it: same trace_id,
        # fresh hop id) so this worker's spans — and the service spans
        # under the submit below — join the submitter's trace across the
        # process boundary; an absent/malformed context serves unlinked
        ctx = None
        if req.trace_ctx and tracing.propagation_enabled():
            parent = tracing.SpanContext.from_traceparent(req.trace_ctx)
            if parent is not None:
                ctx = parent.child()
        with tracing.context_scope(ctx), tracing.span(
            "verify.rpc.serve",
            {"sigs": len(req.items), "attempt": req.attempt,
             "key_type": req.key_type or "ed25519"}
            if tracing.enabled() else None,
        ):
            resp = self._verify_response(req, deadline)
        if resp is None:
            return
        # socket-level response shaping (delay / drop seams)
        d = fail.armed("rpc_delay_ms")
        if d:
            fail.jittered_sleep(d)
        pct = fail.armed("rpc_drop_pct")
        if pct is not None and fail.should_drop(pct):
            self.logger.warning(
                f"verifyd: injected response drop (rid="
                f"{req.request_id.hex()[:12]})"
            )
            return
        self._send(conn, wmtx, wire.PlaneMessage(verify_response=resp))

    def _verify_response(
        self, req: wire.VerifyRequest, deadline: float
    ) -> wire.VerifyResponse | None:
        rid = req.request_id
        if not rid or not req.digest:
            return wire.VerifyResponse(
                request_id=rid, status=wire.STATUS_BAD_REQUEST,
                error="missing request_id/digest",
            )
        items = [(it.pub, it.msg, it.sig) for it in req.items]
        if wire.batch_digest(items) != req.digest:
            return wire.VerifyResponse(
                request_id=rid, status=wire.STATUS_BAD_REQUEST,
                error="digest does not match items",
            )
        state, entry = self.dedup.begin(rid, req.digest)
        if state == "mismatch":
            return wire.VerifyResponse(
                request_id=rid, status=wire.STATUS_BAD_REQUEST,
                error="request_id reused with a different batch digest",
            )
        if state == "dup":
            # idempotent retry: never re-verify — attach to the original
            # (possibly still in flight) and answer its exact response
            with self._stats_mtx:
                self._deduped += 1
            if not entry["event"].wait(max(0.0, deadline - time.monotonic())):
                return wire.VerifyResponse(
                    request_id=rid, status=wire.STATUS_DEADLINE,
                    error="original verification still in flight",
                )
            cached = entry["response"]
            if cached is None:
                # the original aborted without a cacheable answer
                return wire.VerifyResponse(
                    request_id=rid, status=wire.STATUS_ERROR,
                    error="original verification aborted", deduped=True,
                )
            return wire.VerifyResponse(
                request_id=rid, status=cached.status, all_ok=cached.all_ok,
                verdicts=list(cached.verdicts), error=cached.error,
                scope=cached.scope, deduped=True,
            )
        # first sight: run it
        try:
            klass = Klass(req.klass)
        except ValueError:
            self.dedup.abort(rid)
            return wire.VerifyResponse(
                request_id=rid, status=wire.STATUS_BAD_REQUEST,
                error=f"unknown class {req.klass}",
            )
        mode = mode_for_key_type(req.key_type or "")
        if mode is None:
            # an unknown key type must never fall through to a default
            # verifier — the verdicts would be garbage with OK status
            self.dedup.abort(rid)
            return wire.VerifyResponse(
                request_id=rid, status=wire.STATUS_BAD_REQUEST,
                error=f"unknown key_type {req.key_type!r}",
            )
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            self.dedup.abort(rid)  # a retry with fresh budget may run
            return wire.VerifyResponse(
                request_id=rid, status=wire.STATUS_DEADLINE,
                error="budget exhausted on arrival",
            )
        try:
            ticket = self.svc.submit(
                items, klass, mode, tenant=req.tenant or None
            )
        except VerifyServiceBackpressure as e:
            with self._stats_mtx:
                self._rejected += 1
            resp = wire.VerifyResponse(
                request_id=rid, status=wire.STATUS_BACKPRESSURE,
                error=str(e), scope=e.scope,
            )
            self.dedup.finish(rid, resp)  # a retry is equally rejected
            return resp
        try:
            all_ok, per = ticket.collect(remaining)
        except TimeoutError:
            # the ticket may still settle later; don't cache a verdict
            # that the service might yet produce — a fresh retry re-asks
            self.dedup.abort(rid)
            return wire.VerifyResponse(
                request_id=rid, status=wire.STATUS_DEADLINE,
                error="verification outlived the request budget",
            )
        except BaseException as e:  # noqa: BLE001 — answer the wire, keep serving
            with self._stats_mtx:
                self._errors += 1
            self.logger.error(f"verifyd: verification failed: {e!r}")
            self.dedup.abort(rid)
            return wire.VerifyResponse(
                request_id=rid, status=wire.STATUS_ERROR, error=repr(e),
            )
        resp = wire.VerifyResponse(
            request_id=rid, status=wire.STATUS_OK, all_ok=bool(all_ok),
            verdicts=[1 if v else 0 for v in per],
        )
        self.dedup.finish(rid, resp)
        return resp

    def _handle_proof_guarded(self, req: wire.ProofRequest, conn, wmtx) -> None:
        try:
            self._handle_proof(req, conn, wmtx)
        finally:
            self._req_sem.release()

    def _handle_proof(self, req: wire.ProofRequest, conn, wmtx) -> None:
        """The proof_request twin of _handle_verify: same budget,
        trace-adoption, and response-shaping seams around
        _proof_response."""
        deadline = time.monotonic() + max(0, req.budget_ms) / 1e3
        with self._stats_mtx:
            self._requests += 1
        ctx = None
        if req.trace_ctx and tracing.propagation_enabled():
            parent = tracing.SpanContext.from_traceparent(req.trace_ctx)
            if parent is not None:
                ctx = parent.child()
        with tracing.context_scope(ctx), tracing.span(
            "verify.proof.serve",
            {"queries": len(req.queries or []),
             "trees": len(req.trees or []), "attempt": req.attempt}
            if tracing.enabled() else None,
        ):
            resp = self._proof_response(req, deadline)
        if resp is None:
            return
        d = fail.armed("rpc_delay_ms")
        if d:
            fail.jittered_sleep(d)
        pct = fail.armed("rpc_drop_pct")
        if pct is not None and fail.should_drop(pct):
            self.logger.warning(
                f"verifyd: injected proof response drop (rid="
                f"{(req.request_id or b'').hex()[:12]})"
            )
            return
        self._send(conn, wmtx, wire.PlaneMessage(proof_response=resp))

    def _proof_response(
        self, req: wire.ProofRequest, deadline: float
    ) -> wire.ProofResponse:
        from ..models import proof_server as PS

        rid = req.request_id
        try:
            trees, queries = wire.validate_proof_request(req)
        except ValueError as e:
            return wire.ProofResponse(
                request_id=rid or b"", status=wire.STATUS_BAD_REQUEST,
                error=str(e),
            )
        state, entry = self.dedup.begin(rid, req.digest)
        if state == "mismatch":
            return wire.ProofResponse(
                request_id=rid, status=wire.STATUS_BAD_REQUEST,
                error="request_id reused with a different proof digest",
            )
        if state == "dup":
            with self._stats_mtx:
                self._deduped += 1
            if not entry["event"].wait(max(0.0, deadline - time.monotonic())):
                return wire.ProofResponse(
                    request_id=rid, status=wire.STATUS_DEADLINE,
                    error="original proof batch still in flight",
                )
            cached = entry["response"]
            if cached is None:
                return wire.ProofResponse(
                    request_id=rid, status=wire.STATUS_ERROR,
                    error="original proof batch aborted", deduped=True,
                )
            return wire.ProofResponse(
                request_id=rid, status=cached.status,
                proofs=list(cached.proofs or []), error=cached.error,
                scope=cached.scope, deduped=True,
            )
        try:
            klass = Klass(req.klass)
        except ValueError:
            self.dedup.abort(rid)
            return wire.ProofResponse(
                request_id=rid, status=wire.STATUS_BAD_REQUEST,
                error=f"unknown class {req.klass}",
            )
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            self.dedup.abort(rid)
            return wire.ProofResponse(
                request_id=rid, status=wire.STATUS_DEADLINE,
                error="budget exhausted on arrival",
            )
        digests = [PS.register_tree(lv) for lv in trees]
        items = [PS.encode_query(digests[t], i) for (t, i) in queries]
        try:
            ticket = self.svc.submit(
                items, klass, MODE_PROOF, tenant=req.tenant or None
            )
        except VerifyServiceBackpressure as e:
            with self._stats_mtx:
                self._rejected += 1
            resp = wire.ProofResponse(
                request_id=rid, status=wire.STATUS_BACKPRESSURE,
                error=str(e), scope=e.scope,
            )
            self.dedup.finish(rid, resp)
            return resp
        try:
            _all_ok, rows = ticket.collect(remaining)
        except TimeoutError:
            self.dedup.abort(rid)
            return wire.ProofResponse(
                request_id=rid, status=wire.STATUS_DEADLINE,
                error="proof generation outlived the request budget",
            )
        except BaseException as e:  # noqa: BLE001 — answer the wire, keep serving
            with self._stats_mtx:
                self._errors += 1
            self.logger.error(f"verifyd: proof batch failed: {e!r}")
            self.dedup.abort(rid)
            return wire.ProofResponse(
                request_id=rid, status=wire.STATUS_ERROR, error=repr(e),
            )
        resp = wire.ProofResponse(
            request_id=rid, status=wire.STATUS_OK,
            proofs=[
                wire.ProofMsg(total=0) if p is None else wire.ProofMsg(
                    total=p.total, index=p.index,
                    leaf_hash=p.leaf_hash, aunts=list(p.aunts),
                )
                for p in rows
            ],
        )
        self.dedup.finish(rid, resp)
        return resp

    def _send(self, conn, wmtx, msg: wire.PlaneMessage) -> None:
        try:
            with wmtx:
                conn.sendall(wire.frame(msg))
        except OSError as e:
            # the client died/reconnected: its retry path owns recovery
            self.logger.info(f"verifyd: response send failed: {e!r}")

    # -------------------------------------------------------------- status

    def stats(self) -> dict:
        with self._stats_mtx:
            server = {
                "addr": self.addr,
                "pid": os.getpid(),
                "started_unix": self._started_unix,
                "requests": self._requests,
                "deduped": self._deduped,
                "rejected": self._rejected,
                "errors": self._errors,
                "dedup_entries": len(self.dedup),
            }
        with self._conns_mtx:
            server["connections"] = len(self._conns)
        return {"server": server, "service": self.svc.stats(lock_timeout=0.5)}


# ----------------------------------------------------------- process entry

def spawn_verifyd(
    addr: str = "127.0.0.1:0",
    extra_env: dict[str, str] | None = None,
    log_path: str | None = None,
    ready_timeout_s: float = 30.0,
) -> tuple[subprocess.Popen, str]:
    """Spawn a verifyd subprocess and wait for its READY line; returns
    (proc, bound_addr).  Used by the chaos/soak harnesses and tests —
    production deploys run ``scripts/verifyd.py`` directly.  The child
    runs on the CPU backend unless the caller's environment names a
    platform, for the same reason e2e nodes do: a chip belongs to one
    process at a time, and the spawning process may hold it."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("COMETBFT_TPU_DEVICE_BATCH_MIN", None)  # tests force 1; see runner
    # the spawning process is typically remote-bound to THIS plane; the
    # plane itself must verify locally, never forward (see __init__)
    env.pop("COMETBFT_TPU_VERIFYRPC_ADDR", None)
    env.update(extra_env or {})
    if log_path:
        os.makedirs(os.path.dirname(os.path.abspath(log_path)), exist_ok=True)
        log_f = open(log_path, "ab")
    else:
        log_f = subprocess.DEVNULL
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "cometbft_tpu.verifysvc.server",
             "--addr", addr],
            env=env,
            stdout=subprocess.PIPE,
            stderr=log_f,
            text=True,
        )
    finally:
        if log_f is not subprocess.DEVNULL:
            log_f.close()  # the child holds its own fd; don't leak ours
    deadline = time.monotonic() + ready_timeout_s
    # deadline-bounded raw reads (select + os.read on the pipe fd, never
    # readline): a child that wedges before printing READY must make
    # this raise at the deadline, not park the caller forever — the
    # same unbounded-blocking-read shape the socket-without-timeout
    # lint bans.  Raw fd reads bypass proc.stdout's buffer; that's fine,
    # nothing else consumes stdout after the READY line.
    import select

    fd = proc.stdout.fileno()
    buf = ""
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        readable, _, _ = select.select([fd], [], [], remaining)
        if not readable:
            break
        chunk = os.read(fd, 4096).decode("utf-8", "replace")
        if not chunk:
            break  # EOF: the child exited or closed stdout
        buf += chunk
        for line in buf.splitlines():
            if line.startswith(_READY_PREFIX):
                bound = line[len(_READY_PREFIX):].strip()
                # stop consuming stdout: nothing else is written there
                return proc, bound
    try:
        proc.kill()
    except OSError:
        pass
    raise RuntimeError(
        f"verifyd did not become ready within {ready_timeout_s}s "
        f"(stdout so far: {buf!r})"
    )


def main(argv: list[str] | None = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        description="verifyd: the shared out-of-process verify plane"
    )
    p.add_argument("--addr", default="127.0.0.1:0",
                   help="host:port to listen on (port 0 = ephemeral; the "
                        "bound address is printed as 'VERIFYD READY addr=')")
    args = p.parse_args(argv)
    server = VerifyServer(args.addr)
    server.start()
    print(f"{_READY_PREFIX}{server.addr}", flush=True)
    stop = threading.Event()

    def _sig(_signum, _frame):
        stop.set()

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    while not stop.is_set():
        stop.wait(0.5)
    server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
