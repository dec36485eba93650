"""What decides ``correct``, copied from chip_smoke.py (PR 21) so that a
later PR may change the program and never the yardstick: verdict
vectors against the plain reference, a tampered commit refused at its
first flipped index, which route served every batch (the service's
stats, the hub's counters, the span ring), and what JAX compiled when.
"""

from __future__ import annotations

import copy
import threading
import time

from . import reference

FLIPPED = (0.7777, 0.0123, 0.9001)  # tampered rows, as fractions of the width

# spans that mean a batch was answered from the host (models/verifier,
# models/comb_verifier, verifysvc/client, verifysvc/service)
FALLBACK_SPANS = (
    "verify.host_route",
    "verify.svc_fallback",
    "verify.collect_stall_fallback",
    "verify.failover.reverify",
)


# the hub's counters of batches that the host answered or answered again
HOST_COUNTERS = (
    "verify_svc_host_reverify", "verify_svc_collect_timeout",
    "verify_svc_failover", "verify_host_route",
)


class CheckFailure(Exception):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailure(what)


# ------------------------------------------------------------ jax events


class JaxEvents:
    """What JAX itself reports (jax.monitoring): every backend compile
    (or load from the persistent cache) with the time it ended, and the
    cache's requests and hits."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
    HITS = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.compiles: list[tuple[float, str, float]] = []  # (ended, fun, s)
        self.counts: dict[str, int] = {}
        self._mtx = threading.Lock()

    def install(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event != self.COMPILE:
            return
        fun = kw.get("fun_name", "?").removeprefix("jit(").removesuffix(")")
        with self._mtx:
            self.compiles.append((time.monotonic(), fun, secs))

    def _event(self, event: str, **kw) -> None:
        with self._mtx:
            self.counts[event] = self.counts.get(event, 0) + 1

    def compiled_since(self, t: float) -> list[str]:
        """Programs whose backend compile ended after monotonic time t."""
        with self._mtx:
            return [fun for ended, fun, _ in self.compiles if ended >= t]

    def summary(self) -> dict:
        with self._mtx:
            return {
                "backend_compile_s": {
                    fun: round(s, 3) for _, fun, s in self.compiles if s >= 1.0
                },
                "programs": len(self.compiles),
                "cache_requests": self.counts.get(self.REQUESTS, 0),
                "cache_hits": self.counts.get(self.HITS, 0),
            }


# ------------------------------------------------------------ the checks


def tampered(commit, width: int):
    """A copy of ``commit`` with a few signatures flipped (well under
    1/3 of the power), and the flipped indices."""
    idxs = sorted({int(f * width) for f in FLIPPED})
    bad = copy.deepcopy(commit)
    for i in idxs:
        cs = bad.signatures[i]
        cs.signature = cs.signature[:-1] + bytes([cs.signature[-1] ^ 1])
    return bad, idxs


def check_vector(valset, commit, sign_bytes, want_bad: list[int]) -> None:
    """The per-signature vector of the batch verifier the node would
    make for this set, against the plain reference, position by
    position.  ``sign_bytes[i]`` is the reference's own encoding of what
    validator i signed; the program is handed ITS encoding."""
    from cometbft_tpu.crypto import batch as crypto_batch

    program_bytes = commit.vote_sign_bytes_fn(valset.chain_id)
    vals = valset.vals.validators
    bv = crypto_batch.create_batch_verifier(
        "ed25519", pubkeys=valset.vals.pub_keys_bytes()
    )
    for i, v in enumerate(vals):
        bv.add(v.pub_key.bytes(), program_bytes(i), commit.signatures[i].signature)
    ok, vec = bv.verify()
    oracle = [
        reference.verify(v.pub_key.bytes(), sign_bytes[i],
                         commit.signatures[i].signature)
        for i, v in enumerate(vals)
    ]
    require(len(vec) == len(oracle), "verdict vector has the wrong length")
    diff = [i for i, (a, b) in enumerate(zip(vec, oracle)) if a != b]
    require(
        not diff,
        f"{valset.chain_id}: verdicts differ from the reference at rows "
        f"{diff[:8]} ({len(diff)} in all)",
    )
    require(
        [i for i, b in enumerate(oracle) if not b] == want_bad,
        f"{valset.chain_id}: the reference itself blames the wrong rows",
    )
    require(ok == (not want_bad), f"{valset.chain_id}: all-ok flag is wrong")


def check_refused(valset, block_id, height: int, bad, first_bad: int) -> None:
    from cometbft_tpu.types.validation import (
        CommitVerificationError, verify_commit,
    )

    try:
        verify_commit(valset.chain_id, valset.vals, block_id, height, bad)
    except CommitVerificationError as e:
        require(
            f"(#{first_bad})" in str(e),
            f"{valset.chain_id}: tampered commit refused at the wrong index: {e}",
        )
        return
    raise CheckFailure(f"{valset.chain_id}: tampered commit was accepted")


# ------------------------------------------------ which route served what


def _counter_total(counter) -> float:
    return sum(float(line.rsplit(" ", 1)[1]) for line in counter.expose())


def route_counters() -> dict:
    """The service's own stats and the hub's fallback counters: read in
    every run, span ring or not."""
    from cometbft_tpu.utils.metrics import hub
    from cometbft_tpu.verifysvc.service import global_service

    st = global_service().stats()
    m = hub()
    return {
        "backend_mode": st["backend_mode"],
        "failover_trips": st["failover"]["trips"],
        "rejected": st["rejected"],
        "dispatched_batches": sum(st["dispatched_batches"].values()),
        **{c: _counter_total(getattr(m, c)) for c in HOST_COUNTERS},
    }


def route_spans(events: list[dict]) -> dict:
    """The span ring (chrome trace events) reduced to what says which
    route served: spans of one batch share its trace id
    (verifysvc/client)."""
    from cometbft_tpu.utils import tracing

    by_trace: dict[str, list[str]] = {}
    names: dict[str, int] = {}
    for e in events:
        if e.get("ph") not in ("X", "i"):
            continue
        names[e["name"]] = names.get(e["name"], 0) + 1
        tid = (e.get("args") or {}).get("trace_id")
        if tid is not None:
            by_trace.setdefault(tid, []).append(e["name"])
    waits_wrong = no_program = 0
    for seen in by_trace.values():
        for _ in range(seen.count("verify.sched.dispatch")):
            if seen.count("verify.device_wait") != 1:
                waits_wrong += 1
            if "verify.device_wait" not in seen:
                no_program += 1
    return {
        "fallback_spans": {n: names.get(n, 0) for n in FALLBACK_SPANS},
        "dispatch_spans": names.get("verify.sched.dispatch", 0),
        "device_wait_spans": names.get("verify.device_wait", 0),
        "batches_without_exactly_one_device_wait": waits_wrong,
        "batches_without_a_device_program": no_program,
        "spans_dropped": tracing.dropped_count(),
    }


def span_fallbacks(spans: dict) -> list[str]:
    """Batches the span ring shows were answered from the host."""
    out = [f"{k} {n} span(s)" for n, k in spans["fallback_spans"].items() if k]
    if spans["spans_dropped"]:
        out.append(f"span ring dropped {spans['spans_dropped']} events")
    return out


def route_failures(counters: dict, spans: dict | None = None) -> list[str]:
    """Why this run does NOT prove that the device served every batch
    (empty: it does).  ``spans`` is the ring of a run that had it on
    from before the first batch to after the last: then every batch the
    service dispatched must be in it, with one device wait."""
    out = []
    if counters["backend_mode"] != "tpu":
        out.append(f"backend_mode is {counters['backend_mode']!r}")
    if counters["failover_trips"]:
        out.append(f"{counters['failover_trips']} failover trip(s)")
    if any(counters["rejected"].values()):
        out.append(f"rejected submits: {counters['rejected']}")
    for c in HOST_COUNTERS:
        if counters[c]:
            out.append(f"{c} = {counters[c]:g}")
    if not counters["dispatched_batches"]:
        out.append("the service dispatched no batch")
    if spans is None:
        return out
    out += span_fallbacks(spans)
    if spans["dispatch_spans"] != counters["dispatched_batches"]:
        out.append(
            f"span ring holds {spans['dispatch_spans']} dispatches, the "
            f"service made {counters['dispatched_batches']}"
        )
    if (spans["device_wait_spans"] != spans["dispatch_spans"]
            or spans["batches_without_exactly_one_device_wait"]):
        out.append(
            f"{spans['dispatch_spans']} batches dispatched, "
            f"{spans['device_wait_spans']} device waits, "
            f"{spans['batches_without_exactly_one_device_wait']} batches "
            "without exactly one"
        )
    if spans["batches_without_a_device_program"]:
        out.append(f"{spans['batches_without_a_device_program']} batch(es) "
                   "ran no device program")
    return out
