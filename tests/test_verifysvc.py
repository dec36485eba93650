"""Unified verify service (cometbft_tpu/verifysvc): priority scheduling,
adaptive batch formation, backpressure, blame-order preservation, and the
mempool CheckTx client.

All tests are CPU-only and fast: batches stay below the device
threshold (models/verifier._device_batch_min), so the underlying
verifiers host-route and no XLA program compiles — the scheduler logic
under test is identical either way.
"""

import threading
import time

import pytest

from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.utils.metrics import hub as mhub
from cometbft_tpu.verifysvc import checktx
from cometbft_tpu.verifysvc.client import ServiceBatchVerifier
from cometbft_tpu.verifysvc.service import (
    Klass,
    VerifyService,
    VerifyServiceBackpressure,
    _parse_weights,
)

WAIT = 10.0  # generous collect timeout; everything here resolves in ms


def _sigs(n, tag=b"t", tamper=()):
    out = []
    for i in range(n):
        sk = host.PrivKey.from_seed(bytes([7 + i]) * 32)
        msg = b"%s-%d" % (tag, i)
        sig = sk.sign(msg)
        if i in tamper:
            msg += b"!"
        out.append((sk.pub_key().data, msg, sig))
    return out


def _flush_count(klass: str, reason: str) -> float:
    return mhub().verify_svc_flush.value(**{"class": klass, "reason": reason})


@pytest.fixture
def svc():
    services = []

    def make(**kw):
        s = VerifyService(**kw)
        services.append(s)
        return s

    yield make
    for s in services:
        s.stop()


# ------------------------------------------------------------ scheduling


def test_consensus_never_delayed_behind_mempool(svc):
    """The acceptance property, asserted via the per-class metrics: with
    a mempool backlog queued (inside its coalescing deadline), a
    consensus submission dispatches immediately — at the moment the
    consensus batch resolves, the mempool class has flushed nothing."""
    s = svc(
        batch_max=64,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
    )
    # record dispatch order by class: wrap _dispatch (not the verifier
    # factory) so the class is visible
    order = []
    real_dispatch = s._dispatch

    def record_dispatch(klass, batch, reason):
        order.append(klass)
        return real_dispatch(klass, batch, reason)

    s._dispatch = record_dispatch
    mp_before = _flush_count("mempool", "deadline") + _flush_count(
        "mempool", "full"
    )
    mp_tickets = [s.submit(_sigs(3, b"mp%d" % i), Klass.MEMPOOL) for i in range(4)]
    cs_ticket = s.submit(_sigs(5, b"cs"), Klass.CONSENSUS)
    ok, per = cs_ticket.collect(WAIT)
    assert ok and per == [True] * 5
    # consensus flushed; mempool (deadline 60s, 12 < 64 sigs) has not
    assert order and order[0] == Klass.CONSENSUS
    assert (
        _flush_count("mempool", "deadline") + _flush_count("mempool", "full")
        == mp_before
    )
    assert mhub().verify_svc_queue_depth.value(**{"class": "mempool"}) == 12.0
    assert not any(t.done() for t in mp_tickets)


def test_deadline_triggered_flush(svc):
    s = svc(
        batch_max=1024,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 50, Klass.BACKGROUND: 25,
        },
    )
    before = _flush_count("mempool", "deadline")
    t0 = time.monotonic()
    ok, per = s.submit(_sigs(2, b"dl"), Klass.MEMPOOL).collect(WAIT)
    waited = time.monotonic() - t0
    assert ok and per == [True, True]
    assert waited >= 0.045  # held for the coalescing window…
    assert _flush_count("mempool", "deadline") == before + 1  # …then flushed


@pytest.mark.parametrize("klass, mode", [
    (Klass.BACKGROUND, ("comb", object())),
    (Klass.BLOCKSYNC, ("comb", object())),
    (Klass.MEMPOOL, ("bls",)),
])
def test_a_request_that_dispatches_solo_waits_for_no_deadline(svc, klass, mode):
    """A comb- or bls-bound request can be joined by nothing, so its
    class's flush deadline (a coalescing window) does not hold it: it is
    ready at once and flushes with reason ``solo``, in every class that
    has a deadline, while a plain request of that class still waits."""
    from cometbft_tpu.verifysvc.service import _HostBatchVerifier

    deadlines = {k: 0 for k in Klass}
    deadlines[klass] = 60_000
    s = svc(batch_max=1024, deadlines_ms=deadlines)
    s._make_verifier = lambda mode: _HostBatchVerifier(("plain",))
    label = klass.name.lower()
    solo = _flush_count(label, "solo")
    t0 = time.monotonic()
    bound = s.submit(_sigs(3, b"bound", tamper=(1,)), klass, mode)
    # queued behind it (a queue is first in, first out: a solo request
    # BEHIND a coalescible one would wait for that one's flush)
    plain = s.submit(_sigs(2, b"plain"), klass)
    ok, per = bound.collect(WAIT)
    assert time.monotonic() - t0 < 5.0  # not the 60 s of its class
    assert not ok and per == [True, False, True]
    assert _flush_count(label, "solo") == solo + 1
    assert not plain.done()  # coalescible: still inside its window


def test_full_batch_flush_and_coalescing(svc):
    """Two sub-width requests coalesce; crossing the batch width flushes
    with reason=full before the (absurd) deadline."""
    s = svc(
        batch_max=4,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
    )
    before = _flush_count("mempool", "full")
    t1 = s.submit(_sigs(2, b"f1", tamper=(1,)), Klass.MEMPOOL)
    t2 = s.submit(_sigs(2, b"f2"), Klass.MEMPOOL)
    ok1, per1 = t1.collect(WAIT)
    ok2, per2 = t2.collect(WAIT)
    # one coalesced batch, each request judged on its own slice
    assert not ok1 and per1 == [True, False]
    assert ok2 and per2 == [True, True]
    assert _flush_count("mempool", "full") == before + 1


def test_coalesces_concurrent_senders(svc):
    """The CheckTx shape: single-signature submissions from concurrent
    threads merge into ONE device batch inside the class deadline."""
    s = svc(
        batch_max=1024,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 150, Klass.BACKGROUND: 25,
        },
    )
    before_dl = _flush_count("mempool", "deadline")
    results = {}

    def sender(i):
        results[i] = s.submit(_sigs(1, b"snd%d" % i), Klass.MEMPOOL).collect(WAIT)

    threads = [
        threading.Thread(target=sender, args=(i,), name=f"t-sender-{i}")
        for i in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT)
    assert all(results[i] == (True, [True]) for i in range(6))
    assert _flush_count("mempool", "deadline") == before_dl + 1


def test_backpressure_rejection_and_caller_fallback(svc):
    s = svc(
        queue_max=4,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
    )
    rej_before = mhub().verify_svc_rejected.value(**{"class": "mempool"})
    s.submit(_sigs(4, b"fill"), Klass.MEMPOOL)  # parks at the bound
    with pytest.raises(VerifyServiceBackpressure):
        s.submit(_sigs(1, b"over"), Klass.MEMPOOL)
    assert mhub().verify_svc_rejected.value(**{"class": "mempool"}) == rej_before + 1

    # flight-recorder event landed
    from cometbft_tpu.utils.flightrec import recorder

    kinds = [e["kind"] for e in recorder().dump()["entries"]]
    assert "verifysvc_backpressure" in kinds

    # caller-side fallback: the BatchVerifier client degrades to an
    # inline host verification with correct results and blame order
    bv = ServiceBatchVerifier(Klass.MEMPOOL, service=s)
    for pub, msg, sig in _sigs(3, b"fb", tamper=(2,)):
        bv.add(pub, msg, sig)
    ok, per = bv.verify()
    assert not ok and per == [True, True, False]

    # other classes are unaffected by mempool's full queue
    ok, per = s.submit(_sigs(2, b"cs-ok"), Klass.CONSENSUS).collect(WAIT)
    assert ok and per == [True, True]


def test_fifo_blame_order_across_classes(svc):
    """Per-request blame follows each request's OWN add() order no
    matter how classes interleave or in which order tickets are
    collected."""
    s = svc(
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 5,
            Klass.MEMPOOL: 20, Klass.BACKGROUND: 10,
        },
    )
    t_mp = s.submit(_sigs(4, b"mp", tamper=(0,)), Klass.MEMPOOL)
    t_bg = s.submit(_sigs(3, b"bg", tamper=(1,)), Klass.BACKGROUND)
    t_cs = s.submit(_sigs(5, b"cs", tamper=(3,)), Klass.CONSENSUS)
    t_bs = s.submit(_sigs(2, b"bs"), Klass.BLOCKSYNC)
    # collect out of submission AND priority order
    ok_bg, per_bg = t_bg.collect(WAIT)
    ok_cs, per_cs = t_cs.collect(WAIT)
    ok_mp, per_mp = t_mp.collect(WAIT)
    ok_bs, per_bs = t_bs.collect(WAIT)
    assert (not ok_mp) and per_mp == [False, True, True, True]
    assert (not ok_bg) and per_bg == [True, False, True]
    assert (not ok_cs) and per_cs == [True, True, True, False, True]
    assert ok_bs and per_bs == [True, True]


def test_collect_clock_stops_for_a_compile_only(svc, monkeypatch):
    """The client's collect() bound is for a stuck scheduler.  A batch
    waiting for its program to compile — on a chip with a cold cache a
    first-shape compile outlasts the bound — is work: the clock stands
    still for it, runs again when it ends, and runs anyway once the
    compile has outlasted COMPILE_BOUND_S."""
    from cometbft_tpu.verifysvc import service as service_mod
    from cometbft_tpu.verifysvc.service import Ticket

    t = Ticket(1)
    with pytest.raises(TimeoutError):
        t.collect(0.1)  # no compile: the bound holds
    t._resolve((True, [True]))
    assert t.collect(0) == (True, [True])  # settled: no wait to bound

    t = Ticket(1)
    t.compiling_since = time.monotonic()
    threading.Timer(0.5, t._resolve, args=((True, [True]),)).start()
    assert t.collect(0.1) == (True, [True])  # outlasted the bound 5x

    t = Ticket(1)
    t.compiling_since = time.monotonic()
    threading.Timer(0.3, setattr, args=(t, "compiling_since", None)).start()
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        t.collect(0.2)
    assert 0.3 <= time.monotonic() - t0 < 5.0

    monkeypatch.setattr(service_mod, "COMPILE_BOUND_S", 0.3)
    t = Ticket(1)
    t.compiling_since = time.monotonic()  # a compile that never returns
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        t.collect(0.2)
    assert 0.3 <= time.monotonic() - t0 < 5.0

    # and the service raises the flag for as long as the verifier says
    s = svc(deadlines_ms={k: 0 for k in Klass})
    seen = {}

    class CompilingBV:
        _entry = None  # offloaded to the host worker
        on_compile = None

        def add(self, pub, msg, sig):
            pass

        def submit(self):
            seen["assembling"] = seen["ticket"].compiling()
            self.on_compile(True)
            seen["compiling"] = seen["ticket"].compiling()
            self.on_compile(False)
            seen["dispatching"] = seen["ticket"].compiling()
            return ("sync", (True, [True]))

        def collect(self, ticket):
            return ticket[1]

    s._make_verifier = lambda mode: CompilingBV()
    gate = threading.Event()
    real_dispatch = s._dispatch
    s._dispatch = lambda *a: (gate.wait(WAIT), real_dispatch(*a))
    seen["ticket"] = s.submit(_sigs(1), Klass.CONSENSUS)
    gate.set()
    assert seen["ticket"].collect(WAIT) == (True, [True])
    assert seen == {
        "ticket": seen["ticket"], "assembling": False, "compiling": True,
        "dispatching": False,
    }


def test_hung_host_work_still_answers_from_host(svc, monkeypatch):
    """Only the compile is off the collect clock.  A submit() that hangs
    on the host worker outside it — the uncached path's transfers and
    dispatch run there, and an accelerator can hang instead of erroring
    — must not park the caller: the bound expires and the caller gets
    host verdicts in its own add() order."""
    from cometbft_tpu.verifysvc import service as service_mod

    monkeypatch.setenv("COMETBFT_TPU_VERIFYSVC_COLLECT_TIMEOUT_MS", "300")
    s = svc(deadlines_ms={k: 0 for k in Klass})
    release = threading.Event()

    class HangsAfterCompileBV:
        _entry = None  # offloaded to the host worker
        on_compile = None

        def add(self, *item):
            pass

        def submit(self):
            self.on_compile(True)
            time.sleep(0.5)  # a compile longer than the bound: not charged
            self.on_compile(False)
            release.wait(WAIT)  # the transfer hangs "forever"
            return ("sync", (True, [True] * 3))

        def collect(self, ticket):
            return ticket[1]

    s._make_verifier = lambda mode: HangsAfterCompileBV()
    before = mhub().verify_svc_collect_timeout.value(**{"class": "consensus"})
    items = _sigs(3, b"hang", tamper=(1,))
    bv = ServiceBatchVerifier(Klass.CONSENSUS, service=s, tenant="hang-t")
    for pub, msg, sig in items:
        bv.add(pub, msg, sig)
    t0 = time.monotonic()
    ok, per = bv.verify()
    waited = time.monotonic() - t0
    assert 0.5 <= waited < 5.0  # the compile and then the bound, not forever
    assert (not ok) and per == [True, False, True]  # host verdicts, own order
    assert (
        mhub().verify_svc_collect_timeout.value(**{"class": "consensus"})
        == before + 1
    )
    release.set()  # unpark the host worker so teardown joins cleanly
    service_mod._reset_stall_gate()


def test_host_queue_respects_class_priority(svc):
    """Submit-time work is offloaded to the host worker through a
    class-priority queue: with the worker busy, later-queued consensus
    work overtakes earlier-queued mempool/background work."""
    s = svc(deadlines_ms={k: 0 for k in Klass})
    gate = threading.Event()
    run_order = []

    class FakeBV:
        _entry = None  # plain shape -> _submit_is_offloaded is True

        def __init__(self):
            self.items = []

        def add(self, pub, msg, sig):
            self.items.append((pub, msg, sig))

        def submit(self):
            tag = self.items[0][1].split(b"-")[0].decode()
            if not run_order:
                gate.wait(WAIT)  # first task parks the worker
            run_order.append(tag)
            return ("sync", (True, [True] * len(self.items)))

        def collect(self, ticket):
            return ticket[1]

    s._make_verifier = lambda mode: FakeBV()
    tickets = [s.submit(_sigs(1, b"bg1"), Klass.BACKGROUND)]
    time.sleep(0.15)  # worker is now parked inside bg1's submit
    for tag, klass in (
        (b"mp", Klass.MEMPOOL),
        (b"bg2", Klass.BACKGROUND),
        (b"cs", Klass.CONSENSUS),
    ):
        tickets.append(s.submit(_sigs(1, tag), klass))
        time.sleep(0.15)  # let the scheduler queue each on the host q
    gate.set()
    for t in tickets:
        assert t.collect(WAIT) == (True, [True])
    # consensus overtook the mempool/background work queued before it
    assert run_order == ["bg1", "cs", "mp", "bg2"]


def test_weighted_interleave_parsing():
    assert _parse_weights("consensus=8,blocksync=4,mempool=2,background=1") == {
        Klass.CONSENSUS: 8, Klass.BLOCKSYNC: 4,
        Klass.MEMPOOL: 2, Klass.BACKGROUND: 1,
    }
    # malformed entries drop, zero/negative weights drop, empty = strict
    assert _parse_weights("consensus=2,junk,=3,mempool=0,x=1") == {
        Klass.CONSENSUS: 2
    }
    assert _parse_weights("") == {}


def test_empty_submit_resolves_immediately(svc):
    s = svc()
    assert s.submit([], Klass.CONSENSUS).collect(0.1) == (False, [])
    bv = ServiceBatchVerifier(Klass.CONSENSUS, service=s)
    assert bv.verify() == (False, [])


def test_dispatch_error_fails_tickets_not_service(svc):
    """With failover OFF (the pre-failover contract), a dispatch error
    fails the tickets; the scheduler itself survives.  The failover-ON
    behavior (host re-verify, identical verdicts) is pinned in
    tests/test_failover.py."""
    s = svc(deadlines_ms={k: 0 for k in Klass}, failover=False)

    def boom(mode):
        raise RuntimeError("no backend")

    s._make_verifier = boom
    with pytest.raises(RuntimeError, match="no backend"):
        s.submit(_sigs(2, b"err"), Klass.CONSENSUS).collect(WAIT)
    # the scheduler survived and keeps serving
    s._make_verifier = VerifyService._make_verifier.__get__(s)
    ok, per = s.submit(_sigs(2, b"ok"), Klass.CONSENSUS).collect(WAIT)
    assert ok and per == [True, True]


def test_stop_fails_stranded_tickets(svc):
    s = svc(
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
    )
    t = s.submit(_sigs(1, b"strand"), Klass.MEMPOOL)
    s.stop()
    with pytest.raises(VerifyServiceBackpressure):
        t.collect(WAIT)


# --------------------------------------------- (tenant, class) scheduling


def test_tenant_quota_confines_backpressure(svc):
    """One tenant at its per-class quota rejects with scope=tenant while
    other tenants (and the class as a whole) keep admitting — the
    rogue-flood isolation property."""
    s = svc(
        queue_max=1000, tenant_quota=4,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
    )
    s.submit(_sigs(4, b"qa"), Klass.MEMPOOL, tenant="quota-a")  # at quota
    with pytest.raises(VerifyServiceBackpressure) as ei:
        s.submit(_sigs(1, b"qa2"), Klass.MEMPOOL, tenant="quota-a")
    assert ei.value.tenant == "quota-a" and ei.value.scope == "tenant"
    assert ei.value.limit == 4

    # the offender's quota does not starve the neighbor tenant
    t_b = s.submit(_sigs(2, b"qb"), Klass.MEMPOOL, tenant="quota-b")
    assert t_b is not None

    # nor the offender's OTHER classes (quota is per (tenant, class))
    ok, per = s.submit(
        _sigs(2, b"qa-cs"), Klass.CONSENSUS, tenant="quota-a"
    ).collect(WAIT)
    assert ok and per == [True, True]

    # flight-recorder event carries tenant + scope
    from cometbft_tpu.utils.flightrec import recorder

    ev = [
        e for e in recorder().dump()["entries"]
        if e["kind"] == "verifysvc_backpressure"
        and e.get("detail", {}).get("tenant") == "quota-a"
    ]
    assert ev and ev[-1]["detail"]["scope"] == "tenant"

    # per-tenant tallies: the reject landed on the offender only
    st = s.stats()
    assert st["tenants"]["quota-a"]["rejected"] == 1
    assert st["tenants"].get("quota-b", {}).get("rejected", 0) == 0


def test_class_bound_still_caps_across_tenants(svc):
    """The class-wide queue bound is a second ceiling over the sum of
    tenants: many tenants can't overcommit the class by each staying
    under their own quota."""
    s = svc(
        queue_max=4, tenant_quota=1000,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
    )
    s.submit(_sigs(3, b"ca"), Klass.MEMPOOL, tenant="cls-a")
    with pytest.raises(VerifyServiceBackpressure) as ei:
        s.submit(_sigs(2, b"cb"), Klass.MEMPOOL, tenant="cls-b")
    assert ei.value.scope == "class" and ei.value.tenant == "cls-b"


def test_tenant_weighted_fair_interleave(svc):
    """Within one class, ready tenants interleave by weight: with
    a=2/b=1 and a backlog of four requests each, tenant a gets two
    dispatch slots for b's one while both are ready — a deeper queue
    buys no extra share."""
    import threading as _threading

    s = svc(
        batch_max=1,  # 1-sig requests never coalesce: one dispatch each
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
        tenant_weights={"wa": 2, "wb": 1},
    )

    class SyncBV:
        _entry = object()  # not offloaded: dispatch settles inline
        _fallback = None

        def __init__(self):
            self.items = []

        def add(self, *item):
            self.items.append(item)

        def submit(self):
            return ("sync", (True, [True] * len(self.items)))

        def collect(self, ticket):
            return ticket[1]

    s._make_verifier = lambda mode: SyncBV()
    gate = _threading.Event()
    order = []
    real_dispatch = s._dispatch

    def recording_dispatch(klass, batch, reason):
        if not order:
            gate.wait(WAIT)  # park the scheduler on the primer dispatch
        order.append(batch[0].tenant)
        return real_dispatch(klass, batch, reason)

    s._dispatch = recording_dispatch
    tickets = [s.submit(_sigs(1, b"primer"), Klass.MEMPOOL, tenant="wx")]
    time.sleep(0.15)  # scheduler is now parked inside the primer dispatch
    for i in range(4):
        tickets.append(s.submit(_sigs(1, b"wa%d" % i), Klass.MEMPOOL, tenant="wa"))
    for i in range(4):
        tickets.append(s.submit(_sigs(1, b"wb%d" % i), Klass.MEMPOOL, tenant="wb"))
    gate.set()
    for t in tickets:
        assert t.collect(WAIT) == (True, [True])
    assert order[0] == "wx" and sorted(order[1:]) == ["wa"] * 4 + ["wb"] * 4
    # while BOTH tenants were ready (first 6 picks), shares follow the
    # 2:1 weights; the tail drains whoever remains
    contended = order[1:7]
    assert contended.count("wa") == 4 and contended.count("wb") == 2
    # and the interleave really alternates (b is never starved to the end)
    assert "wb" in contended[:2] or "wb" in contended[:3]


def test_tenant_round_robin_equal_weights(svc):
    """No weights configured: ready tenants alternate strictly."""
    import threading as _threading

    s = svc(
        batch_max=1,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
    )

    class SyncBV:
        _entry = object()
        _fallback = None

        def __init__(self):
            self.items = []

        def add(self, *item):
            self.items.append(item)

        def submit(self):
            return ("sync", (True, [True] * len(self.items)))

        def collect(self, ticket):
            return ticket[1]

    s._make_verifier = lambda mode: SyncBV()
    gate = _threading.Event()
    order = []
    real_dispatch = s._dispatch

    def recording_dispatch(klass, batch, reason):
        if not order:
            gate.wait(WAIT)
        order.append(batch[0].tenant)
        return real_dispatch(klass, batch, reason)

    s._dispatch = recording_dispatch
    tickets = [s.submit(_sigs(1, b"p"), Klass.MEMPOOL, tenant="rx")]
    time.sleep(0.15)
    for i in range(3):
        tickets.append(s.submit(_sigs(1, b"ra%d" % i), Klass.MEMPOOL, tenant="ra"))
        tickets.append(s.submit(_sigs(1, b"rb%d" % i), Klass.MEMPOOL, tenant="rb"))
    gate.set()
    for t in tickets:
        assert t.collect(WAIT) == (True, [True])
    assert order[1:] in (
        ["ra", "rb", "ra", "rb", "ra", "rb"],
        ["rb", "ra", "rb", "ra", "rb", "ra"],
    )


def test_consensus_outranks_other_tenants_mempool(svc):
    """Strict class priority is GLOBAL across tenants: tenant A's
    consensus batch dispatches before tenant B's ready mempool backlog
    however the tenant interleave stands."""
    s = svc(
        batch_max=64,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
    )
    order = []
    real_dispatch = s._dispatch

    def record(klass, batch, reason):
        order.append((klass, batch[0].tenant))
        return real_dispatch(klass, batch, reason)

    s._dispatch = record
    for i in range(3):
        s.submit(_sigs(2, b"mpx%d" % i), Klass.MEMPOOL, tenant="chainB")
    ok, per = s.submit(
        _sigs(2, b"csx"), Klass.CONSENSUS, tenant="chainA"
    ).collect(WAIT)
    assert ok and per == [True, True]
    assert order and order[0] == (Klass.CONSENSUS, "chainA")


def test_default_tenant_from_knob(svc, monkeypatch):
    """A process claims its tenant via COMETBFT_TPU_VERIFYSVC_TENANT;
    submits without an explicit tenant land there, so the whole node
    becomes that tenant with zero call-site changes."""
    from cometbft_tpu.verifysvc.service import default_tenant

    assert default_tenant() == "default"
    monkeypatch.setenv("COMETBFT_TPU_VERIFYSVC_TENANT", "my-chain")
    assert default_tenant() == "my-chain"
    s = svc(deadlines_ms={k: 0 for k in Klass})
    ok, per = s.submit(_sigs(1, b"dt"), Klass.CONSENSUS).collect(WAIT)
    assert ok
    assert s.stats()["tenants"]["my-chain"]["dispatched_batches"] == 1


def test_tenant_state_is_pruned_when_drained(svc):
    """Scheduler state stays bounded under a churning tenant-id stream:
    a drained tenant leaves the queue dicts entirely."""
    s = svc(deadlines_ms={k: 0 for k in Klass})
    for i in range(8):
        ok, per = s.submit(
            _sigs(1, b"churn%d" % i), Klass.CONSENSUS, tenant=f"churn-{i}"
        ).collect(WAIT)
        assert ok
    with s._cond:
        assert s._queues[Klass.CONSENSUS] == {}
        assert s._queued_sigs[Klass.CONSENSUS] == {}


def test_client_collect_timeout_degrades_to_host(svc, monkeypatch):
    """Satellite: a live-but-stuck scheduler (ticket accepted, never
    resolved) no longer parks a consensus caller forever — the bounded
    collect expires, stall forensics land, and the caller gets correct
    host verdicts in its own add() order."""
    import threading as _threading

    from cometbft_tpu.utils.flightrec import recorder
    from cometbft_tpu.verifysvc import service as service_mod

    monkeypatch.setenv("COMETBFT_TPU_VERIFYSVC_COLLECT_TIMEOUT_MS", "300")
    s = svc(deadlines_ms={k: 0 for k in Klass}, failover=False)
    release = _threading.Event()

    class StuckBV:
        _entry = object()
        _fallback = None

        def __init__(self):
            self.items = []

        def add(self, *item):
            self.items.append(item)

        def submit(self):
            return ("stuck", list(self.items))

        def collect(self, ticket):
            release.wait(WAIT)  # the collector parks here "forever"
            return (True, [True] * len(ticket[1]))

    s._make_verifier = lambda mode: StuckBV()
    before = mhub().verify_svc_collect_timeout.value(**{"class": "consensus"})
    items = _sigs(3, b"stall", tamper=(1,))
    bv = ServiceBatchVerifier(Klass.CONSENSUS, service=s, tenant="stall-t")
    for pub, msg, sig in items:
        bv.add(pub, msg, sig)
    t0 = time.monotonic()
    ok, per = bv.verify()
    waited = time.monotonic() - t0
    assert 0.25 <= waited < 5.0  # bounded, not forever
    assert (not ok) and per == [True, False, True]  # host verdicts, own order
    assert (
        mhub().verify_svc_collect_timeout.value(**{"class": "consensus"})
        == before + 1
    )
    stalls = [
        e for e in recorder().dump()["entries"]
        if e["kind"] == "verifysvc_collect_stall"
        and e.get("detail", {}).get("tenant") == "stall-t"
    ]
    assert stalls and stalls[-1]["detail"]["sigs"] == 3
    release.set()  # unpark the collector so teardown joins cleanly
    service_mod._reset_stall_gate()


def test_collect_stall_forensics_artifact(tmp_path):
    """report_collect_stall writes ONE rate-limited artifact naming the
    stuck class/tenant (and never raises)."""
    from cometbft_tpu.verifysvc import service as service_mod

    service_mod._reset_stall_gate()
    p1 = service_mod.report_collect_stall(
        Klass.CONSENSUS, "tenant-x", 5, 12.3, artifact_dir=str(tmp_path)
    )
    assert p1 and (tmp_path / p1.split("/")[-1]).exists()
    with open(p1) as f:
        body = f.read()
    assert "collect() deadline expired" in body and "tenant-x" in body
    # second report inside the rate window is suppressed (storm control)
    p2 = service_mod.report_collect_stall(
        Klass.CONSENSUS, "tenant-x", 5, 12.3, artifact_dir=str(tmp_path)
    )
    assert p2 is None
    service_mod._reset_stall_gate()


def test_checktx_collect_timeout_falls_back_to_host(svc, monkeypatch):
    import threading as _threading

    from cometbft_tpu.verifysvc import service as service_mod

    monkeypatch.setenv("COMETBFT_TPU_VERIFYSVC_COLLECT_TIMEOUT_MS", "200")
    s = svc(deadlines_ms={k: 0 for k in Klass}, failover=False)
    release = _threading.Event()

    class StuckBV:
        _entry = object()
        _fallback = None

        def __init__(self):
            self.items = []

        def add(self, *item):
            self.items.append(item)

        def submit(self):
            return ("stuck", list(self.items))

        def collect(self, ticket):
            release.wait(WAIT)
            return (True, [True] * len(ticket[1]))

    s._make_verifier = lambda mode: StuckBV()
    sk = host.PrivKey.from_seed(b"z" * 32)
    tx = checktx.make_signed_tx(sk, b"stuck-but-served")
    assert checktx.verify_tx_signature(tx, service=s) is True  # host path
    release.set()
    service_mod._reset_stall_gate()


# ------------------------------------------------------- CheckTx client


def test_signed_tx_envelope_roundtrip():
    sk = host.PrivKey.from_seed(b"e" * 32)
    tx = checktx.make_signed_tx(sk, b"payload-bytes")
    kt, pub, sig, payload = checktx.parse_signed_tx(tx)
    assert kt == "ed25519"
    assert pub == sk.pub_key().data and payload == b"payload-bytes"
    assert checktx.parse_signed_tx(b"unsigned") is None
    assert checktx.parse_signed_tx(checktx.MAGIC + b"short") is None


def test_legacy_envelope_wire_unchanged_after_key_type_byte(svc):
    """Envelope versioning pin (ISSUE 15): the PRE-key-type v1 wire —
    MAGIC | pub(32) | sig(64) | payload, built by hand exactly as every
    pre-v2 writer emitted it — must still parse to the same fields and
    verify unchanged, and ed25519 make_signed_tx must still EMIT that
    exact legacy wire (old planes keep understanding new txs)."""
    s = svc()
    sk = host.PrivKey.from_seed(b"v1" * 16)
    payload = b"old-wire-payload"
    sig = sk.sign(checktx.SIGN_DOMAIN + payload)
    legacy = checktx.MAGIC + sk.pub_key().data + sig + payload
    # the writer still emits byte-identical v1 for ed25519 keys
    assert checktx.make_signed_tx(sk, payload) == legacy
    kt, pub, psig, ppayload = checktx.parse_signed_tx(legacy)
    assert (kt, pub, psig, ppayload) == ("ed25519", sk.pub_key().data, sig, payload)
    assert checktx.verify_tx_signature(legacy, service=s) is True
    # tampering still detected through the legacy parse
    bad = bytearray(legacy)
    bad[-1] ^= 1
    assert checktx.verify_tx_signature(bytes(bad), service=s) is False


def test_v2_envelope_key_type_byte(svc):
    """The v2 wire: MAGIC_V2 | key_type(1) | pub | sig | payload, with
    per-type widths; unknown key-type bytes and truncated envelopes
    pass through unsigned (None) exactly like short v1 headers."""
    from cometbft_tpu.crypto import secp256k1 as secp

    s = svc()
    sk = secp.PrivKey.from_seed(b"v2-secp")
    tx = checktx.make_signed_tx(sk, b"typed-payload")
    assert tx.startswith(checktx.MAGIC_V2)
    assert tx[len(checktx.MAGIC_V2)] == checktx.KEY_TYPE_BYTES["secp256k1"]
    kt, pub, sig, payload = checktx.parse_signed_tx(tx)
    assert kt == "secp256k1" and len(pub) == 33 and len(sig) == 64
    assert payload == b"typed-payload"
    # a hand-built v2 ed25519 envelope parses too (the byte is enough)
    ed = host.PrivKey.from_seed(b"m" * 32)
    esig = ed.sign(checktx.SIGN_DOMAIN + b"p")
    v2ed = checktx.MAGIC_V2 + b"\x00" + ed.pub_key().data + esig + b"p"
    assert checktx.parse_signed_tx(v2ed) == ("ed25519", ed.pub_key().data, esig, b"p")
    assert checktx.verify_tx_signature(v2ed, service=s) is True
    # unknown key type byte / truncation -> unsigned pass-through
    assert checktx.parse_signed_tx(checktx.MAGIC_V2 + b"\x7f" + b"x" * 200) is None
    assert checktx.parse_signed_tx(checktx.MAGIC_V2 + b"\x01" + b"x" * 10) is None
    assert checktx.parse_signed_tx(checktx.MAGIC_V2) is None


def test_checktx_bit_identical_to_host_path(svc):
    """Service-batched CheckTx verdicts must match the host path bit for
    bit over valid, tampered-sig, tampered-payload, wrong-key, and
    unsigned txs."""
    s = svc(
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 5, Klass.BACKGROUND: 25,
        },
    )
    sk = host.PrivKey.from_seed(b"c" * 32)
    sk2 = host.PrivKey.from_seed(b"d" * 32)
    good = checktx.make_signed_tx(sk, b"k=v")
    bad_sig = bytearray(good)
    bad_sig[len(checktx.MAGIC) + 40] ^= 1  # flip a signature byte
    bad_payload = good + b"?"
    wrong_key = (
        checktx.MAGIC + sk2.pub_key().data + good[len(checktx.MAGIC) + 32 :]
    )
    corpus = [good, bytes(bad_sig), bad_payload, wrong_key, b"plain=tx", b""]

    def host_verdict(tx):
        parsed = checktx.parse_signed_tx(tx)
        if parsed is None:
            return None
        _, pub, sig, payload = parsed
        return host.verify_signature(pub, checktx.SIGN_DOMAIN + payload, sig)

    for tx in corpus:
        assert checktx.verify_tx_signature(tx, service=s) == host_verdict(tx)


def test_checktx_host_fallback_on_backpressure(svc):
    s = svc(
        queue_max=2,
        deadlines_ms={
            Klass.CONSENSUS: 0, Klass.BLOCKSYNC: 2,
            Klass.MEMPOOL: 60_000, Klass.BACKGROUND: 60_000,
        },
    )
    s.submit(_sigs(2, b"clog"), Klass.MEMPOOL)  # queue now at its bound
    sk = host.PrivKey.from_seed(b"f" * 32)
    tx = checktx.make_signed_tx(sk, b"still-works")
    assert checktx.verify_tx_signature(tx, service=s) is True  # host path


def test_mempool_checktx_gate(svc):
    """CListMempool admits valid signed txs, rejects invalid signatures
    before the app round trip, and leaves unsigned txs untouched."""
    from cometbft_tpu.mempool import CListMempool, MempoolConfig
    from cometbft_tpu.mempool.mempool import InvalidTxSignatureError
    from cometbft_tpu.wire import abci_pb as pb

    class AcceptAllClient:
        def __init__(self):
            self.seen = []

        def check_tx(self, req):
            self.seen.append(req.tx)
            return pb.CheckTxResponse(code=0, gas_wanted=1)

        def flush(self):
            pass

    client = AcceptAllClient()
    mp = CListMempool(MempoolConfig(), client)
    sk = host.PrivKey.from_seed(b"g" * 32)

    good = checktx.make_signed_tx(sk, b"signed-good")
    mp.check_tx(good)
    assert mp.size() == 1 and client.seen == [good]

    bad = bytearray(checktx.make_signed_tx(sk, b"signed-bad"))
    bad[-1] ^= 1  # corrupt the payload -> signature mismatch
    failed_before = mhub().mp_failed_txs.value()
    with pytest.raises(InvalidTxSignatureError):
        mp.check_tx(bytes(bad))
    assert mp.size() == 1
    assert client.seen == [good]  # the app never saw the bad tx
    assert mhub().mp_failed_txs.value() == failed_before + 1
    # rejected tx left the cache: a corrected resubmission is not deduped
    with pytest.raises(InvalidTxSignatureError):
        mp.check_tx(bytes(bad))

    mp.check_tx(b"unsigned=ok")  # no envelope: gate is a no-op
    assert mp.size() == 2


def test_mempool_checktx_gate_disabled(monkeypatch):
    from cometbft_tpu.mempool import CListMempool, MempoolConfig
    from cometbft_tpu.wire import abci_pb as pb

    monkeypatch.setenv("COMETBFT_TPU_VERIFYSVC_CHECKTX", "0")

    class AcceptAllClient:
        def check_tx(self, req):
            return pb.CheckTxResponse(code=0, gas_wanted=1)

        def flush(self):
            pass

    mp = CListMempool(MempoolConfig(), AcceptAllClient())
    sk = host.PrivKey.from_seed(b"h" * 32)
    bad = bytearray(checktx.make_signed_tx(sk, b"x"))
    bad[-1] ^= 1
    mp.check_tx(bytes(bad))  # gate off: the app owns validation
    assert mp.size() == 1


# ------------------------------------------------------------- plumbing


def test_rpc_route_registered():
    from cometbft_tpu.rpc.core import ROUTES

    assert "verify_svc_status" in ROUTES


def test_service_stats_shape(svc):
    s = svc()
    ok, per = s.verify(_sigs(2, b"st"), Klass.CONSENSUS)
    assert ok and per == [True, True]
    st = s.stats()
    assert st["dispatched_batches"]["consensus"] == 1
    assert set(st["queued"]) == {
        "consensus", "blocksync", "mempool", "background", "proof",
    }
    assert st["deadline_ms"]["consensus"] == 0.0


def test_create_batch_verifier_routes_through_service(monkeypatch):
    """The factory seam: device-capable backends get a verify-service
    client; the cpu backend keeps the sequential host verifier (no
    async seam, callers run sync)."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.models.verifier import CpuEd25519BatchVerifier

    bv = crypto_batch.create_batch_verifier("ed25519")
    assert isinstance(bv, ServiceBatchVerifier)
    assert bv.klass == Klass.CONSENSUS
    bv2 = crypto_batch.create_batch_verifier("ed25519", klass=Klass.BLOCKSYNC)
    assert bv2.klass == Klass.BLOCKSYNC

    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "cpu")
    bv3 = crypto_batch.create_batch_verifier("ed25519")
    assert isinstance(bv3, CpuEd25519BatchVerifier)
    assert not hasattr(bv3, "submit")
