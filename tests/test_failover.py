"""Degraded-mode failover (verifysvc/service.py) + the fault registry
(utils/fail.py): automatic TPU->CPU switchover, stranded-batch host
re-verification with blame order preserved, probation restore, and the
injectable faults that prove it all on CPU-only CI.

All tests are fast and CPU-only: the "device" is a fake verifier whose
tickets route through the real scheduler/collector/host-worker threads,
so the machinery under test (trip detection, generation respawn,
first-wins settlement) is the production code path end to end.
"""

import glob
import threading
import time

import pytest

from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.utils import fail, healthmon
from cometbft_tpu.utils.flightrec import recorder
from cometbft_tpu.utils.metrics import hub as mhub
from cometbft_tpu.verifysvc.client import ServiceBatchVerifier, resolve_mode
from cometbft_tpu.verifysvc.service import (
    MODE_CPU_FALLBACK,
    MODE_PLAIN,
    MODE_TPU,
    Klass,
    VerifyService,
    _HostBatchVerifier,
)

WAIT = 15.0


def _sigs(n, tag=b"t", tamper=()):
    out = []
    for i in range(n):
        sk = host.PrivKey.from_seed(bytes([11 + i]) * 32)
        msg = b"%s-%d" % (tag, i)
        sig = sk.sign(msg)
        if i in tamper:
            msg += b"!"
        out.append((sk.pub_key().data, msg, sig))
    return out


def _host_verdicts(items):
    res = [host.verify_signature(p, m, s) for (p, m, s) in items]
    return all(res) and bool(res), res


def _probe(ok, detail="stub"):
    return healthmon.ProbeResult(ok, detail, 0.0)


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    fail.clear_all()
    yield
    fail.clear_all()


@pytest.fixture
def svc(tmp_path):
    services = []

    def make(**kw):
        kw.setdefault("artifact_dir", str(tmp_path))
        kw.setdefault("probe_fn", lambda _t: _probe(False, "probe off"))
        s = VerifyService(**kw)
        services.append(s)
        return s

    yield make
    fail.clear_all()  # un-wedge parked workers before joining them
    for s in services:
        s.stop()


class FakeDeviceBV:
    """A 'device' verifier: returns a non-sync ticket (so the collector's
    device-wait seam — where the wedge faults bite — is exercised) whose
    collect() computes host verdicts."""

    _entry = object()  # non-None: not offloaded to the host worker
    _fallback = None

    def __init__(self):
        self._items = []

    def add(self, pub, msg, sig):
        self._items.append((pub, msg, sig))

    def submit(self):
        return ("dev", list(self._items))

    def collect(self, ticket):
        return _host_verdicts(ticket[1])


def _fake_device(s):
    """Stand a fake device in for the TPU path ONLY: in CPU fallback
    mode the production routing (_HostBatchVerifier) must stay in
    charge — that switch is part of what these tests verify."""
    real = VerifyService._make_verifier.__get__(s)
    s._make_verifier = (
        lambda mode: FakeDeviceBV() if s.backend_mode == MODE_TPU
        else real(mode)
    )


def _verify(s, items, klass):
    """submit+collect with a bounded wait: a regression that strands a
    ticket must FAIL the test, never hang it."""
    return s.submit(items, klass).collect(WAIT)


def _new_events(seq0, kind):
    return [
        e for e in recorder().dump()["entries"]
        if e["seq"] > seq0 and e["kind"] == kind
    ]


def _last_seq():
    entries = recorder().dump()["entries"]
    return entries[-1]["seq"] if entries else 0


# -------------------------------------------------------- fault registry


def test_fault_registry_arm_clear_consume():
    assert fail.armed("wedge_device") is None  # zero-cost fast path
    fail.arm("wedge_device")
    assert fail.armed("wedge_device") == 1.0
    fail.clear("wedge_device")
    assert fail.armed("wedge_device") is None

    fail.arm("double_sign", 2)
    assert fail.consume("double_sign") == 2.0
    assert fail.consume("double_sign") == 1.0
    assert fail.consume("double_sign") is None  # self-disarmed
    assert fail.fired()["double_sign"] >= 2

    with pytest.raises(ValueError, match="unknown fault"):
        fail.arm("not_a_fault")


def test_fault_env_arming(monkeypatch):
    import importlib

    import cometbft_tpu.utils.fail as fail_mod

    monkeypatch.setenv("COMETBFT_TPU_FAULT_SLOW_COLLECT", "2.5")
    monkeypatch.setenv("COMETBFT_TPU_FAULT_DROP_P2P_PCT", "junk")
    try:
        importlib.reload(fail_mod)
        assert fail_mod.armed("slow_collect") == 2.5
        assert fail_mod.armed("drop_p2p_pct") == 1.0  # non-numeric -> 1
    finally:
        monkeypatch.delenv("COMETBFT_TPU_FAULT_SLOW_COLLECT")
        monkeypatch.delenv("COMETBFT_TPU_FAULT_DROP_P2P_PCT")
        importlib.reload(fail_mod)
        fail_mod.clear_all()


def test_wedge_wait_blocks_until_cleared():
    assert fail.wedge_wait() == 0.0  # unarmed: instant
    fail.arm("wedge_device")
    released = []

    def waiter():
        released.append(fail.wedge_wait(poll_s=0.01))

    t = threading.Thread(target=waiter, name="t-wedge-waiter")
    t.start()
    time.sleep(0.15)
    assert not released  # still parked
    fail.clear("wedge_device")
    t.join(WAIT)
    assert released and released[0] >= 0.1


def test_drop_p2p_seam():
    from cometbft_tpu.p2p.conn.connection import MConnection

    assert fail.should_drop(0) is False
    assert fail.should_drop(100) is True
    assert MConnection._fault_drop() is False  # unarmed
    fail.arm("drop_p2p_pct", 100)
    assert MConnection._fault_drop() is True
    fail.clear("drop_p2p_pct")
    assert MConnection._fault_drop() is False


def test_probe_devices_honors_wedge_fault():
    fail.arm("wedge_device")
    t0 = time.monotonic()
    res = healthmon.probe_devices(30.0)
    assert time.monotonic() - t0 < 1.0  # no subprocess, no waiting
    assert not res.ok and res.timed_out
    assert "wedge_device" in res.detail


# ------------------------------------------------- acceptance: the trip


def test_wedge_mid_batch_trips_and_preserves_blame_order(svc, tmp_path):
    """THE acceptance scenario, in-process: under mixed load (consensus
    + mempool + background), a device wedge mid-batch trips the service
    to CPU mode within the deadline — every stranded ticket resolves
    with verdicts bit-identical to the host path, per-sig blame in the
    caller's own add() order, exactly one forensics artifact and one
    mode-transition flightrec event are emitted, and clearing the fault
    restores TPU mode via probation — all asserted from the emitted
    metrics/flightrec/artifacts."""
    probe_ok = threading.Event()
    s = svc(
        deadlines_ms={k: 0 for k in Klass},
        batch_deadline_s=0.3,
        failover_tick_s=0.05,
        probation_ok=2,
        probe_period_s=0.05,
        probe_fn=lambda _t: _probe(probe_ok.is_set()),
    )
    _fake_device(s)
    seq0 = _last_seq()
    mode_before = mhub().verify_svc_backend_mode.value()

    loads = {
        "cs": (_sigs(5, b"cs", tamper=(3,)), Klass.CONSENSUS),
        "mp1": (_sigs(3, b"mp1", tamper=(0,)), Klass.MEMPOOL),
        "mp2": (_sigs(2, b"mp2"), Klass.MEMPOOL),
        "bg": (_sigs(4, b"bg", tamper=(1, 2)), Klass.BACKGROUND),
    }
    fail.arm("wedge_device")  # the wedge is live when the batches land
    tickets = {
        name: s.submit(items, klass) for name, (items, klass) in loads.items()
    }

    # every stranded ticket resolves (host re-verify), blame bit-exact
    for name, (items, _k) in loads.items():
        ok, per = tickets[name].collect(WAIT)
        assert (ok, per) == _host_verdicts(items), name

    assert s.backend_mode == MODE_CPU_FALLBACK
    st = s.stats()
    assert st["backend_mode"] == "cpu_fallback"
    assert st["failover"]["trips"] == 1
    assert "deadline" in st["failover"]["last_trip_reason"]

    # exactly one to_cpu flightrec event + one forensics artifact
    to_cpu = _new_events(seq0, "verifysvc_failover")
    assert [e["detail"]["direction"] for e in to_cpu] == ["to_cpu"]
    assert to_cpu[0]["detail"]["stranded_batches"] >= 1

    deadline = time.monotonic() + WAIT
    while st["failover"]["last_artifact"] is None and time.monotonic() < deadline:
        time.sleep(0.05)
        st = s.stats()
    artifacts = glob.glob(str(tmp_path / "cometbft-health-*"))
    assert len(artifacts) == 1 and st["failover"]["last_artifact"] == artifacts[0]
    with open(artifacts[0]) as f:
        body = f.read()
    assert "failover to cpu_fallback" in body and "verify service (at trip)" in body

    # the mode gauge flipped
    assert mhub().verify_svc_backend_mode.value() == 1.0

    # post-trip submissions keep resolving, host-side, wedge still armed
    items = _sigs(3, b"post", tamper=(2,))
    assert _verify(s, items, Klass.CONSENSUS) == _host_verdicts(items)

    # heal: probe starts succeeding -> probation restores TPU mode
    fail.clear("wedge_device")
    probe_ok.set()
    deadline = time.monotonic() + WAIT
    while s.backend_mode != MODE_TPU and time.monotonic() < deadline:
        time.sleep(0.05)
    assert s.backend_mode == MODE_TPU
    assert mhub().verify_svc_backend_mode.value() == 0.0
    restores = [
        e for e in _new_events(seq0, "verifysvc_failover")
        if e["detail"]["direction"] == "to_tpu"
    ]
    assert len(restores) == 1
    assert s.stats()["failover"]["restores"] == 1

    # back in TPU mode the fake device serves again, vanilla
    items = _sigs(2, b"again")
    assert _verify(s, items, Klass.CONSENSUS) == _host_verdicts(items)
    mhub().verify_svc_backend_mode.set(mode_before)  # don't leak to other tests


def test_health_sentinel_wedged_trips_service(svc):
    """The second trip trigger: no stuck batch at all, but the health
    sentinel judges the accelerator wedged — the watchdog must trip
    preemptively so the NEXT batch routes host-side instead of
    stranding."""
    mon = healthmon.HealthMonitor(
        probe_fn=lambda _t: _probe(False, "down"), wedge_after=1,
        probe_period_s=60.0,
    )
    mon._state = healthmon.STATE_WEDGED
    healthmon.install(mon)
    try:
        s = svc(deadlines_ms={k: 0 for k in Klass}, failover_tick_s=0.05)
        s._ensure_started()
        deadline = time.monotonic() + WAIT
        while s.backend_mode != MODE_CPU_FALLBACK and time.monotonic() < deadline:
            time.sleep(0.02)
        assert s.backend_mode == MODE_CPU_FALLBACK
        assert "sentinel" in s.stats()["failover"]["last_trip_reason"]
        items = _sigs(2, b"hw", tamper=(1,))
        assert _verify(s, items, Klass.CONSENSUS) == _host_verdicts(items)
    finally:
        healthmon.uninstall()


def test_fail_dispatch_reverifies_on_host(svc):
    """An injected dispatch error (fail_dispatch): with failover on, the
    batch re-verifies host-side with bit-identical verdicts — no failed
    tickets, no mode flip (errors are not hangs)."""
    s = svc(deadlines_ms={k: 0 for k in Klass})
    before = mhub().verify_svc_host_reverify.value(cause="dispatch_error")
    fail.arm("fail_dispatch")
    items = _sigs(4, b"fd", tamper=(1, 3))
    assert _verify(s, items, Klass.MEMPOOL) == _host_verdicts(items)
    assert s.backend_mode == MODE_TPU  # an error round-trips, not trips
    assert (
        mhub().verify_svc_host_reverify.value(cause="dispatch_error")
        == before + 1
    )
    fail.clear("fail_dispatch")
    items = _sigs(2, b"ok")
    assert _verify(s, items, Klass.MEMPOOL) == _host_verdicts(items)


def test_slow_collect_fault_delays_but_resolves(svc):
    s = svc(deadlines_ms={k: 0 for k in Klass})
    _fake_device(s)
    fail.arm("slow_collect", 0.3)
    items = _sigs(2, b"slow")
    t0 = time.monotonic()
    assert _verify(s, items, Klass.CONSENSUS) == _host_verdicts(items)
    assert time.monotonic() - t0 >= 0.25


def test_ticket_resolution_is_first_wins():
    from cometbft_tpu.verifysvc.service import Ticket

    t = Ticket(1)
    assert t._resolve((True, [True])) is True
    assert t._resolve((False, [False])) is False  # late loser discarded
    assert t._fail(RuntimeError("late")) is False
    assert t.collect(0.1) == (True, [True])


def test_sweep_resolves_batch_that_raced_the_trip(svc):
    """A batch can bind a device verifier concurrently with a trip (the
    scheduler reads the mode before tracking) and miss the stranded
    snapshot: the CPU-mode sweep must still resolve it once it is
    overdue on the device deadline."""
    from cometbft_tpu.verifysvc.service import _Request

    s = svc(
        deadlines_ms={k: 0 for k in Klass},
        batch_deadline_s=0.2,
        failover_tick_s=0.05,
    )
    s._ensure_started()
    assert s.trip_to_cpu("test: simulated wedge") is True
    # simulate the raced batch: tracked as dispatched-to-device AFTER
    # the trip snapshot, its collector parked in the wedge forever
    items = _sigs(3, b"race", tamper=(1,))
    req = _Request(items, Klass.CONSENSUS, MODE_PLAIN)
    batch = [req]
    s._track_inflight(batch, "device")
    assert req.ticket.collect(WAIT) == _host_verdicts(items)
    # the sweep also untracks the entry: a stale ever-aging record
    # would re-trip the service the moment probation restores
    deadline = time.monotonic() + WAIT
    while id(batch) in s._inflight and time.monotonic() < deadline:
        time.sleep(0.02)
    assert id(batch) not in s._inflight


def test_host_loop_reroutes_stale_device_payload_after_trip(svc):
    """A device-bound payload queued on the host worker when the trip
    lands (or racing it with pending tickets) must not be submitted to
    the wedged device: done batches are skipped, pending ones are
    rebuilt on the host path — and degraded traffic keeps flowing."""
    from cometbft_tpu.verifysvc.service import _Request

    s = svc(deadlines_ms={k: 0 for k in Klass})
    s._ensure_started()
    assert s.trip_to_cpu("test: wedge") is True
    fail.arm("wedge_device")  # a device collect would park forever
    items = _sigs(3, b"stale", tamper=(0,))
    req = _Request(items, Klass.CONSENSUS, MODE_PLAIN)
    bv = FakeDeviceBV()
    for pub, msg, sig in items:
        bv.add(pub, msg, sig)
    s._track_inflight([req], "host")
    s._hostq.put((int(Klass.CONSENSUS), next(s._hostseq), (bv, [req], 0)))
    assert req.ticket.collect(WAIT) == _host_verdicts(items)
    items2 = _sigs(2, b"after")
    assert _verify(s, items2, Klass.CONSENSUS) == _host_verdicts(items2)


def test_host_worker_time_exempt_from_trip_deadline(svc):
    """Host-worker submit time (cold XLA compiles: legitimate
    minutes-long work) never counts toward the device trip deadline —
    the deadline clock starts at the host->device relabel."""
    from cometbft_tpu.verifysvc.service import _Request

    s = svc(batch_deadline_s=0.2)
    items = _sigs(1, b"cold")
    batch = [_Request(items, Klass.CONSENSUS, MODE_PLAIN)]
    s._track_inflight(batch, "host")
    rec = s._inflight[id(batch)]
    rec["since"] -= 300.0  # five minutes "compiling" on the host worker
    assert s._trip_reason() is None  # host time exempt
    s._relabel_inflight(batch, "device")  # forwarded to the collector
    assert s._trip_reason() is None  # deadline clock just started
    rec["device_since"] -= 1.0
    assert "deadline" in s._trip_reason()
    s._untrack_inflight(batch)


def test_compile_wait_exempt_from_trip_deadline_up_to_a_bound(svc, monkeypatch):
    """A device-tracked batch waiting for its program to compile (a comb
    batch's first payload width compiles on the staging thread, minutes
    on a cold cache) is off the deadline clock; the clock starts anew
    when the program is there, so a hang AFTER the compile still trips;
    and a compile past COMPILE_BOUND_S counts as the hang it is."""
    from cometbft_tpu.verifysvc import service as service_mod

    class CompilingBV(FakeDeviceBV):
        on_compile = None  # the service sets it per dispatched batch
        release = threading.Event()

        def submit(self):
            self.on_compile(True)  # program missing: waits from here
            CompilingBV.told = self.on_compile
            return ("dev", list(self._items))

        def collect(self, ticket):
            CompilingBV.release.wait(WAIT)
            return _host_verdicts(ticket[1])

    s = svc(batch_deadline_s=0.2, failover_tick_s=0.05)
    s._make_verifier = lambda mode: (
        CompilingBV() if s.backend_mode == MODE_TPU
        else _HostBatchVerifier(mode)
    )
    items = _sigs(3, b"cold", tamper=(1,))
    ticket = s.submit(items, Klass.CONSENSUS)
    time.sleep(0.8)  # four deadlines "compiling"
    assert s.backend_mode == MODE_TPU and not ticket.done()
    assert s.stats()["in_flight"][0]["device_age_s"] is None
    CompilingBV.told(False)  # compiled; the dispatch is made only now
    assert (s.stats()["in_flight"][0]["device_age_s"] or 0.0) < 0.2
    assert ticket.collect(WAIT) == _host_verdicts(items)  # the trip's re-verify
    assert s.backend_mode == MODE_CPU_FALLBACK
    CompilingBV.release.set()

    # a compile that never returns is a hang once the bound is past
    monkeypatch.setattr(service_mod, "COMPILE_BOUND_S", 0.3)
    s2 = svc(batch_deadline_s=0.2, failover_tick_s=0.05)
    CompilingBV.release = threading.Event()
    s2._make_verifier = lambda mode: (
        CompilingBV() if s2.backend_mode == MODE_TPU
        else _HostBatchVerifier(mode)
    )
    assert s2.submit(items, Klass.CONSENSUS).collect(WAIT) == _host_verdicts(items)
    assert s2.backend_mode == MODE_CPU_FALLBACK
    CompilingBV.release.set()


def test_service_restarts_after_stop(svc):
    """stop() then a later submit restarts the service; the stale stop
    signal must not leave the failover watchdog busy-spinning."""
    s = svc(deadlines_ms={k: 0 for k in Klass})
    items = _sigs(2, b"r1")
    assert _verify(s, items, Klass.CONSENSUS) == _host_verdicts(items)
    s.stop()
    assert s._stop_ev.is_set()
    items = _sigs(2, b"r2", tamper=(0,))
    assert _verify(s, items, Klass.CONSENSUS) == _host_verdicts(items)
    assert not s._stop_ev.is_set()


# ------------------------------------------- repeated failover cycling


def test_repeated_failover_cycles_multitenant(svc):
    """Satellite (PR 12): three full trip→probation→restore cycles
    under CONCURRENT multi-tenant load — zero lost tickets (every
    collect resolves) and per-request blame preserved bit-identical to
    the host path across every cycle, for every tenant and class."""
    probe_ok = threading.Event()
    s = svc(
        deadlines_ms={k: 0 for k in Klass},
        batch_deadline_s=0.25,
        failover_tick_s=0.03,
        probation_ok=1,
        probe_period_s=0.03,
        probe_fn=lambda _t: _probe(probe_ok.is_set()),
    )
    _fake_device(s)
    stop = threading.Event()
    res_mtx = threading.Lock()
    results: list[tuple[str, list, tuple]] = []
    errors: list[str] = []

    def loader(tenant: str, klass: Klass, tag: bytes):
        i = 0
        while not stop.is_set():
            items = _sigs(3, tag + b"-%d" % (i % 4), tamper=(i % 3,))
            try:
                got = s.submit(items, klass, tenant=tenant).collect(WAIT)
            except Exception as e:  # noqa: BLE001 — a lost/errored ticket fails the test
                errors.append(f"{tenant}: {type(e).__name__}: {e}")
                return
            with res_mtx:
                results.append((tenant, items, got))
            i += 1
            time.sleep(0.005)

    loaders = [
        ("chain-a", Klass.CONSENSUS, b"la"),
        ("chain-b", Klass.CONSENSUS, b"lb"),
        ("chain-b", Klass.MEMPOOL, b"lm"),
        ("chain-c", Klass.BACKGROUND, b"lc"),
    ]
    threads = [
        threading.Thread(
            target=loader, args=spec, name=f"t-cycle-loader-{i}"
        )
        for i, spec in enumerate(loaders)
    ]
    for t in threads:
        t.start()
    try:
        for cycle in range(3):
            probe_ok.clear()
            fail.arm("wedge_device")
            deadline = time.monotonic() + WAIT
            while (
                s.backend_mode != MODE_CPU_FALLBACK
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            assert s.backend_mode == MODE_CPU_FALLBACK, f"cycle {cycle}: no trip"
            n0 = len(results)
            time.sleep(0.2)  # degraded traffic must keep flowing
            assert len(results) > n0, f"cycle {cycle}: no progress while tripped"
            fail.clear("wedge_device")
            probe_ok.set()
            deadline = time.monotonic() + WAIT
            while s.backend_mode != MODE_TPU and time.monotonic() < deadline:
                time.sleep(0.02)
            assert s.backend_mode == MODE_TPU, f"cycle {cycle}: no restore"
            time.sleep(0.1)  # restored traffic flows before the next trip
    finally:
        stop.set()
        for t in threads:
            t.join(WAIT)
    assert not errors, errors
    st = s.stats()
    assert st["failover"]["trips"] == 3 and st["failover"]["restores"] == 3
    # every resolved ticket, from every cycle/mode, bit-identical to the
    # host path with blame in its own add() order — and all four tenant
    # streams made progress
    assert len(results) >= 20
    seen_tenants = set()
    for tenant, items, got in results:
        seen_tenants.add(tenant)
        assert got == _host_verdicts(items), tenant
    assert seen_tenants == {"chain-a", "chain-b", "chain-c"}
    # per-tenant dispatch accounting survived the worker respawns
    tallies = st["tenants"]
    for tenant in seen_tenants:
        assert tallies[tenant]["dispatched_batches"] > 0


# ---------------------------------------------------- CPU-mode routing


def test_make_verifier_bypasses_comb_in_cpu_mode(svc):
    s = svc()
    s._backend_mode = MODE_CPU_FALLBACK
    bv = s._make_verifier(("comb", object()))
    assert isinstance(bv, _HostBatchVerifier)


def test_resolve_mode_bypasses_comb_bind_when_tripped(monkeypatch):
    """A tripped global service makes resolve_mode return MODE_PLAIN
    without ever touching the comb cache — a table build is device work
    and would hang with the wedged device."""
    from cometbft_tpu.verifysvc import service as service_mod

    s = VerifyService(probe_fn=lambda _t: _probe(False))
    s._backend_mode = MODE_CPU_FALLBACK  # tripped, threads never started
    monkeypatch.setattr(service_mod, "_GLOBAL", s)
    called = []
    monkeypatch.setattr(
        "cometbft_tpu.models.comb_verifier.global_cache",
        lambda: called.append(1),
    )
    pubs = [bytes([i % 256]) * 32 for i in range(600)]  # >= comb_min
    assert resolve_mode(pubs) == MODE_PLAIN
    assert not called


def test_client_fallback_and_cpu_mode_identical_results(svc):
    s = svc(deadlines_ms={k: 0 for k in Klass})
    s._backend_mode = MODE_CPU_FALLBACK
    items = _sigs(4, b"cli", tamper=(0, 2))
    bv = ServiceBatchVerifier(Klass.BLOCKSYNC, service=s)
    for pub, msg, sig in items:
        bv.add(pub, msg, sig)
    assert bv.verify() == _host_verdicts(items)


# ----------------------------------------------------------- RPC plumbing


def test_fault_rpc_routes_registered_and_gated(monkeypatch):
    from cometbft_tpu.rpc.core import ROUTES, Environment, RPCError

    for route in ("arm_fault", "clear_fault", "faults"):
        assert route in ROUTES

    env = Environment(node=None)  # fault routes never touch the node
    with pytest.raises(RPCError, match="disabled"):
        env.arm_fault(name="wedge_device")
    with pytest.raises(RPCError, match="disabled"):
        env.clear_fault()
    # observing is never unsafe
    assert env.faults()["rpc_enabled"] is False

    monkeypatch.setenv("COMETBFT_TPU_FAULT_RPC", "1")
    assert env.arm_fault(name="slow_collect", value=1.5) == {
        "armed": {"slow_collect": 1.5}
    }
    assert env.faults()["armed"] == {"slow_collect": 1.5}
    with pytest.raises(RPCError, match="unknown fault"):
        env.arm_fault(name="bogus")
    assert env.clear_fault() == {"armed": {}}


# --------------------------------------------------- consensus seam unit


def test_double_sign_seam_broadcasts_conflicting_vote():
    """The _maybe_double_sign seam: armed, a signed non-nil prevote is
    accompanied by a BROADCAST-only conflicting vote that verifies under
    the validator's key and differs only in block_id — the raw material
    of DuplicateVoteEvidence."""
    from types import SimpleNamespace

    from cometbft_tpu.consensus.state import ConsensusState
    from cometbft_tpu.privval.file_pv import FilePV
    from cometbft_tpu.types.block import BlockID, PartSetHeader
    from cometbft_tpu.types.vote import Vote
    from cometbft_tpu.wire.canonical import PREVOTE_TYPE, Timestamp

    pv = FilePV.generate()
    chain_id = "seam-chain"
    vote = Vote(
        type=PREVOTE_TYPE, height=5, round=0,
        block_id=BlockID(
            hash=b"\xaa" * 32, part_set_header=PartSetHeader(1, b"\xbb" * 32)
        ),
        timestamp=Timestamp.from_unix_ns(1),
        validator_address=pv.get_address(), validator_index=0,
    )
    sent = []
    cs = SimpleNamespace(
        priv_validator=pv,
        broadcast_hook=sent.append,
        _replay_mode=False,
        state=SimpleNamespace(chain_id=chain_id),
        logger=SimpleNamespace(error=lambda *_a, **_k: None),
    )

    # unarmed: nothing happens (zero-cost path)
    ConsensusState._maybe_double_sign(cs, vote)
    assert not sent

    fail.arm("double_sign", 1)
    # nil votes never burn the shot
    nil_vote = Vote(
        type=PREVOTE_TYPE, height=5, round=0, block_id=BlockID(),
        timestamp=Timestamp.from_unix_ns(1),
        validator_address=pv.get_address(), validator_index=0,
    )
    ConsensusState._maybe_double_sign(cs, nil_vote)
    assert not sent and fail.armed("double_sign") is not None

    ConsensusState._maybe_double_sign(cs, vote)
    assert len(sent) == 1
    conflicting = sent[0].vote
    assert (conflicting.height, conflicting.round, conflicting.type) == (
        vote.height, vote.round, vote.type,
    )
    assert conflicting.block_id.hash != vote.block_id.hash
    conflicting.verify(chain_id, pv.get_pub_key())  # raises if bad
    # one-shot: consumed
    assert fail.armed("double_sign") is None
    ConsensusState._maybe_double_sign(cs, vote)
    assert len(sent) == 1


# ------------------------------------------------------------ stats shape


def test_stats_carry_failover_section(svc):
    s = svc()
    st = s.stats()
    assert st["backend_mode"] == "tpu"
    fo = st["failover"]
    assert fo["enabled"] is True and fo["trips"] == 0
    assert fo["batch_deadline_ms"] > 0
    assert "last_artifact" in fo and "last_trip_reason" in fo
