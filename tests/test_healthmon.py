"""Node health sentinel (cometbft_tpu/utils/healthmon): hang-proof
probe judging, the ok→degraded→wedged state machine, heartbeat
staleness blame, forensics artifact rate-limiting, the /tpu_health
route, and off-by-default zero overhead.

All fast and CPU-only: probes are stubbed (an Event-blocked stub stands
in for an accelerator that hangs — the real probe, in both its forms,
is exercised once each at the end), periods are tens of milliseconds, and
the sentinel is driven deterministically through tick() except for the
one end-to-end test that runs the real thread.
"""

import os
import threading
import time

import pytest

from cometbft_tpu.utils import healthmon
from cometbft_tpu.utils.flightrec import recorder as flightrec
from cometbft_tpu.utils.healthmon import (
    STATE_DEGRADED,
    STATE_OK,
    STATE_WEDGED,
    HealthMonitor,
    ProbeResult,
)
from cometbft_tpu.utils.metrics import hub as mhub

WAIT = 10.0


def _ok_probe(timeout_s):
    return ProbeResult(True, "cpu", 0.001)


def _fail_probe(timeout_s):
    return ProbeResult(False, "probe exited 1", 0.002)


class _BlockingProbe:
    """A probe wedged like a hung accelerator: blocks until released (or
    forever), which the sentinel must survive without ever blocking."""

    def __init__(self):
        self.release = threading.Event()
        self.calls = 0

    def __call__(self, timeout_s):
        self.calls += 1
        self.release.wait(WAIT)
        return ProbeResult(True, "late", 0.0)


@pytest.fixture
def mon():
    """Construct-and-install monitors; always uninstalled afterwards so
    beats drop back to the zero-overhead no-op for every other test."""
    made = []

    def make(**kw):
        kw.setdefault("probe_period_s", 0.05)
        kw.setdefault("probe_timeout_s", 0.05)
        kw.setdefault("probe_grace_s", 0.05)
        kw.setdefault("artifact_min_interval_s", 0.0)
        m = HealthMonitor(**kw)
        made.append(m)
        healthmon.install(m)
        return m

    yield make
    healthmon.uninstall()
    for m in made:
        m.stop()


# ------------------------------------------------------- state machine


def test_ok_probe_keeps_state_ok(mon, tmp_path):
    m = mon(probe_fn=_ok_probe, artifact_dir=str(tmp_path))
    m.tick()
    deadline = time.monotonic() + WAIT
    while m.snapshot()["probe_attempts"] == 0:
        assert time.monotonic() < deadline
        time.sleep(0.005)
        m.tick()
    snap = m.snapshot()
    assert snap["state"] == STATE_OK
    assert snap["ready"] is True
    assert snap["last_probe"]["ok"] is True
    assert snap["consecutive_probe_failures"] == 0
    assert list(tmp_path.iterdir()) == []  # healthy: no forensics


def test_failing_probe_walks_degraded_then_wedged(mon, tmp_path):
    m = mon(probe_fn=_fail_probe, wedge_after=2, artifact_dir=str(tmp_path))
    now = time.monotonic()
    m.tick(now)  # kicks probe 1 (worker ingests the failure async)
    deadline = time.monotonic() + WAIT
    while m.snapshot()["consecutive_probe_failures"] < 1:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    m.tick(now + 0.01)  # state-machine pass; next probe period not reached
    assert m.snapshot()["state"] == STATE_DEGRADED
    # second probe period -> second failure -> wedged
    m.tick(now + 0.06)
    deadline = time.monotonic() + WAIT
    while m.snapshot()["consecutive_probe_failures"] < 2:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    m.tick(now + 0.07)
    snap = m.snapshot()
    assert snap["state"] == STATE_WEDGED
    assert snap["ready"] is False


def test_blocking_probe_never_blocks_sentinel_and_wedges(mon, tmp_path):
    """The acceptance scenario: a probe that blocks PAST its deadline
    (the stubbed hung accelerator) drives the state to wedged via judged
    hang failures, and every tick() returns promptly — the sentinel
    itself is hang-proof."""
    probe = _BlockingProbe()
    m = mon(probe_fn=probe, wedge_after=2, artifact_dir=str(tmp_path))
    t0 = time.monotonic()
    m.tick(t0)  # kicks the probe; worker thread now parked in the stub
    assert time.monotonic() - t0 < 0.5  # tick returned, probe still stuck
    # past deadline+grace: judged as a hang -> failure 1 -> degraded
    m.tick(t0 + 0.11)
    snap = m.snapshot()
    assert snap["consecutive_probe_failures"] == 1
    assert snap["state"] == STATE_DEGRADED
    assert snap["last_probe"]["timed_out"] is True
    # next probe period with the worker STILL stuck: failure 2 -> wedged
    m.tick(t0 + 0.17)
    snap = m.snapshot()
    assert snap["consecutive_probe_failures"] == 2
    assert snap["state"] == STATE_WEDGED
    assert probe.calls == 1  # never piles up probe threads on a wedge
    probe.release.set()


def test_probe_recovery_snaps_back_to_ok(mon, tmp_path):
    results = [ProbeResult(False, "probe exited 1", 0.0)]

    def probe(timeout_s):
        return results[-1]

    m = mon(probe_fn=probe, wedge_after=1, artifact_dir=str(tmp_path))
    deadline = time.monotonic() + WAIT
    while m.snapshot()["state"] != STATE_WEDGED:
        assert time.monotonic() < deadline
        m.tick()
        time.sleep(0.005)
    results.append(ProbeResult(True, "tpu", 0.01))
    deadline = time.monotonic() + WAIT
    while m.snapshot()["state"] != STATE_OK:
        assert time.monotonic() < deadline
        m.tick()
        time.sleep(0.005)
    snap = m.snapshot()
    assert snap["consecutive_probe_failures"] == 0
    assert snap["ready"] is True


# ----------------------------------------------------------- heartbeats


def test_stale_heartbeat_blames_exact_loop(mon, tmp_path):
    m = mon(
        probe_fn=_ok_probe,
        artifact_dir=str(tmp_path),
        loops={"my-loop": 0.05, "other-loop": 30.0},
    )
    healthmon.beat("my-loop")
    healthmon.beat("other-loop")
    m.tick()
    assert m.snapshot()["stale_loops"] == []
    time.sleep(0.08)
    m.tick()
    snap = m.snapshot()
    assert snap["state"] == STATE_DEGRADED
    assert snap["stale_loops"] == ["my-loop"]  # other-loop NOT blamed
    assert snap["loops"]["my-loop"]["stale"] is True
    assert snap["loops"]["other-loop"]["stale"] is False
    # the artifact blames the exact loop (and only it) in its reason line
    arts = list(tmp_path.iterdir())
    assert len(arts) == 1
    text = arts[0].read_text()
    reason = next(l for l in text.splitlines() if l.startswith("reason:"))
    assert "stale heartbeat(s): my-loop" in reason
    assert "other-loop" not in reason
    # a fresh beat clears the staleness and the state
    healthmon.beat("my-loop")
    m.tick()
    assert m.snapshot()["state"] == STATE_OK


def test_retired_loop_is_not_audited(mon, tmp_path):
    m = mon(probe_fn=_ok_probe, artifact_dir=str(tmp_path),
            loops={"done-loop": 0.02})
    healthmon.beat("done-loop")
    healthmon.retire("done-loop")  # clean exit (blocksync handoff)
    time.sleep(0.05)
    m.tick()
    snap = m.snapshot()
    assert snap["state"] == STATE_OK
    assert "done-loop" not in snap["loops"]


def test_informational_loop_reported_but_never_stale(mon, tmp_path):
    m = mon(probe_fn=_ok_probe, artifact_dir=str(tmp_path),
            loops={"switch-accept": None})
    healthmon.beat("switch-accept")
    time.sleep(0.05)
    m.tick()
    snap = m.snapshot()
    assert snap["state"] == STATE_OK
    assert snap["loops"]["switch-accept"]["deadline_s"] is None
    assert snap["loops"]["switch-accept"]["age_s"] >= 0.0


# ------------------------------------------------------------ forensics


def test_exactly_one_artifact_per_incident(mon, tmp_path):
    m = mon(probe_fn=_ok_probe, artifact_dir=str(tmp_path),
            loops={"loopy": 0.03})
    healthmon.beat("loopy")
    time.sleep(0.05)
    for _ in range(5):  # stays stale across many audits
        m.tick()
        time.sleep(0.005)
    assert len(list(tmp_path.iterdir())) == 1  # ONE per incident
    # recovery closes the incident ...
    healthmon.beat("loopy")
    m.tick()
    assert m.snapshot()["state"] == STATE_OK
    # ... and a NEW incident captures a second artifact
    time.sleep(0.05)
    m.tick()
    assert m.snapshot()["state"] == STATE_DEGRADED
    assert len(list(tmp_path.iterdir())) == 2


def test_artifact_min_interval_rate_limits_flapping(mon, tmp_path):
    m = mon(probe_fn=_ok_probe, artifact_dir=str(tmp_path),
            artifact_min_interval_s=3600.0, loops={"flappy": 0.03})
    healthmon.beat("flappy")
    time.sleep(0.05)
    m.tick()
    assert len(list(tmp_path.iterdir())) == 1
    healthmon.beat("flappy")
    m.tick()  # recovered
    time.sleep(0.05)
    m.tick()  # second incident inside the interval floor
    assert m.snapshot()["state"] == STATE_DEGRADED
    assert len(list(tmp_path.iterdir())) == 1  # floor held


def test_artifact_contents_and_snapshot_pointer(mon, tmp_path):
    m = mon(probe_fn=_fail_probe, wedge_after=1, artifact_dir=str(tmp_path))
    t0 = time.monotonic()
    m.tick(t0)
    deadline = time.monotonic() + WAIT
    while m.snapshot()["last_artifact"] is None:
        assert time.monotonic() < deadline
        time.sleep(0.005)
        m.tick()
    path = m.snapshot()["last_artifact"]
    assert os.path.dirname(path) == str(tmp_path)
    text = open(path).read()
    assert "=== stall forensics ===" in text
    assert "consecutive probe failure(s)" in text
    assert "=== health snapshot ===" in text
    assert "=== verify service ===" in text  # in-flight batch ages live here
    assert "=== consensus flight recorder ===" in text
    assert "=== threads ===" in text


# --------------------------------------- transitions: flightrec + metrics


def test_transition_emits_flightrec_event_and_metrics(mon, tmp_path):
    before = [
        e for e in flightrec().dump()["entries"] if e["kind"] == "health"
    ]
    m = mon(probe_fn=_fail_probe, wedge_after=1, artifact_dir=str(tmp_path))
    t0 = time.monotonic()
    m.tick(t0)
    deadline = time.monotonic() + WAIT
    while m.snapshot()["state"] != STATE_WEDGED:
        assert time.monotonic() < deadline
        time.sleep(0.005)
        m.tick()
    events = [
        e for e in flightrec().dump()["entries"] if e["kind"] == "health"
    ]
    assert len(events) == len(before) + 1  # ONE transition event
    ev = events[-1]
    assert ev["detail"]["state"] == STATE_WEDGED
    assert ev["detail"]["prev"] == STATE_OK
    assert mhub().health_state.value() == 2.0
    assert mhub().health_probe_consec_failures.value() >= 1.0
    # recovery transitions back and the gauge follows
    m._probe_fn = _ok_probe
    deadline = time.monotonic() + WAIT
    while m.snapshot()["state"] != STATE_OK:
        assert time.monotonic() < deadline
        m.tick()
        time.sleep(0.005)
    assert mhub().health_state.value() == 0.0


# ------------------------------------------------- end-to-end (real thread)


def test_sentinel_thread_end_to_end_wedge(mon, tmp_path):
    """The acceptance criterion, with the real sentinel thread: a
    stubbed wedged probe (blocks past its deadline) drives the state to
    wedged with NO caller thread ever blocking, emits exactly one
    forensics artifact + flight-recorder event + health_state
    transition, and /tpu_health reports it all."""
    probe = _BlockingProbe()
    m = mon(
        probe_fn=probe,
        probe_period_s=0.04,
        probe_timeout_s=0.04,
        probe_grace_s=0.02,
        wedge_after=2,
        artifact_dir=str(tmp_path),
    )
    m.start()
    try:
        # node loops keep beating while the sentinel works — never blocked
        t0 = time.monotonic()
        while time.monotonic() - t0 < WAIT:
            beat_t0 = time.monotonic()
            healthmon.beat("cs-receive")
            assert time.monotonic() - beat_t0 < 0.1
            if healthmon.snapshot()["state"] == STATE_WEDGED:
                break
            time.sleep(0.01)
        snap = healthmon.snapshot()
        assert snap["state"] == STATE_WEDGED, snap
        assert snap["ready"] is False
        assert snap["consecutive_probe_failures"] >= 2
        assert snap["last_probe"]["timed_out"] is True
        assert "cs-receive" in snap["loops"]
        arts = list(tmp_path.iterdir())
        assert len(arts) == 1  # exactly one artifact for the incident
        assert snap["last_artifact"] == str(arts[0])
        wedge_events = [
            e
            for e in flightrec().dump()["entries"]
            if e["kind"] == "health"
            and e["detail"]["state"] == STATE_WEDGED
        ]
        assert len(wedge_events) >= 1
        assert mhub().health_state.value() == 2.0
    finally:
        probe.release.set()
        m.stop()


# ------------------------------------------------------------- surfaces


def test_tpu_health_route_registered_and_health_stays_empty():
    from cometbft_tpu.rpc.core import ROUTES, Environment

    assert "tpu_health" in ROUTES
    assert ROUTES["tpu_health"][0] == ""  # no params
    env = Environment(object())
    # wire-compat: /health is {} by contract, whatever the sentinel says
    assert env.health() == {}


def test_tpu_health_serves_snapshot(mon, tmp_path):
    from cometbft_tpu.rpc.core import Environment

    m = mon(probe_fn=_ok_probe, artifact_dir=str(tmp_path))
    m.tick()
    out = Environment(object()).tpu_health()
    assert out["enabled"] is True
    assert out["state"] in (STATE_OK, STATE_DEGRADED, STATE_WEDGED)
    import json

    json.dumps(out)  # the RPC layer serializes it verbatim


def test_disabled_monitor_is_zero_overhead_noop():
    assert healthmon.monitor() is None  # fixture teardown guarantees this
    healthmon.beat("anything")  # must not record, raise, or allocate state
    healthmon.retire("anything")
    snap = healthmon.snapshot()
    assert snap["enabled"] is False
    assert snap["ready"] is True  # no signal = don't drain the node
    assert snap["loops"] == {}
    # maybe_start honors the off-by-default knob
    assert os.environ.get("COMETBFT_TPU_HEALTH") in (None, "", "0")
    assert healthmon.maybe_start() is None
    assert healthmon.monitor() is None


# ------------------------------------------------ the real probe


def test_probe_in_process_spawns_no_child(monkeypatch):
    """A process that has imported JAX holds (or will hold) the chip,
    which belongs to one process at a time: its probe stays in-process
    — one trivial computation on its own device — and never starts a
    child that would ask for the same chip."""
    import subprocess
    import sys

    assert "jax" in sys.modules  # conftest imported it

    def no_children(*a, **kw):
        raise AssertionError("an in-process probe must not spawn a child")

    monkeypatch.setattr(subprocess, "Popen", no_children)
    res = healthmon.probe_devices(60.0)
    assert res.ok is True and res.timed_out is False
    assert res.platform == "cpu" == healthmon.expected_platform()
    assert res.device_kind and res.device_count >= 1


def test_probe_wrong_platform_is_not_ok():
    """A probe is ok only if it found the platform the caller expects:
    a JAX that quietly fell back to cpu is not a healthy accelerator."""
    res = healthmon.probe_devices(60.0, expect="tpu")
    assert res.ok is False and res.timed_out is False
    assert res.platform == "cpu"  # what it found is still reported
    assert "expected 'tpu'" in res.detail


def test_expected_platform_defaults_to_tpu(monkeypatch):
    import sys

    monkeypatch.delitem(sys.modules, "jax")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert healthmon.expected_platform() == "tpu"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu,tpu")
    assert healthmon.expected_platform() == "cpu"


def test_probe_in_child_ok_on_cpu(monkeypatch):
    """The child form (for a process that has not imported JAX) against
    the CPU backend."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    res = healthmon._probe_in_child(60.0)
    assert res.ok is True and res.timed_out is False
    assert res.latency_s < 60.0
    assert (res.platform, res.device_kind) == ("cpu", "cpu")


# --------------------------------------------- verifysvc in-flight ages


def test_verifysvc_stats_report_in_flight_batch_ages():
    from cometbft_tpu.verifysvc.service import Klass, VerifyService

    gate = threading.Event()

    class SlowBV:
        def __init__(self):
            self.items = []

        def add(self, pub, msg, sig):
            self.items.append((pub, msg, sig))

        def submit(self):
            return ("dev", None)

        def collect(self, ticket):
            gate.wait(WAIT)
            return True, [True] * len(self.items)

    s = VerifyService(batch_max=64, queue_max=1024)
    s._make_verifier = lambda mode: SlowBV()
    try:
        ticket = s.submit([(b"p" * 32, b"m", b"s" * 64)], Klass.MEMPOOL)
        deadline = time.monotonic() + WAIT
        inflight = []
        while not inflight:
            assert time.monotonic() < deadline
            inflight = s.stats()["in_flight"]
            time.sleep(0.005)
        assert inflight[0]["class"] == "mempool"
        assert inflight[0]["sigs"] == 1
        assert inflight[0]["age_s"] >= 0.0
        gate.set()
        ok, per = ticket.collect(WAIT)
        assert ok and per == [True]
        deadline = time.monotonic() + WAIT
        while s.stats()["in_flight"]:
            assert time.monotonic() < deadline
            time.sleep(0.005)
    finally:
        gate.set()
        s.stop()


def test_verifysvc_stats_bounded_lock_wait():
    """The sentinel's forensics pass a lock timeout: stats() must answer
    with the lock-free tallies even while the scheduler lock is held —
    diagnosing a wedge must never block on the wedge."""
    from cometbft_tpu.verifysvc.service import VerifyService

    s = VerifyService()
    held = threading.Event()
    release = threading.Event()

    def holder():
        with s._cond:
            held.set()
            release.wait(WAIT)

    t = threading.Thread(target=holder, name="test-lock-holder")
    t.start()
    try:
        assert held.wait(WAIT)
        st = s.stats(lock_timeout=0.05)
        assert st["queued"] == {"lock_busy": True}
        assert "in_flight" in st and "dispatched_batches" in st
    finally:
        release.set()
        t.join(timeout=WAIT)
        s.stop()
