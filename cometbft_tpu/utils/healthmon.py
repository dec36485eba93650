"""Node health sentinel: hang-proof accelerator probes, a heartbeat
registry, and automatic stall forensics.

The failure this module exists for is an accelerator that HANGS instead
of erroring: backend initialisation or a device call that blocks for
minutes without ever raising.  A node has no way to even *notice* that
state unless something watches from outside the blocked call.  This
module is the observability plane that makes device wedges, stalled
scheduler loops, and hung consensus routines first-class signals:

* **Hang-proof accelerator probe** (:func:`probe_devices`), right for a
  chip that belongs to ONE process at a time.  A process that has
  imported JAX holds (or is about to hold) the chip, so it probes
  in-process: one trivial computation on its own default device, run on
  a worker thread and judged from outside with a hard deadline.  A
  process that has not imported JAX (a script, before it attaches)
  asks a throwaway subprocess instead (own session, killpg escalation,
  poll-don't-communicate), which exits — and frees the chip — before
  the caller attaches.  Either way the probe is ``ok`` only when the
  platform it found is the one the caller expects
  (:func:`expected_platform`): a JAX that quietly fell back to ``cpu``
  is not a healthy accelerator.  The sentinel additionally wraps
  whatever probe function it is given in a worker thread with its own
  deadline, so even a misbehaving probe (or a stubbed one in tests) can
  never hang the sentinel itself.

* **Tri-state health machine**: ``ok → degraded → wedged`` driven by
  consecutive probe failures (``COMETBFT_TPU_HEALTH_WEDGE_AFTER``) and
  by heartbeat staleness; a recovered probe snaps back to ``ok``.

* **Heartbeat registry**: long-lived loops call ``healthmon.beat(name)``
  each iteration; the sentinel audits beat ages against per-loop
  deadlines (:data:`DEFAULT_LOOPS`) and blames the exact loop that went
  quiet.  Loops that exit cleanly call :func:`retire` so a finished
  blocksync is never mistaken for a stalled one.  With monitoring off
  (the default) ``beat()`` is one module-bool check — zero overhead, the
  same contract as ``utils/tracing``.

* **Automatic stall forensics**: on a probe deadline breach or a stale
  heartbeat the sentinel captures ONE rate-limited diagnosis artifact
  per incident (``utils/debugdump.stall_report``: all-thread stacks,
  verify-service ``stats()`` snapshot with in-flight batch ages,
  flight-recorder dump, recent trace-ring events) to ``$TMPDIR``, plus a
  flight-recorder event and hub metrics (``health_state`` gauge, probe
  latency histogram, consecutive-failure gauge, per-loop beat-age
  gauges) on every transition.

Liveness vs readiness (load-balancer wiring): the wire-compatible
``/health`` RPC stays ``{}`` — it answers iff the RPC thread is alive
(**liveness**).  The new ``/tpu_health`` RPC serves this module's
snapshot; route traffic away when ``state`` is ``wedged``
(**readiness**) and restart the process when ``/health`` itself stops
answering.

The sentinel thread itself must never hang with the accelerator: it
only ever *waits with timeouts* (probe results are read from a worker
thread, the verify-service snapshot uses a bounded lock acquire).
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from dataclasses import dataclass

from . import envknobs
from .log import get_logger

STATE_OK = "ok"
STATE_DEGRADED = "degraded"
STATE_WEDGED = "wedged"
_STATE_CODE = {STATE_OK: 0, STATE_DEGRADED: 1, STATE_WEDGED: 2}

# Per-loop heartbeat deadlines (seconds).  A loop is stale when its last
# beat is older than its deadline; None = informational only (the loop
# legitimately blocks indefinitely — socket accept, event-driven work —
# so age is reported in /tpu_health but never audited).  Deadlines leave
# generous headroom over each loop's worst legitimate iteration:
# cs-receive processes one input under the consensus lock (a commit
# verification), verifysvc-collect blocks on a device result, and
# verifysvc-host may run a cold-bucket XLA compile.
DEFAULT_LOOPS: dict[str, float | None] = {
    "cs-receive": 15.0,
    "cs-watchdog": 35.0,
    "verifysvc-sched": 10.0,
    "verifysvc-collect": 60.0,
    "verifysvc-host": 300.0,
    # informational: the failover watchdog legitimately blocks for a
    # whole probation probe (which has its own hard deadline)
    "verifysvc-failover": None,
    "blocksync-events": 15.0,
    "blocksync-pool": 60.0,
    "blockpool": 15.0,
    "metrics-pump": 15.0,
    "metrics-sample": 30.0,
    "mempool-recheck": None,
    "switch-accept": None,
}


# ----------------------------------------------------------------- probe


@dataclass
class ProbeResult:
    """Outcome of one accelerator probe attempt."""

    ok: bool
    detail: str
    latency_s: float
    timed_out: bool = False
    # what the probe found, as JAX reports it (None when it found
    # nothing: a hang, a crash, an injected fault)
    platform: str | None = None
    device_kind: str | None = None
    device_count: int | None = None

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "detail": self.detail,
            "latency_s": round(self.latency_s, 3),
            "timed_out": self.timed_out,
            "platform": self.platform,
            "device_kind": self.device_kind,
            "device_count": self.device_count,
        }


def expected_platform() -> str:
    """The platform this process was told to run on: the first entry of
    ``jax_platforms`` (``JAX_PLATFORMS``, or ``jax.config`` once JAX is
    imported) when one is set — an explicit request, such as the test
    suite's ``cpu`` — and otherwise ``tpu``, the accelerator this system
    is written for."""
    jax = sys.modules.get("jax")
    spec = (
        jax.config.jax_platforms if jax is not None
        else os.environ.get("JAX_PLATFORMS")
    ) or ""
    return spec.split(",")[0].strip() or "tpu"


def probe_devices(timeout_s: float, expect: str | None = None) -> ProbeResult:
    """Probe the accelerator within ``timeout_s``; ``ok`` only if the
    platform found is ``expect`` (default :func:`expected_platform`).

    A chip belongs to one process at a time, so WHERE the probe runs
    depends on the caller.  A process that has imported JAX probes
    in-process (:func:`_probe_in_process`): a second process asking for
    the chip this one holds would fail or hang, and read as a wedge on
    a healthy chip.  A process that has not imported JAX probes in a
    throwaway subprocess (:func:`_probe_in_child`), so that an
    accelerator that blocks forever in backend initialisation — no
    exception, unkillable in-process — costs a killed child, not this
    process.
    """
    from . import fail

    if fail.armed("wedge_device") is not None:
        # injected wedge (utils/fail): report the hang a wedged
        # accelerator would produce, immediately and deterministically —
        # honored here so the sentinel and the failover probation loop
        # both see the same wedged world
        return ProbeResult(
            False,
            "injected fault: wedge_device (probe reported as hung)",
            float(timeout_s),
            timed_out=True,
        )
    if expect is None:
        expect = expected_platform()
    if "jax" in sys.modules:
        res = _probe_in_process(timeout_s)
    else:
        res = _probe_in_child(timeout_s)
    if res.ok and res.platform != expect:
        res.ok = False
        res.detail = f"found platform {res.platform!r}, expected {expect!r}"
    return res


def _found(platform: str, kind: str, count: int, latency: float) -> ProbeResult:
    return ProbeResult(
        True, f"{platform} ({kind} x{count})", latency,
        platform=platform, device_kind=kind, device_count=count,
    )


# The in-process probe's one worker: a device call that never returns
# parks its thread forever, so at most one exists — while it is parked,
# later probes report the hang without starting another.
_INPROC_MTX = threading.Lock()
_INPROC_THREAD: threading.Thread | None = None


def _probe_in_process(timeout_s: float) -> ProbeResult:
    """One trivial computation on this process's own default device —
    host to device, one executed op, device to host — on a worker
    thread, judged from here with the deadline."""
    global _INPROC_THREAD
    t0 = time.monotonic()
    box: list = []

    def run():
        try:
            import jax
            import numpy as np

            devs = jax.devices()
            x = jax.device_put(np.int32(20), devs[0])
            if int(x + x) != 40:
                raise RuntimeError("device computed 20 + 20 != 40")
            box.append((devs[0].platform, devs[0].device_kind, len(devs)))
        except BaseException as e:  # noqa: BLE001 — a probe failure is the finding
            box.append(e)

    with _INPROC_MTX:
        if _INPROC_THREAD is not None and _INPROC_THREAD.is_alive():
            return ProbeResult(
                False,
                "an earlier in-process probe is still blocked in its "
                "device call",
                0.0,
                timed_out=True,
            )
        t = _INPROC_THREAD = threading.Thread(
            target=run, name="healthmon-devprobe", daemon=True
        )
        t.start()
    t.join(timeout_s)
    latency = time.monotonic() - t0
    if t.is_alive():
        return ProbeResult(
            False,
            f"device call hung >{timeout_s:g}s (accelerator not answering)",
            latency,
            timed_out=True,
        )
    got = box[0]
    if isinstance(got, BaseException):
        return ProbeResult(
            False, f"probe raised {type(got).__name__}: {got}", latency
        )
    return _found(*got, latency)


_CHILD_CODE = (
    "import jax, json; d = jax.devices(); "
    "print(json.dumps([d[0].platform, d[0].device_kind, len(d)]))"
)


def _probe_in_child(timeout_s: float) -> ProbeResult:
    """``jax.devices()`` in a throwaway subprocess with a hard deadline.
    Only for a caller that has not imported JAX: the child exits, and
    frees the chip, before the caller attaches.  Popen + poll deadline
    rather than ``subprocess.run(timeout=...)``: run() reaps the killed
    child with an unbounded communicate(), and a child wedged in
    uninterruptible device I/O would hang the reap — the exact failure
    this probe exists to detect.  The child runs in its own session so
    the kill escalation (SIGKILL to the whole group) also takes out any
    helper processes it spawned; nothing here ever blocks on the child's
    pipes after a kill.
    """
    import json
    import signal

    t0 = time.monotonic()
    with open(os.devnull, "wb") as devnull:
        proc = subprocess.Popen(
            [sys.executable, "-c", _CHILD_CODE],
            stdout=subprocess.PIPE,
            stderr=devnull,
            text=True,
            start_new_session=True,
        )
        deadline = t0 + timeout_s
        step = min(0.5, max(timeout_s / 10.0, 0.01))
        while proc.poll() is None and time.monotonic() < deadline:
            time.sleep(step)
        if proc.poll() is None:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (OSError, ProcessLookupError):
                proc.kill()
            return ProbeResult(
                False,
                f"jax.devices() hung >{timeout_s:g}s "
                "(accelerator not answering)",
                time.monotonic() - t0,
                timed_out=True,
            )
        out = proc.stdout.read() if proc.stdout else ""
        latency = time.monotonic() - t0
        if proc.returncode != 0:
            return ProbeResult(
                False, f"probe exited {proc.returncode}", latency
            )
    try:
        platform, kind, count = json.loads(out.strip().splitlines()[-1])
    except (IndexError, ValueError):
        return ProbeResult(False, f"unreadable probe output {out!r}", latency)
    return _found(platform, kind, count, latency)


# -------------------------------------------------------------- monitor


class HealthMonitor:
    """The sentinel: periodic hang-proof probes + heartbeat audits.

    Construction reads the ``COMETBFT_TPU_HEALTH_*`` knobs once;
    explicit constructor arguments override them (tests).  ``probe_fn``
    takes a timeout in seconds and returns a :class:`ProbeResult`; the
    default is :func:`probe_devices`.  Whatever it is, it runs on a
    dedicated worker thread and the sentinel judges it from outside with
    ``deadline + grace`` — a probe that blocks forever is recorded as a
    hang (one failure per period) without the sentinel ever blocking.
    """

    def __init__(
        self,
        probe_fn=None,
        probe_period_s: float | None = None,
        probe_timeout_s: float | None = None,
        probe_grace_s: float = 2.0,
        wedge_after: int | None = None,
        artifact_min_interval_s: float | None = None,
        artifact_dir: str | None = None,
        loops: dict[str, float | None] | None = None,
    ):
        self._probe_fn = probe_fn if probe_fn is not None else probe_devices
        self.probe_period_s = (
            probe_period_s if probe_period_s is not None
            else max(1, envknobs.get_int(envknobs.HEALTH_PERIOD_MS)) / 1e3
        )
        self.probe_timeout_s = (
            probe_timeout_s if probe_timeout_s is not None
            else max(1, envknobs.get_int(envknobs.HEALTH_PROBE_TIMEOUT_MS)) / 1e3
        )
        self.probe_grace_s = max(0.0, probe_grace_s)
        self.wedge_after = max(
            1, wedge_after if wedge_after is not None
            else envknobs.get_int(envknobs.HEALTH_WEDGE_AFTER)
        )
        self.artifact_min_interval_s = (
            artifact_min_interval_s if artifact_min_interval_s is not None
            else max(
                0, envknobs.get_int(envknobs.HEALTH_ARTIFACT_MIN_INTERVAL_MS)
            ) / 1e3
        )
        self.artifact_dir = (
            artifact_dir if artifact_dir is not None
            else (envknobs.get_str(envknobs.HEALTH_DIR) or None)
        )
        self.logger = get_logger("healthmon")

        self._mtx = threading.Lock()
        # heartbeat registry: name -> last beat (monotonic); deadlines
        # separate so beat() stays a single dict store
        self._beats: dict[str, float] = {}
        self._deadlines: dict[str, float | None] = dict(
            DEFAULT_LOOPS if loops is None else loops
        )
        self._stale: set[str] = set()

        # probe bookkeeping (all guarded by _mtx)
        self._state = STATE_OK
        self._consec_failures = 0
        self._last_result: ProbeResult | None = None
        self._last_result_at: float | None = None
        self._probe_attempts = 0
        self._transitions = 0
        self._last_artifact: str | None = None
        self._last_artifact_at: float | None = None
        self._incident_active = False

        # in-flight probe attempt: (generation, started_at monotonic);
        # None when no attempt outstanding.  judged=True once the
        # sentinel counted it as a hang — a late completion of a judged
        # attempt is discarded.
        self._attempt: dict | None = None
        self._attempt_gen = 0

        self._stop_ev = threading.Event()
        self._thread: threading.Thread | None = None
        self._next_probe = 0.0  # fire immediately on start

    # ---------------------------------------------------------- lifecycle

    @property
    def state(self) -> str:
        """Current tri-state health (atomic str read, no lock: the
        verify service's failover watchdog polls this every tick)."""
        return self._state

    @property
    def last_probe_at(self) -> float | None:
        """Monotonic time of the last ingested probe result (atomic
        read).  The failover watchdog compares this against its own
        last restore so a sentinel verdict that predates the restore —
        the sentinel probes far less often than probation — can't
        immediately re-trip a just-restored service."""
        return self._last_result_at

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop_ev.clear()
        self._thread = threading.Thread(
            target=self._sentinel_loop, name="healthmon-sentinel", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop_ev.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
        self._thread = None

    # --------------------------------------------------------- heartbeats

    def register_loop(self, name: str, deadline_s: float | None) -> None:
        with self._mtx:
            self._deadlines[name] = deadline_s

    def beat(self, name: str) -> None:
        # one dict store, no lock: under the GIL a float store is atomic
        # and the sentinel reading a torn-by-a-tick value is harmless —
        # this is the hot path every loop iteration pays
        self._beats[name] = time.monotonic()

    def retire(self, name: str) -> None:
        """A loop is exiting cleanly: stop auditing it.  A blocksync
        pool that handed off to consensus must not read as stalled.
        The whole removal holds _mtx so it serializes with the
        sentinel's audit — an unlocked remove could lose to a
        concurrent audit's set() and resurrect the gauge series,
        frozen forever."""
        from .metrics import hub as _mhub

        with self._mtx:
            self._beats.pop(name, None)
            self._stale.discard(name)
            # drop the exported series too: a frozen age for a dead loop
            # reads on a dashboard as a live loop that stopped aging
            _mhub().health_beat_age.remove(loop=name)

    # ------------------------------------------------------------- probing

    def _kick_probe_locked(self, now: float) -> None:
        """Start a probe attempt on a fresh worker thread — unless the
        previous worker is still stuck inside the probe, in which case
        the stuck attempt keeps being judged instead (at most ONE probe
        thread exists however wedged the accelerator is)."""
        if self._attempt is not None:
            return
        self._attempt_gen += 1
        gen = self._attempt_gen
        self._attempt = {"gen": gen, "started": now, "judged": False}

        def run():
            try:
                res = self._probe_fn(self.probe_timeout_s)
            except BaseException as e:  # noqa: BLE001 — a probe bug is a failed probe
                res = ProbeResult(
                    False, f"probe raised {type(e).__name__}: {e}", 0.0
                )
            with self._mtx:
                att = self._attempt
                if att is None or att["gen"] != gen:
                    return  # superseded
                if att["judged"]:
                    # already counted as a hang; a (late) answer just
                    # clears the slot so the next period can probe again
                    self._attempt = None
                    return
                self._attempt = None
                self._ingest_probe_locked(res)

        threading.Thread(target=run, name="healthmon-probe", daemon=True).start()

    def _ingest_probe_locked(self, res: ProbeResult) -> None:
        from .metrics import hub as _mhub

        self._probe_attempts += 1
        self._last_result = res
        self._last_result_at = time.monotonic()
        m = _mhub()
        # synthetic hang results carry the cumulative blocked duration in
        # latency_s (useful in /tpu_health); the histogram promises "a
        # hang is clamped at the probe deadline", so clamp here
        m.health_probe_seconds.observe(min(res.latency_s, self.probe_timeout_s))
        m.health_probe_total.inc(
            result="ok" if res.ok else ("hang" if res.timed_out else "fail")
        )
        if res.ok:
            self._consec_failures = 0
        else:
            self._consec_failures += 1
        m.health_probe_consec_failures.set(self._consec_failures)

    def _judge_attempt_locked(self, now: float) -> None:
        """A probe attempt past deadline+grace is a hang — count it
        without waiting for the worker (which may be stuck forever)."""
        att = self._attempt
        if att is None or att["judged"]:
            return
        if now - att["started"] > self.probe_timeout_s + self.probe_grace_s:
            att["judged"] = True
            self._ingest_probe_locked(
                ProbeResult(
                    False,
                    "probe thread still blocked past "
                    f"{self.probe_timeout_s:g}s deadline",
                    now - att["started"],
                    timed_out=True,
                )
            )

    # -------------------------------------------------------------- audit

    def _audit_beats_locked(self, now: float) -> None:
        """Recompute the stale set and export per-loop beat ages."""
        from .metrics import hub as _mhub

        m = _mhub()
        for name, last in list(self._beats.items()):
            age = now - last
            m.health_beat_age.set(age, loop=name)
            deadline = self._deadlines.get(name)
            if deadline is None:
                continue
            if age > deadline:
                self._stale.add(name)
            else:
                self._stale.discard(name)

    def _device_state_locked(self) -> str:
        if self._consec_failures >= self.wedge_after:
            return STATE_WEDGED
        if self._consec_failures > 0:
            return STATE_DEGRADED
        return STATE_OK

    def tick(self, now: float | None = None) -> None:
        """One sentinel cycle: kick/judge the probe, audit beats, run the
        state machine, capture forensics.  The sentinel thread calls this
        periodically; tests call it directly for determinism.  Never
        blocks: every interaction with possibly-wedged machinery is
        judged from outside with deadlines."""
        now = time.monotonic() if now is None else now
        capture_reason: str | None = None
        with self._mtx:
            if now >= self._next_probe:
                self._next_probe = now + self.probe_period_s
                att = self._attempt
                if att is not None and att["judged"]:
                    # the worker is STILL stuck inside an already-judged
                    # probe: no new probe can start (one worker max), but
                    # every elapsed period is another failure — a device
                    # wedged hard enough to trap the thread forever must
                    # still walk degraded -> wedged
                    self._ingest_probe_locked(
                        ProbeResult(
                            False,
                            "probe thread still blocked "
                            f"({now - att['started']:.1f}s since attempt "
                            "start)",
                            now - att["started"],
                            timed_out=True,
                        )
                    )
                else:
                    self._kick_probe_locked(now)
            self._judge_attempt_locked(now)
            self._audit_beats_locked(now)
            new_state = self._device_state_locked()
            if new_state == STATE_OK and self._stale:
                new_state = STATE_DEGRADED
            transitioned = new_state != self._state
            prev = self._state
            if transitioned:
                self._state = new_state
                self._transitions += 1
            # one artifact per incident: the first transition out of ok
            # (or a stale loop appearing while otherwise ok) opens an
            # incident, recovery to ok closes it
            if new_state == STATE_OK:
                self._incident_active = False
            elif not self._incident_active:
                self._incident_active = True
                rate_limited = (
                    self._last_artifact_at is not None
                    and now - self._last_artifact_at
                    < self.artifact_min_interval_s
                )
                if not rate_limited:
                    self._last_artifact_at = now
                    capture_reason = self._incident_reason_locked()
            if transitioned:
                self._record_transition_locked(prev, new_state)
        if capture_reason is not None:
            path = self._capture_forensics(capture_reason)
            with self._mtx:
                self._last_artifact = path

    def _incident_reason_locked(self) -> str:
        parts = []
        if self._consec_failures:
            detail = self._last_result.detail if self._last_result else "?"
            parts.append(
                f"{self._consec_failures} consecutive probe failure(s): "
                f"{detail}"
            )
        if self._stale:  # audit ran under this same lock hold
            parts.append(
                f"stale heartbeat(s): {', '.join(sorted(self._stale))}"
            )
        return "; ".join(parts) or "unknown"

    def _record_transition_locked(self, prev: str, new: str) -> None:
        from .flightrec import recorder as _flightrec
        from .metrics import hub as _mhub

        m = _mhub()
        m.health_state.set(_STATE_CODE[new])
        m.health_transitions.inc(state=new)
        detail = self._last_result.detail if self._last_result else ""
        _flightrec().record(
            "health",
            state=new,
            prev=prev,
            consec_failures=self._consec_failures,
            stale_loops=sorted(self._stale),
            probe=detail,
        )
        log = self.logger.warning if new != STATE_OK else self.logger.info
        log(
            f"health state {prev} -> {new} "
            f"(probe failures={self._consec_failures}, "
            f"stale={sorted(self._stale) or '[]'} {detail})"
        )

    # ----------------------------------------------------------- forensics

    def _capture_forensics(self, reason: str) -> str | None:
        """One diagnosis artifact: snapshot + verifysvc stats (bounded
        lock wait) + flight recorder + trace ring + all-thread stacks.
        Runs OUTSIDE self._mtx (beat() never contends) and must never
        raise — it runs while the node is already in trouble."""
        import json as _json

        from . import debugdump, tracing
        from .metrics import hub as _mhub

        try:
            sections: list[tuple[str, str]] = [
                (
                    "health snapshot",
                    _json.dumps(self.snapshot(), indent=1, default=str),
                )
            ]
            try:
                # peek the module global, never global_service(): the
                # accessor CONSTRUCTS a service on demand, and a
                # diagnostic path must not install fresh global state
                # (nor report a fabricated empty scheduler as real)
                from ..verifysvc import service as _vsvc

                svc = _vsvc._GLOBAL
                stats = (
                    svc.stats(lock_timeout=0.5)
                    if svc is not None
                    else "not running (no verify service in this process)"
                )
                sections.append(
                    ("verify service", _json.dumps(stats, indent=1, default=str))
                )
            except Exception as e:  # noqa: BLE001 — partial forensics beat none
                sections.append(("verify service", f"unavailable: {e!r}"))
            if tracing.enabled():
                events = tracing.chrome_trace_events()[-256:]
                sections.append(
                    ("trace ring (newest 256)", _json.dumps(events, default=str))
                )
            path = debugdump.stall_report(
                reason, sections, directory=self.artifact_dir
            )
            _mhub().health_forensics.inc()
            self.logger.warning(f"stall forensics written to {path}")
            return path
        except Exception as e:  # noqa: BLE001 — forensics must never hurt the node
            self.logger.warning(f"stall forensics capture failed: {e!r}")
            return None

    # ------------------------------------------------------------ sentinel

    def _sentinel_loop(self) -> None:
        # tick fast enough to honor small test periods, slow enough to
        # be invisible in production (<=4 wakeups/s worst case)
        step = max(0.05, min(1.0, self.probe_period_s / 4.0))
        while not self._stop_ev.wait(step):
            try:
                self.tick()
            except Exception as e:  # noqa: BLE001 — the sentinel outlives one bad cycle
                self.logger.warning(f"sentinel tick failed: {e!r}")

    # ------------------------------------------------------------ snapshot

    def snapshot(self) -> dict:
        """The /tpu_health payload (JSON-serializable)."""
        now = time.monotonic()
        beats = dict(self._beats)  # racy-read safe: atomic dict copy
        with self._mtx:
            last = self._last_result
            attempt = self._attempt
            out = {
                "enabled": True,
                "state": self._state,
                "ready": self._state != STATE_WEDGED,
                "consecutive_probe_failures": self._consec_failures,
                "wedge_after": self.wedge_after,
                "probe_period_s": self.probe_period_s,
                "probe_timeout_s": self.probe_timeout_s,
                "probe_attempts": self._probe_attempts,
                "last_probe": (
                    {
                        **last.to_dict(),
                        "age_s": (
                            round(now - self._last_result_at, 3)
                            if self._last_result_at is not None
                            else None
                        ),
                    }
                    if last is not None
                    else None
                ),
                "probe_in_flight_s": (
                    round(now - attempt["started"], 3) if attempt else None
                ),
                "stale_loops": sorted(self._stale),
                "transitions": self._transitions,
                "last_artifact": self._last_artifact,
            }
            deadlines = dict(self._deadlines)
        out["loops"] = {
            name: {
                "age_s": round(now - t, 3),
                "deadline_s": deadlines.get(name),
                "stale": name in out["stale_loops"],
            }
            for name, t in sorted(beats.items())
        }
        return out

    def wedge_report(self) -> dict:
        """Compact structured view for embedding in artifacts/bench
        lines: state + last probe + stale loops."""
        with self._mtx:
            return {
                "state": self._state,
                "consecutive_probe_failures": self._consec_failures,
                "last_probe": (
                    self._last_result.to_dict() if self._last_result else None
                ),
                "stale_loops": sorted(self._stale),
                "last_artifact": self._last_artifact,
            }


# ------------------------------------------------------- module plumbing

_ENABLED = False
_MON: HealthMonitor | None = None
_MON_MTX = threading.Lock()


def beat(name: str) -> None:
    """Heartbeat from a long-lived loop.  Off by default: one module-bool
    check, no allocation, no lock — safe on every hot loop."""
    if not _ENABLED:
        return
    mon = _MON
    if mon is not None:
        mon.beat(name)


def retire(name: str) -> None:
    """A loop is exiting cleanly; stop auditing its heartbeat."""
    if not _ENABLED:
        return
    mon = _MON
    if mon is not None:
        mon.retire(name)


def monitor() -> HealthMonitor | None:
    return _MON


def install(mon: HealthMonitor) -> HealthMonitor:
    """Make ``mon`` the process monitor and enable beats (tests and
    :func:`maybe_start`).  Does not start the sentinel thread."""
    global _MON, _ENABLED
    with _MON_MTX:
        _MON = mon
        _ENABLED = True
    return mon


def uninstall() -> None:
    """Stop and drop the process monitor; beats go back to no-ops."""
    global _MON, _ENABLED
    with _MON_MTX:
        mon, _MON = _MON, None
        _ENABLED = False
    if mon is not None:
        mon.stop()


def maybe_start() -> HealthMonitor | None:
    """Knob-gated production entry (node.start): installs and starts the
    sentinel when ``COMETBFT_TPU_HEALTH=1``; returns None (and keeps the
    zero-overhead no-op path) otherwise."""
    if not envknobs.get_bool(envknobs.HEALTH):
        return None
    with _MON_MTX:
        if _MON is not None:
            return _MON
    mon = install(HealthMonitor())
    mon.start()
    return mon


def snapshot() -> dict:
    """The /tpu_health payload; a disabled monitor still answers (the
    RPC responding at all is the liveness half of the contract)."""
    mon = _MON
    if mon is None:
        return {
            "enabled": False,
            "state": "unknown",
            "ready": True,
            "loops": {},
            "stale_loops": [],
            "last_probe": None,
            "last_artifact": None,
        }
    return mon.snapshot()
