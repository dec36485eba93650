"""Fault injection: crash points and an armable runtime fault registry.

Two generations of failure tooling share this module:

* **Crash points** (:func:`fail_point`, reference: internal/fail/fail.go:47):
  each call increments a process-global counter; when it reaches
  ``FAIL_TEST_INDEX`` the process exits immediately with status 75
  (os._exit — no cleanup, no flushes: a real crash).  Sprinkled through
  the commit path (consensus/state.py, state/execution.py) so the
  crash-at-every-step recovery tests can kill a node between any two
  persistence operations and assert WAL + handshake replay recover it
  (reference sites: state.go:1872,1889,1912, execution.go:267,274).

* **Fault registry** (:func:`arm` / :func:`clear` / :func:`armed`): named,
  parameterized faults the chaos harness arms at runtime — via the
  ``COMETBFT_TPU_FAULT_*`` env knobs at process start, or live over RPC
  (``arm_fault`` / ``clear_fault``, gated on ``COMETBFT_TPU_FAULT_RPC``).
  Seams in the verify service, the health probe, consensus vote signing,
  and the p2p send path check the registry and misbehave deterministically
  while a fault is armed, so a backend wedge mid-batch (or a byzantine
  double-sign, or a lossy link) is injectable in-process on CPU-only CI.

  Known faults:

  ====================  ====================================================
  ``wedge_device``      Device result waits block (the verify-service
                        settle seam parks until the fault clears) and the
                        accelerator probe reports a hang — the in-process
                        twin of an accelerator that hangs instead of
                        erroring.
  ``slow_collect``      Device result waits take an extra <value> seconds.
  ``fail_dispatch``     Verify-service dispatch raises InjectedFault.
  ``drop_p2p_pct``      <value> percent of outbound p2p messages are
                        silently dropped at the MConnection send seam.
  ``delay_p2p_ms``      Outbound p2p writes are delayed <value> ms ±50%
                        jitter at the MConnection send routine (the wire
                        write, never a caller thread) — a laggy link
                        without tc/netem, composable with the drop fault
                        for genuinely flaky-network soaks.
  ``double_sign``       The next <value> signed non-nil prevotes are
                        accompanied by a conflicting broadcast-only vote
                        (byzantine equivocation feeding evidence/).
  ``plane_crash``       Armed in a verifyd process (verifysvc/server):
                        the <value>'th verify request kill -9s the plane
                        mid-batch (os._exit semantics via SIGKILL — no
                        response, no cleanup).  Deterministic "the plane
                        died with THIS batch in flight".
  ``plane_stall``       Like ``plane_crash`` but SIGSTOP: the plane
                        freezes mid-batch (connections stay open, nothing
                        answers) until an external SIGCONT.
  ``rpc_delay_ms``      verifyd responses are delayed <value> ms ±50%
                        jitter before hitting the socket.
  ``rpc_drop_pct``      <value> percent of verifyd responses are silently
                        dropped (the request WAS verified; the client's
                        deadline/retry machinery must recover).
  ====================  ====================================================

Zero cost when nothing is armed: every seam's first check is one
module-level bool read (the tracing/healthmon contract).  Crash points
stay zero-cost when ``FAIL_TEST_INDEX`` is unset (one env read at import).
"""

from __future__ import annotations

import os
import random
import sys
import threading
import time

EXIT_CODE = 75  # distinct from normal exits so tests can assert the crash

_target = int(os.environ.get("FAIL_TEST_INDEX", "-1"))
_counter = 0


def fail_point(label: str = "") -> None:
    """Crash here if this is the FAIL_TEST_INDEX'th fail point."""
    global _counter
    if _target < 0:
        return
    _counter += 1
    if _counter == _target:
        print(f"FAIL_TEST_INDEX={_target} hit at {label!r}", file=sys.stderr)
        sys.stderr.flush()
        os._exit(EXIT_CODE)


def points_hit() -> int:
    return _counter


# ------------------------------------------------------- fault registry

FAULTS = (
    "wedge_device",
    "slow_collect",
    "fail_dispatch",
    "drop_p2p_pct",
    "delay_p2p_ms",
    "double_sign",
    "plane_crash",
    "plane_stall",
    "rpc_delay_ms",
    "rpc_drop_pct",
)

_ANY_ARMED = False  # fast-path bool: every seam checks this first
_MTX = threading.Lock()
_ARMED: dict[str, float] = {}
_FIRED: dict[str, int] = {}
# cleared-or-armed notification so wedge_wait() wakes promptly
_CHANGED = threading.Event()
_RAND = random.Random()


class InjectedFault(RuntimeError):
    """Raised by a seam whose fault is armed (e.g. ``fail_dispatch``)."""


def arm(name: str, value: float = 1.0) -> None:
    """Arm a fault.  ``value`` parameterizes it (seconds for
    ``slow_collect``, a percentage for ``drop_p2p_pct``, a shot count for
    ``double_sign``); unknown names raise so a typo'd chaos scenario
    fails loudly instead of injecting nothing."""
    global _ANY_ARMED
    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r} (known: {', '.join(FAULTS)})")
    with _MTX:
        _ARMED[name] = float(value)
        _ANY_ARMED = True
        _CHANGED.set()
        _CHANGED.clear()


def clear(name: str) -> None:
    global _ANY_ARMED
    with _MTX:
        _ARMED.pop(name, None)
        _ANY_ARMED = bool(_ARMED)
        _CHANGED.set()
        _CHANGED.clear()


def clear_all() -> None:
    global _ANY_ARMED
    with _MTX:
        _ARMED.clear()
        _ANY_ARMED = False
        _CHANGED.set()
        _CHANGED.clear()


def armed(name: str) -> float | None:
    """The fault's armed value, or None.  One bool read when nothing is
    armed — safe on every hot path."""
    if not _ANY_ARMED:
        return None
    with _MTX:
        v = _ARMED.get(name)
        if v is not None:
            _FIRED[name] = _FIRED.get(name, 0) + 1
    return v


def consume(name: str) -> float | None:
    """Like :func:`armed` but decrements a shot count: a fault armed with
    value N fires N times then disarms itself (``double_sign`` arms one
    equivocation, not an equivocation per height forever)."""
    global _ANY_ARMED
    if not _ANY_ARMED:
        return None
    with _MTX:
        v = _ARMED.get(name)
        if v is None:
            return None
        _FIRED[name] = _FIRED.get(name, 0) + 1
        if v <= 1.0:
            _ARMED.pop(name, None)
            _ANY_ARMED = bool(_ARMED)
        else:
            _ARMED[name] = v - 1.0
    return v


def active() -> dict[str, float]:
    """Snapshot of armed faults (the ``faults`` RPC payload)."""
    with _MTX:
        return dict(_ARMED)


def fired() -> dict[str, int]:
    """How many times each fault's seam has observed it armed."""
    with _MTX:
        return dict(_FIRED)


def _peek(name: str) -> float | None:
    """armed() without bumping the fire tally — for poll loops, so the
    ``faults`` RPC's per-fault counts mean 'times a seam bit', not
    'times a parked seam re-checked'."""
    if not _ANY_ARMED:
        return None
    with _MTX:
        return _ARMED.get(name)


def wedge_wait(name: str = "wedge_device", poll_s: float = 0.05) -> float:
    """Block while ``name`` is armed — the injected analogue of a device
    result wait that never completes.  Returns the seconds blocked (0.0
    on the unarmed fast path).  The wait polls a shared change event so
    clearing the fault releases every parked seam within ``poll_s``.
    Counts as ONE fire however long it parks."""
    if not _ANY_ARMED or armed(name) is None:
        return 0.0
    t0 = time.monotonic()
    while _peek(name) is not None:
        _CHANGED.wait(poll_s)
    return time.monotonic() - t0


def should_drop(pct: float) -> bool:
    """One Bernoulli roll for the percentage faults (``drop_p2p_pct``,
    ``rpc_drop_pct``; clamped to [0, 100])."""
    if pct <= 0:
        return False
    if pct >= 100:
        return True
    return _RAND.random() * 100.0 < pct


def jittered_sleep(ms: float) -> float:
    """Sleep ``ms`` milliseconds ±50% uniform jitter (the latency faults
    ``delay_p2p_ms`` / ``rpc_delay_ms``); returns the seconds slept."""
    if ms <= 0:
        return 0.0
    d = (ms / 1e3) * (0.5 + _RAND.random())
    time.sleep(d)
    return d


def _arm_from_env() -> None:
    """Arm faults named by the declared COMETBFT_TPU_FAULT_* knobs — the
    e2e runner sets them per node process; production never does.  Read
    through the envknobs registry so the knob inventory stays complete
    (envknobs is stdlib-only, so this import adds nothing to the crash-
    point fast path)."""
    from . import envknobs

    for name, knob in (
        ("wedge_device", envknobs.FAULT_WEDGE_DEVICE),
        ("slow_collect", envknobs.FAULT_SLOW_COLLECT),
        ("fail_dispatch", envknobs.FAULT_FAIL_DISPATCH),
        ("drop_p2p_pct", envknobs.FAULT_DROP_P2P_PCT),
        ("delay_p2p_ms", envknobs.FAULT_DELAY_P2P_MS),
        ("double_sign", envknobs.FAULT_DOUBLE_SIGN),
        ("plane_crash", envknobs.FAULT_PLANE_CRASH),
        ("plane_stall", envknobs.FAULT_PLANE_STALL),
        ("rpc_delay_ms", envknobs.FAULT_RPC_DELAY_MS),
        ("rpc_drop_pct", envknobs.FAULT_RPC_DROP_PCT),
    ):
        spec = envknobs.get_str(knob).strip()
        if not spec:
            continue
        try:
            arm(name, float(spec))
        except ValueError:
            arm(name, 1.0)  # any non-numeric truthy spec arms with 1


_arm_from_env()
