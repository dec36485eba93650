"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so that every sharding/collective
code path (the multi-chip design) is exercised without real TPU hardware,
mirroring how the reference tests multi-node behavior in-process
(reference: internal/consensus/common_test.go topology).

The suite runs on the CPU backend whatever the host offers: the platform
is pinned with jax.config.update below (authoritative even if something
imported jax before this file), and XLA_FLAGS — read lazily at first
backend initialization — injects the host-device count.  Subprocesses the
tests spawn (e2e nodes, failpoint crash-children, verifyd) force
JAX_PLATFORMS=cpu themselves: an accelerator belongs to one process at a
time, and a child must never contend for it.
"""

import os

# NOTE on COMETBFT_TPU_DEVICE_BATCH_MIN: kernel test modules pin it to 1
# locally (test_comb, test_comb_smoke, test_comb_routing, test_parallel,
# test_blocksync_replay) so tiny batches exercise the device paths under
# test.  It must NOT be forced suite-wide: in-process consensus network
# tests would then batch-verify 4-signature commits through freshly
# compiling XLA programs, stalling rounds until the liveness watchdog
# fires (observed: test_four_validator_network_commits_blocks).

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Lock-order witness (analysis/lockwitness): ON for every suite run —
# each run doubles as a deadlock hunt — unless explicitly disabled with
# COMETBFT_TPU_LOCKCHECK=0.  Installed here, after jax (whose import-time
# internals we don't want to witness) and BEFORE any other cometbft_tpu
# module import, so every lock the framework creates is wrapped.  The
# knob is read raw because importing utils.envknobs would drag in
# utils/__init__ (service, logging) ahead of the install; lockwitness
# itself is stdlib-only and exports the get_bool-mirroring spellings.
from cometbft_tpu.analysis import lockwitness as _lockwitness  # noqa: E402

_lockcheck = os.environ.get("COMETBFT_TPU_LOCKCHECK", "").strip().lower()
if _lockcheck not in _lockwitness.FALSE_SPELLINGS:
    _lockwitness.install(raise_on_violation=_lockcheck == "raise")
else:
    _lockwitness = None

# Persistent compilation cache: the Ed25519 kernel takes minutes to compile
# on the CPU backend; cache compiled executables across test runs
# (utils/compilecache: JAX_COMPILATION_CACHE_DIR if set, else the
# repo-local tests/.jax_cache).  Imported after the lockwitness install
# above, so the helper's module-level state is witnessed.
from cometbft_tpu.utils import compilecache as _compilecache  # noqa: E402

_compilecache.enable()


import pytest  # noqa: E402


@pytest.fixture
def tiny_device_batches(monkeypatch):
    """Route tiny batches onto the DEVICE kernels: modules that test
    device verification opt in via
    `pytestmark = pytest.mark.usefixtures("tiny_device_batches")` —
    the production threshold
    (models/verifier._device_batch_min) would host-route their V=4..64
    batches and silently skip the code under test.  Never force this
    suite-wide: in-process consensus tests would stall rounds behind
    XLA compiles and trip the liveness watchdog."""
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")


@pytest.fixture(autouse=True)
def _watchdog_must_not_fire():
    """The consensus liveness watchdog is a production backstop for bug
    classes fixed in r4; a healthy state machine never needs it (the
    reference has no watchdog — internal/consensus/state.go:795-884).
    Fail any in-process test during which it re-kicks so regressions in
    timeout scheduling surface as the root cause, not as a silent 20 s
    hiccup the watchdog papers over."""
    from cometbft_tpu.consensus.state import ConsensusState

    before = ConsensusState.watchdog_fire_count
    yield
    after = ConsensusState.watchdog_fire_count
    assert after == before, (
        f"consensus watchdog re-kicked {after - before}x during this test: "
        "a scheduled timeout evaporated (see state.py _watchdog_routine)"
    )


@pytest.fixture(autouse=True)
def _no_lock_order_violations():
    """Fail the test during which the lock witness recorded an order
    cycle or a sleep-while-locked, pinning the blame to the scenario
    that produced it (mirrors the watchdog fixture above).  Violations
    raised by background daemon threads land on whichever test is
    running — close enough to identify the culprit."""
    if _lockwitness is None:
        yield
        return
    # snapshot by identity, not index: lockwitness.clear() (used by the
    # witness's own tests to scrub intentional violations) would strand
    # an index snapshot past the list end and mask later real violations
    before = _lockwitness.violations()  # pins the objects against id reuse
    before_ids = {id(v) for v in before}
    yield
    new = [v for v in _lockwitness.violations() if id(v) not in before_ids]
    assert not new, (
        "lock witness recorded violation(s) during this test:\n"
        + "\n".join(v.render() for v in new)
    )


def find_leaked_compile_threads(frames=None):
    """Surviving background threads parked inside JAX/XLA machinery —
    the exit-134 bug class: a daemon thread still compiling (a leaked
    comb table build, a BLS kernel trace) races interpreter teardown and
    aborts the whole run with ``terminate called without an active
    exception`` and NO blame (the PR-13 ``resolve_mode`` bug died
    exactly this way; every test had passed).  Returns
    [(thread_name, formatted_stack)].

    ``frames`` is injectable for the guard's own test; default is the
    live ``sys._current_frames()``.  Only jax/jaxlib/xla frames flag:
    the framework's long-lived daemons (verifysvc scheduler, tracing
    ring, health sentinel) idle in framework code and must not trip a
    suite-wide gate."""
    import sys as _sys
    import threading as _threading
    import traceback as _traceback

    if frames is None:
        frames = _sys._current_frames()
    offenders = []
    for t in _threading.enumerate():
        if t is _threading.main_thread() or t.ident is None:
            continue
        fr = frames.get(t.ident)
        if fr is None:
            continue
        stack = _traceback.extract_stack(fr)
        if any(
            ("/jax/" in (f.filename or ""))
            or ("jaxlib" in (f.filename or ""))
            or ("/xla" in (f.filename or ""))
            for f in stack
        ):
            offenders.append(
                (t.name, "".join(_traceback.format_list(stack)))
            )
    return offenders


def pytest_sessionfinish(session, exitstatus):
    """Tier-1 exit-134 guard: after the whole session, assert no
    non-test background compile/daemon thread survives inside JAX/XLA.
    All dots green while a background comb-table compile aborts the
    interpreter at exit was a REAL lost round — this turns that silent
    134 into a named thread with a stack."""
    offenders = find_leaked_compile_threads()
    if not offenders:
        return
    import sys as _sys

    lines = [
        "",
        "=" * 70,
        "LEAKED BACKGROUND COMPILE THREAD(S) AT SESSION END "
        "(exit-134 guard):",
        "a test kicked off device work (table build / kernel trace) and "
        "exited without draining it; interpreter teardown will race the "
        "compile and can abort the run with no blame.",
    ]
    for name, stack in offenders:
        lines.append("-" * 70)
        lines.append(f"thread: {name}")
        lines.append(stack.rstrip())
    lines.append("=" * 70)
    print("\n".join(lines), file=_sys.stderr, flush=True)
    # fail the run visibly: rc=1 with the report above beats the silent
    # SIGABRT the leak would otherwise risk.  (wrap_session returns
    # session.exitstatus AFTER this hook, so the assignment sticks.)
    session.exitstatus = max(int(exitstatus or 0), 1)


@pytest.fixture
def cpu_crypto_backend(monkeypatch):
    """Force the sequential host verifier (storage/domain-logic tests
    that don't exercise the kernel).  A fixture, NOT a module-level
    os.environ write: pytest imports every test module at collection
    time, so module-level env mutation leaks into the whole suite and
    silently reroutes other files' verifier paths."""
    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "cpu")
