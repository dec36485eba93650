"""The analyzers, analyzed: unit fixtures for every linter check, the
allowlist round-trip, the runtime lock-order witness, and the GATE test
that keeps ``cometbft_tpu/`` lint-clean — run the tier-1 suite and you
have run the linter."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading

import pytest

from cometbft_tpu.analysis import (
    jax_purity,
    linter,
    lock_blocking,
    lockwitness,
    metrics_registry,
    raw_env,
    socket_timeout,
    swallowed_exc,
    thread_names,
    unchecked_shift_width,
)
from cometbft_tpu.utils import envknobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mod(src: str, path: str = "cometbft_tpu/fake/mod.py") -> linter.Module:
    return linter.Module(path, src)


# ------------------------------------------------- per-check fixtures

def test_lock_blocking_trips_on_each_blocking_kind():
    src = '''
import time

class C:
    def bad(self):
        with self._mtx:
            self.sock.sendall(b"x")       # 1
            self.thread.join()            # 2
            time.sleep(1)                 # 3
            self.q.get()                  # 4
            self.ev.wait()                # 5
            self.fut.result()             # 6
            self.sock.recv(10)            # 7
'''
    found = lock_blocking.check(_mod(src))
    assert len(found) == 7, [f.message for f in found]
    assert all(f.check == "lock-held-across-blocking-call" for f in found)


def test_lock_blocking_ignores_bounded_and_deferred():
    src = '''
class C:
    def ok(self):
        with self._lock:
            self.q.get(timeout=1.0)       # bounded
            self.thread.join(2.0)         # bounded
            ", ".join(["a"])              # str.join
            self.d.get("key")             # dict.get has args

            def later():
                self.sock.recv(10)        # deferred body, not under lock
        self.sock.recv(10)                # lock released
'''
    assert lock_blocking.check(_mod(src)) == []


def test_lock_blocking_sees_context_manager_expressions():
    src = '''
import contextlib

class C:
    def bad(self):
        with self._mtx:
            with contextlib.closing(self.sock.accept()[0]) as conn:
                pass

    def ok(self):
        # same shape, no lock held: the accept() itself is fine
        with contextlib.closing(self.sock.accept()[0]) as conn:
            pass
'''
    (f,) = lock_blocking.check(_mod(src))
    assert "accept()" in f.message and "_mtx" in f.message


def test_lock_blocking_nested_with_tracks_innermost():
    src = '''
class C:
    def bad(self):
        with self._outer_mtx:
            with self._inner_lock:
                self.sock.sendall(b"x")
'''
    (f,) = lock_blocking.check(_mod(src))
    assert "_inner_lock" in f.message


def test_socket_timeout_trips_on_each_shape():
    src = '''
import socket

def dial(host, port):
    sock = socket.socket()                    # 1: no settimeout in scope
    sock.connect((host, port))                # 2: socky receiver
    return sock

def read(sock):
    return sock.recv(4096)                    # 3

def listen(host):
    return socket.create_server((host, 0))    # 4

def dial2(host, port):
    return socket.create_connection((host, port))  # 5: no timeout arg
'''
    found = socket_timeout.check(_mod(src))
    assert len(found) == 5, [f.render() for f in found]
    assert all(f.check == "socket-without-timeout" for f in found)


def test_socket_timeout_cleared_by_function_or_class_scope():
    src = '''
import socket

def dial_ok(host, port):
    s = socket.socket()
    s.settimeout(2.0)                          # clears the whole function
    s.connect((host, port))
    return s

def dial_timeout_arg(host, port):
    return socket.create_connection((host, port), 5.0)   # positional

def dial_timeout_kw(host, port):
    return socket.create_connection((host, port), timeout=5.0)

def blocking_declared(sock):
    sock.settimeout(None)                      # deliberate: declared
    return sock.recv(10)

class Client:
    def __init__(self, host, port):
        self.sock = socket.create_connection((host, port), 2.0)

    def read(self):
        # cleared by the CLASS scope: the constructor dialed with a
        # timeout — the create-in-one-method, read-in-another idiom
        return self.sock.recv(4096)

def sql(path):
    import sqlite3
    return sqlite3.connect(path)               # not a socket: never flagged
'''
    assert socket_timeout.check(_mod(src)) == []


def test_socket_timeout_one_class_does_not_launder_another():
    src = '''
import socket

class Good:
    def __init__(self):
        self.sock = socket.create_connection(("h", 1), 2.0)

class Bad:
    def read(self, sock):
        return sock.recv(10)
'''
    (f,) = socket_timeout.check(_mod(src))
    assert f.check == "socket-without-timeout" and ".recv" in f.message


def test_swallowed_exc_trips_on_bare_and_broad_pass():
    src = '''
def loop():
    try:
        work()
    except Exception:
        pass
    try:
        work()
    except:
        raise SystemExit
'''
    found = swallowed_exc.check(_mod(src))
    assert len(found) == 2
    assert any("bare" in f.message for f in found)


def test_swallowed_exc_trips_on_continue_break_and_bare_return():
    src = '''
def loop():
    while True:
        try:
            work()
        except Exception:
            continue              # iteration vanishes untraced
    for _ in it:
        try:
            work()
        except Exception:
            break                 # loop ends silently
    try:
        work()
    except Exception:
        return None               # constant bail-out, error dropped
'''
    found = swallowed_exc.check(_mod(src))
    assert len(found) == 3, [f.message for f in found]


def test_swallowed_exc_allows_computed_fallback_return():
    src = '''
def read(path, default):
    try:
        return parse(path)
    except Exception:
        return default            # real fallback value, not a swallow
'''
    assert swallowed_exc.check(_mod(src)) == []


def test_swallowed_exc_allows_narrow_and_handled():
    src = '''
def loop():
    try:
        work()
    except OSError:
        pass                      # narrow type: fine
    try:
        work()
    except Exception as e:
        log.warning(f"boom {e}")  # handled: fine
'''
    assert swallowed_exc.check(_mod(src)) == []


def test_raw_env_trips_on_all_read_forms():
    src = '''
import os

a = os.environ.get("COMETBFT_TPU_FOO", "")
b = os.getenv("COMETBFT_TPU_BAR")
c = os.environ["COMETBFT_TPU_BAZ"]
d = "COMETBFT_TPU_QUX" in os.environ
'''
    found = raw_env.check(_mod(src))
    assert len(found) == 4, [f.message for f in found]


def test_raw_env_ignores_writes_other_vars_and_envknobs_itself():
    src = '''
import os

os.environ["COMETBFT_TPU_FOO"] = "1"          # write
env = dict(os.environ)
env.pop("COMETBFT_TPU_FOO", None)             # child-env scrub
x = os.environ.get("XLA_FLAGS", "")           # not our namespace
'''
    assert raw_env.check(_mod(src)) == []
    # the registry module itself is exempt
    exempt = '''
import os
v = os.environ.get("COMETBFT_TPU_FOO")
'''
    assert raw_env.check(_mod(exempt, "cometbft_tpu/utils/envknobs.py")) == []


def test_jax_purity_traces_roots_and_closure():
    src = '''
import os
import jax
from jax import lax

def helper(x):
    print("traced once, never again")
    return x

@jax.jit
def kernel(x):
    v = os.environ.get("COMETBFT_TPU_FOO")
    y = float(x)
    return helper(x)

def body(i, acc):
    return acc.item()

def outer(x):
    with jax.ensure_compile_time_eval():
        print("exempt: compile-time eval")
    return lax.fori_loop(0, 4, body, x)

_J = jax.jit(outer)
'''
    found = jax_purity.check(_mod(src, "cometbft_tpu/ops/fake.py"))
    msgs = "\n".join(f.message for f in found)
    assert "env read" in msgs
    assert "float() on parameter 'x'" in msgs
    assert ".item()" in msgs
    assert "print()" in msgs  # via the helper() closure
    assert "exempt" not in msgs and len(found) == 4
    # out of ops//parallel/ scope: silent
    assert jax_purity.check(_mod(src, "cometbft_tpu/types/fake.py")) == []


def test_metrics_registry_import_aware():
    src = '''
from collections import Counter
from .utils.metrics import Gauge

word_counts = Counter()          # collections.Counter: fine
g = Gauge("depth")               # direct metric construction: flagged
'''
    found = metrics_registry.check(_mod(src))
    assert len(found) == 1 and "Gauge" in found[0].message
    # utils/metrics.py itself constructs the classes — exempt
    assert metrics_registry.check(
        _mod(src, "cometbft_tpu/utils/metrics.py")
    ) == []


def test_thread_names_flags_unnamed():
    src = '''
import threading
from concurrent.futures import ThreadPoolExecutor

threading.Thread(target=f, daemon=True).start()          # flagged
threading.Thread(target=f, name="worker").start()        # named: fine
ThreadPoolExecutor(max_workers=2)                        # flagged
ThreadPoolExecutor(max_workers=2, thread_name_prefix="x")
'''
    found = thread_names.check(_mod(src))
    assert len(found) == 2


# ------------------------------------------------- allowlist round-trip

def test_allowlist_round_trip_and_stale_detection():
    al = linter.Allowlist.parse(
        "# header comment\n"
        "raw-env-read cometbft_tpu/foo.py:7   # justified\n"
        "unnamed-thread cometbft_tpu/bar.py   # whole file\n"
        "raw-env-read cometbft_tpu/gone.py:1  # stale\n"
    )
    hit = linter.Finding("raw-env-read", "cometbft_tpu/foo.py", 7, 0, "m")
    wrong_line = linter.Finding("raw-env-read", "cometbft_tpu/foo.py", 8, 0, "m")
    any_line = linter.Finding("unnamed-thread", "cometbft_tpu/bar.py", 99, 0, "m")
    abs_path = linter.Finding(
        "raw-env-read", "/abs/prefix/cometbft_tpu/foo.py", 7, 0, "m"
    )
    assert al.suppresses(hit)
    assert not al.suppresses(wrong_line)
    assert al.suppresses(any_line)
    assert al.suppresses(abs_path)  # suffix match on '/' boundary
    stale = al.unused()
    assert [e.path for e in stale] == ["cometbft_tpu/gone.py"]


def test_allowlist_rejects_malformed_lines():
    import pytest

    with pytest.raises(ValueError):
        linter.Allowlist.parse("justacheckid\n")
    with pytest.raises(ValueError):
        linter.Allowlist.parse("check path:NaN\n")


# ------------------------------------------------- lock-order witness

def test_lockwitness_reports_ab_ba_inversion_across_threads():
    installed_here = not lockwitness.installed()
    if installed_here:
        lockwitness.install()
    try:
        baseline = len(lockwitness.violations())
        A, B = threading.Lock(), threading.Lock()

        def t1():
            with A:
                with B:
                    pass

        def t2():
            with B:
                with A:
                    pass

        th1 = threading.Thread(target=t1, name="witness-t1")
        th1.start()
        th1.join()  # sequential: records A->B without deadlocking
        th2 = threading.Thread(target=t2, name="witness-t2")
        th2.start()
        th2.join()

        new = lockwitness.violations()[baseline:]
        cycles = [v for v in new if v.kind == "order-cycle"]
        assert cycles, "B->A after A->B must close a cycle"
        rep = cycles[0].render()
        # both stacks present: the closing edge and the prior edge
        assert "stack recording new edge" in rep
        assert "stack that recorded prior edge" in rep
        assert "t1" in rep or "t2" in rep or "Lock@" in rep
    finally:
        # scrub the intentional violation so the conftest per-test
        # assertion doesn't blame this test, and drop the A/B edges
        lockwitness.clear()
        if installed_here:
            lockwitness.uninstall()


def test_lockwitness_reports_inflight_deadlock():
    """The case the serialized inversion above can't cover: both threads
    actually deadlock.  Edges are recorded on the blocking-acquire
    ATTEMPT, so the cycle must report even though neither acquire ever
    succeeds — a post-acquire hook would hang silently, which is the
    worst possible outcome for the run that most needs the witness."""
    import time

    installed_here = not lockwitness.installed()
    if installed_here:
        lockwitness.install()
    try:
        baseline = len(lockwitness.violations())
        A, B = threading.Lock(), threading.Lock()
        both_held = threading.Barrier(2)

        def grab(first, second):
            with first:
                both_held.wait(5)  # guarantee the real deadlock
                with second:
                    pass

        # daemon: these two park forever in inner.acquire; the
        # interpreter may exit with them blocked
        t1 = threading.Thread(
            target=grab, args=(A, B), name="witness-dl-1", daemon=True
        )
        t2 = threading.Thread(
            target=grab, args=(B, A), name="witness-dl-2", daemon=True
        )
        t1.start(); t2.start()
        deadline = time.monotonic() + 5
        cycles = []
        while time.monotonic() < deadline and not cycles:
            cycles = [
                v for v in lockwitness.violations()[baseline:]
                if v.kind == "order-cycle"
            ]
            time.sleep(0.01)
        assert cycles, "in-flight deadlock never reported"
        rep = cycles[0].render()
        assert "stack recording new edge" in rep
        assert "stack that recorded prior edge" in rep
    finally:
        lockwitness.clear()
        if installed_here:
            lockwitness.uninstall()


def test_lockwitness_reports_sleep_while_locked():
    import time

    installed_here = not lockwitness.installed()
    if installed_here:
        lockwitness.install()
    try:
        baseline = len(lockwitness.violations())
        L = threading.Lock()
        with L:
            time.sleep(0.001)
        new = lockwitness.violations()[baseline:]
        assert any(v.kind == "blocking-while-locked" for v in new)
    finally:
        lockwitness.clear()
        if installed_here:
            lockwitness.uninstall()


def test_lockwitness_cross_thread_release_keeps_held_exact():
    """threading.Lock may legally be released by a different thread
    (handoff).  The witness must scrub the ACQUIRING thread's held
    entry, or that thread records phantom edges forever."""
    import time

    installed_here = not lockwitness.installed()
    if installed_here:
        lockwitness.install()
    try:
        baseline = len(lockwitness.violations())
        handoff = threading.Lock()
        other = threading.Lock()
        released = threading.Event()

        def t1():
            handoff.acquire()  # released by t2
            released.wait(5)
            # if the handoff entry leaked, both of these would emit
            # violations (phantom edge + phantom sleep-under-lock)
            with other:
                pass
            time.sleep(0.001)

        def t2():
            time.sleep(0.05)
            handoff.release()
            released.set()

        a = threading.Thread(target=t1, name="witness-owner")
        b = threading.Thread(target=t2, name="witness-releaser")
        a.start(); b.start(); a.join(); b.join()
        assert lockwitness.violations()[baseline:] == []
    finally:
        lockwitness.clear()
        if installed_here:
            lockwitness.uninstall()


def test_lockwitness_reentrant_rlock_release_keeps_held_exact():
    """Two reentrant acquires need two releases to clear the held-set;
    a leaked entry would flag the follow-up sleep as under-lock."""
    import time

    installed_here = not lockwitness.installed()
    if installed_here:
        lockwitness.install()
    try:
        baseline = len(lockwitness.violations())
        r = threading.RLock()
        with r:
            with r:
                pass
        time.sleep(0.001)  # held-set must be empty here
        assert lockwitness.violations()[baseline:] == []
    finally:
        lockwitness.clear()
        if installed_here:
            lockwitness.uninstall()


def test_lockwitness_queue_and_condition_stay_exact():
    """Condition.wait fully releases the underlying (witnessed) lock via
    _release_save; the held-set must reflect that or every queue.get
    would look like sleep-under-lock."""
    import queue
    import time

    installed_here = not lockwitness.installed()
    if installed_here:
        lockwitness.install()
    try:
        baseline = len(lockwitness.violations())
        q = queue.Queue()

        def producer():
            time.sleep(0.01)
            q.put("x")

        threading.Thread(target=producer, name="witness-prod").start()
        assert q.get(timeout=5) == "x"
        assert lockwitness.violations()[baseline:] == []
    finally:
        lockwitness.clear()
        if installed_here:
            lockwitness.uninstall()


# ------------------------------------------------- envknobs registry

def test_lint_rejects_nonexistent_path():
    import pytest

    with pytest.raises(FileNotFoundError):
        linter.lint_paths(["no/such/dir_typo"])


def test_lockwitness_raise_mode_does_not_leak_the_lock():
    """When a cycle-closing acquire raises (LOCKCHECK=raise), the lock
    being acquired must be handed back — otherwise the witness
    manufactures the very deadlock it reports."""
    import pytest

    was_installed = lockwitness.installed()
    lockwitness.install(raise_on_violation=True)
    try:
        A, B = threading.Lock(), threading.Lock()
        with A:
            with B:
                pass
        with B:
            with pytest.raises(RuntimeError, match="order cycle"):
                A.acquire()
        assert A.acquire(timeout=1), "lock leaked locked by the witness"
        A.release()
    finally:
        lockwitness.clear()
        # restore the conftest's record-only mode (or uninstall if this
        # test installed it)
        if was_installed:
            lockwitness.install(raise_on_violation=False)
        else:
            lockwitness.uninstall()


def test_envknobs_typed_getters(monkeypatch):
    monkeypatch.setenv(envknobs.COMB_MIN, "77")
    assert envknobs.get_int(envknobs.COMB_MIN) == 77
    monkeypatch.setenv(envknobs.COMB_MIN, "junk")
    assert envknobs.get_int(envknobs.COMB_MIN) == 32  # declared default
    monkeypatch.setenv(envknobs.SECP_GLV, "0")
    assert envknobs.get_bool(envknobs.SECP_GLV) is False
    monkeypatch.delenv(envknobs.SECP_GLV, raising=False)
    assert envknobs.get_bool(envknobs.SECP_GLV) is True
    # set-but-empty (`KNOB= cmd`) means default, never False — this
    # knob keys a compiled-program cache
    monkeypatch.setenv(envknobs.SECP_GLV, "")
    assert envknobs.get_bool(envknobs.SECP_GLV) is True
    monkeypatch.delenv(envknobs.DEVICE_BATCH_MIN, raising=False)
    assert envknobs.get_opt_int(envknobs.DEVICE_BATCH_MIN) is None
    monkeypatch.setenv(envknobs.DEVICE_BATCH_MIN, "9")
    assert envknobs.get_opt_int(envknobs.DEVICE_BATCH_MIN) == 9


def test_envknobs_undeclared_knob_is_loud():
    import pytest

    with pytest.raises(KeyError):
        envknobs.get_str("COMETBFT_TPU_NOT_A_KNOB")


def test_lockwitness_bool_spellings_match_envknobs():
    """The raw COMETBFT_TPU_LOCKCHECK readers (lockwitness.maybe_install,
    tests/conftest.py) cannot import envknobs before the witness installs,
    so they use lockwitness.TRUE/FALSE_SPELLINGS — which must stay
    identical to get_bool's sets or test and production spell the knob
    differently."""
    assert lockwitness.TRUE_SPELLINGS == envknobs._TRUE
    assert lockwitness.FALSE_SPELLINGS == envknobs._FALSE


def test_knobs_doc_is_generated_and_current():
    with open(os.path.join(REPO, "docs", "knobs.md"), encoding="utf-8") as f:
        on_disk = f.read()
    assert on_disk == envknobs.to_markdown(), (
        "docs/knobs.md is stale — regenerate with "
        "`python -m cometbft_tpu.utils.envknobs > docs/knobs.md`"
    )


# ------------------------------------- unchecked-shift-width (range plane)

def test_unchecked_shift_width_flags_dynamic_amounts():
    src = '''
import jax
import jax.numpy as jnp
from jax import lax

@jax.jit
def k(x, widths):
    a = lax.shift_left(x, jnp.sum(x))        # device-computed amount
    b = x >> widths[0]                       # indexed from an array
    c = jnp.right_shift(x, lax.rem(x, x))    # traced call as amount
    return a + b + c
'''
    found = unchecked_shift_width.check(_mod(src, "cometbft_tpu/ops/fake.py"))
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 3, msgs
    assert "computed by jnp.sum(...)" in msgs
    assert "indexed from an array" in msgs
    assert "computed by lax.rem(...)" in msgs
    assert all(f.check == "unchecked-shift-width" for f in found)


def test_unchecked_shift_width_exempts_static_amounts():
    # literals, module constants, unrolled-loop variables, dtype-pinning
    # constructors over static args, compile-time eval, and host code
    src = '''
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BITS = 12

@jax.jit
def k(x, idx):
    a = x >> 12
    b = lax.shift_left(x, BITS)
    for r in (7, 9, 13):
        x = x ^ (x >> np.uint32(r))
    c = jnp.left_shift(x, jnp.asarray(BITS - 4, jnp.uint32))
    with jax.ensure_compile_time_eval():
        d = x >> idx[0]
    return a + b + c

def host_only(x, n):
    return x >> n[0]
'''
    assert unchecked_shift_width.check(
        _mod(src, "cometbft_tpu/ops/fake.py")
    ) == []


def test_unchecked_shift_width_scope_and_registration():
    src = '''
import jax

@jax.jit
def k(x, w):
    return x >> w[0]
'''
    # outside ops//parallel//models: silent
    assert unchecked_shift_width.check(
        _mod(src, "cometbft_tpu/types/fake.py")
    ) == []
    # the range-plane AST subset is registered (scripts/lint.py
    # --check range resolves through it)
    assert "unchecked-shift-width" in linter.RANGE_CHECK_IDS
    assert set(linter.RANGE_CHECK_IDS) <= set(linter.all_checks())


# ------------------------------------------------- the gate

def test_linter_runs_clean_over_cometbft_tpu():
    """THE gate: zero non-allowlisted findings over the package, zero
    stale allowlist entries, and every allowlist entry carries a
    justification comment.  lint_paths runs every registered check, so
    the kernel-plane trio (untracked-jit / host-sync-in-hot-path /
    weak-type-literal, PR 4) and the sharded-plane check
    (donated-read-after-dispatch, PR 6) are asserted present first — the
    gate must not silently narrow if check registration regresses."""
    assert set(linter.KERNEL_CHECK_IDS) <= set(linter.all_checks())
    assert set(linter.SHARDING_CHECK_IDS) <= set(linter.all_checks())
    allowlist = linter.Allowlist.load(linter.default_allowlist_path())
    findings, stale = linter.lint_paths(
        [os.path.join(REPO, "cometbft_tpu")], allowlist=allowlist
    )
    assert not findings, "new lint findings:\n" + "\n".join(
        f.render() for f in findings
    )
    assert not stale, "stale allowlist entries: " + ", ".join(
        f"line {e.lineno}" for e in stale
    )
    for e in allowlist.entries:
        assert "#" in allowlist.raw_lines[e.lineno - 1], (
            f"allowlist line {e.lineno} has no justification comment"
        )


def test_lint_script_json_contract(tmp_path):
    """scripts/lint.py is the CI entrypoint: one subprocess run over a
    deliberately bad file proves the --json shape, the finding payload,
    and the non-zero exit (the clean-tree exit-0 side is the in-process
    gate test above — no need to lint the whole package twice)."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import os\nv = os.environ.get('COMETBFT_TPU_X', '')\n"
        "try:\n    pass\nexcept Exception:\n    pass\n"
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         str(bad), "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert data["ok"] is False
    checks = {f["check"] for f in data["findings"]}
    assert "raw-env-read" in checks
    assert "swallowed-exception-in-thread" in checks
    for f in data["findings"]:
        assert {"check", "path", "line", "col", "message"} <= set(f)


GATE_STUBS = {
    # gate id -> (module, function lint.py calls, what the stub returns,
    #             the summary --json must carry under the gate's key)
    "kernel": (
        "kernelcheck", "run_check", "([], [])",
        {"ok": True, "kernels": 0, "primitive_total": 0, "eqns": {},
         "findings": []},
    ),
    "sharding": (
        "shardcheck", "run_subprocess",
        "([], {'ok': True, 'device_count': 8,"
        " 'kernels': {'sharded_merkle_root': {'eqns': 633}}})",
        {"ok": True, "findings": 0, "device_count": 8,
         "kernels": {"sharded_merkle_root": {"eqns": 633}}},
    ),
    "range": (
        "rangecheck", "run_check", "([], [])",
        {"ok": True, "kernels": 0, "headroom": {}, "findings": []},
    ),
}


@pytest.mark.parametrize("gate", sorted(GATE_STUBS))
def test_lint_json_carries_each_trace_gates_summary(gate, tmp_path):
    """scripts/lint.py --check <gate> --json is where a trace gate's
    machine-readable summary is read: wire check with the pass stubbed
    (the real passes are the slow gates)."""
    mod, fn, result, want = GATE_STUBS[gate]
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    code = (
        "import sys\n"
        f"sys.path.insert(0, {os.path.join(REPO, 'scripts')!r})\n"
        "import lint\n"
        f"from cometbft_tpu.analysis import {mod}\n"
        f"{mod}.{fn} = lambda **kw: {result}\n"
        f"sys.exit(lint.main([{str(clean)!r}, '--check', {gate!r}, '--json']))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    data = json.loads(proc.stdout)
    assert data["ok"] is True and data["findings"] == []
    assert data[gate] == want


@pytest.mark.parametrize("gate", ["kernelcheck", "rangecheck"])
def test_golden_holds_exactly_the_manifests_kernels(gate):
    """No tracing: a golden entry whose manifest row is gone (or a row
    with no entry) is caught here, not only by the slow gates.  The
    shard golden's half is tests/test_shardcheck.py's
    test_real_sharded_programs_census_is_reshard_free."""
    import importlib

    from cometbft_tpu.analysis import kernel_manifest

    mod = importlib.import_module(f"cometbft_tpu.analysis.{gate}")
    assert set(mod.load_fingerprints()) == {
        k.name for k in kernel_manifest.KERNELS
    }
