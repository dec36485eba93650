"""chip_smoke.py's own logic, on the CPU: a hidden host route gives the
right verdicts and must still fail the smoke, because the smoke reads
the counters and the span ring, not only the verdicts.  (As a command
the script needs a TPU; its legs run here at a tiny width.)"""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke as a module, over a fresh metrics hub, a fresh global
    verify service and an empty span ring."""
    from cometbft_tpu.utils import fail, metrics, tracing
    from cometbft_tpu.verifysvc import service as svc_mod

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(metrics, "_HUB", metrics.Hub())
    svc_mod.reset_global_service()
    was_on = tracing.enabled()
    tracing.set_enabled(True)
    tracing.reset()
    yield mod
    fail.clear_all()
    svc_mod.reset_global_service()
    tracing.set_enabled(was_on)
    tracing.reset()


def _commit_items(n):
    from cometbft_tpu.crypto import ed25519 as host

    keys = [host.PrivKey.from_seed(bytes([i + 1]) * 32) for i in range(n)]
    return [
        (k.pub_key().data, b"vote-%d" % i, k.sign(b"vote-%d" % i))
        for i, k in enumerate(keys)
    ]


def test_forced_host_reverify_fails_the_route_check(smoke):
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.utils import fail

    assert smoke.route_failures(smoke.route_report()) == []  # nothing ran yet

    fail.arm("fail_dispatch")
    bv = crypto_batch.create_batch_verifier("ed25519")
    for it in _commit_items(4):
        bv.add(*it)
    ok, vec = bv.verify()
    fail.clear("fail_dispatch")
    # the hidden route answers correctly — which is why verdicts alone
    # prove nothing about the device
    assert ok and vec == [True] * 4

    rep = smoke.route_report()
    assert rep["verify_svc_host_reverify"] == 1
    assert rep["dispatch_spans"] == 1 and rep["device_wait_spans"] == 0
    assert rep["batches_by_program"] == {"host": 1}
    problems = smoke.route_failures(rep)
    assert any("verify_svc_host_reverify" in p for p in problems), problems
    assert any("device waits" in p for p in problems), problems


def test_host_routed_small_batch_fails_the_route_check(smoke):
    """Below COMETBFT_TPU_DEVICE_BATCH_MIN a batch verifies on the host
    (models/verifier): right verdicts, a `verify.host_route` span, and a
    failed smoke."""
    from cometbft_tpu.crypto import batch as crypto_batch

    bv = crypto_batch.create_batch_verifier("ed25519")
    for it in _commit_items(4):
        bv.add(*it)
    assert bv.verify() == (True, [True] * 4)
    problems = smoke.route_failures(smoke.route_report())
    assert any("verify.host_route" in p for p in problems), problems


def test_command_fails_for_want_of_a_tpu():
    """`python chip_smoke.py` on a host whose JAX offers no TPU exits
    non-zero, says so, and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, SMOKE], capture_output=True, text=True,
        timeout=120, env=env, cwd=REPO,
    )
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_result_line_is_last_and_has_only_the_contract_keys(
    smoke, monkeypatch, capsys
):
    """A run that held ends stdout with {"ok", "device": {"platform",
    "kind", "count"}} exactly; the facts go on the line before."""
    import json
    import types

    import jax

    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    monkeypatch.setattr(
        smoke, "run", lambda wl, ws, facts: facts.update(routes={"x": 1})
    )
    assert smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    head, _, facts = lines[-2].partition(": ")
    assert head == "chip_smoke facts" and json.loads(facts)["routes"] == {"x": 1}


def test_failed_run_prints_no_result(smoke, monkeypatch, capsys):
    import types

    import jax

    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [dev])

    def run(wl, ws, facts):
        raise smoke.SmokeFailure("a check did not hold")

    monkeypatch.setattr(smoke, "run", run)
    with pytest.raises(smoke.SmokeFailure):
        smoke.main()
    assert capsys.readouterr().out == ""


@pytest.mark.slow  # compiles the uncached, table-build and comb programs
def test_legs_hold_at_a_tiny_width(smoke, monkeypatch):
    """Both legs end to end on the CPU backend at widths 10 and 5, with
    the thresholds lowered so that the same routes are taken as at
    10,000 and 175: background table build, uncached program meanwhile,
    then the comb program and blocksync; the small set bound at first
    sight and served by the comb program (both widths share the one
    128-lane program here)."""
    monkeypatch.setenv("COMETBFT_TPU_COMB_MIN", "4")
    monkeypatch.setenv("COMETBFT_TPU_COMB_ASYNC_MIN", "8")
    monkeypatch.setenv("COMETBFT_TPU_COMB_HOST_BUILD_MAX", "0")
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")
    facts = smoke.run(10, 5, {})
    assert facts["routes"]["batches_by_program"]["comb"] >= 6
    assert facts["comb_lanes"] == [128]
