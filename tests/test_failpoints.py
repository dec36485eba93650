"""Crash-at-every-step recovery: a real node process is crashed at each
fail point in the commit path (FAIL_TEST_INDEX) and must recover via
WAL + handshake replay on restart (reference: internal/fail/fail.go,
replay_test.go crash-at-every-WAL-write)."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from cometbft_tpu.utils.fail import EXIT_CODE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rpc(port, method, **params):
    req = json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": method, "params": params}
    ).encode()
    with urllib.request.urlopen(
        urllib.request.Request(
            f"http://127.0.0.1:{port}",
            data=req,
            headers={"Content-Type": "application/json"},
        ),
        timeout=3,
    ) as f:
        out = json.loads(f.read())
    if "error" in out:
        raise RuntimeError(out["error"])
    return out["result"]


def test_fail_point_counter(monkeypatch):
    import importlib

    monkeypatch.setenv("FAIL_TEST_INDEX", "-1")
    import cometbft_tpu.utils.fail as fail

    importlib.reload(fail)
    before = fail.points_hit()
    fail.fail_point("x")  # disabled: no counting, no crash
    assert fail.points_hit() == before


@pytest.mark.slow
def test_crash_at_every_commit_step_recovers(tmp_path):
    """For each fail point index: run a node until it self-crashes at
    that point, then restart clean and require the chain to advance past
    the crash height with the same app hash lineage."""
    home = str(tmp_path / "fp")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
    env.pop("COMETBFT_TPU_DEVICE_BATCH_MIN", None)  # conftest forces 1
    env["JAX_PLATFORMS"] = "cpu"

    def cli(*a, **kw):
        return subprocess.run(
            [sys.executable, "-m", "cometbft_tpu", *a],
            env=env, capture_output=True, text=True, **kw,
        )

    assert cli("--home", home, "init", "--chain-id", "fp-chain").returncode == 0
    port = 37701
    for k, v in (
        ("rpc.laddr", f"tcp://127.0.0.1:{port}"),
        ("p2p.laddr", "tcp://127.0.0.1:37700"),
        ("consensus.timeout_propose", "0.8"),
        ("consensus.timeout_prevote", "0.4"),
        ("consensus.timeout_precommit", "0.4"),
    ):
        r = cli("--home", home, "config", "set", k, v)
        assert r.returncode == 0, (k, r.stderr)

    def wait_height(target, timeout=90):
        deadline = time.monotonic() + timeout
        h = -1
        while time.monotonic() < deadline:
            try:
                h = int(
                    _rpc(port, "status")["sync_info"]["latest_block_height"]
                )
                if h >= target:
                    return h
            except Exception:
                pass
            time.sleep(0.5)
        return h

    # 5 fail points per commit: before save_block, before/after WAL
    # end_height, after FinalizeBlock, after SaveFinalizeBlockResponse
    for idx in (1, 2, 3, 4, 5):
        crash_env = dict(env)
        crash_env["FAIL_TEST_INDEX"] = str(idx)
        node = subprocess.Popen(
            [sys.executable, "-m", "cometbft_tpu", "--home", home, "start"],
            env=crash_env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )
        rc = node.wait(timeout=120)
        assert rc == EXIT_CODE, f"idx {idx}: expected crash exit, got {rc}"

        # restart clean: WAL replay + ABCI handshake must recover
        node = subprocess.Popen(
            [sys.executable, "-m", "cometbft_tpu", "--home", home, "start"],
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )
        try:
            before = wait_height(0, timeout=60)
            assert before >= 0, f"idx {idx}: node did not come back"
            got = wait_height(before + 2)
            assert got >= before + 2, (
                f"idx {idx}: chain stuck at {got} after crash recovery"
            )
        finally:
            node.terminate()
            node.wait(timeout=20)
