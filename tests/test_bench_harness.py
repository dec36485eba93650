"""bench.py always prints one parseable JSON line, names the device on
it, and exits non-zero whenever the run did not measure what it set out
to measure.

A run that cannot reach its device must neither vanish (no line) nor
pass for a measurement (exit 0, a host-path number under a device
metric's name).  These tests pin the contract: one line, ``platform``
present, ``error`` set, non-zero exit, no degraded round.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

# the embedded contract passes are minutes of CPU tracing; their wiring
# is covered by test_kernelcheck / test_shardcheck / test_rangecheck
_NO_STATIC_PASSES = {
    "BENCH_KERNELCHECK": "0", "BENCH_SHARDCHECK": "0", "BENCH_RANGECHECK": "0",
}


def _run(env_extra: dict) -> tuple[int, dict]:
    env = os.environ.copy()
    env.pop("JAX_PLATFORMS", None)
    env.update(_NO_STATIC_PASSES)
    env.update(env_extra)
    r = subprocess.run(
        [sys.executable, BENCH],
        capture_output=True,
        text=True,
        timeout=120,
        env=env,
        cwd=REPO,
    )
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, f"exactly one JSON line expected, got: {r.stdout!r}"
    return r.returncode, json.loads(lines[0])


def test_unavailable_backend_is_a_failed_round():
    rc, out = _run(
        {"JAX_PLATFORMS": "no_such_platform", "BENCH_PROBE_TIMEOUT": "60"}
    )
    assert rc != 0
    assert out["metric"] == "verify_commit_p50_10k_ms"
    assert out["value"] is None
    assert "backend-unavailable" in out["error"]
    assert isinstance(out["phases"], dict)
    assert "kernelcheck" not in out  # BENCH_KERNELCHECK=0 honored
    # the line still names the device fields: nothing was found
    assert out["platform"] is None and out["device_count"] is None
    # ...and carries the probe's own structured account
    # (utils/healthmon.ProbeResult), the shape /tpu_health serves
    assert out["probe"]["ok"] is False
    assert out["probe"]["timed_out"] is False  # exited, didn't hang
    assert isinstance(out["probe"]["latency_s"], (int, float))
    # no degraded round: nothing was measured on the host instead
    assert "backend_mode" not in out and "scheduler" not in out


def test_wrong_platform_is_a_failed_round():
    """With no platform requested the bench expects a TPU; a JAX that
    finds only the CPU backend is a failed probe, named as such — never
    a quiet CPU run."""
    rc, out = _run({"BENCH_PROBE_TIMEOUT": "110"})
    assert rc != 0
    assert out["value"] is None
    assert out["platform"] == "cpu"  # what the probe found
    assert "expected 'tpu'" in out["error"]


def test_crash_after_attach_is_a_failed_round():
    # An explicit CPU request passes the probe step; then the run itself
    # dies early: a bogus size makes main() raise before any device work.
    rc, out = _run(
        {
            "JAX_PLATFORMS": "cpu",
            "BENCH_SKIP_PROBE": "1",
            "BENCH_N": "not-a-number",
        }
    )
    assert rc != 0
    assert out["value"] is None
    assert "error" in out
    # the device is stamped as what it is: an explicit CPU-backend run
    assert out["platform"] == "cpu" and out["device_kind"]
    assert out["device_count"] >= 1
