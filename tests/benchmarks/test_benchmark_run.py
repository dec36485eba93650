"""The benchmark's command and its one-cell run, on the CPU: as a
command it needs a TPU and must fail here with nothing on stdout; its
legs run at a tiny width through ``harness.run_cell``, the internal
entry that skips only the TPU check.  Eight signatures take the
program's host route, which gives right verdicts, and the run must
still say ``correct: false``: it reads the route, not only the verdicts
(the negative test tests/test_chip_smoke.py has for the smoke)."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import checks, harness, reference, spec  # noqa: E402
from benchmarks.drivers import commit_serial  # noqa: E402

BENCH = spec.load_benchmark()
ARGS = ["--workload", "commit-175-serial", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def test_command_fails_for_want_of_a_tpu():
    r = subprocess.run(
        [sys.executable, *BENCH["command"][1:], *ARGS], capture_output=True,
        text=True, timeout=120, cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout == ""


def test_command_fails_where_only_the_benchmark_is(tmp_path):
    """BENCHMARK.json and the files under ``paths`` alone, without the
    program: non-zero, no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(
            os.path.join(REPO, p), tmp_path / p,
            ignore=shutil.ignore_patterns("__pycache__", ".trace"),
        )
    r = subprocess.run(
        [sys.executable, *BENCH["command"][1:], *ARGS], capture_output=True,
        text=True, timeout=120, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
    )
    assert r.returncode != 0
    assert r.stdout == ""


@pytest.mark.parametrize("kw", [
    dict(height=1, round_=0, seconds=1_700_000_123, nanos=0),
    dict(height=8, round_=0, seconds=1_700_000_999, nanos=0),
    dict(height=1 << 40, round_=3, seconds=1, nanos=999_999_999),
    dict(height=0, round_=0, seconds=0, nanos=0),
])
def test_reference_sign_bytes_equal_the_programs(kw):
    """The reference encodes CanonicalVote by hand; the program's
    encoder must give the same bytes, or every commit the benchmark
    signs would be refused."""
    from cometbft_tpu.wire.canonical import (
        PRECOMMIT_TYPE, CanonicalBlockID, CanonicalPartSetHeader, Timestamp,
        vote_sign_bytes,
    )

    block_hash, parts_hash = bytes(range(32)), bytes(range(32, 64))
    want = vote_sign_bytes(
        "bench-valset-175", PRECOMMIT_TYPE, kw["height"], kw["round_"],
        CanonicalBlockID(
            hash=block_hash,
            part_set_header=CanonicalPartSetHeader(total=1, hash=parts_hash),
        ),
        Timestamp(seconds=kw["seconds"], nanos=kw["nanos"]),
    )
    got = reference.precommit_sign_bytes(
        "bench-valset-175", kw["height"], kw["round_"], block_hash, 1,
        parts_hash, kw["seconds"], kw["nanos"],
    )
    assert got == want


def test_reference_verdicts():
    key = reference.private_key(1, b"t", 0)
    pub, sig = reference.public_bytes(key), key.sign(b"msg")
    assert reference.verify(pub, b"msg", sig)
    assert not reference.verify(pub, b"msg2", sig)
    assert not reference.verify(pub, b"msg", sig[:-1] + bytes([sig[-1] ^ 1]))
    assert not reference.verify(b"\x00" * 31, b"msg", sig)


@pytest.fixture
def fresh(monkeypatch):
    """A fresh metrics hub, a fresh global verify service and an empty
    span ring around a run_cell."""
    from cometbft_tpu.utils import metrics, tracing
    from cometbft_tpu.verifysvc import service as svc_mod

    monkeypatch.setattr(metrics, "_HUB", metrics.Hub())
    svc_mod.reset_global_service()
    was_on = tracing.enabled()
    yield
    svc_mod.reset_global_service()
    tracing.set_enabled(was_on)
    tracing.reset()


def tiny_cell(width: int, pool: int = 3, warm_s: float = 0.05):
    cell = spec.resolve("commit-175-serial")
    cell.config = dict(cell.config, validators=width)
    cell.traffic = dict(cell.traffic, pool=pool, warm_s=warm_s)
    return cell


def run_tiny(cell, seconds=0.4, seed=(1 << 31) + 77):
    import jax

    return harness.run_cell(
        cell, seed, seconds, False, time.monotonic(), jax.devices())  # (result, facts)


def test_host_routed_run_counts_rightly_and_is_not_correct(fresh):
    result, facts = run_tiny(tiny_cell(8))
    assert result["correct"] is False
    assert any("verify.host_route span(s)" in p for p in facts["problems"])
    assert result["failed"] == 0 and result["attempted"] > 3
    assert facts["samples"] == {"request_s": result["attempted"]}  # one each
    assert list(result) == ["correct", "attempted", "failed", "device",
                            "metrics", "compared"]  # what was compared: last
    assert all(c["limit"] == 0 for c in result["compared"].values())
    assert result["compared"]["batches_off_the_device"]["value"] > 0
    assert result["compared"]["requests_failed"]["value"] == 0
    assert facts["route"]["verify_host_route"] > 0  # the counter, ring or not
    assert set(result["metrics"]) == {"verdict_p50_ms", "verdict_p90_ms", "setup_s"}
    for name, m in result["metrics"].items():
        assert m["value"] > 0 and m["unit"] == ("s" if name == "setup_s" else "ms")
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(result)


def test_a_verdict_that_raises_is_a_failed_request(fresh, monkeypatch):
    """One commit of the pool of three is tampered after set-up: every
    third verdict raises, is counted, and the traffic goes on."""
    setup = commit_serial.setup

    def setup_then_tamper(cell, seed, log):
        state = setup(cell, seed, log)
        sc = state.pool[2]
        sc.commit, _ = checks.tampered(sc.commit, 8)
        return state

    monkeypatch.setattr(commit_serial, "setup", setup_then_tamper)
    result, _ = run_tiny(tiny_cell(8, warm_s=0))  # no warm-up to raise in
    assert result["correct"] is False
    assert result["compared"]["requests_failed"]["value"] == result["failed"]
    assert result["attempted"] >= 3
    assert result["failed"] in (result["attempted"] // 3,
                                (result["attempted"] + 1) // 3)


def test_a_program_that_stops_verifying_is_caught_after_the_window(
        fresh, monkeypatch):
    """An answer altered where it is produced: after set-up's checks the
    program's verify_commit accepts whatever it is given.  No request of
    the window fails, and the run is still not correct: the check after
    the window hands it a tampered commit through the same entry."""
    from cometbft_tpu.types import validation

    setup = commit_serial.setup

    def setup_then_break(cell, seed, log):
        state = setup(cell, seed, log)
        monkeypatch.setattr(validation, "verify_commit", lambda *a, **kw: None)
        return state

    monkeypatch.setattr(commit_serial, "setup", setup_then_break)
    result, facts = run_tiny(tiny_cell(8))
    assert result["failed"] == 0 and result["attempted"] > 3
    assert result["correct"] is False
    assert any(p.startswith("after the window:") and "accepted" in p
               for p in facts["problems"])


class StandIn:
    """A driver that verifies nothing and keeps the time of every call:
    its verdict takes ``cost`` seconds."""

    def __init__(self, cost: float):
        self.cost, self.warm_calls, self.run_calls = cost, [], []
        self.ring_on_in_warm = None

    def setup(self, cell, seed, log):
        return commit_serial.State(None, [None] * 4, cell.traffic["warm_s"], log)

    def verdict(self, valset, sc):
        from cometbft_tpu.utils import tracing

        self.ring_on_in_warm = tracing.enabled()
        self.warm_calls.append(time.monotonic())
        time.sleep(self.cost)
        self.warm_ended = time.monotonic()

    def run(self, state, window):
        while not window.expired():
            with window.request():
                self.run_calls.append(time.monotonic())
                time.sleep(self.cost)
                window.sample("request_s", self.cost)

    def finish(self, state):
        return []

    end_to_end = staticmethod(commit_serial.end_to_end)


@pytest.mark.parametrize("cost", [0.002, 0.02])
def test_the_warm_up_is_a_time_and_none_of_it_is_sampled(fresh, monkeypatch, cost):
    """The driver's warm-up with a stand-in verdict: it lasts ``warm_s``
    whatever a verdict costs (a count would shrink with the verdict),
    runs as the window does (ring off in an untraced run), ends before
    the window opens, and leaves no sample in it."""
    import jax

    stand_in = StandIn(cost)
    monkeypatch.setattr(commit_serial, "verdict", stand_in.verdict)
    stand_in.warm = commit_serial.warm  # the driver's own rule
    cell = tiny_cell(8, warm_s=0.3)
    cell.driver = stand_in
    t0 = time.monotonic()
    result, facts = harness.run_cell(cell, 5, 0.2, False, t0, jax.devices())
    warm = stand_in.warm_calls
    # a loaded machine sleeps longer than asked: the time holds, the
    # count only has its ceiling
    assert 0.3 <= stand_in.warm_ended - warm[0] < 0.3 + 1.0
    assert warm[-1] - warm[0] < 0.3
    assert 3 <= len(warm) <= 0.3 / cost + 1
    assert stand_in.ring_on_in_warm is False
    assert warm[-1] < stand_in.run_calls[0]
    assert facts["setup_s"] >= 0.3  # the warm-up is set-up
    assert facts["samples"] == {"request_s": result["attempted"]}
    assert result["attempted"] == len(stand_in.run_calls)
    thirds = facts["end_to_end"]["verdict_ms_thirds"]
    assert sum(t["n"] for t in thirds) == result["attempted"]
    assert "verdict_ms_thirds" not in result["metrics"]  # the facts line only


@pytest.mark.parametrize("traffic", sorted(
    {w["traffic"] for w in BENCH["workloads"]}))
def test_traffic_files_state_the_warm_up_as_a_time(traffic):
    t = spec.load_json(os.path.join(
        REPO, "benchmarks", "traffic", traffic + ".json"))
    assert "warm_verdicts" not in t
    assert 3 <= t["warm_s"] <= 30  # seconds of the cell's own traffic


def test_a_failed_check_of_setup_prints_no_result(fresh, monkeypatch, capsys):
    import types

    import jax

    def setup(cell, seed, log):
        raise checks.CheckFailure("verdicts differ from the reference")

    monkeypatch.setattr(commit_serial, "setup", setup)
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    assert harness.main(ARGS, time.monotonic()) == 1
    assert capsys.readouterr().out == ""


def test_same_seed_same_inputs_and_lengths_never_depend_on_it():
    from benchmarks import data

    cell = tiny_cell(5)
    a = data.make_commits(data.make_valset(cell.config, 11), 2)
    b = data.make_commits(data.make_valset(cell.config, 11), 2)
    c = data.make_commits(data.make_valset(cell.config, (1 << 31) + 5), 2)
    sigs = lambda pool: [s.signature for sc in pool for s in sc.commit.signatures]
    assert sigs(a) == sigs(b) and sigs(a) != sigs(c)
    assert [len(m) for sc in a for m in sc.sign_bytes] == [
        len(m) for sc in c for m in sc.sign_bytes]


@pytest.mark.slow  # compiles the uncached, table-build and comb programs
@pytest.mark.parametrize("width,comb", [(8, False), (10, True)])
def test_legs_hold_over_the_device_programs(fresh, monkeypatch, width, comb):
    """The same legs with the thresholds lowered (here, never in the
    benchmark) so that the routes of 175 and of 10,000 validators are
    taken: the uncached program, and tables bound in set-up and then
    the comb program."""
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")
    if comb:
        monkeypatch.setenv("COMETBFT_TPU_COMB_MIN", "8")
        monkeypatch.setenv("COMETBFT_TPU_COMB_ASYNC_MIN", "8")
        monkeypatch.setenv("COMETBFT_TPU_COMB_HOST_BUILD_MAX", "0")
    result, facts = run_tiny(tiny_cell(width), seconds=2.0)
    assert facts["problems"] == []
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
