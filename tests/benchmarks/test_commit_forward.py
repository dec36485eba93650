"""The cell ``commit-175-moving-set`` on the CPU: it resolves through
BENCHMARK.json, its driver runs at 8 validators and 40 heights through
``harness.run_cell``, and each control (a guarantee broken) comes out
not correct: a run answered from the host, a tampered commit left in
the chain, a ``verify_commit`` that accepts everything after set-up, a
window that meets a set already bound.  With the floors lowered (here,
never in the benchmark) and the table cache bounded at three entries
the requests run the comb program at 128 lanes, every one a miss served
by one incremental bind, and the run is correct.
"""

import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import checks, harness, spec  # noqa: E402
from benchmarks.drivers import commit_forward  # noqa: E402
from tests.benchmarks.cell_appended import BENCHES, bench_for  # noqa: E402

CELL = "commit-175-moving-set"
TABLE_METRICS = {
    "table_bind_ms": "verifier", "table_build_ms": "verifier",
    "table_assemble_ms": "verifier", "table_assemble_device_ms": "kernels",
}


@pytest.fixture
def fresh(monkeypatch):
    """A fresh metrics hub, a fresh global verify service, an empty
    table cache bounded at three entries and an empty span ring around
    a run_cell."""
    from cometbft_tpu.models import comb_verifier as cv
    from cometbft_tpu.utils import metrics, tracing
    from cometbft_tpu.verifysvc import service as svc_mod

    monkeypatch.setattr(metrics, "_HUB", metrics.Hub())
    monkeypatch.setattr(cv, "_GLOBAL_CACHE", cv.ValsetCombCache(
        max_bytes=3 * cv.LANE_BUCKET * cv.TABLE_BYTES_PER_LANE))
    svc_mod.reset_global_service()
    was_on = tracing.enabled()
    yield
    svc_mod.reset_global_service()
    tracing.set_enabled(was_on)
    tracing.reset()


@pytest.fixture
def bound(fresh, monkeypatch):
    """Eight validators bind and their eight rows run the comb program."""
    monkeypatch.setenv("COMETBFT_TPU_COMB_MIN", "4")
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")


def small_cell(heights: int = 40, warm_s: float = 0.05):
    cell = spec.resolve(CELL)
    cell.config = dict(cell.config, validators=8, heights=heights)
    cell.traffic = dict(cell.traffic, warm_s=warm_s)
    return cell


def run_small(cell, seconds=1.0, seed=(1 << 31) + 34):
    import jax

    return harness.run_cell(
        cell, seed, seconds, False, time.monotonic(), jax.devices())


def after_setup(monkeypatch, then):
    """The driver's set-up, and ``then(state)`` once it has passed."""
    setup = commit_forward.setup

    def setup_then(cell, seed, log):
        state = setup(cell, seed, log)
        then(state)
        return state

    monkeypatch.setattr(commit_forward, "setup", setup_then)


@pytest.fixture(params=BENCHES)
def bench(request):
    return bench_for(request.param)


def test_the_cell_resolves_to_its_own_files(bench):
    cell = spec.resolve(CELL, bench)
    assert cell.driver is commit_forward and cell.chips == 1
    assert cell.config["name"] == "chain-175-key-a-block"
    assert (cell.config["validators"], cell.config["heights"]) == (175, 4096)
    assert cell.config["reduced"] == [] == next(
        c["reduced"] for c in bench["configs"]
        if c["name"] == cell.config["name"])
    assert len(cell.config["guarantees"]) >= 7
    assert cell.traffic["driver"] == "commit_forward"
    assert cell.traffic["warm_s"] == 5 and cell.traffic["trace_requests"] == 10
    assert {m["name"] for m in cell.end_to_end} == {
        "verdict_p50_ms", "verdict_p90_ms", "setup_s"}
    # what commit-175-serial reports, and the four of the bind; found by
    # name, so that what later PRs append changes nothing here
    serial = {m["name"] for m in spec.resolve("commit-175-serial", bench).per_layer}
    names = {m["name"] for m in cell.per_layer}
    assert serial | set(TABLE_METRICS) <= names
    assert not serial & set(TABLE_METRICS)
    for m in cell.per_layer:
        if m["name"] in TABLE_METRICS:
            assert m["moves"] == "verdict_p50_ms"
            assert (m["layer"], m["unit"]) == (TABLE_METRICS[m["name"]], "ms")
    readers = {m["name"]: (m["reader"], m["args"]) for m in cell.per_layer}
    assert readers["table_bind_ms"] == (
        "span_median", {"per": "span", "spans": ["verify.table_bind"]})
    assert readers["table_assemble_device_ms"] == (
        "xplane_module_time", {"modules": ["jit__assemble_churn"]})
    config = next(c for c in bench["configs"] if c["name"] == cell.config["name"])
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    for entry in (config, workload):
        assert len(entry["why"]) <= 200 and len(entry.get("source", "")) <= 200


def test_every_request_a_miss_served_by_one_incremental_bind(bound):
    result, facts = run_small(small_cell())
    assert facts["problems"] == []
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"verdict_p50_ms", "verdict_p90_ms", "setup_s"}
    assert list(result)[-1] == "compared"
    forward = facts["end_to_end"]["commit_forward"]
    n = forward["window_requests"]
    assert n == result["attempted"] >= 1 and "chain_exhausted" not in forward
    assert forward["window_last_height"] - forward["window_first_height"] == n - 1
    assert forward["window_cache"] == {
        "comb_table_cache.miss": n, "comb_program_cache.hit": n,
        "comb_table_bind.incremental": n, "comb_fresh_keys": n,
        "comb_table_evictions": n,
    }
    # set-up bound two sets in full and filled the cache past its bound
    assert forward["cache_entries"] == 3
    assert forward["setup_cache"]["comb_table_bind.full"] == 2
    assert forward["setup_cache"]["comb_table_evictions"] >= 1
    assert facts["route"]["verify_host_route"] == 0


def test_a_window_that_meets_a_bound_set_is_not_correct(bound, monkeypatch):
    from cometbft_tpu.models.comb_verifier import global_cache

    def bind_ahead(state):
        pubs = [v.pub for v in state.chain.vals(state.next_height + 1)]
        global_cache().ensure(pubs)

    after_setup(monkeypatch, bind_ahead)
    result, facts = run_small(small_cell(warm_s=0), seconds=0.5)
    assert result["correct"] is False and result["failed"] == 0
    assert any(p.startswith("inside the window: comb_table_cache.hit grew by 1")
               for p in facts["problems"])


def test_a_host_routed_run_is_right_and_not_correct(fresh):
    """Eight validators take the program's host route: every verdict is
    right, and the run still says not correct."""
    result, facts = run_small(small_cell(heights=400), seconds=0.3)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["correct"] is False
    assert result["compared"]["batches_off_the_device"]["value"] > 0
    assert result["compared"]["requests_failed"]["value"] == 0
    forward = facts["end_to_end"]["commit_forward"]
    assert forward["fill_heights"] == 0 and forward["window_cache"] == {}
    assert any("comb_table_cache.miss grew by 0" in p for p in facts["problems"])


def test_a_tampered_commit_left_in_the_chain_is_not_correct(fresh, monkeypatch):
    def tamper(state):
        for h in range(state.next_height, state.last_height + 1, 3):
            block_id, commit = state.commits[h]
            state.commits[h] = (block_id, checks.tampered(commit, 8)[0])

    after_setup(monkeypatch, tamper)
    result, _ = run_small(small_cell(heights=400, warm_s=0), seconds=0.3)
    assert result["correct"] is False
    assert 1 <= result["failed"] < result["attempted"]
    assert result["compared"]["requests_failed"]["value"] == result["failed"]


def test_a_verify_commit_that_accepts_everything_is_not_correct(fresh, monkeypatch):
    from cometbft_tpu.types import validation

    after_setup(monkeypatch, lambda state: monkeypatch.setattr(
        validation, "verify_commit", lambda *a, **kw: None))
    result, facts = run_small(small_cell(heights=400, warm_s=0), seconds=0.3)
    assert result["correct"] is False and result["failed"] == 0
    assert any(p.startswith("after the window:") and "accepted" in p
               for p in facts["problems"])


def test_a_chain_that_runs_out_ends_the_window_and_still_reports(fresh):
    result, facts = run_small(small_cell(heights=20, warm_s=0), seconds=30.0)
    forward = facts["end_to_end"]["commit_forward"]
    assert forward["chain_exhausted"] is True
    # heights 1 and 2 are set-up's, the last two the closing check's
    assert (forward["window_first_height"], forward["window_last_height"]) == (3, 18)
    assert result["attempted"] == forward["window_requests"] == 16
    assert facts["window_s"] < 30.0 and result["failed"] == 0
    assert {"verdict_p50_ms", "verdict_p90_ms"} <= set(result["metrics"])
    # the closing check ran on the heights kept back
    assert not any(p.startswith("after the window:") for p in facts["problems"])
