"""The cell ``commit-10k-churn`` on the CPU: it resolves through
BENCHMARK.json, its chain rotates its keys as the configuration says,
its driver runs at 8 validators and 2 keys an epoch through
``harness.run_cell``, and each control (a guarantee broken) comes out
not correct: a run answered from the host, a tampered commit left in
the chain, a ``verify_commit`` that accepts everything after set-up, a
driver that does not wait for the epoch's tables.  With the floors
lowered (here, never in the benchmark) and the table cache bounded at
two entries the rotation's verdict runs the uncached program at bucket
16 while ``comb-build`` binds the set, the other four the comb program
at 128 lanes, and the run is correct.
"""

import os
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import checks, epoch_chain, harness, spec  # noqa: E402
from benchmarks import reference_light as ref  # noqa: E402
from benchmarks.drivers import commit_epochs  # noqa: E402
from tests.benchmarks.cell_appended import BENCHES, bench_for  # noqa: E402

CELL = "commit-10k-churn"
NEW_METRICS = {
    "uncached_verify_device_ms": ("kernels", "device_trace"),
    "uncached_assemble_ms": ("verifier", "program_span"),
}
# the background bind's, shared with commit-175-moving-set
TABLE_METRICS = {"table_bind_ms", "table_build_ms", "table_assemble_ms",
                 "table_assemble_device_ms"}


@pytest.fixture
def fresh(monkeypatch):
    """A fresh metrics hub, a fresh global verify service, an empty
    table cache bounded at two entries and an empty span ring around a
    run_cell."""
    from cometbft_tpu.models import comb_verifier as cv
    from cometbft_tpu.utils import metrics, tracing
    from cometbft_tpu.verifysvc import service as svc_mod

    monkeypatch.setattr(metrics, "_HUB", metrics.Hub())
    monkeypatch.setattr(cv, "_GLOBAL_CACHE", cv.ValsetCombCache(
        max_bytes=2 * cv.LANE_BUCKET * cv.TABLE_BYTES_PER_LANE))
    svc_mod.reset_global_service()
    was_on = tracing.enabled()
    yield
    commit_epochs.no_bind_running()
    svc_mod.reset_global_service()
    tracing.set_enabled(was_on)
    tracing.reset()


@pytest.fixture
def warming(fresh, monkeypatch):
    """Eight validators bind in the background, their rows run the
    uncached program meanwhile and the comb program afterwards."""
    monkeypatch.setenv("COMETBFT_TPU_COMB_MIN", "4")
    monkeypatch.setenv("COMETBFT_TPU_COMB_ASYNC_MIN", "8")
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")


def small_cell(epochs: int = 40, warm_s: float = 0.05):
    cell = spec.resolve(CELL)
    cell.config = dict(cell.config, validators=8, rotated_per_epoch=2,
                       epochs=epochs)
    cell.traffic = dict(cell.traffic, warm_s=warm_s)
    return cell


def run_small(cell, seconds=1.0, seed=(1 << 31) + 36):
    import jax

    return harness.run_cell(
        cell, seed, seconds, False, time.monotonic(), jax.devices())


def after_setup(monkeypatch, then):
    """The driver's set-up, and ``then(state)`` once it has passed."""
    setup = commit_epochs.setup

    def setup_then(cell, seed, log):
        state = setup(cell, seed, log)
        then(state)
        return state

    monkeypatch.setattr(commit_epochs, "setup", setup_then)


@pytest.fixture(params=BENCHES)
def bench(request):
    return bench_for(request.param)


def test_the_cell_resolves_to_its_own_files(bench):
    cell = spec.resolve(CELL, bench)
    assert cell.driver is commit_epochs and cell.chips == 1
    cfg = cell.config
    assert cfg["name"] == "chain-10k-epoch-rotation"
    assert (cfg["validators"], cfg["rotated_per_epoch"], cfg["epoch_heights"],
            cfg["epochs"]) == (10000, 100, 5, 40)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert cfg["reduced"] == entry["reduced"] == ["epoch_heights", "epochs"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert len(cfg["guarantees"]) >= 8
    assert {"rotated_share", "newcomer_never_first", "signed_commits",
            "epoch_lengths_of_named_chains"} <= set(cfg["assumed"])
    # the comb program is commit-10k-serial's: the sign-bytes have its width
    serial = spec.resolve("commit-10k-serial", bench)
    assert len(cfg["assumed"]["chain_id"]) == len(serial.config["assumed"]["chain_id"])
    assert cell.traffic["driver"] == "commit_epochs"
    assert (cell.traffic["warm_s"], cell.traffic["trace_requests"],
            cell.traffic["trace_max_s"]) == (5, 10, 12)
    assert {m["name"] for m in cell.end_to_end} == {
        "verdict_p50_ms", "verdict_p90_ms", "setup_s"}
    # what commit-10k-serial reports, the two of the uncached program and
    # the four of the background bind; found by name, so that what later
    # PRs append changes nothing here
    names = {m["name"] for m in cell.per_layer}
    serial_names = {m["name"] for m in serial.per_layer}
    assert serial_names | set(NEW_METRICS) | TABLE_METRICS <= names
    assert not serial_names & (set(NEW_METRICS) | TABLE_METRICS)
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["moves"] == "verdict_p90_ms"
            assert (m["layer"], m["source"]) == NEW_METRICS[m["name"]]
        else:
            assert m["moves"] == "verdict_p50_ms"
    readers = {m["name"]: (m["reader"], m["args"]) for m in cell.per_layer}
    assert readers["uncached_verify_device_ms"] == (
        "xplane_module_time", {"modules": ["jit_verify_batch"]})
    assert readers["uncached_assemble_ms"] == (
        "span_median", {"per": "span", "spans": ["verify.uncached_assemble"]})
    assert readers["table_bind_ms"] == (
        "span_median", {"per": "span", "spans": ["verify.table_bind"]})
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    for e in (entry, workload):
        assert len(e["why"]) <= 200 and len(e.get("source", "")) <= 200


# ------------------------------------------------------------- the chain


def chain_of(seed: int, **kw):
    return epoch_chain.Chain(dict(small_cell().config, **kw), seed)


@pytest.mark.parametrize("seed", [3, (1 << 31) + 36])
def test_the_chain_rotates_its_oldest_keys_and_no_newcomer_sorts_first(seed):
    chain = chain_of(seed, validators=12, rotated_per_epoch=3, epochs=60)
    stream = {}  # public key -> index in the seed's stream

    def index(pub):
        while pub not in stream:
            i = len(stream)
            stream[ref_pub(chain, i)] = i
        return stream[pub]

    last = None
    for e in range(chain.epochs):
        vals = chain.vals(e)
        assert vals == ref.sorted_set(vals) and len({v.pub for v in vals}) == 12
        assert {v.power for v in vals} == {10}
        ids = sorted(index(v.pub) for v in vals)
        if last is not None:
            dropped, added = set(last) - set(ids), set(ids) - set(last)
            # the three oldest go, three later keys of the stream come
            assert dropped == set(last[:3]) and len(added) == 3
            assert min(added) > max(last)
            assert index(vals[0].pub) in last  # the first key is no newcomer
        last = ids
    # the stream's keys that are in no set are the ones skipped, and a
    # chain of 60 epochs of 12 has skipped some
    used = {i for e in range(chain.epochs) for i in
            (stream[v.pub] for v in chain.vals(e))}
    assert set(range(max(used) + 1)) - used == set(chain.skipped) != set()
    again = chain_of(seed, validators=12, rotated_per_epoch=3, epochs=60)
    assert again.vals(59) == chain.vals(59) and again.skipped == chain.skipped


def ref_pub(chain, i: int) -> bytes:
    from benchmarks import reference

    return reference.public_bytes(
        reference.private_key(chain.seed, b"chain-10k-epoch-rotation", i))


def test_a_commit_is_signed_by_its_epochs_set_over_the_references_bytes():
    from benchmarks import reference

    chain = chain_of(7)
    sc = chain.commit(3, 1)
    assert sc.height == chain.height(3, 1) == 17 and sc.commit.height == 17
    vals = chain.vals(3)
    assert [s.validator_address for s in sc.commit.signatures] == [
        v.address for v in vals]
    assert len(set(sc.sign_bytes)) == 1 and all(
        reference.verify(v.pub, sc.sign_bytes[i], sc.commit.signatures[i].signature)
        for i, v in enumerate(vals))
    assert chain.commit(3, 1) is sc  # made once
    assert chain.commit(3, 0).block_id != sc.block_id
    with pytest.raises(KeyError):
        chain.commit(40, 0)


def test_the_derived_sets_equal_the_chains_and_a_wrong_one_is_caught(monkeypatch):
    chain = chain_of(11, epochs=6)
    sets = commit_epochs.derived_sets(chain)
    assert sorted(sets) == list(range(6))
    for e, vals in sets.items():
        assert [(v.pub_key.bytes(), v.voting_power) for v in vals.validators] == [
            (v.pub, v.power) for v in chain.vals(e)]
        # nothing has read it: no pubkey list, no per-set facts
        assert getattr(vals, "_pub_keys_bytes", None) is None
        assert vals._facts() == {"size": 8}
    # a node whose update loses a change derives another set: caught
    from cometbft_tpu.types.validators import ValidatorSet

    update = ValidatorSet.update_with_change_set
    monkeypatch.setattr(ValidatorSet, "update_with_change_set",
                        lambda self, changes: update(self, changes[:-1]))
    with pytest.raises(checks.CheckFailure, match="epoch 1: the derived set"):
        commit_epochs.derived_sets(chain)


# ---------------------------------------------------------- the whole run


def test_an_epoch_is_one_miss_one_incremental_bind_one_eviction_four_hits(warming):
    result, facts = run_small(small_cell())
    assert facts["problems"] == []
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"verdict_p50_ms", "verdict_p90_ms", "setup_s"}
    assert list(result)[-1] == "compared"
    epochs = facts["end_to_end"]["commit_epochs"]
    n, hits = epochs["rotation_ms"]["n"], epochs["hit_ms"]["n"]
    assert n >= 1 and n + hits == result["attempted"]
    assert 4 * (n - 1) <= hits <= 4 * n and "chain_exhausted" not in epochs
    assert epochs["window_last_epoch"] - epochs["window_first_epoch"] == n - 1
    assert epochs["window_cache"] == {
        "comb_table_cache.miss": n, "comb_table_cache.hit": hits,
        "comb_program_cache.hit": hits, "comb_warming": n,
        "comb_table_bind.incremental": n, "comb_fresh_keys": 2 * n,
        "comb_table_evictions": n,
    }
    assert epochs["warming_wait_ms"]["n"] == n
    # set-up: epoch 0 in full, epochs 1 to 3 each a miss on the warming
    # route, the cache past its bound of two entries
    setup = epochs["setup_cache"]
    assert setup["comb_table_bind.full"] == 1 and epochs["fill_epochs"] == 0
    assert setup["comb_table_cache.miss"] == 1 + setup["comb_warming"] == 4
    assert setup["comb_table_bind.incremental"] == 3
    assert setup["comb_table_evictions"] == 2 and epochs["window_first_epoch"] >= 5
    assert facts["route"]["verify_host_route"] == 0


def test_a_driver_that_does_not_wait_is_not_correct(warming, monkeypatch):
    """Without the wait the epoch's second request meets the bind still
    running: a ``building``, answered by the uncached program too."""
    from cometbft_tpu.models import comb_verifier as cv

    build = cv._build_tables

    def slow_build(pub_arr):
        threading.Event().wait(0.5)  # a build that takes its time
        return build(pub_arr)

    def no_wait(state):
        monkeypatch.setattr(cv, "_build_tables", slow_build)
        monkeypatch.setattr(commit_epochs, "wait_resident", lambda s, e: 0.0)

    after_setup(monkeypatch, no_wait)
    result, facts = run_small(small_cell(warm_s=0), seconds=0.4)
    assert result["correct"] is False and result["failed"] == 0
    assert any(p.startswith("inside the window: comb_table_cache.building grew by")
               for p in facts["problems"])


def test_a_host_routed_run_is_right_and_not_correct(fresh):
    """Eight validators take the program's host route: every verdict is
    right, and the run still says not correct."""
    result, facts = run_small(small_cell(), seconds=0.3)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["correct"] is False
    assert result["compared"]["batches_off_the_device"]["value"] > 0
    assert result["compared"]["requests_failed"]["value"] == 0
    epochs = facts["end_to_end"]["commit_epochs"]
    assert epochs["fill_epochs"] == 0 and epochs["window_cache"] == {}
    assert any("comb_table_cache.miss grew by 0" in p for p in facts["problems"])


def test_a_tampered_commit_left_in_the_chain_is_not_correct(fresh, monkeypatch):
    def tamper(state):
        for e in range(state.next_epoch, state.last_epoch + 1, 2):
            sc = state.chain.commit(e, commit_epochs.SECOND)
            sc.commit = checks.tampered(sc.commit, 8)[0]

    after_setup(monkeypatch, tamper)
    result, _ = run_small(small_cell(warm_s=0), seconds=0.3)
    assert result["correct"] is False
    assert 1 <= result["failed"] < result["attempted"]
    assert result["compared"]["requests_failed"]["value"] == result["failed"]


def test_a_verify_commit_that_accepts_everything_is_not_correct(fresh, monkeypatch):
    from cometbft_tpu.types import validation

    after_setup(monkeypatch, lambda state: monkeypatch.setattr(
        validation, "verify_commit", lambda *a, **kw: None))
    result, facts = run_small(small_cell(warm_s=0), seconds=0.3)
    assert result["correct"] is False and result["failed"] == 0
    assert any(p.startswith("after the window:") and "accepted" in p
               for p in facts["problems"])


def test_a_chain_that_runs_out_ends_the_window_and_still_reports(fresh):
    result, facts = run_small(small_cell(epochs=9, warm_s=0), seconds=30.0)
    epochs = facts["end_to_end"]["commit_epochs"]
    assert epochs["chain_exhausted"] is True
    # epochs 0 to 3 are set-up's, the last two the closing check's
    assert (epochs["window_first_epoch"], epochs["window_last_epoch"]) == (4, 6)
    assert result["attempted"] == 15 and epochs["rotation_ms"]["n"] == 3
    assert facts["window_s"] < 30.0 and result["failed"] == 0
    assert {"verdict_p50_ms", "verdict_p90_ms"} <= set(result["metrics"])
    assert not any(p.startswith("after the window:") for p in facts["problems"])
