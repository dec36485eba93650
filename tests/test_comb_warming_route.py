"""The warming route (PR 36): a set of ``COMB_ASYNC_MIN`` keys or more
that the table cache does not hold is answered by the uncached program
while the thread ``comb-build`` binds it, and by the comb program on the
incrementally bound entry afterwards.  At 8 validators on the CPU, with
the floors lowered (128 lanes, bucket 16), on the chain of the cell
``commit-10k-churn`` at 2 keys an epoch: both routes give the plain
reference's verdict vectors and refusals, honest and flipped; the
counters say which route a request took (one ``miss`` and one warming
verdict a rotation, a ``building`` and a second warming verdict for a
request that meets the bind still running); the span ``verify.table_bind``
says which thread bound; and the sets a node derives equal the chain's.
"""

import os
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import epoch_chain, spec  # noqa: E402
from benchmarks.drivers import commit_epochs  # noqa: E402
from benchmarks.drivers.commit_epochs import ROTATION, SECOND  # noqa: E402
from cometbft_tpu.models import comb_verifier as cv  # noqa: E402
from cometbft_tpu.utils import metrics, tracing  # noqa: E402
from cometbft_tpu.verifysvc.client import resolve_mode  # noqa: E402
from cometbft_tpu.verifysvc.service import MODE_PLAIN  # noqa: E402

CONFIG = spec.resolve("commit-10k-churn").config


def chain_of(seed: int, validators: int = 8, rotated: int = 2, epochs: int = 6):
    return epoch_chain.Chain(
        dict(CONFIG, validators=validators, rotated_per_epoch=rotated,
             epochs=epochs), seed)


@pytest.fixture
def warming(monkeypatch):
    """A fresh hub and an empty table cache; eight validators bind in
    the background and their rows run on the device."""
    from cometbft_tpu.verifysvc import service as svc_mod

    monkeypatch.setenv("COMETBFT_TPU_COMB_MIN", "4")
    monkeypatch.setenv("COMETBFT_TPU_COMB_ASYNC_MIN", "8")
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")
    hub = metrics.Hub()
    monkeypatch.setattr(metrics, "_HUB", hub)
    monkeypatch.setattr(cv, "_GLOBAL_CACHE", cv.ValsetCombCache())
    svc_mod.reset_global_service()
    yield hub
    commit_epochs.no_bind_running()
    svc_mod.reset_global_service()


def state_of(chain) -> commit_epochs.State:
    return commit_epochs.State(
        chain, commit_epochs.derived_sets(chain), 1, chain.epochs - 1, 0.0,
        True, print)


def table_cache(hub) -> dict:
    return {r: hub.comb_table_cache.value(result=r)
            for r in ("hit", "miss", "building")}


@pytest.mark.parametrize("tamper", [False, True], ids=["honest", "flipped"])
def test_both_routes_give_the_references_vector(warming, tamper):
    """The rotation's commit on a set not yet bound: the uncached
    program; the epoch's second commit once the entry is resident: the
    comb program over tables bound incrementally (2 fresh lanes)."""
    state = state_of(chain_of(36))
    cv.global_cache().ensure(state.sets[0].pub_keys_bytes())
    # check_vector holds the verdicts to benchmarks/reference.py row by
    # row, and the look-up to the route named
    commit_epochs.check_vector(state, 1, ROTATION, tamper, "miss")
    assert warming.comb_warming.value(lanes="128") == 1
    commit_epochs.wait_resident(state, 1)
    commit_epochs.check_vector(state, 1, SECOND, tamper, "hit")
    assert table_cache(warming) == {"hit": 1, "miss": 2, "building": 0}
    assert warming.comb_warming.value(lanes="128") == 1
    assert warming.comb_table_bind.value(kind="incremental") == 1
    assert warming.comb_fresh_keys.value() == 8 + 2
    assert warming.verify_host_route.value(
        lane="uncached", reason="below_batch_min") == 0


@pytest.mark.parametrize("route", ["miss", "hit"], ids=["uncached", "comb"])
def test_both_routes_refuse_at_the_references_index(warming, route):
    state = state_of(chain_of(37))
    cv.global_cache().ensure(state.sets[0].pub_keys_bytes())
    if route == "hit":
        cv.global_cache().ensure(state.sets[1].pub_keys_bytes())
    commit_epochs.check_refused(state, 1, ROTATION, route)
    commit_epochs.wait_resident(state, 1)
    assert warming.comb_warming.value(lanes="128") == (route == "miss")


def test_a_request_that_meets_the_bind_still_running_is_a_building(warming,
                                                                   monkeypatch):
    """One miss a rotation starts one bind; until it lands every request
    of the set is answered None, counted ``building``, and takes the
    warming route too."""
    build, gate = cv._build_tables, threading.Event()

    def gated(pub_arr):
        assert gate.wait(60)
        return build(pub_arr)

    chain = chain_of(38)
    old, new = ([v.pub for v in chain.vals(e)] for e in (0, 1))
    cv.global_cache().ensure(old)
    monkeypatch.setattr(cv, "_build_tables", gated)
    assert resolve_mode(new) == MODE_PLAIN
    assert resolve_mode(new) == MODE_PLAIN
    assert table_cache(warming) == {"hit": 0, "miss": 2, "building": 1}
    assert warming.comb_warming.value(lanes="128") == 2
    assert [t.name for t in threading.enumerate()].count("comb-build") == 1
    gate.set()
    commit_epochs.no_bind_running()
    mode = resolve_mode(new)
    assert mode[0] == "comb" and mode[1].index == {p: i for i, p in enumerate(new)}
    assert table_cache(warming) == {"hit": 1, "miss": 2, "building": 1}
    assert warming.comb_warming.value(lanes="128") == 2
    assert warming.comb_table_bind.value(kind="incremental") == 1


def test_a_set_under_the_async_floor_never_counts_as_warming(warming, monkeypatch):
    monkeypatch.setenv("COMETBFT_TPU_COMB_ASYNC_MIN", "9")
    chain = chain_of(39)
    mode = resolve_mode([v.pub for v in chain.vals(0)])
    assert mode[0] == "comb"  # bound in the caller's thread
    assert table_cache(warming) == {"hit": 0, "miss": 1, "building": 0}
    assert warming.comb_warming.expose() == ["cometbft_verify_comb_warming_total 0.0"]


@pytest.mark.parametrize("floor,thread", [(8, "background"), (9, "caller")])
def test_the_bind_span_says_which_thread_bound(warming, monkeypatch, floor, thread):
    monkeypatch.setenv("COMETBFT_TPU_COMB_ASYNC_MIN", str(floor))
    chain = chain_of(40)
    old, new = ([v.pub for v in chain.vals(e)] for e in (0, 1))
    was_on = tracing.enabled()
    tracing.set_enabled(True)
    tracing.reset()
    try:
        cv.global_cache().ensure(old)
        resolve_mode(new)
        commit_epochs.no_bind_running()
        binds = [e["args"] for e in tracing.chrome_trace_events()
                 if e["name"] == "verify.table_bind"]
    finally:
        tracing.set_enabled(was_on)
        tracing.reset()
    assert binds == [
        {"thread": "caller", "kind": "full", "fresh": 8, "lanes": 128},
        {"thread": thread, "kind": "incremental", "fresh": 2, "lanes": 128},
    ]


@pytest.mark.parametrize("n,mesh_width,lanes", [
    (1, None, 128), (128, None, 128), (129, None, 256), (175, None, 256),
    (10_000, None, 10_112), (10_000, 4, 10_000), (10_001, 4, 10_004),
])
def test_lane_count_is_the_bucket_a_set_binds_at(n, mesh_width, lanes):
    class Mesh:
        class devices:
            size = mesh_width

    assert cv.lane_count(n, None if mesh_width is None else Mesh) == lanes


@pytest.mark.parametrize("validators,rotated,epochs", [
    (8, 2, 12), (40, 5, 10), (130, 13, 6),
])
def test_the_sets_a_node_derives_equal_the_chains(validators, rotated, epochs):
    """Keys and powers in set order, every epoch, by ValidatorSet.copy()
    and update_with_change_set as state/execution.update_state applies
    them; and each derived set is an object of its own."""
    chain = chain_of(41, validators, rotated, epochs)
    sets = commit_epochs.derived_sets(chain)
    assert len({id(s) for s in sets.values()}) == epochs
    for e in range(epochs):
        assert [(v.pub_key.bytes(), v.voting_power)
                for v in sets[e].validators] == [
            (v.pub, v.power) for v in chain.vals(e)]
        if e:
            stays = {v.pub for v in chain.vals(e)} & {
                v.pub for v in chain.vals(e - 1)}
            assert len(stays) == validators - rotated
