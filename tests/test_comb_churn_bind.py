"""The half of ``models/comb_verifier.ValsetCombCache`` that a chain
with a moving validator set lives in (PR 34): the miss branch of
``ensure``, the incremental bind (reuse scan, host build of the fresh
keys, ``_assemble_churn``, ``_finish_entry``) and the LRU's eviction at
its bytes bound, at 10 validators on the CPU (one lane bucket, 128).

An incrementally bound entry equals a full build bit for bit wherever
the replaced key sat; a chain of 24 heights under a bound of three
entries keeps the newest and drops the oldest first; verdicts on an
incrementally bound entry equal the plain reference's; the span
``verify.table_bind`` and the three counters say what a bind did; and
``ValidatorSet.update_with_change_set`` gives the chain's sets with
per-set facts of their own.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import checks, light_chain, reference  # noqa: E402
from benchmarks.drivers import commit_forward  # noqa: E402
from cometbft_tpu.models import comb_verifier as cv  # noqa: E402
from cometbft_tpu.utils import metrics, tracing  # noqa: E402

WIDTH = 10
HEIGHTS = 24
ENTRY_BYTES = cv.LANE_BUCKET * cv.TABLE_BYTES_PER_LANE
CONFIG = {
    "name": "churn-bind-test", "validators": WIDTH, "heights": HEIGHTS + 2,
    "assumed": {"chain_id": "churn-bind", "voting_power": 10,
                "block_seconds": 60},
}


def _pub(i: int) -> bytes:
    return reference.public_bytes(reference.private_key(34, b"churn", i))


def _fresh_hub(monkeypatch):
    hub = metrics.Hub()
    monkeypatch.setattr(metrics, "_HUB", hub)
    return hub


def _arrays(entry):
    return (np.asarray(entry.tables), np.asarray(entry.valid),
            np.asarray(entry.pubs))


def _assert_same_entry(got, want):
    for a, b in zip(_arrays(got), _arrays(want)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert got.index == want.index


# ----------------------------------------- incremental equals full, by place

# the sorted set's keys, and newcomers found by where they sort among them
OLD = sorted(_pub(i) for i in range(WIDTH))


def _newcomer(lo: bytes, hi: bytes) -> bytes:
    """A key that sorts after ``lo`` and before ``hi``."""
    return next(p for p in map(_pub, range(WIDTH, 4096)) if lo < p < hi)


PLACES = {
    # the replaced key's place in the sorted set -> (dropped, the new key)
    "start": (0, _newcomer(OLD[1], OLD[2])),
    "middle": (WIDTH // 2, _newcomer(OLD[WIDTH // 2 - 1], OLD[WIDTH // 2 + 1])),
    "end": (WIDTH - 1, _newcomer(OLD[-2], b"\xff" * 32)),
    # the newcomer sorts first: the pad lanes repeat it and are fresh too
    "newcomer_first": (WIDTH // 2, _newcomer(b"", OLD[0])),
}


@pytest.mark.parametrize("place", PLACES)
def test_incremental_bind_equals_a_full_build(place, monkeypatch):
    hub = _fresh_hub(monkeypatch)
    dropped, new_key = PLACES[place]
    new = sorted(OLD[:dropped] + OLD[dropped + 1:] + [new_key])
    cache = cv.ValsetCombCache()
    cache.ensure(OLD)
    got = cache.ensure(new)
    _assert_same_entry(got, cv.ValsetCombCache._build(new))
    assert got.tables.shape[-1] == cv.LANE_BUCKET and got.tables.nbytes == ENTRY_BYTES
    assert got.index == {p: i for i, p in enumerate(new)}
    # two binds of the cache (and the reference build, not the cache's)
    assert hub.comb_table_cache.value(result="miss") == 2
    assert hub.comb_table_bind.value(kind="incremental") == 1
    assert hub.comb_table_bind.value(kind="full") == 2
    # one key built, however many lanes carry it
    assert hub.comb_fresh_keys.value() == 2 * WIDTH + 1
    assert (new[0] == new_key) == (place == "newcomer_first")


# ------------------------------------- a chain under a bound of three entries


@pytest.fixture(scope="module")
def walked():
    """24 heights of the benchmark's chain (one key replaced a block)
    through a cache bounded at three entries; what the cache held after
    every bind."""
    hub = metrics.Hub()
    was, metrics._HUB = metrics._HUB, hub
    try:
        chain = light_chain.Chain(CONFIG, 34)
        cache = cv.ValsetCombCache(max_bytes=3 * ENTRY_BYTES)
        seen = []
        for h in range(1, HEIGHTS + 1):
            pubs = [v.pub for v in chain.vals(h)]
            entry = cache.ensure(pubs)
            seen.append({
                "h": h, "fp": cache.fingerprint(pubs), "entry": entry,
                "pubs": pubs, "held": list(cache._entries),
                "bytes": sum(e.tables.nbytes for e in cache._entries.values()),
                "counts": {
                    "miss": hub.comb_table_cache.value(result="miss"),
                    "full": hub.comb_table_bind.value(kind="full"),
                    "incremental": hub.comb_table_bind.value(kind="incremental"),
                    "fresh": hub.comb_fresh_keys.value(),
                    "evictions": hub.comb_table_evictions.value(),
                },
            })
        return seen
    finally:
        metrics._HUB = was


@pytest.mark.parametrize("h", range(1, HEIGHTS + 1))
def test_chain_under_a_bound_of_three_entries(walked, h):
    at = walked[h - 1]
    # the newest is held, the bound is kept, the oldest went first
    assert at["held"] == [w["fp"] for w in walked[max(0, h - 3):h]]
    assert at["bytes"] == min(h, 3) * ENTRY_BYTES <= 3 * ENTRY_BYTES
    # counters 1:1 with binds, fresh keys and evictions
    assert at["counts"] == {
        "miss": h, "full": 1, "incremental": h - 1,
        "fresh": WIDTH + h - 1, "evictions": max(0, h - 3),
    }
    # and the entry is the one a full build of that height's set gives
    if h in (2, HEIGHTS // 2, HEIGHTS):
        _assert_same_entry(at["entry"], cv.ValsetCombCache._build(at["pubs"]))


# ----------------------------------------------------- the span's own labels


def test_bind_spans_say_what_the_bind_did(monkeypatch):
    _fresh_hub(monkeypatch)
    was_on = tracing.enabled()
    tracing.set_enabled(True)
    tracing.reset()
    try:
        cache = cv.ValsetCombCache()
        cache.ensure(OLD)
        cache.ensure(OLD[1:] + [PLACES["end"][1]])
        cache.ensure(OLD)  # resident: no bind, no span
        events = [e for e in tracing.chrome_trace_events() if e.get("ph") == "X"]
    finally:
        tracing.set_enabled(was_on)
        tracing.reset()
    binds = [e for e in events if e["name"] == "verify.table_bind"]
    assert [(e["args"]["kind"], e["args"]["fresh"], e["args"]["lanes"])
            for e in binds] == [("full", WIDTH, 128), ("incremental", 1, 128)]
    assemble = [e for e in events if e["name"] == "verify.table_assemble"]
    builds = [e for e in events if e["name"] == "verify.table_build"]
    assert len(assemble) == 1 and len(builds) == 2
    # the incremental bind's span holds its build and its assemble
    lo, hi = binds[1]["ts"], binds[1]["ts"] + binds[1]["dur"]
    for inner in (assemble[0], builds[1]):
        assert lo <= inner["ts"] and inner["ts"] + inner["dur"] <= hi


# ------------------------------------------ verdicts on an incremental entry


def test_verdicts_on_an_incrementally_bound_entry_equal_the_references(monkeypatch):
    """Height 2's commit through ``CombBatchVerifier`` on the entry bound
    from height 1's, honest and with three signatures flipped."""
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")
    _fresh_hub(monkeypatch)
    chain = light_chain.Chain(CONFIG, 35)
    cache = cv.ValsetCombCache()
    cache.ensure([v.pub for v in chain.vals(1)])
    entry = cache.ensure([v.pub for v in chain.vals(2)])
    block = chain.block(2)
    _, commit = commit_forward.program_commit(block)
    bad, flipped = checks.tampered(commit, WIDTH)
    assert len(flipped) == 3
    for c, want_bad in ((commit, []), (bad, flipped)):
        bv = cv.CombBatchVerifier(entry)
        for i, v in enumerate(block.vals):
            bv.add(v.pub, block.sign_bytes(i), c.signatures[i].signature)
        ok, vec = bv.verify()
        oracle = [reference.verify(v.pub, block.sign_bytes(i),
                                   c.signatures[i].signature)
                  for i, v in enumerate(block.vals)]
        assert list(vec) == oracle
        assert [i for i, good in enumerate(oracle) if not good] == want_bad
        assert ok == (not want_bad)


# ---------------------------------- the sets a node derives along the chain


@pytest.fixture(scope="module")
def derived():
    chain = light_chain.Chain(CONFIG, 36)
    return chain, commit_forward.derived_sets(chain)


@pytest.mark.parametrize("h", [2, 3, HEIGHTS // 2, HEIGHTS + 2])
def test_update_with_change_set_gives_the_chains_set_and_fresh_facts(derived, h):
    chain, sets = derived
    vals, before = sets[h], sets[h - 1]
    assert vals.pub_keys_bytes() == [v.pub for v in chain.vals(h)]
    assert vals.hash() == chain.block(h).header.validators_hash
    (gone,) = {v.address for v in chain.vals(h - 1)} - {
        v.address for v in chain.vals(h)}
    (came,) = {v.address for v in chain.vals(h)} - {
        v.address for v in chain.vals(h - 1)}
    # each set object has facts of its own: the dropped address is found
    # in the old set and not in the new, the added one the other way
    assert vals.get_by_address(gone) == (-1, None)
    assert before.get_by_address(gone)[1].address == gone
    i, v = vals.get_by_address(came)
    assert v is vals.validators[i] and v.address == came
    assert before.get_by_address(came) == (-1, None)
    assert vals.address_index() is not before.address_index()
    assert list(vals.voting_powers()) == [10] * WIDTH
    assert vals.total_voting_power() == 10 * WIDTH
