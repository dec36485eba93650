"""PR 26: every validator set the device serves at all is served from
resident comb tables.  The floor (crypto/batch.comb_min = the device
batch floor), one compiled program per (lanes, payload width) for the
whole process, lanes in buckets of 128, and the table cache bounded by
bytes.  Routing, program sharing and eviction run without the kernel (a
marker table build and a stand-in program, both instant); the verdicts
run the real program at the one shape the fast tier already compiles
(128 lanes, one SHA-512 block).
"""

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.models import comb_verifier as cv
from cometbft_tpu.models import verifier
from cometbft_tpu.utils.metrics import hub
from cometbft_tpu.verifysvc.client import resolve_mode
from cometbft_tpu.verifysvc.service import MODE_PLAIN


def _pubs(n, tag=0):
    return [bytes([tag, i & 0xFF, i >> 8]) + bytes(29) for i in range(n)]


@pytest.fixture
def marker_tables(monkeypatch):
    """Tables of one int32 a lane holding the key's index byte: an
    entry is built at once, and a lane says whose it is."""
    built = []

    def build(a):
        a = np.asarray(a)
        built.append(a.shape[0])
        return a[None, :, 1].astype(np.int32), np.ones((a.shape[0],), bool)

    monkeypatch.setattr(cv, "_build_tables", build)
    monkeypatch.setattr(cv, "_GLOBAL_CACHE", cv.ValsetCombCache())
    return built


# ------------------------------------------------------------ the floor


@pytest.mark.parametrize(
    "n,lanes", [(175, 256), (32, 128), (31, None)], ids=["175", "32", "31"]
)
def test_default_floor_binds_every_set_the_device_serves(marker_tables, n, lanes):
    """No knob set: 175 validators (the benchmark's chain) and 32 bind,
    31 do not (both device verifiers would answer them from the host
    anyway)."""
    assert crypto_batch.comb_min() == verifier._device_batch_min() == 32
    pubs = _pubs(n)
    mode = resolve_mode(pubs)
    bv = crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
    if lanes is None:
        assert mode == bv._mode == MODE_PLAIN and marker_tables == []
        return
    assert mode[0] == "comb" and bv._mode[1] is mode[1]
    assert (mode[1].size, mode[1].vpad) == (n, lanes)
    assert marker_tables == [lanes]  # bound once, in the caller's thread


@pytest.mark.parametrize(
    "n,lanes",
    [(1, 128), (128, 128), (129, 256), (175, 256), (176, 256), (257, 384),
     (10_000, 10_112)],
)
def test_lanes_come_in_buckets_of_128(marker_tables, n, lanes):
    """Pad lanes repeat the set's first key, are in no one's index and
    carry a defined table."""
    e = cv.ValsetCombCache().ensure(_pubs(n))
    assert (e.size, e.vpad, len(e.index)) == (n, lanes, n)
    assert np.asarray(e.pubs).shape == (lanes, 32)
    assert (np.asarray(e.pubs)[n:] == np.asarray(e.pubs)[0]).all()
    assert max(e.index.values()) == n - 1
    assert e.acquire_slab(100).buf.shape == (lanes, 100)


# ------------------------------------- one program per shape, not per set


def _stand_in_program(tables, valid, pubs, payload):
    """_device_verify's signature and result, no arithmetic: a row is
    good iff it is live."""
    import jax.numpy as jnp

    live = payload[:, 67] == 1
    return jnp.concatenate(
        [jnp.packbits(live), jnp.all(valid).astype(jnp.uint8)[None]]
    )


SETS = {
    "x175": (_pubs(175, 1), 40),
    "y175": (_pubs(175, 2), 40),
    "x176": (_pubs(176, 1), 40),
    "x175_reordered": (_pubs(175, 1)[::-1], 40),
    "x175_long_votes": (_pubs(175, 1), 120),
    "x256": (_pubs(256, 1), 40),
    "x257": (_pubs(257, 1), 40),
}


@pytest.mark.parametrize(
    "steps,compiles",
    [
        (["x175", "y175"], 1),  # two sets, one lane bucket, one width
        (["x175", "x176"], 1),  # the set gains a member inside its bucket
        (["x175", "x175_reordered", "x175"], 1),  # order moved with power
        (["x175", "x175_long_votes", "y175"], 2),  # a payload width is a shape
        (["x256", "x257", "x176"], 2),  # 257 validators open the next bucket
    ],
    ids=["two_sets", "grown_set", "reordered_set", "payload_width", "next_bucket"],
)
def test_entries_of_one_shape_share_one_compiled_program(
    marker_tables, monkeypatch, steps, compiles
):
    monkeypatch.setattr(cv, "_device_verify", _stand_in_program)
    monkeypatch.setattr(verifier, "_COMB_PROGRAMS", {})
    cache = cv.ValsetCombCache()
    count = lambda r: hub().comb_program_cache.value(result=r)
    before = count("compile"), count("hit")
    told = []
    for name in steps:
        pubs, mlen = SETS[name]
        bv = cv.CombBatchVerifier(cache.ensure(pubs))
        bv.on_compile = told.append
        for pk in pubs:
            bv.add(pk, b"v" * mlen, bytes(64))
        assert bv.verify() == (True, [True] * len(pubs))
    assert count("compile") - before[0] == compiles == len(verifier._COMB_PROGRAMS)
    assert count("hit") - before[1] == len(steps) - compiles
    # whoever runs a clock on the batch is told of a compile, and of
    # nothing else: a set change inside a shape keeps consensus on the clock
    assert told == [True, False] * compiles


def test_the_program_key_is_lanes_and_payload_width(marker_tables, monkeypatch):
    """One accumulation, so nothing else: an environment variable of the
    name that once chose a second one moves neither the key nor the
    gauge of the schedule the program was traced with."""
    monkeypatch.setattr(cv, "_device_verify", _stand_in_program)
    monkeypatch.setattr(verifier, "_COMB_PROGRAMS", {})
    pubs, mlen = SETS["x175"]
    e = cv.ValsetCombCache().ensure(pubs)
    assert cv._program_key(e, 100) == (256, 100)
    monkeypatch.setenv("COMETBFT_TPU_COMB_TREE", "0")
    assert cv._program_key(e, 100) == (256, 100)
    hub().comb_fold_chains.set(0, lanes="256")
    bv = cv.CombBatchVerifier(e)
    for pk in pubs:
        bv.add(pk, b"v" * mlen, bytes(64))
    assert bv.verify() == (True, [True] * len(pubs))
    (key,) = verifier._COMB_PROGRAMS
    assert key == (256, cv._payload_width([(pubs[0], b"v" * mlen, bytes(64))]))
    assert hub().comb_fold_chains.value(lanes="256") == 8
    # the exponentiation's form, read off the backend: no TPU here
    assert hub().comb_pow_form.value(lanes="256", form="array") == 1
    assert hub().comb_pow_form.value(lanes="256", form="kernel") == 0
    # and the chains' (PR 37): the array form's K, 8 at 256 lanes, where
    # a TPU's kernel takes F.chain_rule's 4
    assert hub().comb_chain_form.value(lanes="256", form="array") == 1
    assert hub().comb_chain_form.value(lanes="256", form="kernel") == 0


# ------------------------------------------------- the cache, by bytes


def test_default_bound_is_two_10k_entries_and_many_small_ones():
    bound = cv.ValsetCombCache()._max_bytes
    assert cv.TABLE_BYTES_PER_LANE == 152_064
    assert bound // (10_112 * cv.TABLE_BYTES_PER_LANE) == 2  # as before PR 26
    assert bound // (256 * cv.TABLE_BYTES_PER_LANE) == 79  # 175-validator sets


ENTRY = 4 * 128  # bytes of a 128-lane marker entry


@pytest.mark.parametrize(
    "bound,script,kept",
    [
        # many small entries stay: the old count of two is gone
        (16 * ENTRY, "a b c d e f g h", "a b c d e f g h"),
        # over the bound: oldest first
        (3 * ENTRY, "a b c d", "b c d"),
        (3 * ENTRY, "a b c d e", "c d e"),
        # the entry in use (looked up again) is the newest, never the victim
        (3 * ENTRY, "a b c a d", "c a d"),
        (2 * ENTRY, "a b a c a d", "a d"),
        # an entry larger than the bound is kept while it is the newest
        (ENTRY // 2, "a b", "b"),
        # a large set evicts as many small ones as it needs, no more
        (6 * ENTRY, "a b c d BIG", "d BIG"),
        (7 * ENTRY, "BIG a b", "BIG a b"),
        (6 * ENTRY, "BIG a b", "a b"),
    ],
)
def test_cache_evicts_by_bytes_oldest_first(marker_tables, bound, script, kept):
    sets = {n: _pubs(40, tag=i) for i, n in enumerate("abcdefgh")}
    sets["BIG"] = _pubs(4 * 128 + 1, tag=9)  # 640 lanes: five small entries
    c = cv.ValsetCombCache(max_bytes=bound)
    for name in script.split():
        e = c.ensure(sets[name])
        assert e.tables.nbytes == 4 * e.vpad
        assert c.get(c.fingerprint(sets[name])) is e  # never the newest
    resident = [
        n for n in sets if c.fingerprint(sets[n]) in c._entries
    ]
    assert sorted(resident) == sorted(kept.split())
    order = [c.fingerprint(sets[n]) for n in kept.split()]
    assert list(c._entries) == order  # least recently used first


# ------------------------------ the real program, against the host oracle

N = 40  # no multiple of 128; at the default floor or above


def _signers(n=N, tag=110):
    keys = [host.PrivKey.from_seed(bytes([tag, i]) * 16) for i in range(n)]
    return keys, [k.pub_key().data for k in keys]


def _vote(i, nil=False):
    return (b"nil-%d" if nil else b"block-%d") % i


CASES = {
    # verify_commit: every row, some flipped
    "full": dict(rows=range(N), bad={3, 17, 39}),
    # absent validators are never added, nil votes sign other bytes
    "absent_and_nil": dict(
        rows=[i for i in range(N) if i not in (1, 9, 30)], nil={4, 22}, bad={12},
    ),
    # the default light check stops at +2/3: 27 of 40, in set order
    "light_subset": dict(rows=range(N * 2 // 3 + 1), bad={0, 26}),
    "light_subset_all_good": dict(rows=range(N * 2 // 3 + 1)),
    # blocksync and evidence add in an order of their own
    "out_of_order": dict(rows=[39, 0, 20, 7, 33, 8, 21], nil={7}, bad={20, 8}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_verdicts_and_blame_order_equal_the_host_oracle(
    monkeypatch, tiny_device_batches, case
):
    """Through the seam under the default floor, at 40 validators in
    128 lanes: the vector equals the oracle's position by position in
    add() order, so the first False is the row validation blames."""
    monkeypatch.setattr(cv, "_GLOBAL_CACHE", cv.ValsetCombCache())
    spec = CASES[case]
    keys, pubs = _signers()
    bv = crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
    assert bv._mode[0] == "comb" and bv._mode[1].vpad == 128
    items = []
    for i in spec["rows"]:
        msg = _vote(i, nil=i in spec.get("nil", ()))
        sig = keys[i].sign(msg)
        if i in spec.get("bad", ()):
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        items.append((pubs[i], msg, sig))
        bv.add(*items[-1])
    before = hub().verify_host_route.value(lane="comb", reason="below_batch_min")
    ok, vec = bv.verify()
    oracle = [host.verify_signature(*it) for it in items]
    assert vec == oracle and ok == all(oracle)
    bad_rows = [i for i, good in zip(spec["rows"], vec) if not good]
    assert bad_rows == [i for i in spec["rows"] if i in spec.get("bad", ())]
    assert hub().verify_host_route.value(
        lane="comb", reason="below_batch_min") == before  # the device answered


def test_pad_lanes_never_reach_a_verdict(tiny_device_batches):
    """The program's own bitmap: 40 good rows set, the 88 pad lanes (a
    real key, never scattered into, live 0) clear, whatever a slab held
    before; the caller sees 40 verdicts."""
    keys, pubs = _signers()
    entry = cv.ValsetCombCache().ensure(pubs)
    assert (entry.size, entry.vpad) == (N, 128)
    assert entry.tables.nbytes == 128 * cv.TABLE_BYTES_PER_LANE
    assert np.asarray(entry.valid).all()  # pad lanes hold a valid key
    for rows in (range(N), range(0, N, 2)):
        bv = cv.CombBatchVerifier(entry)
        for i in rows:
            bv.add(pubs[i], _vote(i), keys[i].sign(_vote(i)))
        ticket = bv.submit()
        packed = np.asarray(ticket[1][0].result()[0])
        bits = np.unpackbits(packed[:-1], count=entry.vpad).astype(bool)
        assert packed[-1] == 1 and bits.sum() == len(rows)
        assert bits[list(rows)].all() and not bits[N:].any()
        assert bv.collect(ticket) == (True, [True] * len(rows))
