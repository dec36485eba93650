"""Host-side logic of the comb-cached verifier path, without kernels:
seam routing (crypto/batch.create_batch_verifier), row scatter/mask
ordering, foreign-key fallback demotion, and cache keying.  The device
math itself is covered by the slow tier (tests/test_comb.py)."""

import threading

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.models import comb_verifier as cv

pytestmark = pytest.mark.usefixtures("tiny_device_batches")


def _fake_entry(pubs, good_rows=None):
    """A cache entry whose verify_fn checks shapes on host instead of
    running the kernel: row i is 'valid' iff its R half is non-zero
    (i.e. some signature was scattered there) and i is in good_rows."""
    e = cv._CacheEntry.__new__(cv._CacheEntry)
    e.tables = None
    e.valid = None
    e.pubs = None
    e.index = {pk: i for i, pk in enumerate(pubs)}
    e.size = len(pubs)
    e.vpad = len(pubs)
    e.mesh = None
    e._slabs = {}
    e._slab_mtx = threading.Lock()

    def fake_verify(tables, valid, entry_pubs, payload):
        payload = np.asarray(payload)
        V = len(pubs)
        maxm = payload.shape[1] - 68
        assert maxm >= 32 and maxm % 32 == 0  # bucketed width
        assert payload.shape[0] == V
        r = payload[:, :32]
        mlen = (
            payload[:, 64].astype(np.int64)
            | (payload[:, 65].astype(np.int64) << 8)
            | (payload[:, 66].astype(np.int64) << 16)
        )
        live = payload[:, 67] == 1
        populated = r.any(axis=1)
        # scattered rows carry their message bytes at the static offset
        msgs = payload[:, 68:]
        assert (mlen <= maxm).all()
        for i in range(V):
            if live[i] and mlen[i]:
                assert msgs[i, : mlen[i]].any()
            if not live[i]:
                assert not payload[i].any()
        ok = populated.copy()
        if good_rows is not None:
            for i in range(V):
                ok[i] = ok[i] and (i in good_rows)
        bits = np.packbits(ok & live)
        all_ok = np.uint8((ok | ~live).all())
        return np.concatenate([bits, all_ok[None]])

    e.verify_fn = fake_verify
    return e


def _sig_items(n, seed=60):
    keys = [host.PrivKey.from_seed(bytes([seed + i]) * 32) for i in range(n)]
    pubs = [k.pub_key().data for k in keys]
    return pubs, [
        (pubs[i], b"m%d" % i, keys[i].sign(b"m%d" % i)) for i in range(n)
    ]


def test_seam_routes_by_size_and_backend(monkeypatch):
    pubs, _ = _sig_items(4)
    monkeypatch.setenv("COMETBFT_TPU_COMB_MIN", "5")
    bv = crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
    assert not isinstance(bv, cv.CombBatchVerifier)  # below threshold

    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "cpu")
    monkeypatch.setenv("COMETBFT_TPU_COMB_MIN", "2")
    bv = crypto_batch.create_batch_verifier("ed25519", pubkeys=pubs)
    assert not isinstance(bv, cv.CombBatchVerifier)  # cpu backend opts out


def test_scatter_order_and_mask():
    pubs, items = _sig_items(6)
    e = _fake_entry(pubs)
    bv = cv.CombBatchVerifier(e)
    # add out of set order, skipping some validators
    order = [4, 0, 5, 2]
    for i in order:
        p, m, s = items[i]
        bv.add(p, m, s)
    ok, per = bv.verify()
    assert ok and per == [True] * len(order)

    # one bad row: blame must land at the add position, not the set row
    e = _fake_entry(pubs, good_rows={0, 2, 4})  # row 5 bad
    bv = cv.CombBatchVerifier(e)
    for i in order:
        p, m, s = items[i]
        bv.add(p, m, s)
    ok, per = bv.verify()
    assert not ok and per == [True, True, False, True]  # add index of row 5


def test_foreign_key_demotes_to_uncached(monkeypatch):
    pubs, items = _sig_items(4)
    e = _fake_entry(pubs[:3])  # last key missing from the cached set
    bv = cv.CombBatchVerifier(e)
    for p, m, s in items:  # 4th add triggers the demotion + replay
        bv.add(p, m, s)
    assert bv._fallback is not None and len(bv._fallback._items) == 4
    # fallback is the generic verifier with identical semantics
    ok, per = bv.verify()
    assert ok and per == [True] * 4


def test_cache_keying():
    """An entry is keyed by the pubkey list in set order: a set whose
    order changes is another entry (and, from PR 26, the same compiled
    program: tests/test_comb_resident.py has that and the eviction
    rule)."""
    c = cv.ValsetCombCache()
    sets = [[bytes([i]) * 32 for i in range(k, k + 3)] for k in (0, 10, 20)]
    sets.append(sets[0][::-1])
    fps = [c.fingerprint(s) for s in sets]
    assert len({bytes(f) for f in fps}) == 4
    assert c.fingerprint(list(sets[0])) == fps[0]
    assert all(c.get(f) is None for f in fps)


def test_incremental_churn_reuses_rows(monkeypatch):
    """A validator-set change must rebuild only the new keys: unchanged
    validators' table rows are gathered from the previous entry (possibly
    reordered), fresh keys go through the build kernel in a padded bucket."""
    import jax.numpy as jnp

    built_batches = []

    def fake_build(a):
        a = np.asarray(a)
        built_batches.append(a.shape[0])
        # marker table (lanes minor, like the real layout): every lane
        # filled with its pubkey's first byte
        t = jnp.asarray(
            np.broadcast_to(a[None, None, :, 0], (4, 2, a.shape[0])).astype(
                np.int32
            )
        )
        return t, jnp.ones((a.shape[0],), bool)

    # patch the host/device routing seam (PR 11), not the jit wrapper:
    # small builds default to the host precompute path
    monkeypatch.setattr(cv, "_build_tables", fake_build)

    c = cv.ValsetCombCache()
    pk = lambda x: bytes([x]) * 32

    def lanes(e):  # the set's rows, then pad lanes repeating row 0
        got = np.asarray(e.tables)[0, 0, :].tolist()
        assert e.vpad == len(got) == cv.LANE_BUCKET
        assert got[e.size:] == got[:1] * (e.vpad - e.size)
        assert np.asarray(e.valid).all()
        return got[: e.size]

    e1 = c.ensure([pk(1), pk(2), pk(3)])
    assert built_batches == [cv.LANE_BUCKET]
    assert lanes(e1) == [1, 2, 3]

    # churn: drop 3, add 9, reorder — only the fresh key is built (padded
    # to a power-of-two bucket of 1), other rows gathered from e1
    e2 = c.ensure([pk(2), pk(9), pk(1)])
    assert built_batches == [cv.LANE_BUCKET, 1]
    assert lanes(e2) == [2, 9, 1]
    assert e2.index == {pk(2): 0, pk(9): 1, pk(1): 2}

    # three fresh keys pad to a 4-bucket; reused row (and the pad lanes
    # that repeat it) still gathered
    e3 = c.ensure([pk(1), pk(5), pk(6), pk(7)])
    assert built_batches == [cv.LANE_BUCKET, 1, 4]
    assert lanes(e3) == [1, 5, 6, 7]


def test_validator_set_pubkeys_cache_invalidation():
    from cometbft_tpu.types.validators import Validator, ValidatorSet

    keys = [host.PrivKey.from_seed(bytes([80 + i]) * 32) for i in range(3)]
    vals = ValidatorSet(
        [Validator(k.pub_key(), voting_power=10) for k in keys]
    )
    pks1 = vals.pub_keys_bytes()
    assert pks1 is vals.pub_keys_bytes()  # cached
    new_key = host.PrivKey.from_seed(bytes([99]) * 32)
    vals.update_with_change_set(
        [Validator(new_key.pub_key(), voting_power=10)]
    )
    pks2 = vals.pub_keys_bytes()
    assert pks2 is not pks1 and new_key.pub_key().bytes() in pks2


def test_duplicate_pubkey_demotes_to_uncached():
    """The scatter holds one row per validator; a second signature under
    the same key must not overwrite the first (last-write-wins would
    falsely accept a bad earlier signature)."""
    pubs, items = _sig_items(3)
    e = _fake_entry(pubs)
    bv = cv.CombBatchVerifier(e)
    p, m, s = items[0]
    bv.add(p, m + b"tampered", s)  # bad sig under key 0
    bv.add(p, m, s)  # good sig under the SAME key
    bv.add(*items[1])
    assert bv._fallback is not None  # demoted, not scattered
    ok, per = bv.verify()
    assert not ok and per == [False, True, True]

