"""The engine — not just the kernel — at the flagship 10,000-validator
scale (round-5 verdict item 4): a real chain built through the
BlockExecutor with 10k-signature commits, verified through
types/validation.py (not a synthetic batch), vote-set bitmaps
and proposer rotation at full width, and `validators` pagination over
the 10k set.

Crypto runs on the sequential host path: the comb/Straus device kernels
are shape-tested separately (tests/test_comb.py V=8/V=10; the
benchmark's commit-10k-serial cell on the real chip) — a 10k-lane compile on the CPU test backend takes hours and
proves nothing the small shapes don't.  What 10k exercises here is the
ENGINE: set construction, priority cycling, VoteSet majority tracking,
commit assembly width, batch-verify assembly + blame indexing, and the
store/RPC paths (reference: types/vote_set.go:60, state/store.go:923).
"""

import pytest

pytestmark = pytest.mark.slow  # ~minutes of host signing/verifying

from cometbft_tpu.crypto import ed25519 as host

V10K = 10_000


@pytest.fixture(scope="module")
def keys_10k():
    return [
        host.PrivKey.from_seed(i.to_bytes(2, "big") + b"\x10" * 30)
        for i in range(V10K)
    ]


def test_engine_commits_heights_at_10k(keys_10k, cpu_crypto_backend):
    from cometbft_tpu.types.validation import (
        CommitVerificationError,
        verify_commit,
        verify_commit_light,
    )

    from tests.test_blocksync_replay import _build_chain

    n_blocks = 3
    genesis, blocks, (state0, ex2, store2, conns2) = _build_chain(
        n_blocks, keys_10k, chain_id="engine-10k"
    )
    try:
        vals = state0.validators
        assert vals.size() == V10K
        assert vals.total_voting_power() == 10 * V10K

        # commit for height 1 (inside block 2) verifies through the real
        # verify path — full and light — at 10k-signature width
        from cometbft_tpu.types.block import BlockID

        b1, _c1 = blocks[0]
        b2, _c2 = blocks[1]
        commit1 = b2.last_commit
        assert len(commit1.signatures) == V10K
        parts = b1.make_part_set()
        bid = BlockID(hash=b1.hash(), part_set_header=parts.header)
        verify_commit("engine-10k", vals, bid, 1, commit1)
        verify_commit_light("engine-10k", vals, bid, 1, commit1)

        # blame indexing at full width: tamper signature #7777
        import copy

        bad = copy.deepcopy(commit1)
        cs = bad.signatures[7777]
        cs.signature = cs.signature[:-1] + bytes([cs.signature[-1] ^ 1])
        with pytest.raises(CommitVerificationError, match="#7777"):
            verify_commit("engine-10k", vals, bid, 1, bad)

        # the consumer engine applies the full chain (executor +
        # validate_block's embedded 10k-commit verification)
        from cometbft_tpu.blocksync.reactor import BlocksyncReactor
        from cometbft_tpu.blocksync import pool as pool_mod

        reactor = BlocksyncReactor(state0, ex2, store2, block_sync=False)
        reactor.pool.set_peer_range("p1", 1, n_blocks)
        for h in range(1, n_blocks + 1):
            reactor.pool.requesters[h] = pool_mod._Requester(
                h, peer_id="p1", got_block_from="p1", block=blocks[h - 1][0]
            )
        from tests.test_blocksync_replay import _drive_reactor

        assert _drive_reactor(
            reactor, lambda: store2.height >= n_blocks - 1, timeout=600
        ), f"stalled at {store2.height}"
        assert store2.load_block(1).hash() == b1.hash()
        st = ex2.store.load()
        assert st.last_block_height == n_blocks - 1
        assert st.validators.size() == V10K
    finally:
        conns2.stop()


def test_validators_pagination_at_10k(keys_10k):
    """`validators` RPC pagination over a 10k set (rpc/core/consensus.go
    Validators + validate_page semantics)."""
    from cometbft_tpu.rpc.core import Environment
    from cometbft_tpu.state.state import make_genesis_state
    from cometbft_tpu.state.store import StateStore
    from cometbft_tpu.store.db import MemDB
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.wire.canonical import Timestamp

    genesis = GenesisDoc(
        chain_id="page-10k",
        genesis_time=Timestamp(seconds=1_700_000_000),
        validators=[
            GenesisValidator(
                pub_key_type="ed25519", pub_key_bytes=k.pub_key().data, power=10
            )
            for k in keys_10k
        ],
        app_hash=b"",
    )
    state = make_genesis_state(genesis)
    store = StateStore(MemDB())
    store.bootstrap(state)

    class _Node:
        state_store = store
        block_store = None

    env = Environment.__new__(Environment)
    env.node = _Node()
    env._height_or_latest = lambda h: 1

    seen = 0
    addresses = set()
    page = 1
    while True:
        out = env.validators(height=1, page=page, per_page=100)
        assert int(out["total"]) == V10K
        n = int(out["count"])
        if n == 0:
            break
        seen += n
        for v in out["validators"]:
            addresses.add(v["address"])
        if seen >= V10K:
            break
        page += 1
    assert seen == V10K
    assert len(addresses) == V10K  # no duplicates across pages


def test_comb_bitmap_width_non_pow2():
    """Packed-bitmap readback at a validator count that is NOT a multiple
    of 8: unpackbits(count=vpad) must not truncate or misalign rows
    (verdict weak #4's vpad/bitmap-width shape class).  From PR 26 a
    single-chip entry's lanes are a multiple of 128, so the bitmap has
    no padding byte; the last real row still sits mid-byte."""
    from cometbft_tpu.models import comb_verifier as cv

    n = 10
    keys = [host.PrivKey.from_seed(bytes([i + 1]) * 32) for i in range(n)]
    pubs = [k.pub_key().data for k in keys]
    entry = cv.ValsetCombCache().ensure(pubs)
    assert (entry.size, entry.vpad) == (n, 128)
    bv = cv.CombBatchVerifier(entry)
    for i, k in enumerate(keys):
        msg = b"w-%d" % i
        bv.add(pubs[i], msg + (b"!" if i == 9 else b""), k.sign(msg))
    ok, per = bv.verify()
    # row 9 lives in the second bitmap byte — exactly the padding edge
    assert not ok and per == [i != 9 for i in range(n)]
