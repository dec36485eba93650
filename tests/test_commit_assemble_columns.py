"""commit.assemble by columns (types/validation._assemble_commit_batch)
against the reference's per-row loop, which lives here as the oracle.

The oracle is the loop of validation.go:265 as the package ran it until
the columns replaced it: one pass over commit.signatures that decides,
looks up, encodes, asks the cache, calls bv.add() and tallies a row at a
time.  For every input the columns must hand the verifier the same
triples in the same order, return the same rows, count the same power
and raise the same refusal (type and text; of two faults in one commit
the one the loop met first).

No signature here is real: nothing is verified, only assembled.
"""

from __future__ import annotations

import random
import sys
from pathlib import Path

import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.types import validation as V
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    ZERO_TIME,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
)
from cometbft_tpu.types.validators import Validator, ValidatorSet
from cometbft_tpu.utils import metrics
from cometbft_tpu.verifysvc.client import ServiceBatchVerifier
from cometbft_tpu.verifysvc.service import MODE_BLS, MODE_PLAIN, MODE_SECP
from cometbft_tpu.wire.canonical import (
    PRECOMMIT_TYPE,
    Timestamp,
    vote_sign_bytes,
    vote_sign_bytes_columns,
    vote_sign_bytes_frame,
)

CHAIN = "columns-chain"
BLOCK_ID = BlockID(hash=b"\x17" * 32, part_set_header=PartSetHeader(3, b"\x23" * 32))
HEIGHT, ROUND = 4321, 2

# every varint width of a timestamp: nanos of 0 (left out), 1..5 bytes;
# seconds of 0 (left out), 1, 5 and 6 bytes, and negative (ten bytes)
NANOS = [0, 1, 127, 128, 16_383, 16_384, 2_097_151, 2_097_152,
         268_435_455, 268_435_456, 999_999_999]
SECONDS = [0, 1, -1, ZERO_TIME.seconds, 1_700_000_000, 1 << 35]


# ------------------------------------------------------------ the oracle


def oracle_assemble(bv, chain_id, vals, commit, voting_power_needed,
                    ignore_sig, count_sig, count_all_signatures,
                    lookup_by_index, cache):
    """The per-row loop, as types/validation had it (PR 31), returning
    the tally beside the rows."""
    seen_vals: dict[int, int] = {}
    batch_sig_idxs: list[int] = []
    tallied = 0
    sign_bytes_at = commit.vote_sign_bytes_fn(chain_id)

    for idx, cs in enumerate(commit.signatures):
        if ignore_sig(cs):
            continue
        if lookup_by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = scan_by_address(vals, cs.validator_address)
            if val is None:
                continue
            if val_idx in seen_vals:
                raise V.CommitVerificationError(
                    f"double vote from {val} ({seen_vals[val_idx]} and {idx})"
                )
            seen_vals[val_idx] = idx

        sign_bytes = sign_bytes_at(idx)

        cache_hit = False
        if cache is not None:
            cv = cache.get(cs.signature)
            cache_hit = (
                cv is not None
                and cv.validator_address == val.pub_key.address()
                and cv.vote_sign_bytes == sign_bytes
            )
        if not cache_hit:
            bv.add(val.pub_key.bytes(), sign_bytes, cs.signature)
            batch_sig_idxs.append(idx)

        if count_sig(cs):
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            break

    if tallied <= voting_power_needed:
        raise V.NotEnoughVotingPowerError(got=tallied, needed=voting_power_needed)
    return batch_sig_idxs, sign_bytes_at, tallied


def scan_by_address(vals, address):
    """ValidatorSet.get_by_address as a scan from the front (PR 31)."""
    for i, v in enumerate(vals.validators):
        if v.address == address:
            return i, v
    return -1, None


def oracle_judge(ok, valid_sigs, commit, batch_sig_idxs, sign_bytes_at, cache):
    """The judging half as it was: the rows encoded a second time."""
    if ok:
        if cache is not None:
            for idx in batch_sig_idxs:
                cs = commit.signatures[idx]
                cache.add(cs.signature, V.SignatureCacheValue(
                    cs.validator_address, sign_bytes_at(idx)))
        return
    for i, sig_ok in enumerate(valid_sigs):
        idx = batch_sig_idxs[i]
        cs = commit.signatures[idx]
        if not sig_ok:
            raise V.CommitVerificationError(
                f"wrong signature (#{idx}): {cs.signature.hex()}")
        if cache is not None:
            cache.add(cs.signature, V.SignatureCacheValue(
                cs.validator_address, sign_bytes_at(idx)))
    raise V.CommitVerificationError(
        "BUG: batch verification failed with no invalid signatures")


# the entry shapes: (commit_only, count_all_signatures, lookup_by_index,
# with a cache); verify_commit keeps every row that is not absent and
# counts the rows for the block, the light checks keep and count those
SHAPES = {
    "verify_commit": (False, True, True, False),
    "light": (True, False, True, True),
    "light_count_all": (True, True, True, True),
    "trusting": (True, False, False, True),
    "trusting_count_all": (True, True, False, True),
}


def lambdas(commit_only):
    if commit_only:
        return (lambda cs: not cs.for_block()), (lambda cs: True)
    return (lambda cs: cs.absent_flag()), (lambda cs: cs.for_block())


class AddOnly:
    """A batch verifier of the old contract: add() and nothing else."""

    def __init__(self):
        self._inner = ServiceBatchVerifier()

    def add(self, pub_key, msg, sig):
        self._inner.add(pub_key, msg, sig)

    @property
    def _items(self):
        return self._inner._items


# ------------------------------------------------------------- the data


def make_set(rng, n):
    return ValidatorSet([
        Validator(ed25519.PubKey(rng.randbytes(32)), rng.randrange(1, 50))
        for _ in range(n)
    ])


def stamp(rng):
    if rng.random() < 0.5:  # what a chain carries: now, to the nanosecond
        return Timestamp(seconds=1_700_000_000 + rng.randrange(3),
                         nanos=rng.randrange(1_000_000_000))
    return Timestamp(seconds=rng.choice(SECONDS), nanos=rng.choice(NANOS))


def make_commit(rng, vals, foreign_share=0.0):
    """A commit over ``vals`` in set order with flags mixed; with
    ``foreign_share`` that share of the rows is signed by validators of
    another set (the light client's trusting check meets such)."""
    sigs = []
    for v in vals.validators:
        flag = rng.choices(
            [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_ABSENT],
            [80, 10, 10],
        )[0]
        if flag == BLOCK_ID_FLAG_ABSENT:
            sigs.append(CommitSig.absent())
            continue
        address = v.address if rng.random() >= foreign_share else rng.randbytes(20)
        sigs.append(CommitSig(flag, address, stamp(rng), rng.randbytes(64)))
    return Commit(HEIGHT, ROUND, BLOCK_ID, sigs)


def kept_rows(commit, commit_only):
    return [
        i for i, cs in enumerate(commit.signatures)
        if (cs.for_block() if commit_only else not cs.absent_flag())
    ]


def prefill(rng, vals, commit, rows, by_index):
    """A SignatureCache that knows a third of ``rows``: of those a half
    are hits, a quarter carry another address and a quarter other
    sign-bytes (near-misses: the row goes to the verifier)."""
    cache = V.SignatureCache()
    sign_bytes_at = commit.vote_sign_bytes_fn(CHAIN)
    for i in rows:
        if rng.random() >= 1 / 3:
            continue
        cs = commit.signatures[i]
        val = (vals.validators[i] if by_index
               else scan_by_address(vals, cs.validator_address)[1])
        address = val.address if val is not None else cs.validator_address
        msg = sign_bytes_at(i)
        kind = rng.choice(["hit", "hit", "address", "bytes"])
        if kind == "address":
            address = rng.randbytes(20)
        if kind == "bytes":
            msg = msg[:-1] + bytes([msg[-1] ^ 1])
        cache.add(cs.signature, V.SignatureCacheValue(address, msg))
    return cache


SCENARIOS = [
    "plain", "cache", "bar_under", "bar_over", "double_vote",
    "malformed_then_double", "double_then_malformed", "malformed",
    "per_row_timestamp", "all_cached", "odd_flag",
]


def build(scenario, shape, n, seed):
    """(vals, commit, needed, cache factory) of one case."""
    commit_only, count_all, by_index, cached = SHAPES[shape]
    rng = random.Random(f"{scenario}/{shape}/{n}/{seed}")
    vals = make_set(rng, n)
    commit = make_commit(rng, vals, foreign_share=0.0 if by_index else 0.3)
    rows = kept_rows(commit, commit_only)
    sigs = commit.signatures
    third = vals.total_voting_power() // 3
    needed = third * 2 if by_index else third
    if not by_index:
        rows = [i for i in rows
                if scan_by_address(vals, sigs[i].validator_address)[1] is not None]

    def a_row(lo, hi):
        """A kept row in the lo..hi share of the kept rows (a set of
        four may keep none: then an absent row, which nothing reads)."""
        if not rows:
            return 0
        first = min(int(lo * len(rows)), len(rows) - 1)
        return rows[rng.randrange(first, max(int(hi * len(rows)), first + 1))]

    if scenario in ("double_vote", "malformed_then_double", "double_then_malformed"):
        # the row at 2/3 votes again as the validator of an earlier row
        # (a fault only where validators are looked up by address)
        early, late = a_row(0.0, 0.3), a_row(0.6, 0.7)
        if early != late:
            sigs[late].validator_address = sigs[early].validator_address
    if scenario in ("malformed", "malformed_then_double"):
        sigs[a_row(0.3, 0.5)].signature = rng.randbytes(63)
    if scenario == "double_then_malformed":
        sigs[a_row(0.8, 1.0)].signature = rng.randbytes(65)
    if scenario == "per_row_timestamp":
        sigs[a_row(0.0, 1.0)].timestamp = Timestamp(seconds=1 << 70, nanos=5)
    if scenario == "odd_flag":
        # a flag the wire can carry and no rule names: neither absent
        # nor for the block, whatever its size
        sigs[a_row(0.0, 0.5)].block_id_flag = 1 << 63
        sigs[a_row(0.5, 1.0)].block_id_flag = 77
    if scenario in ("bar_under", "bar_over"):
        # the bar put at what the rows count (refused: the tally must be
        # over it) and one under
        ignore, count = lambdas(commit_only)
        try:
            total = oracle_assemble(
                ServiceBatchVerifier(), CHAIN, vals, commit, -1, ignore, count,
                True, by_index, None)[2]
        except V.CommitVerificationError:
            total = needed + 1
        needed = total if scenario == "bar_under" else total - 1

    def cache():
        if not cached:
            return None
        if scenario == "all_cached":
            full = V.SignatureCache()
            fn = commit.vote_sign_bytes_fn(CHAIN)
            for i in rows:
                val = (vals.validators[i] if by_index else
                       scan_by_address(vals, sigs[i].validator_address)[1])
                full.add(sigs[i].signature,
                         V.SignatureCacheValue(val.address, fn(i)))
            return full
        if scenario == "plain":
            return V.SignatureCache()
        return prefill(random.Random(seed), vals, commit, rows, by_index)

    return vals, commit, needed, cache


def outcome(fn):
    """What a call came to: its value, or the refusal's type and text."""
    try:
        return ("ok", fn())
    except (V.CommitVerificationError, ValueError) as e:
        return ("raised", type(e).__name__, str(e), getattr(e, "got", None))


@pytest.mark.parametrize("n", [4, 175, 2000])
@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_columns_equal_the_per_row_loop(scenario, shape, n):
    commit_only, count_all, by_index, _ = SHAPES[shape]
    ignore, count = lambdas(commit_only)
    for seed in range(3 if n < 2000 else 1):
        vals, commit, needed, cache = build(scenario, shape, n, seed)

        bv_want = ServiceBatchVerifier()

        def want():
            idxs, at, tallied = oracle_assemble(
                bv_want, CHAIN, vals, commit, needed, ignore, count,
                count_all, by_index, cache())
            return idxs, [at(i) for i in idxs], tallied

        expected = outcome(want)

        for bv in (ServiceBatchVerifier(), AddOnly()):
            def got():
                idxs, msgs = V._assemble_commit_batch(
                    bv, CHAIN, vals, commit, needed, commit_only, count_all,
                    by_index, cache())
                tallied = V._select_rows(
                    vals, commit, needed, commit_only, count_all, by_index)[2]
                return idxs, msgs, tallied

            assert outcome(got) == expected, (scenario, shape, n, seed)
            assert bv._items == bv_want._items, (scenario, shape, n, seed)


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("verdict", ["ok", "blame_first", "blame_middle", "no_blame"])
def test_judge_fills_the_cache_from_the_assembled_column(shape, verdict):
    """_judge_batch_result with the assembled sign-bytes leaves the
    cache and raises as the judging half did when it encoded the rows
    again."""
    commit_only, count_all, by_index, cached = SHAPES[shape]
    ignore, count = lambdas(commit_only)
    vals, commit, needed, _ = build("plain", shape, 175, 7)
    want_cache = V.SignatureCache() if cached else None
    got_cache = V.SignatureCache() if cached else None
    idxs, at, _ = oracle_assemble(
        ServiceBatchVerifier(), CHAIN, vals, commit, needed, ignore, count,
        count_all, by_index, want_cache)
    got_idxs, msgs = V._assemble_commit_batch(
        ServiceBatchVerifier(), CHAIN, vals, commit, needed, commit_only,
        count_all, by_index, got_cache)
    assert got_idxs == idxs
    valid = [True] * len(idxs)
    if verdict == "blame_first":
        valid[0] = False
    if verdict == "blame_middle":
        valid[len(valid) // 2] = False
    ok = verdict == "ok"
    expected = outcome(lambda: oracle_judge(ok, valid, commit, idxs, at, want_cache))
    assert outcome(lambda: V._judge_batch_result(
        ok, valid, commit, got_idxs, msgs, got_cache)) == expected
    if cached:
        assert got_cache._d == want_cache._d
        assert list(got_cache._d) == list(want_cache._d)  # eviction order


# ---------------------------------------------------- the entry points


class Recording(ServiceBatchVerifier):
    """Answers every batch all-true without a service, and keeps it."""

    made: list = []

    def submit(self):
        return ("sync", (True, [True] * len(self._items)))


@pytest.fixture
def recording(monkeypatch):
    Recording.made = []

    def create(key_type, pubkeys=None, klass=None, tenant=None):
        Recording.made.append(Recording())
        return Recording.made[-1]

    monkeypatch.setattr(V.crypto_batch, "create_batch_verifier", create)
    return Recording


@pytest.mark.parametrize("n", [4, 175])
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("entry", ["verify_commit", "verify_commit_light",
                                   "verify_commit_light_trusting",
                                   "submit_verify_commit_light"])
def test_entry_points_hand_over_the_loops_batch(recording, entry, scenario, n):
    shape = {"verify_commit": "verify_commit", "verify_commit_light": "light",
             "verify_commit_light_trusting": "trusting",
             "submit_verify_commit_light": "light"}[entry]
    commit_only, count_all, by_index, _ = SHAPES[shape]
    ignore, count = lambdas(commit_only)
    vals, commit, _, cache = build(scenario, shape, n, 11)
    third = vals.total_voting_power() // 3
    needed = third if entry.endswith("trusting") else vals.total_voting_power() * 2 // 3
    bv_want = ServiceBatchVerifier()
    want_cache, got_cache = cache(), cache()

    def want():
        idxs, at, _ = oracle_assemble(
            bv_want, CHAIN, vals, commit, needed, ignore, count, count_all,
            by_index, want_cache)
        if idxs:
            oracle_judge(True, [True] * len(idxs), commit, idxs, at, want_cache)

    def got():
        if entry == "verify_commit":
            V.verify_commit(CHAIN, vals, BLOCK_ID, HEIGHT, commit)
        elif entry == "verify_commit_light":
            V.verify_commit_light(CHAIN, vals, BLOCK_ID, HEIGHT, commit,
                                  cache=got_cache)
        elif entry == "verify_commit_light_trusting":
            V.verify_commit_light_trusting(CHAIN, vals, commit, cache=got_cache)
        else:
            pending = V.submit_verify_commit_light(
                CHAIN, vals, BLOCK_ID, HEIGHT, commit, cache=got_cache)
            assert pending is not None
            pending.collect()

    assert outcome(got) == outcome(want)
    assert len(recording.made) == 1
    assert recording.made[0]._items == bv_want._items
    if got_cache is not None:
        assert got_cache._d == want_cache._d


@pytest.mark.parametrize("lookup_by_index", [True, False])
def test_single_path_shares_the_lookup_and_the_flag_rule(lookup_by_index):
    """_verify_commit_single (the sets the batch path refuses) reads the
    same address index and the same flags: over really signed rows it
    accepts, stops early, refuses the tally and, looking up by address,
    a double vote, by the loop's text."""
    rng = random.Random(3)
    keys = [ed25519.PrivKey.from_seed(rng.randbytes(32)) for _ in range(6)]
    vals = ValidatorSet([Validator(k.pub_key(), 10) for k in keys])
    by_address = {k.pub_key().address(): k for k in keys}
    commit = Commit(HEIGHT, ROUND, BLOCK_ID, [
        CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, stamp(rng), b"")
        for v in vals.validators
    ])
    commit.signatures[3] = CommitSig.absent()
    for i, cs in enumerate(commit.signatures):
        if not cs.absent_flag():
            cs.signature = by_address[cs.validator_address].sign(
                commit.vote_sign_bytes(CHAIN, i))

    def single(needed, commit_only=True, count_all=True):
        return outcome(lambda: V._verify_commit_single(
            CHAIN, vals, commit, needed, commit_only, count_all,
            lookup_by_index, None))

    assert single(49) == ("ok", None)
    assert single(49, commit_only=False) == ("ok", None)
    assert single(50)[1:] == (
        "NotEnoughVotingPowerError",
        "invalid commit -- insufficient voting power: got 50, needed more than 50",
        50,
    )
    # early exit: the broken row behind the bar is never reached
    commit.signatures[5].signature = bytes(64)
    assert single(30, count_all=False) == ("ok", None)
    assert single(30)[2].startswith("wrong signature (#5)")
    # row 4 votes again as row 1's validator
    commit.signatures[4].validator_address = commit.signatures[1].validator_address
    got = single(39, count_all=False)
    if lookup_by_index:
        assert got == ("ok", None)
    else:
        assert got[2] == f"double vote from {vals.validators[1]} (1 and 4)"


# ----------------------------------------------- the column encoder alone


def timestamp_classes():
    out = [(s, n) for s in SECONDS for n in NANOS]
    out += [(s, -1) for s in (0, 5)]  # a negative nanos is a ten-byte varint
    out += [((1 << 63) - 1, 1), (-(1 << 63), 999_999_999)]
    return out


@pytest.mark.parametrize("for_block", [True, False])
def test_column_encoder_is_byte_identical(for_block):
    """Every row equals wire/canonical.vote_sign_bytes and
    Commit.vote_sign_bytes over every timestamp class, for block and nil
    rows, alone and mixed into one call."""
    classes = timestamp_classes()
    flag = BLOCK_ID_FLAG_COMMIT if for_block else BLOCK_ID_FLAG_NIL
    commit = Commit(HEIGHT, ROUND, BLOCK_ID, [
        CommitSig(flag, b"\x01" * 20, Timestamp(seconds=s, nanos=n), b"s" * 64)
        for s, n in classes
    ])
    rows, path = commit.vote_sign_bytes_rows(CHAIN, list(range(len(classes))))
    assert path == "columns"
    frames = [
        vote_sign_bytes_frame(CHAIN, PRECOMMIT_TYPE, HEIGHT, ROUND, bid)
        for bid in (BLOCK_ID.to_canonical(), None)
    ]
    bid = BLOCK_ID.to_canonical() if for_block else None
    for i, (s, n) in enumerate(classes):
        ts = Timestamp(seconds=s, nanos=n)
        want = vote_sign_bytes(CHAIN, PRECOMMIT_TYPE, HEIGHT, ROUND, bid, ts)
        assert rows[i] == want, (s, n)
        assert rows[i] == commit.vote_sign_bytes(CHAIN, i), (s, n)
        assert vote_sign_bytes_columns(
            frames, [not for_block], [s], [n]) == [want], (s, n)
    # any selection, any order
    picks = [len(classes) - 1, 0, 7, 7, 3]
    assert commit.vote_sign_bytes_rows(CHAIN, picks)[0] == [rows[i] for i in picks]
    assert commit.vote_sign_bytes_rows(CHAIN, []) == ([], "columns")


def test_column_encoder_equals_the_benchmarks_reference():
    """benchmarks/reference.precommit_sign_bytes is written by hand from
    canonical.proto and shares no code with the package: equal over the
    timestamps it can express (no negative number)."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import reference

    classes = [(s, n) for s, n in timestamp_classes() if s >= 0 and n >= 0]
    commit = Commit(HEIGHT, ROUND, BLOCK_ID, [
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x01" * 20,
                  Timestamp(seconds=s, nanos=n), b"s" * 64)
        for s, n in classes
    ])
    rows, _ = commit.vote_sign_bytes_rows(CHAIN, list(range(len(classes))))
    for row, (s, n) in zip(rows, classes):
        assert row == reference.precommit_sign_bytes(
            CHAIN, HEIGHT, ROUND, BLOCK_ID.hash, BLOCK_ID.part_set_header.total,
            BLOCK_ID.part_set_header.hash, s, n), (s, n)


@pytest.mark.parametrize("odd", [1 << 63, -(1 << 63) - 1, 1 << 70])
def test_a_timestamp_outside_int64_takes_the_per_row_route(odd):
    commit = Commit(HEIGHT, ROUND, BLOCK_ID, [
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x01" * 20,
                  Timestamp(seconds=s, nanos=7), b"s" * 64)
        for s in (1_700_000_000, odd, 0)
    ])
    rows, path = commit.vote_sign_bytes_rows(CHAIN, [0, 1, 2])
    assert path == "per_row"
    assert rows == [commit.vote_sign_bytes(CHAIN, i) for i in range(3)]


def test_equal_and_distinct_timestamps_take_one_route():
    """The benchmark's commits carry one timestamp on every row, a
    chain's do not: both are one pass of the same code, and neither
    leaves anything on the Commit."""
    rng = random.Random(9)
    vals = make_set(rng, 64)
    equal = make_commit(rng, vals)
    for cs in equal.signatures:
        cs.timestamp = Timestamp(seconds=1_700_000_123)
    distinct = make_commit(rng, vals)
    for commit in (equal, distinct):
        idxs = kept_rows(commit, False)
        rows, path = commit.vote_sign_bytes_rows(CHAIN, idxs)
        assert path == "columns"
        assert rows == [commit.vote_sign_bytes(CHAIN, i) for i in idxs]
        assert Commit.__slots__ == (
            "height", "round", "block_id", "signatures", "_hash")


# ------------------------------------------------- the engagement counter


def counted():
    c = metrics.hub().commit_assemble_rows
    return c.value(path="columns"), c.value(path="per_row")


def test_counter_reads_columns_for_a_benchmark_shaped_commit():
    rng = random.Random(21)
    vals = make_set(rng, 175)
    commit = Commit(HEIGHT, ROUND, BLOCK_ID, [
        CommitSig(BLOCK_ID_FLAG_COMMIT, v.address,
                  Timestamp(seconds=1_700_000_001), rng.randbytes(64))
        for v in vals.validators
    ])
    before = counted()
    V._assemble_commit_batch(
        ServiceBatchVerifier(), CHAIN, vals, commit,
        vals.total_voting_power() * 2 // 3, False, True, True, None)
    after = counted()
    assert (after[0] - before[0], after[1] - before[1]) == (175, 0)
    # ZERO_TIME fits the columns (negative seconds are ten bytes)
    commit.signatures[3].timestamp = ZERO_TIME
    V._assemble_commit_batch(
        ServiceBatchVerifier(), CHAIN, vals, commit,
        vals.total_voting_power() * 2 // 3, False, True, True, None)
    assert counted() == (after[0] + 175, after[1])
    # a timestamp beyond int64 does not: the whole commit goes row by row
    commit.signatures[3].timestamp = Timestamp(seconds=1 << 64)
    V._assemble_commit_batch(
        ServiceBatchVerifier(), CHAIN, vals, commit,
        vals.total_voting_power() * 2 // 3, False, True, True, None)
    assert counted() == (after[0] + 175, after[1] + 175)


def test_span_carries_rows_and_path():
    from cometbft_tpu.utils import tracing

    rng = random.Random(22)
    vals = make_set(rng, 8)
    commit = make_commit(rng, vals)
    for cs in commit.signatures:
        cs.block_id_flag = BLOCK_ID_FLAG_COMMIT
        cs.signature = cs.signature or rng.randbytes(64)
    was = tracing.enabled()
    tracing.set_enabled(True)
    try:
        tracing.reset()
        V._assemble_commit_batch(
            ServiceBatchVerifier(), CHAIN, vals, commit, 0, True, True, True, None)
        spans = [e for e in tracing.chrome_trace_events()
                 if e.get("name") == "commit.assemble"]
    finally:
        tracing.set_enabled(was)
    assert len(spans) == 1
    assert spans[0]["args"]["rows"] == 8 and spans[0]["args"]["path"] == "columns"


# ------------------------------------------------------ the per-set facts


def test_set_facts_follow_a_change_of_membership():
    rng = random.Random(31)
    vals = make_set(rng, 12)
    gone = vals.validators[5]
    stays = vals.validators[2]
    assert vals.get_by_address(gone.address) == (5, gone)
    assert vals.voting_powers().tolist() == [v.voting_power for v in vals.validators]
    assert vals.all_keys_have_same_type()

    twin = vals.copy()  # carries the facts; they are its own from here
    assert twin.address_index() is vals.address_index()

    new = Validator(ed25519.PubKey(rng.randbytes(32)), 1000)
    twin.update_with_change_set([Validator(gone.pub_key, 0), new])
    assert twin.get_by_address(gone.address) == (-1, None)
    assert twin.get_by_address(new.address)[0] == 0  # the heaviest sorts first
    assert twin.get_by_address(new.address)[1].address == new.address
    i, v = twin.get_by_address(stays.address)
    assert twin.validators[i] is v and v.address == stays.address
    assert twin.voting_powers().tolist() == [v.voting_power for v in twin.validators]
    assert len(twin.address_index()) == 12
    # the set the copy was made from is as it was
    assert vals.get_by_address(gone.address) == (5, gone)
    assert vals.get_by_address(new.address) == (-1, None)
    assert len(vals.voting_powers()) == 12
    for s in (vals, twin):
        for k, v in enumerate(s.validators):
            assert s.get_by_address(v.address) == (k, v)
            assert scan_by_address(s, v.address) == (k, v)


def test_key_type_fact_is_dropped_with_the_membership():
    from cometbft_tpu.crypto import secp256k1

    rng = random.Random(32)
    vals = make_set(rng, 4)
    assert vals.all_keys_have_same_type()
    other = Validator(secp256k1.PrivKey.generate().pub_key(), 5)
    vals.update_with_change_set([other])
    assert not vals.all_keys_have_same_type()
    assert not vals.copy().all_keys_have_same_type()
    vals.update_with_change_set([Validator(other.pub_key, 0)])
    assert vals.all_keys_have_same_type()
    assert ValidatorSet([]).all_keys_have_same_type()


def test_first_of_two_validators_with_one_address_wins():
    rng = random.Random(33)
    key = ed25519.PubKey(rng.randbytes(32))
    vals = ValidatorSet([Validator(key, 9, 2), Validator(key, 4, 1),
                         Validator(ed25519.PubKey(rng.randbytes(32)), 6)])
    assert vals.get_by_address(key.address())[0] == scan_by_address(
        vals, key.address())[0] == 0


def test_set_facts_are_rebuilt_when_the_list_changes_length():
    # the guard pub_keys_bytes has: nothing in the package edits
    # .validators in place, but a stale index must not outlive it
    rng = random.Random(34)
    vals = make_set(rng, 6)
    late = Validator(ed25519.PubKey(rng.randbytes(32)), 1)
    assert vals.get_by_address(late.address) == (-1, None)
    assert len(vals.voting_powers()) == 6 and vals.all_keys_have_same_type()
    vals.validators.append(late)
    assert vals.get_by_address(late.address) == (6, late)
    assert vals.voting_powers().tolist() == [v.voting_power for v in vals.validators]
    gone = vals.validators.pop(0)
    assert vals.get_by_address(gone.address) == (-1, None)
    assert vals.get_by_address(late.address) == (5, late)


# ----------------------------------------------------------- add_many


@pytest.mark.parametrize("mode,pub,sig,bad_pub,bad_sig", [
    (MODE_PLAIN, 32, 64, 31, 63),
    (MODE_BLS, 48, 96, 32, 64),
    (MODE_SECP, 33, 64, 32, 66),
    (MODE_SECP, 20, 65, 21, 63),
])
def test_add_many_is_add_over_columns(mode, pub, sig, bad_pub, bad_sig):
    rng = random.Random(41)
    n = 9
    pubs = [rng.randbytes(pub) for _ in range(n)]
    msgs = [rng.randbytes(rng.randrange(1, 200)) for _ in range(n)]
    sigs = [rng.randbytes(sig) for _ in range(n)]

    def both(pubs, msgs, sigs):
        one, many = ServiceBatchVerifier(mode=mode), ServiceBatchVerifier(mode=mode)

        def row_by_row():
            for row in zip(pubs, msgs, sigs):
                one.add(*row)

        want = outcome(row_by_row)
        assert outcome(lambda: many.add_many(pubs, msgs, sigs)) == want
        assert many._items == one._items
        return want

    assert both(pubs, msgs, sigs)[0] == "ok"
    for at in (0, 4, n - 1):
        bad = list(pubs)
        bad[at] = rng.randbytes(bad_pub)
        assert both(bad, msgs, sigs)[0] == "raised"
        bad = list(sigs)
        bad[at] = rng.randbytes(bad_sig)
        assert both(pubs, msgs, bad)[0] == "raised"
    with pytest.raises(ValueError):
        ServiceBatchVerifier(mode=mode).add_many(pubs, msgs, sigs[:-1])


def test_add_many_holds_ed25519_messages_to_the_payload_bound():
    big = bytes(1 << 24)
    pubs, sigs = [bytes(32)] * 3, [bytes(64)] * 3
    one, many = ServiceBatchVerifier(), ServiceBatchVerifier()
    with pytest.raises(ValueError, match="message too large"):
        many.add_many(pubs, [b"a", big, b"c"], sigs)
    one.add(pubs[0], b"a", sigs[0])
    assert many._items == one._items
    # of a malformed key at row 0 and a message too large at row 1, row 0's
    with pytest.raises(ValueError, match="malformed ed25519"):
        ServiceBatchVerifier().add_many([b"k"] + pubs[1:], [b"a", big, b"c"], sigs)
    # the bls lane has no such bound
    ServiceBatchVerifier(mode=MODE_BLS).add_many([bytes(48)], [big], [bytes(96)])
