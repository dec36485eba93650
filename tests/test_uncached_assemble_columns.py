"""The uncached verifier's batch by columns (models/verifier.
assemble_uncached, ops/sha2.pad_sha512_columns) against the per-row loop,
which lives here as the oracle.

The oracle is TpuEd25519BatchVerifier._submit_device's assembly as the
package ran it until the columns replaced it: one pass over the rows that
writes a, r and s a row at a time and concatenates R || A || M, pad rows
that repeat row 0, and the per-row SHA-512 padding loop of
ops/sha2.pad_messages_sha512.  For every batch the columns must hand the
program the same five arrays, byte for byte, dtype and shape included.

Only the last test verifies anything: the rest assemble.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.models import verifier as VF
from cometbft_tpu.ops import sha2
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_COMMIT,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
)
from cometbft_tpu.utils import tracing
from cometbft_tpu.wire.canonical import Timestamp

CHAIN = "uncached-columns"
BLOCK_ID = BlockID(hash=b"\x31" * 32, part_set_header=PartSetHeader(1, b"\x47" * 32))

# nanos of every varint width: left out, then 1..5 bytes
NANOS = [0, 1, 128, 16_384, 2_097_152, 268_435_456, 999_999_999]


# ------------------------------------------------------------ the oracle


def oracle_pad(msgs, max_len=None):
    """ops/sha2.pad_messages_sha512 as the per-row loop it was."""
    n = len(msgs)
    longest = max((len(m) for m in msgs), default=0)
    if max_len is not None:
        longest = max(longest, max_len)
    nblocks = max(1, (longest + 17 + 127) // 128)
    buf = np.zeros((n, nblocks * 128), dtype=np.uint8)
    active = np.zeros(n, dtype=np.int32)
    for i, m in enumerate(msgs):
        ln = len(m)
        nb = (ln + 17 + 127) // 128
        active[i] = nb
        buf[i, :ln] = np.frombuffer(m, dtype=np.uint8)
        buf[i, ln] = 0x80
        buf[i, nb * 128 - 16 : nb * 128] = np.frombuffer(
            (ln * 8).to_bytes(16, "big"), dtype=np.uint8
        )
    return buf.reshape(n, nblocks, 128), active


def oracle_assemble(items):
    """_submit_device's row loop, as the package had it."""
    n = len(items)
    bucket = VF._next_bucket(n)
    a = np.zeros((bucket, 32), dtype=np.uint8)
    r = np.zeros((bucket, 32), dtype=np.uint8)
    s = np.zeros((bucket, 32), dtype=np.uint8)
    hashed = []
    for i, (pub, msg, sig) in enumerate(items):
        a[i] = np.frombuffer(pub, dtype=np.uint8)
        r[i] = np.frombuffer(sig[:32], dtype=np.uint8)
        s[i] = np.frombuffer(sig[32:], dtype=np.uint8)
        hashed.append(sig[:32] + pub + msg)
    for i in range(n, bucket):
        a[i], r[i], s[i] = a[0], r[0], s[0]
        hashed.append(hashed[0])
    blocks, active = oracle_pad(hashed)
    return a, r, s, blocks, active


def assert_same_arrays(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


# ------------------------------------------------------------ the batches


def sign_bytes(n, seconds=1_700_000_000, nanos=None):
    """Precommit sign-bytes of n rows of one commit; ``nanos(i)`` gives
    row i's nanos, else every row carries the same timestamp."""
    sigs = [
        CommitSig(BLOCK_ID_FLAG_COMMIT, bytes([i % 256]) * 20,
                  Timestamp(seconds=seconds, nanos=nanos(i) if nanos else 7),
                  bytes(64))
        for i in range(n)
    ]
    commit = Commit(5000, 0, BLOCK_ID, sigs)
    return [commit.vote_sign_bytes(CHAIN, i) for i in range(n)]


def rows_for(rng, msgs):
    """Random keys and signatures beside ``msgs``: assembly only."""
    return [(rng.randbytes(32), m, rng.randbytes(64)) for m in msgs]


def rra_lengths(totals):
    """Messages whose padded R || A || M is ``64 + len(msg) + 17`` long."""
    return [bytes(range(t - 81)) for t in totals]


CASES = {
    "one_length": lambda rng: [rng.randbytes(110) for _ in range(40)],
    "sign_bytes_one_timestamp": lambda rng: sign_bytes(50),
    "sign_bytes_distinct_nanos": lambda rng: sign_bytes(
        70, nanos=lambda i: NANOS[i % len(NANOS)]),
    "sign_bytes_chain_nanos": lambda rng: sign_bytes(
        60, nanos=lambda i: rng.randrange(1_000_000_000)),
    "block_edge_128": lambda rng: rra_lengths([127, 128, 129] * 7),
    "block_edge_256": lambda rng: rra_lengths([255, 256, 257] * 7),
    "both_edges_and_empty": lambda rng: (
        rra_lengths([129, 127, 256, 128, 257, 255]) + [b"", b"\x00"]
        + [rng.randbytes(rng.randrange(300)) for _ in range(20)]),
    "n_32": lambda rng: [rng.randbytes(120) for _ in range(32)],
    "n_33": lambda rng: [rng.randbytes(rng.choice((110, 111, 112)))
                         for _ in range(33)],
    "n_one_bucket": lambda rng: sign_bytes(
        64, nanos=lambda i: NANOS[i % len(NANOS)]),
    "n_1": lambda rng: [b"one"],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_columns_equal_the_row_loop(case):
    rng = random.Random(case)
    items = rows_for(rng, CASES[case](rng))
    arrays, lengths = VF.assemble_uncached(items)
    assert_same_arrays(arrays, oracle_assemble(items))
    assert lengths == len({len(m) for _, m, _ in items})
    assert len(arrays[0]) == VF._next_bucket(len(items))


def test_distinct_nanos_give_several_lengths():
    # the case the single-timestamp benchmark chain never shows
    assert len({len(m) for m in sign_bytes(
        70, nanos=lambda i: NANOS[i % len(NANOS)])}) >= 4
    assert len({len(m) for m in sign_bytes(50)}) == 1


@pytest.mark.parametrize("max_len", [None, 0, 300])
def test_pad_messages_sha512_equals_its_row_loop(max_len):
    rng = random.Random(str(max_len))
    msgs = [rng.randbytes(ln) for ln in
            [0, 1, 110, 111, 112, 127, 128, 129, 200, 239, 240, 255, 300]]
    msgs += [rng.randbytes(rng.randrange(260)) for _ in range(30)]
    assert_same_arrays(sha2.pad_messages_sha512(msgs, max_len),
                       oracle_pad(msgs, max_len))
    assert_same_arrays(sha2.pad_messages_sha512([], max_len),
                       oracle_pad([], max_len))


def test_head_columns_are_the_joined_prefix():
    rng = random.Random(7)
    heads = ([rng.randbytes(5) for _ in range(9)],
             [rng.randbytes(3) for _ in range(9)])
    msgs = [rng.randbytes(rng.randrange(250)) for _ in range(9)]
    buf, active, lengths = sha2.pad_sha512_columns(heads, msgs)
    assert_same_arrays((buf, active), oracle_pad(
        [x + y + m for x, y, m in zip(*heads, msgs)]))
    assert lengths == len({len(m) for m in msgs})
    assert buf.flags.writeable  # as the row loop's arrays were


# --------------------------------------------- the span and the verdicts


def signed(n, seed):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        sk = ed25519.PrivKey.from_seed(rng.randbytes(32))
        msg = sign_bytes(1, nanos=lambda _: NANOS[i % len(NANOS)])[0]
        out.append((sk.pub_key().data, msg, sk.sign(msg)))
    return out


def test_verdicts_and_span_labels_on_the_device_path(monkeypatch):
    # below DEVICE_BATCH_MIN the batch would be host-routed
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")
    items = signed(12, 3)
    flipped = {0, 5, 11}
    for i in flipped:
        pub, msg, sig = items[i]
        items[i] = (pub, msg, sig[:40] + bytes([sig[40] ^ 1]) + sig[41:])
    want = VF.CpuEd25519BatchVerifier()
    bv = VF.TpuEd25519BatchVerifier()
    for row in items:
        want.add(*row)
        bv.add(*row)
    was = tracing.enabled()
    tracing.set_enabled(True)
    try:
        tracing.reset()
        got = bv.verify()
        spans = [e for e in tracing.chrome_trace_events()
                 if e.get("name") == "verify.uncached_assemble"]
    finally:
        tracing.set_enabled(was)
    assert got == want.verify()
    assert got[1] == [i not in flipped for i in range(12)]
    assert all(type(v) is bool for v in got[1])
    assert len(spans) == 1
    args = spans[0]["args"]
    assert (args["rows"], args["bucket"]) == (12, 16)
    assert args["lengths"] == len({len(m) for _, m, _ in items}) > 1
