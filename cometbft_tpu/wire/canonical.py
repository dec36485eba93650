"""Canonical sign-bytes messages (reference: proto/cometbft/types/v1/
canonical.proto; serialization entry points types/vote.go VoteSignBytes and
types/proposal.go ProposalSignBytes).

These byte strings are what validators sign and what the TPU batch
verifier hashes — they are consensus-critical and must be deterministic:
sfixed64 height/round (fixed-size, canonical), ascending field order,
non-nullable timestamps always emitted (gogoproto semantics), and the
whole message varint-length-delimited (protoio MarshalDelimited).
"""

from __future__ import annotations

import numpy as np

from .proto import Message, Field, encode_delimited, encode_varint

# SignedMsgType enum (types.proto SIGNED_MSG_TYPE_*)
UNKNOWN_TYPE = 0
PREVOTE_TYPE = 1
PRECOMMIT_TYPE = 2
PROPOSAL_TYPE = 32


class Timestamp(Message):
    """google.protobuf.Timestamp: UTC wall time as (seconds, nanos)."""

    FIELDS = [
        Field(1, "seconds", "varint"),
        Field(2, "nanos", "varint"),
    ]

    @classmethod
    def from_unix_ns(cls, ns: int) -> "Timestamp":
        return cls(seconds=ns // 1_000_000_000, nanos=ns % 1_000_000_000)

    def unix_ns(self) -> int:
        return self.seconds * 1_000_000_000 + self.nanos

    @classmethod
    def now(cls) -> "Timestamp":
        import time

        return cls.from_unix_ns(time.time_ns())

    def __lt__(self, other):
        return self.unix_ns() < other.unix_ns()

    def __le__(self, other):
        return self.unix_ns() <= other.unix_ns()

    def __hash__(self):
        return hash(self.unix_ns())


class CanonicalPartSetHeader(Message):
    FIELDS = [
        Field(1, "total", "varint"),
        Field(2, "hash", "bytes"),
    ]


class CanonicalBlockID(Message):
    FIELDS = [
        Field(1, "hash", "bytes"),
        Field(2, "part_set_header", "message", CanonicalPartSetHeader, emit_default=True),
    ]


class CanonicalVote(Message):
    FIELDS = [
        Field(1, "type", "varint"),
        Field(2, "height", "sfixed64"),
        Field(3, "round", "sfixed64"),
        Field(4, "block_id", "message", CanonicalBlockID),  # nil when voting nil
        Field(5, "timestamp", "message", Timestamp, emit_default=True),
        Field(6, "chain_id", "string"),
    ]


class CanonicalProposal(Message):
    FIELDS = [
        Field(1, "type", "varint"),
        Field(2, "height", "sfixed64"),
        Field(3, "round", "sfixed64"),
        Field(4, "pol_round", "varint"),
        Field(5, "block_id", "message", CanonicalBlockID),
        Field(6, "timestamp", "message", Timestamp, emit_default=True),
        Field(7, "chain_id", "string"),
    ]


class CanonicalVoteExtension(Message):
    FIELDS = [
        Field(1, "extension", "bytes"),
        Field(2, "height", "sfixed64"),
        Field(3, "round", "sfixed64"),
        Field(4, "chain_id", "string"),
    ]


def vote_sign_bytes(
    chain_id: str,
    msg_type: int,
    height: int,
    round_: int,
    block_id: CanonicalBlockID | None,
    timestamp: Timestamp,
) -> bytes:
    """The exact bytes a validator signs for a vote (types/vote.go:VoteSignBytes)."""
    cv = CanonicalVote(
        type=msg_type,
        height=height,
        round=round_,
        block_id=block_id,
        timestamp=timestamp,
        chain_id=chain_id,
    )
    return encode_delimited(cv)


class _CanonicalVotePrefix(Message):
    """Fields 1-4 of CanonicalVote — everything before the timestamp.
    Derived from CanonicalVote.FIELDS so an edit there cannot silently
    diverge this consensus-critical fast path."""

    FIELDS = [f for f in CanonicalVote.FIELDS if f.num < 5]


class _CanonicalVoteSuffix(Message):
    FIELDS = [f for f in CanonicalVote.FIELDS if f.num > 5]


_TS_TAG = bytes([5 << 3 | 2])  # field 5, length-delimited
_SECONDS_TAG = 1 << 3  # Timestamp.seconds, varint
_NANOS_TAG = 2 << 3  # Timestamp.nanos, varint


def vote_sign_bytes_frame(
    chain_id: str,
    msg_type: int,
    height: int,
    round_: int,
    block_id: CanonicalBlockID | None,
) -> tuple[bytes, bytes]:
    """What every vote of one (type, height, round, block_id, chain)
    shares: the encoded fields before the timestamp and the encoded
    fields after it."""
    prefix = _CanonicalVotePrefix(
        type=msg_type, height=height, round=round_, block_id=block_id
    ).encode()
    suffix = _CanonicalVoteSuffix(chain_id=chain_id).encode()
    return prefix, suffix


def make_vote_sign_bytes_batch(
    chain_id: str,
    msg_type: int,
    height: int,
    round_: int,
    block_id: CanonicalBlockID | None,
):
    """Returns sign_bytes(timestamp) closing over the once-encoded
    prefix (fields 1-4) and suffix (chain_id): only the ~13-byte
    timestamp message re-encodes per signature.  The per-row form of
    vote_sign_bytes_columns below; byte-identical to vote_sign_bytes
    (differential-tested)."""
    prefix, suffix = vote_sign_bytes_frame(
        chain_id, msg_type, height, round_, block_id
    )

    def sign_bytes(timestamp: Timestamp) -> bytes:
        ts_payload = timestamp.encode()
        body = (
            prefix
            + _TS_TAG
            + encode_varint(len(ts_payload))
            + ts_payload
            + suffix
        )
        return encode_varint(len(body)) + body

    return sign_bytes


# 2^0, 2^7, ..., 2^63: a uint64 takes as many varint bytes as it reaches
_VARINT_STEPS = np.array([1 << s for s in range(0, 64, 7)], dtype=np.uint64)


def _varint_widths(u: np.ndarray) -> np.ndarray:
    """Bytes each uint64 takes as a varint, 0 for 0 (proto3 leaves a
    zero scalar out, tag and all)."""
    return np.searchsorted(_VARINT_STEPS, u, side="right")


def _put_varints(buf: np.ndarray, col: int, u: np.ndarray, width: int) -> None:
    """Write the uint64s ``u``, all ``width`` bytes wide as varints,
    into columns col..col+width of the rows of ``buf``."""
    shifts = np.arange(0, 7 * width, 7, dtype=np.uint64)
    b = (u >> shifts[:, None]).astype(np.uint8)  # one row a varint byte
    b &= 0x7F
    b[:-1] |= 0x80  # continuation bit on every byte but the last
    buf[:, col:col + width] = b.T


def _encode_rows(
    frame: tuple[bytes, bytes], sec_u: np.ndarray, sec_w: int,
    nano_u: np.ndarray, nano_w: int,
) -> list[bytes]:
    """The sign-bytes of rows that share a frame and the varint widths
    of their seconds and nanos (0: the field is left out): one template
    row, repeated, and the two varints written down their columns."""
    prefix, suffix = frame
    ts = (bytes([_SECONDS_TAG]) + bytes(sec_w) if sec_w else b"") + (
        bytes([_NANOS_TAG]) + bytes(nano_w) if nano_w else b""
    )
    body = prefix + _TS_TAG + encode_varint(len(ts)) + ts + suffix
    row = encode_varint(len(body)) + body
    buf = np.empty((len(sec_u), len(row)), np.uint8)
    buf[:] = np.frombuffer(row, np.uint8)
    col = len(row) - len(suffix) - len(ts)
    if sec_w:
        _put_varints(buf, col + 1, sec_u, sec_w)
        col += 1 + sec_w
    if nano_w:
        _put_varints(buf, col + 1, nano_u, nano_w)
    # one bytes object a row: a void scalar's tolist() is its bytes
    return buf.view(f"V{len(row)}").ravel().tolist()


def vote_sign_bytes_columns(
    frames: list[tuple[bytes, bytes]], kinds, seconds, nanos
) -> list[bytes] | None:
    """The sign-bytes of many votes in one numpy pass: row i is the
    vote of frame ``frames[kinds[i]]`` (vote_sign_bytes_frame) with the
    timestamp (seconds[i], nanos[i]); byte-identical to vote_sign_bytes
    row for row (differential-tested).

    Rows are grouped by (frame, varint width of seconds, of nanos): a
    group's rows have one length and differ only in the timestamp's
    varint bytes (_encode_rows).  A real commit has a handful of groups
    (seconds are five bytes wide until 2106, nanos one to five); the
    cost does not depend on whether the timestamps are equal.

    Returns None where the columns cannot hold the input, i.e. seconds
    or nanos are not all int64 (a Python int beyond 64 bits, a bool, a
    float): the caller encodes such a commit row by row."""
    kinds = np.asarray(kinds)
    seconds = np.asarray(seconds)
    nanos = np.asarray(nanos)
    if len(kinds) == 0:
        return []
    if seconds.dtype != np.int64 or nanos.dtype != np.int64:
        return None
    sec_u = seconds.view(np.uint64)  # two's complement, like encode_varint
    nano_u = nanos.view(np.uint64)
    key = (kinds * 11 + _varint_widths(sec_u)) * 11 + _varint_widths(nano_u)
    out: list = [None] * len(kinds)
    for k in np.unique(key).tolist():
        at = np.flatnonzero(key == k)
        chunks = _encode_rows(
            frames[k // 121], sec_u[at], k // 11 % 11, nano_u[at], k % 11
        )
        for r, chunk in zip(at.tolist(), chunks):
            out[r] = chunk
    return out


def proposal_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    pol_round: int,
    block_id: CanonicalBlockID | None,
    timestamp: Timestamp,
) -> bytes:
    """Bytes signed for a proposal (types/proposal.go:ProposalSignBytes)."""
    cp = CanonicalProposal(
        type=PROPOSAL_TYPE,
        height=height,
        round=round_,
        pol_round=pol_round,
        block_id=block_id,
        timestamp=timestamp,
        chain_id=chain_id,
    )
    return encode_delimited(cp)


def vote_extension_sign_bytes(
    chain_id: str, height: int, round_: int, extension: bytes
) -> bytes:
    """Bytes signed for a vote extension (types/vote.go:VoteExtensionSignBytes)."""
    ve = CanonicalVoteExtension(
        extension=extension, height=height, round=round_, chain_id=chain_id
    )
    return encode_delimited(ve)
