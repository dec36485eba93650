"""The plain reference of light-client verification, independent of
``cometbft_tpu/light`` and ``cometbft_tpu/types``: ed25519 through
OpenSSL and the precommit sign-bytes of ``benchmarks/reference.py``, the
header hash and the validator-set hash encoded by hand from
proto/cometbft/types/v1/types.proto, validator.proto and
crypto/v1/keys.proto, the Merkle tree of RFC 6962, and the rules of
upstream ``light/verifier.go`` (Verify, VerifyAdjacent,
VerifyNonAdjacent), ``types/validation.go`` (VerifyCommitLight,
VerifyCommitLightTrusting) and ``light/client.go`` (verifySkipping).

It answers two questions:

(a) ``hop``: one hop from a trusted block to a new one: accepted, cannot
    be trusted (bisect), or refused with the reason and, for a bad
    signature, its index in the commit;
(b) ``walk``: a fresh client trusted at one height that verifies another
    by skipping: the blocks it fetches in order, every hop it tries with
    its result, the heights it ends up trusting, and how it ends.

Departures from upstream, each on purpose:

- a hop verifies every signature it counts one by one with OpenSSL and
  names the FIRST bad one in commit order: upstream's batch path gives
  the same index (validation.go:384), its sequential path would stop at
  the first bad one too;
- power is tallied before any signature is checked, as upstream's batch
  path does: too little power is "cannot be trusted" even if one of the
  counted signatures is bad;
- only what a skipping walk over full commits meets is written out:
  every commit signature is a precommit FOR the block (no absent or nil
  flag), one key type, no witness disagreement, no backwards walk, no
  pruning inside 1,000 heights;
- ``last_block_id`` is any well-formed block id (a skipping client never
  follows the hash chain), so a block can be made without its parent.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from fractions import Fraction

from . import reference
from .reference import _bytes_field, _varint

BLOCK_PROTOCOL = 11  # version/version.go BlockProtocol
NS = 1_000_000_000
MAX_CLOCK_DRIFT_NS = 10 * NS  # light/client.go defaultMaxClockDrift
SKIP = (9, 16)  # light/client.go verifySkippingNumerator / Denominator

OK = "ok"
CANT_BE_TRUSTED = "cant_be_trusted"
REFUSED = "refused"


# ------------------------------------------------------------- encodings


def _varint_field(num: int, value: int) -> bytes:
    return _varint(num << 3) + _varint(value) if value else b""


def _wrapped(payload: bytes) -> bytes:
    """gogotypes Bytes/String/Int64Value{value = 1}: nil for the zero
    value (types/encoding_helper.go cdcEncode)."""
    return _bytes_field(1, payload) if payload else b""


def sha256(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def merkle_root(leaves: list[bytes]) -> bytes:
    """RFC 6962: leaf 0x00, inner 0x01, split at the largest power of
    two under the count."""
    n = len(leaves)
    if n == 0:
        return sha256(b"")
    if n == 1:
        return sha256(b"\x00" + leaves[0])
    k = 1 << (n - 1).bit_length() - 1
    return sha256(b"\x01" + merkle_root(leaves[:k]) + merkle_root(leaves[k:]))


def address(pub: bytes) -> bytes:
    """crypto/ed25519 PubKey.Address: the first 20 bytes of SHA-256."""
    return sha256(pub)[:20]


def simple_validator(pub: bytes, power: int) -> bytes:
    """SimpleValidator{pub_key = 1: PublicKey{ed25519 = 1}, voting_power = 2}."""
    return _bytes_field(1, _bytes_field(1, pub)) + _varint_field(2, power)


def block_id_bytes(hash_: bytes, parts_total: int, parts_hash: bytes) -> bytes:
    """BlockID{hash = 1, part_set_header = 2 {total = 1, hash = 2}}; the
    part set header is not nullable and always written."""
    parts = _varint_field(1, parts_total)
    parts += _bytes_field(2, parts_hash) if parts_hash else b""
    return (_bytes_field(1, hash_) if hash_ else b"") + _bytes_field(2, parts)


# ----------------------------------------------------------------- types


@dataclass(frozen=True)
class Val:
    pub: bytes
    power: int

    @property
    def address(self) -> bytes:
        return address(self.pub)


def sorted_set(vals: list[Val]) -> list[Val]:
    """types/validator_set.go ValidatorsByVotingPower: power descending,
    then address ascending."""
    return sorted(vals, key=lambda v: (-v.power, v.address))


def valset_hash(vals: list[Val]) -> bytes:
    return merkle_root([simple_validator(v.pub, v.power) for v in vals])


@dataclass
class Header:
    chain_id: str
    height: int
    seconds: int
    last_block_id: tuple[bytes, int, bytes]
    validators_hash: bytes
    next_validators_hash: bytes
    proposer_address: bytes
    last_commit_hash: bytes = b""
    data_hash: bytes = b""
    consensus_hash: bytes = b""
    app_hash: bytes = b""
    last_results_hash: bytes = b""
    evidence_hash: bytes = b""
    app_version: int = 0

    def hash(self) -> bytes:
        """types/block.go Header.Hash: the Merkle root of the fourteen
        fields, each in its own protobuf encoding."""
        return merkle_root([
            _varint_field(1, BLOCK_PROTOCOL) + _varint_field(2, self.app_version),
            _wrapped(self.chain_id.encode()),
            _varint_field(1, self.height),  # Int64Value
            _varint_field(1, self.seconds),  # Timestamp, nanos 0
            block_id_bytes(*self.last_block_id),
            _wrapped(self.last_commit_hash),
            _wrapped(self.data_hash),
            _wrapped(self.validators_hash),
            _wrapped(self.next_validators_hash),
            _wrapped(self.consensus_hash),
            _wrapped(self.app_hash),
            _wrapped(self.last_results_hash),
            _wrapped(self.evidence_hash),
            _wrapped(self.proposer_address),
        ])


@dataclass
class Sig:
    """One precommit FOR the block."""

    address: bytes
    seconds: int
    signature: bytes


@dataclass
class Block:
    """A light block: header, the commit for it, and the set that signed."""

    header: Header
    vals: list[Val]  # in set order
    commit_height: int
    commit_block_id: tuple[bytes, int, bytes]
    sigs: list[Sig]
    # verdicts of single signatures, kept across hops and walks: a
    # signature is checked by OpenSSL once
    _verdicts: dict = field(default_factory=dict, repr=False)

    @property
    def height(self) -> int:
        return self.header.height

    def sign_bytes(self, idx: int) -> bytes:
        h, total, parts = self.commit_block_id
        return reference.precommit_sign_bytes(
            self.header.chain_id, self.commit_height, 0, h, total, parts,
            self.sigs[idx].seconds,
        )

    def sig_ok(self, idx: int, pub: bytes) -> bool:
        key = (idx, pub, self.sigs[idx].signature)
        if key not in self._verdicts:
            self._verdicts[key] = reference.verify(
                pub, self.sign_bytes(idx), self.sigs[idx].signature)
        return self._verdicts[key]


# ------------------------------------------------------------------ a hop


@dataclass(frozen=True)
class HopResult:
    kind: str  # OK, CANT_BE_TRUSTED or REFUSED
    reason: str = ""
    index: int | None = None  # of the first bad signature


def trusting_rows(trusted: Block, new: Block, level: Fraction):
    """VerifyCommitLightTrusting's tally: commit signatures in commit
    order, each looked up by address in the TRUSTED set, until more than
    ``level`` of that set's power is counted.  Returns ([(commit index,
    pubkey)], enough)."""
    by_address = {v.address: v for v in trusted.vals}
    needed = sum(v.power for v in trusted.vals) * level.numerator // level.denominator
    rows, tallied = [], 0
    for idx, s in enumerate(new.sigs):
        v = by_address.get(s.address)
        if v is None:
            continue
        rows.append((idx, v.pub))
        tallied += v.power
        if tallied > needed:
            return rows, True
    return rows, False


def commit_rows(new: Block):
    """VerifyCommitLight's tally: commit signatures by index against the
    block's own set, until more than 2/3 of its power is counted."""
    needed = sum(v.power for v in new.vals) * 2 // 3
    rows, tallied = [], 0
    for idx, v in enumerate(new.vals):
        rows.append((idx, v.pub))
        tallied += v.power
        if tallied > needed:
            return rows, True
    return rows, False


def _first_bad(block: Block, rows) -> int | None:
    return next((i for i, pub in rows if not block.sig_ok(i, pub)), None)


def _header_checks(trusted: Block, new: Block, now_ns: int, drift_ns: int):
    """verifier.go verifyNewHeaderAndVals, with SignedHeader.ValidateBasic
    cut to what can differ here."""
    h = new.header
    if h.chain_id != trusted.header.chain_id:
        return "header belongs to another chain"
    if new.commit_height != h.height:
        return "header and commit height mismatch"
    if new.commit_block_id[0] != h.hash():
        return "commit signs another header"
    if h.height <= trusted.header.height:
        return "height not greater than the trusted one"
    if h.seconds <= trusted.header.seconds:
        return "time not after the trusted header's"
    if h.seconds * NS >= now_ns + drift_ns:
        return "time from the future"
    if h.validators_hash != valset_hash(new.vals):
        return "validators_hash is not the supplied set's"
    return None


def _commit_pass(new: Block) -> HopResult:
    if len(new.sigs) != len(new.vals):
        return HopResult(REFUSED, "commit and set sizes differ")
    rows, enough = commit_rows(new)
    if not enough:
        return HopResult(REFUSED, "under 2/3 of the new set signed")
    bad = _first_bad(new, rows)
    if bad is not None:
        return HopResult(REFUSED, "wrong signature", bad)
    return HopResult(OK)


def hop(trusted: Block, new: Block, now_ns: int, period_ns: int,
        level: Fraction = Fraction(1, 3),
        drift_ns: int = MAX_CLOCK_DRIFT_NS) -> HopResult:
    """light.Verify: adjacent hops by ``next_validators_hash``, the others
    by ``level`` of the trusted set; then 2/3 of the new set."""
    if trusted.header.seconds * NS + period_ns <= now_ns:
        return HopResult(REFUSED, "trusted header expired")
    why = _header_checks(trusted, new, now_ns, drift_ns)
    if why is not None:
        return HopResult(REFUSED, why)
    if new.height == trusted.height + 1:
        if new.header.validators_hash != trusted.header.next_validators_hash:
            return HopResult(REFUSED, "not the trusted header's next validators")
        return _commit_pass(new)
    rows, enough = trusting_rows(trusted, new, level)
    if not enough:
        return HopResult(CANT_BE_TRUSTED)
    bad = _first_bad(new, rows)
    if bad is not None:
        return HopResult(REFUSED, "wrong signature", bad)
    return _commit_pass(new)


# ----------------------------------------------------------------- a walk


@dataclass
class WalkResult:
    fetched: list[int]  # heights asked of the provider, in order
    hops: list[tuple[int, int, HopResult]]  # (trusted, new, result)
    trusted: list[int]  # heights the client's store ends up holding
    ended: str  # OK, CANT_BE_TRUSTED or REFUSED
    why: str = ""


def walk(block_at, trusted_height: int, target: int, now_ns: int,
         period_ns: int, level: Fraction = Fraction(1, 3)) -> WalkResult:
    """A fresh client (client.go NewClient, then VerifyLightBlockAtHeight
    in skipping mode): trusted at ``trusted_height`` once that block's
    own commit carries 2/3 of its set, then verifySkipping with pivots
    at 9/16 of each gap that cannot be trusted.  ``block_at(h)`` is the
    provider and raises KeyError for a height it does not have."""
    out = WalkResult([trusted_height], [], [], OK)
    root = block_at(trusted_height)
    first = _commit_pass(root)
    if first.kind != OK:
        out.ended, out.why = REFUSED, f"trusted block: {first.reason}"
        return out
    out.trusted.append(trusted_height)
    out.fetched.append(target)
    cache, depth, verified = [block_at(target)], 0, root
    accepted = []
    while True:
        new = cache[depth]
        res = hop(verified, new, now_ns, period_ns, level)
        out.hops.append((verified.height, new.height, res))
        if res.kind == CANT_BE_TRUSTED:
            if depth == len(cache) - 1:
                pivot = (verified.height
                         + (new.height - verified.height) * SKIP[0] // SKIP[1])
                out.fetched.append(pivot)
                try:
                    cache.append(block_at(pivot))
                except KeyError:
                    out.ended, out.why = CANT_BE_TRUSTED, f"no block {pivot}"
                    return out
            depth += 1
            continue
        if res.kind == REFUSED:
            out.ended = REFUSED
            out.why = res.reason + (
                "" if res.index is None else f" (#{res.index})")
            return out
        accepted.append(new.height)
        if depth == 0:
            # the witness (the same provider) is asked for the target
            out.fetched.append(target)
            out.trusted += accepted
            return out
        verified, cache, depth = new, cache[:depth], 0
