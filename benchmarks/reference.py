"""The plain reference the configurations are held to, independent of
the code under test: ed25519 through OpenSSL (the `cryptography`
package) and the canonical precommit sign-bytes of CometBFT written out
by hand from proto/cometbft/types/v1/canonical.proto.

The benchmark makes its keys, sign-bytes and signatures with this file
alone; the program only ever verifies them.  So a program whose own
sign-bytes drifted from the protocol's refuses every commit, and a
program whose verdicts drifted from OpenSSL's fails the vector check.
"""

from __future__ import annotations

import hashlib
import struct

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
    Ed25519PublicKey,
)
from cryptography.hazmat.primitives.serialization import (
    Encoding,
    PublicFormat,
)

PRECOMMIT_TYPE = 2


def private_key(seed: int, tag: bytes, i: int) -> Ed25519PrivateKey:
    return Ed25519PrivateKey.from_private_bytes(
        hashlib.sha256(b"%d|%s|%d" % (seed, tag, i)).digest()
    )


def public_bytes(key: Ed25519PrivateKey) -> bytes:
    return key.public_key().public_bytes(Encoding.Raw, PublicFormat.Raw)


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True


def _varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _bytes_field(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def precommit_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    block_hash: bytes,
    parts_total: int,
    parts_hash: bytes,
    seconds: int,
    nanos: int = 0,
) -> bytes:
    """CanonicalVote for a precommit FOR a block, length-delimited:
    type (1, varint), height and round (2, 3, sfixed64, left out at 0),
    block_id (4: hash 1, part_set_header 2: total 1, hash 2), timestamp
    (5, always written), chain_id (6)."""
    parts = (_varint(1 << 3) + _varint(parts_total) if parts_total else b"")
    parts += _bytes_field(2, parts_hash) if parts_hash else b""
    block_id = (_bytes_field(1, block_hash) if block_hash else b"")
    block_id += _bytes_field(2, parts)
    ts = (_varint(1 << 3) + _varint(seconds) if seconds else b"")
    ts += _varint(2 << 3) + _varint(nanos) if nanos else b""
    body = _varint(1 << 3) + _varint(PRECOMMIT_TYPE)
    if height:
        body += _varint(2 << 3 | 1) + struct.pack("<q", height)
    if round_:
        body += _varint(3 << 3 | 1) + struct.pack("<q", round_)
    body += _bytes_field(4, block_id) + _bytes_field(5, ts)
    body += _bytes_field(6, chain_id.encode())
    return _varint(len(body)) + body
