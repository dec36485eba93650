"""The plain reference the secp256k1 configurations are held to,
independent of the code under test: ECDSA over secp256k1 with SHA-256
through OpenSSL (the `cryptography` package), as upstream CometBFT's
crypto/secp256k1 verifies a consensus vote.  What OpenSSL does not rule
on is written out here by hand: the 33-byte compressed key (decoded to
its point, the encoding of SEC 1 section 2.3.3), the 64-byte r || s
signature, and the lower-S rule (s <= n/2; upstream refuses the other
half).  The sign-bytes are benchmarks/reference.py's, signed by
benchmarks/data.sign_commit as for the ed25519 sets.

Keys come from the seed (``ec.derive_private_key``) and signatures are
deterministic (RFC 6979), so a seed makes the same commits.
"""

from __future__ import annotations

import hashlib

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature,
    encode_dss_signature,
)

P = 2**256 - 2**32 - 977  # the field
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141  # the order
PUBKEY_SIZE = 33
SIGNATURE_SIZE = 64

_SIGN = ec.ECDSA(hashes.SHA256(), deterministic_signing=True)
_VERIFY = ec.ECDSA(hashes.SHA256())


class SigningKey:
    """A validator's key: a private scalar drawn from the seed, its
    compressed public key, and ``sign`` as upstream's PrivKey.Sign
    gives it (r || s, s in the lower half)."""

    def __init__(self, seed: int, tag: bytes, i: int):
        d = int.from_bytes(
            hashlib.sha256(b"%d|%s|%d" % (seed, tag, i)).digest(), "big"
        ) % (N - 1) + 1
        self._key = ec.derive_private_key(d, ec.SECP256K1())
        pub = self._key.public_key().public_numbers()
        self.public = bytes([2 | (pub.y & 1)]) + pub.x.to_bytes(32, "big")

    def sign(self, msg: bytes) -> bytes:
        r, s = decode_dss_signature(self._key.sign(msg, _SIGN))
        if s > N // 2:
            s = N - s
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")


def _public_key(pub: bytes):
    """The point of a 33-byte compressed key, or None where the bytes
    encode none: a prefix other than 2 or 3, x not below p, or x^3 + 7
    no square (p = 3 mod 4, so the root is one power)."""
    if len(pub) != PUBKEY_SIZE or pub[0] not in (2, 3):
        return None
    x = int.from_bytes(pub[1:], "big")
    if x >= P:
        return None
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        return None
    if y & 1 != pub[0] & 1:
        y = P - y
    return ec.EllipticCurvePublicNumbers(x, y, ec.SECP256K1()).public_key()


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Whether ``sig`` is validator ``pub``'s signature of ``msg``."""
    if len(sig) != SIGNATURE_SIZE:
        return False
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    if not (0 < r < N and 0 < s <= N // 2):
        return False
    key = _public_key(pub)
    if key is None:
        return False
    try:
        key.verify(encode_dss_signature(r, s), msg, _VERIFY)
    except InvalidSignature:
        return False
    return True
