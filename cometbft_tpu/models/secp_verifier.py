"""Batched secp256k1/ECDSA verifiers — the MODE_SECP data plane behind
the verify-service seam (verifysvc/service.MODE_SECP).

This is the lane real user traffic uses (ROADMAP item 4; PAPERS.md
arXiv:2112.02229): Ethereum-shaped CheckTx ingest is, by transaction
volume, the biggest workload class, and its signatures are ECDSA over
secp256k1 — Cosmos-style (33-byte compressed pubkey, 64-byte r||s over
SHA-256, ``crypto/secp256k1``), Ethereum-style (65-byte uncompressed
pubkey, 65-byte R||S||V over Keccak-256, ``crypto/secp256k1eth``), or
true ecrecover (20-byte sender ADDRESS, 65-byte R||S||V — no pubkey on
the wire at all; the verifier recovers the signer and compares the
derived address, ``crypto/secp256k1eth.verify_address_signature``).
One lane serves all three: rows are told apart by their pubkey length,
exactly as the host modules are told apart by their wire shapes.

Verdict procedure (identical on every path — the bit-identity contract
the failover/remote fallbacks inherit, same shape as models/bls_verifier):

1. host half: the pubkey encoding decodes (compressed decompression /
   uncompressed parse; cached per key — decoding costs a field sqrt;
   ecrecover rows skip decode, their "pubkey" is the target address),
   the signature has the right length for the key's wire format, and
   the message hash (SHA-256 / Keccak-256) is computed — ON DEVICE,
   fused into the verify dispatch (ops/secp256k1.hash_verify_batch),
   when the batch clears ``COMETBFT_TPU_SECP_HASH_DEVICE_MIN`` and
   every message fits ``COMETBFT_TPU_SECP_HASH_MAX_LEN``; the host
   hash loop otherwise (the hashing-residency seam,
   docs/verify_service.md).
2. data half: range + low-s checks, s^-1 and the affine normalization
   (Montgomery batch inversion), u1*G + u2*Q — the GLV endomorphism
   quad-scalar walk by default, the plain Shamir witness under
   ``COMETBFT_TPU_SECP_GLV=0`` — and the x(R') mod n == r /
   Ecrecover-parity / recovered-address verdict — on device
   (ops/secp256k1.verify_batch) when the batch clears
   ``COMETBFT_TPU_SECP_DEVICE_MIN``, on host (the crypto modules'
   own ``verify_signature``) otherwise.  The kernel is constructed to
   be bit-identical to the host lane in every edge
   (tests/test_secp_ops.py pins it over an adversarial corpus).

Unlike BLS there is no aggregate claim: rows are independent, so
MODE_SECP batches COALESCE in the scheduler like plain ed25519 ones
(same-mode requests only) and blame is exactly per-row.

Split of labor: ``CpuSecpBatchVerifier`` is pure host (never imports
jax — the PR-8 failover / PR-13 breaker fallback path);
``TpuSecpBatchVerifier`` routes the batch through the ops/secp256k1
kernel.  Both are DATA PLANE only: production consumers reach them
through the verify service.
"""

from __future__ import annotations

import hashlib
import threading

from ..crypto import secp256k1 as host_secp
from ..crypto import secp256k1eth as host_eth
from ..crypto.keccak import keccak256
from ..utils import envknobs, tracing
from ..utils.metrics import hub as _mhub
from .bls_verifier import _FactCache

COSMOS_PUB = host_secp.PUBKEY_SIZE  # 33: compressed
COSMOS_SIG = host_secp.SIGNATURE_SIZE  # 64: r || s
ETH_PUB = host_eth.PUBKEY_SIZE  # 65: 0x04 || x || y
ETH_SIG = host_eth.SIGNATURE_SIZE  # 65: R || S || V
ECR_PUB = host_eth.ADDRESS_SIZE  # 20: sender address (no pubkey on wire)
ECR_SIG = host_eth.SIGNATURE_SIZE  # 65: R || S || V

_MISS = object()

# pubkey bytes -> affine (x, y) int pair | None (malformed encoding).
# Decoding a compressed key costs one field sqrt (~pow mod p); CheckTx
# ingest repeats senders, so the fact caches like the BLS lane's.
_PK_CACHE: _FactCache | None = None
_PK_CACHE_MTX = threading.Lock()


def _pk_cache() -> _FactCache:
    global _PK_CACHE
    if _PK_CACHE is None:
        with _PK_CACHE_MTX:
            if _PK_CACHE is None:
                _PK_CACHE = _FactCache(
                    max(0, envknobs.get_int(envknobs.SECP_PUBKEY_CACHE))
                )
    return _PK_CACHE


def reset_caches() -> None:
    """Tests and the bench's cold rounds: drop every cached decode (and
    re-read the cache-size knob on next use)."""
    global _PK_CACHE
    _PK_CACHE = None


def _decode_pub(pub: bytes):
    """Pubkey bytes -> affine (x, y) int pair, or None for malformed /
    wrong-length encodings.  Cache-backed; decoding is a per-key FACT
    (same value on every path), so caching can never split verdicts."""
    cache = _pk_cache()
    hit = cache.get(pub, _MISS)
    if hit is not _MISS:
        _mhub().secp_pubkey_cache.inc(result="hit")
        return hit
    _mhub().secp_pubkey_cache.inc(result="miss")
    aff = None
    try:
        if len(pub) == COSMOS_PUB:
            aff = host_secp._decompress(pub)
        elif len(pub) == ETH_PUB:
            aff = host_eth._parse_uncompressed(pub)
    except ValueError:
        aff = None
    cache.put(pub, aff)
    return aff


def _host_verify_one(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """The pure-host verdict oracle: EXACTLY the crypto modules' own
    key-construction + verify gauntlet, selected by pubkey length.
    Malformed anything judges False — a fallback re-verify must never
    raise out of the service's worker loops."""
    try:
        if len(pub) == COSMOS_PUB:
            return host_secp.PubKey(pub).verify_signature(msg, sig)
        if len(pub) == ETH_PUB:
            return host_eth.PubKey(pub).verify_signature(msg, sig)
        if len(pub) == ECR_PUB:
            return host_eth.verify_address_signature(pub, msg, sig)
    except ValueError:
        return False
    return False


def _device_min() -> int:
    return max(1, envknobs.get_int(envknobs.SECP_DEVICE_MIN))


def _bucket(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


def _verify_items(items, use_device: bool) -> tuple[bool, list[bool]]:
    """The ONE verdict procedure both verifier classes run;
    ``use_device`` only moves the batch's field/group arithmetic."""
    n = len(items)
    if n == 0:
        return (False, [])
    if not use_device or n < _device_min():
        res = [_host_verify_one(p, m, s) for (p, m, s) in items]
        return (all(res) and bool(res), res)

    import time as _time

    import numpy as np

    from ..ops import secp256k1 as dev

    t0 = _time.perf_counter()
    b = _bucket(n)
    qx = np.zeros((b, dev.NLIMBS), dtype=np.int32)
    qy = np.zeros((b, dev.NLIMBS), dtype=np.int32)
    valid = np.zeros((b,), dtype=bool)
    e = np.zeros((b, dev.NLIMBS), dtype=np.int32)
    r = np.zeros((b, dev.NLIMBS), dtype=np.int32)
    s = np.zeros((b, dev.NLIMBS), dtype=np.int32)
    is_eth = np.zeros((b,), dtype=bool)
    v = np.zeros((b,), dtype=np.int32)
    is_rec = np.zeros((b,), dtype=bool)
    addr = np.zeros((b, ECR_PUB), dtype=np.uint8)

    # hashing residency: fuse SHA-256/Keccak-256 into the device
    # dispatch when the batch is wide enough to amortize it and every
    # message fits the padded block shape the program compiled for
    hmin = envknobs.get_int(envknobs.SECP_HASH_DEVICE_MIN)
    hmax = envknobs.get_int(envknobs.SECP_HASH_MAX_LEN)
    hash_dev = (
        hmin > 0
        and n >= hmin
        and all(len(msg) <= hmax for (_, msg, _) in items)
    )
    msgs: list[bytes] = [b""] * b

    qxs, qys, es, rs, ss, rows = [], [], [], [], [], []
    for i, (pub, msg, sig) in enumerate(items):
        eth = len(pub) == ETH_PUB
        rec = len(pub) == ECR_PUB
        if rec:
            # no pubkey on the wire: the kernel recovers the signer and
            # compares the derived address — nothing to decode or cache
            if len(sig) != ECR_SIG:
                continue
            aff = (0, 0)
            addr[i] = np.frombuffer(pub, dtype=np.uint8)
        else:
            aff = _decode_pub(pub)
            # the signature wire shape must match the KEY's wire format
            # — the host modules' own length gate
            sig_len = ETH_SIG if eth else COSMOS_SIG
            if aff is None or len(sig) != sig_len:
                continue  # row stays valid=False / s=0 -> judged False
            valid[i] = True
        is_eth[i] = eth
        is_rec[i] = rec
        if eth or rec:
            v[i] = sig[64]
        if hash_dev:
            msgs[i] = msg
        else:
            h = keccak256(msg) if (eth or rec) else hashlib.sha256(msg).digest()
            es.append(int.from_bytes(h, "big"))
        qxs.append(aff[0])
        qys.append(aff[1])
        rs.append(int.from_bytes(sig[:32], "big"))
        ss.append(int.from_bytes(sig[32:64], "big"))
        rows.append(i)
    if rows:
        qx[rows] = dev.ints_to_limbs_np(qxs)
        qy[rows] = dev.ints_to_limbs_np(qys)
        r[rows] = dev.ints_to_limbs_np(rs)
        s[rows] = dev.ints_to_limbs_np(ss)
        if not hash_dev:
            e[rows] = dev.ints_to_limbs_np(es)
    glv = envknobs.get_bool(envknobs.SECP_GLV)
    m = _mhub()
    assembly_s = _time.perf_counter() - t0
    m.verify_phase_seconds.observe(assembly_s, phase="secp_assembly")
    t1 = _time.perf_counter()
    with tracing.span(
        "verify.secp_batch",
        {"sigs": n, "where": "device", "hash": "device" if hash_dev else "host"}
        if tracing.enabled() else None,
    ):
        if hash_dev:
            from ..ops import keccak as kops
            from ..ops import sha2 as sops

            sha_blocks, sha_active = sops.pad_messages_sha256(
                msgs, max_len=hmax
            )
            kec_blocks, kec_active = kops.pad_messages_keccak(
                msgs, max_len=hmax
            )
            ok = dev.hash_verify_batch_device(
                sha_blocks, sha_active, kec_blocks, kec_active,
                qx, qy, valid, r, s, is_eth, v,
                is_rec=is_rec, addr=addr, glv=glv,
            )
        else:
            ok = dev.verify_batch_device(
                qx, qy, valid, e, r, s, is_eth, v,
                is_rec=is_rec, addr=addr, glv=glv,
            )
    m.verify_phase_seconds.observe(
        _time.perf_counter() - t1, phase="secp_device"
    )
    res = [bool(x) for x in ok[:n]]
    return (all(res) and bool(res), res)


def _check_item(pub: bytes, msg: bytes, sig: bytes) -> None:
    if len(pub) not in (ECR_PUB, COSMOS_PUB, ETH_PUB) or len(sig) not in (
        COSMOS_SIG,
        ETH_SIG,
    ):
        raise ValueError("malformed secp256k1 pubkey or signature")


class CpuSecpBatchVerifier:
    """Pure-host ECDSA verification — never imports jax; the
    degraded-mode / breaker-open data plane, bit-identical to the
    device-assisted verifier by construction (the kernel replicates the
    host gauntlet edge for edge)."""

    def __init__(self) -> None:
        self._items: list[tuple[bytes, bytes, bytes]] = []

    def __len__(self) -> int:
        return len(self._items)

    def add(self, pub_key: bytes, msg: bytes, sig: bytes) -> None:
        _check_item(pub_key, msg, sig)
        self._items.append((pub_key, msg, sig))

    def verify(self) -> tuple[bool, list[bool]]:
        return _verify_items(self._items, use_device=False)


class TpuSecpBatchVerifier:
    """Device-assisted ECDSA verification: the whole range-check /
    batch-inversion / Shamir pipeline in one fused kernel dispatch
    (ops/secp256k1.verify_batch) above COMETBFT_TPU_SECP_DEVICE_MIN
    rows, the host loop below it.

    ``_entry = None`` routes submit() through the verify service's
    class-priority host worker (assembly and any cold bucket-shape
    compile are real submit-time work that must never run on the
    scheduler thread).  The ticket is synchronous: a wedged device
    inside the kernel parks the host worker, where the health
    sentinel's trip re-verifies the tracked batch on host."""

    _entry = None
    _fallback = None

    def __init__(self) -> None:
        self._items: list[tuple[bytes, bytes, bytes]] = []

    def __len__(self) -> int:
        return len(self._items)

    def add(self, pub_key: bytes, msg: bytes, sig: bytes) -> None:
        _check_item(pub_key, msg, sig)
        self._items.append((pub_key, msg, sig))

    def submit(self):
        return ("sync", _verify_items(self._items, use_device=True))

    def collect(self, ticket) -> tuple[bool, list[bool]]:
        return ticket[1]

    def verify(self) -> tuple[bool, list[bool]]:
        return self.collect(self.submit())
