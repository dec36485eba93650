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
   fused into the verify dispatch (ops/secp256k1.secp_hash_verify_batch),
   when the batch clears ``COMETBFT_TPU_SECP_HASH_DEVICE_MIN`` and
   every message fits ``COMETBFT_TPU_SECP_HASH_MAX_LEN``; the host
   hash loop otherwise (the hashing-residency seam,
   docs/verify_service.md).
2. data half: range + low-s checks, s^-1 and the affine normalization
   (Montgomery batch inversion), u1*G + u2*Q — the GLV endomorphism
   quad-scalar walk by default, the plain Shamir witness under
   ``COMETBFT_TPU_SECP_GLV=0`` — and the x(R') mod n == r /
   Ecrecover-parity / recovered-address verdict — on device
   (ops/secp256k1.secp_verify_batch) when the batch clears
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

The device programs are compiled ahead of their first call, one per
(bucket, hash route, glv, recover), through models/verifier.program_for:
a batch that waits for its program's compile (minutes at a new bucket)
says so to the verify service, which stops the batch's collect and
failover clocks for that long.  :func:`warm` compiles and runs the
program a validator set's commits will take, for a node's set-up.
"""

from __future__ import annotations

import hashlib
import threading

import numpy as np

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


# compiled device programs, one per (hash route, glv, recover, input
# shapes): the bucket is the shapes' leading axis
_PROGRAMS: dict[tuple, object] = {}
_PROGRAMS_MTX = threading.Lock()


def _program(args: tuple, hash_dev: bool, glv: bool, recover: bool,
             on_compile=None):
    """The program for these inputs, compiled ahead of its first call
    (models/verifier.program_for: ``on_compile`` is told when a batch
    waits for its compile) and counted in
    ``cometbft_verify_secp_program_cache_total{result=hit|compile}``.
    The jitted functions' names are the XLA modules': a profile shows
    ``jit_secp_hash_verify_batch`` or ``jit_secp_verify_batch``."""
    import jax

    from ..ops import secp256k1 as dev
    from .verifier import program_for

    compiled = []

    def lower():
        compiled.append(True)
        fn = (
            jax.jit(dev.secp_hash_verify_batch,
                    static_argnames=("glv", "recover"))
            if hash_dev else
            jax.jit(dev.secp_verify_batch, static_argnames=("glv", "recover"))
        )
        return fn.lower(
            *(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in args),
            glv=glv, recover=recover,
        )

    key = (hash_dev, glv, recover,
           tuple((x.shape, np.dtype(x.dtype).str) for x in args))
    prog = program_for(_PROGRAMS, _PROGRAMS_MTX, key, lower, on_compile)
    _mhub().verify_secp_program_cache.inc(
        result="compile" if compiled else "hit")
    return prog


def _hash_on_device(items) -> bool:
    """Whether a device batch of ``items`` hashes its messages on the
    device (fused into the verify program) or on the host: wide enough
    to amortise it (COMETBFT_TPU_SECP_HASH_DEVICE_MIN) and every message
    within the padded block shape the program compiles for
    (COMETBFT_TPU_SECP_HASH_MAX_LEN)."""
    hmin = envknobs.get_int(envknobs.SECP_HASH_DEVICE_MIN)
    hmax = envknobs.get_int(envknobs.SECP_HASH_MAX_LEN)
    return (
        hmin > 0
        and len(items) >= hmin
        and all(len(msg) <= hmax for (_, msg, _) in items)
    )


def _assemble(items, hash_dev: bool) -> tuple[tuple, bool]:
    """The program's inputs for ``items``, padded to the bucket: the
    pubkeys decoded (cached), the wire-shape gates, r and s as limbs,
    and either the padded message blocks (device hash) or the digests'
    limbs (host hash).  Rows that fail a host gate stay valid=False.
    Returns (inputs, recover): whether any row is an ecrecover row,
    which the program is traced for."""
    from ..ops import secp256k1 as dev

    n = len(items)
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
    keys = (qx, qy, valid)
    sig_cols = (r, s, is_eth, v, is_rec, addr, dev.g_table())
    recover = bool(is_rec.any())
    if not hash_dev:
        return keys + (e,) + sig_cols, recover
    from ..ops import keccak as kops
    from ..ops import sha2 as sops

    hmax = envknobs.get_int(envknobs.SECP_HASH_MAX_LEN)
    sha_blocks, sha_active = sops.pad_messages_sha256(msgs, max_len=hmax)
    kec_blocks, kec_active = kops.pad_messages_keccak(msgs, max_len=hmax)
    blocks = (sha_blocks, sha_active, kec_blocks, kec_active)
    return blocks + keys + sig_cols, recover


def _verify_items(items, use_device: bool,
                  on_compile=None) -> tuple[bool, list[bool]]:
    """The ONE verdict procedure both verifier classes run;
    ``use_device`` only moves the batch's field/group arithmetic.  A
    device verifier's batch under COMETBFT_TPU_SECP_DEVICE_MIN is
    verified on the host, counted and spanned as
    models/verifier.host_route counts the ed25519 lanes'."""
    n = len(items)
    if n == 0:
        return (False, [])
    hub = _mhub()
    if not use_device or n < _device_min():
        if not use_device:
            res = [_host_verify_one(*it) for it in items]
        else:
            hub.verify_host_route.inc(lane="secp", reason="below_batch_min")
            with tracing.span("verify.host_route"):
                res = [_host_verify_one(*it) for it in items]
        hub.verify_secp_rows.inc(n, where="host", hash="host")
        return (all(res) and bool(res), res)

    hash_dev = _hash_on_device(items)
    hash_where = "device" if hash_dev else "host"
    labels = (
        {"rows": n, "bucket": _bucket(n), "hash": hash_where}
        if tracing.enabled() else None
    )
    with tracing.phase("verify.secp_assemble", "secp_assembly", labels):
        args, recover = _assemble(items, hash_dev)
    glv = envknobs.get_bool(envknobs.SECP_GLV)
    prog = _program(args, hash_dev, glv, recover, on_compile)
    labels = (
        {"sigs": n, "bucket": _bucket(n), "where": "device",
         "hash": hash_where}
        if tracing.enabled() else None
    )
    with tracing.phase("verify.secp_batch", "secp_device", labels):
        out = prog(*args)  # device dispatch is asynchronous
        with tracing.phase("verify.device_wait", "device_wait", wait=True):
            ok = np.asarray(out)[:n]
    hub.verify_secp_rows.inc(n, where="device", hash=hash_where)
    res = ok.tolist()
    return (all(res) and bool(res), res)


# a compressed key of the right length that decodes to no curve point
# (x = 0: 7 is no square mod p): warm()'s rows are judged False
_WARM_PUB = b"\x02" + bytes(32)


def warm(rows: int, msg_len: int, on_compile=None):
    """Compile, and run once, the program that a device batch of
    ``rows`` rows whose longest message is ``msg_len`` bytes takes: its
    bucket and its hash route are chosen as a real batch's are.  For a
    node's set-up at the first sight of a secp256k1 validator set (175
    validators sign about 110 bytes of precommit each), so that the
    first commit does not wait for a compile of minutes; the compile is
    announced to ``on_compile`` as a batch's is.  It verifies nothing,
    so it leaves no span and counts no row.  Returns (bucket, hash
    route), or None where such a batch is verified on the host and no
    program is needed."""
    if rows < _device_min():
        return None
    items = [(_WARM_PUB, bytes(msg_len), bytes(COSMOS_SIG))] * rows
    hash_dev = _hash_on_device(items)
    args, recover = _assemble(items, hash_dev)
    glv = envknobs.get_bool(envknobs.SECP_GLV)
    prog = _program(args, hash_dev, glv, recover, on_compile)
    np.asarray(prog(*args))  # the first run loads the program
    return _bucket(rows), "device" if hash_dev else "host"


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
    (ops/secp256k1.secp_verify_batch) above COMETBFT_TPU_SECP_DEVICE_MIN
    rows, the host loop below it.

    ``_entry = None`` routes submit() through the verify service's
    class-priority host worker (assembly and any cold bucket-shape
    compile are real submit-time work that must never run on the
    scheduler thread).  The ticket is synchronous: a wedged device
    inside the kernel parks the host worker, where the health
    sentinel's trip re-verifies the tracked batch on host."""

    _entry = None
    _fallback = None
    # told when this batch's program starts and stops compiling
    # (program_for); the verify service sets it per dispatched batch
    on_compile = None

    def __init__(self) -> None:
        self._items: list[tuple[bytes, bytes, bytes]] = []

    def __len__(self) -> int:
        return len(self._items)

    def add(self, pub_key: bytes, msg: bytes, sig: bytes) -> None:
        _check_item(pub_key, msg, sig)
        self._items.append((pub_key, msg, sig))

    def submit(self):
        return ("sync", _verify_items(
            self._items, use_device=True, on_compile=self.on_compile))

    def collect(self, ticket) -> tuple[bool, list[bool]]:
        return ticket[1]

    def verify(self) -> tuple[bool, list[bool]]:
        return self.collect(self.submit())
