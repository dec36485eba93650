"""Batched Keccak-256 on device (the pre-standardization Ethereum
variant: original 0x01 multi-rate padding, NOT SHA3-256's 0x06 —
bit-identical to the host reference crypto/keccak.py).

At firehose ingest rates the per-tx host hash loop in
models/secp_verifier is the wall the FPGA verification-engine study
(PAPERS.md arXiv:2112.02229) pipelines away; this kernel moves the
Keccak-256 half of the secp lane's message hashing onto the device so
the fused hash->verify dispatch (ops/secp256k1.secp_hash_verify_batch) never
touches the host between payload bytes and verdict.

Layout: the 5x5x64-bit Keccak state rides as TWO (..., 25) uint32
arrays (hi, lo) — 64-bit integers are FORBIDDEN on TPU (see
analysis/kernel_manifest.FORBIDDEN_DTYPES); every step is XOR/AND/NOT/
static-rotate, so the split costs two ops per logical one and no
carries (unlike sha2's (hi, lo) adds).  Flat lane index l = x + 5*y
matches the host absorb order (lane i of a block lands at a[i%5][i//5],
which IS flat index i).  The 24 rounds run as ONE lax.fori_loop body
(round constants indexed dynamically), so the jaxpr stays O(1) in
rounds (range contract: the whole state plane is uint32 XOR/AND/NOT/
rotate — wrap-defined, no signed overflow surface — and every shift
amount is a host constant; certificate ``keccak256_blocks`` in
analysis/range_fingerprints.json pins the proof, and the
unchecked-shift-width linter check keeps the amounts static); the
rho/pi lane permutation is statically unrolled inside the
body (fixed per-lane offsets).

Multi-block messages use the same blocks+active contract as
ops/sha2.sha256_blocks: a static Python loop over the padded block
axis, rows with fewer live blocks stop updating state after their own
final block.
"""

from __future__ import annotations

import threading

import numpy as np

import jax.numpy as jnp
from jax import lax

from ..crypto.keccak import _RC, _ROT

RATE = 136  # 1088-bit rate for 256-bit output; 17 lanes absorbed/block

# round constants split into uint32 halves for the (hi, lo) state
_RC_HI = np.array([rc >> 32 for rc in _RC], dtype=np.uint32)
_RC_LO = np.array([rc & 0xFFFFFFFF for rc in _RC], dtype=np.uint32)

# rho+pi as flat permutation: output lane dst absorbs input lane
# src = x + 5*y rotated by _ROT[x][y]; dst = y + 5*((2x + 3y) % 5)
_PI_DST = np.zeros(25, dtype=np.int64)
_RHO_N = np.zeros(25, dtype=np.int64)
for _x in range(5):
    for _y in range(5):
        _PI_DST[_x + 5 * _y] = _y + 5 * ((2 * _x + 3 * _y) % 5)
        _RHO_N[_x + 5 * _y] = _ROT[_x][_y]


def _rol(h, l, n: int):
    """Rotate the (hi, lo) uint32 pair left by STATIC n in [0, 64)."""
    n %= 64
    if n == 0:
        return h, l
    if n >= 32:
        h, l, n = l, h, n - 32
        if n == 0:
            return h, l
    rh = lax.shift_left(h, np.uint32(n)) | lax.shift_right_logical(
        l, np.uint32(32 - n)
    )
    rl = lax.shift_left(l, np.uint32(n)) | lax.shift_right_logical(
        h, np.uint32(32 - n)
    )
    return rh, rl


def _keccak_f(hi, lo):
    """One Keccak-f[1600] permutation over (..., 25) uint32 halves —
    24 rounds as a fori_loop (round constants indexed by the loop
    counter; everything else in the body is static)."""
    rc_hi = jnp.asarray(_RC_HI)
    rc_lo = jnp.asarray(_RC_LO)

    def round_body(t, carry):
        hi, lo = carry
        # theta: c[x] = xor_y a[x][y]; the (..., 5, 5) view is [y][x]
        h5 = hi.reshape(hi.shape[:-1] + (5, 5))
        l5 = lo.reshape(lo.shape[:-1] + (5, 5))
        ch = h5[..., 0, :] ^ h5[..., 1, :] ^ h5[..., 2, :] ^ h5[..., 3, :] ^ h5[..., 4, :]
        cl = l5[..., 0, :] ^ l5[..., 1, :] ^ l5[..., 2, :] ^ l5[..., 3, :] ^ l5[..., 4, :]
        # d[x] = c[x-1] ^ rol(c[x+1], 1)
        r1h, r1l = _rol(jnp.roll(ch, -1, axis=-1), jnp.roll(cl, -1, axis=-1), 1)
        dh = jnp.roll(ch, 1, axis=-1) ^ r1h
        dl = jnp.roll(cl, 1, axis=-1) ^ r1l
        h5 = h5 ^ dh[..., None, :]
        l5 = l5 ^ dl[..., None, :]
        hi = h5.reshape(hi.shape)
        lo = l5.reshape(lo.shape)
        # rho + pi: static per-lane rotate into the permuted position
        bh = [None] * 25
        bl = [None] * 25
        for src in range(25):
            rh, rl = _rol(hi[..., src], lo[..., src], int(_RHO_N[src]))
            bh[int(_PI_DST[src])] = rh
            bl[int(_PI_DST[src])] = rl
        hi = jnp.stack(bh, axis=-1)
        lo = jnp.stack(bl, axis=-1)
        # chi: a[x][y] = b[x][y] ^ (~b[x+1][y] & b[x+2][y]) over x
        h5 = hi.reshape(hi.shape[:-1] + (5, 5))
        l5 = lo.reshape(lo.shape[:-1] + (5, 5))
        h5 = h5 ^ (~jnp.roll(h5, -1, axis=-1) & jnp.roll(h5, -2, axis=-1))
        l5 = l5 ^ (~jnp.roll(l5, -1, axis=-1) & jnp.roll(l5, -2, axis=-1))
        hi = h5.reshape(hi.shape)
        lo = l5.reshape(lo.shape)
        # iota
        hi = hi.at[..., 0].set(hi[..., 0] ^ rc_hi[t])
        lo = lo.at[..., 0].set(lo[..., 0] ^ rc_lo[t])
        return hi, lo

    return lax.fori_loop(0, 24, round_body, (hi, lo))


def _lanes(block):
    """(..., 136) uint8 block -> little-endian (hi, lo) uint32 lane
    halves, each (..., 17)."""
    b = block.astype(jnp.uint32).reshape(block.shape[:-1] + (17, 8))
    lo = (
        b[..., 0]
        | lax.shift_left(b[..., 1], np.uint32(8))
        | lax.shift_left(b[..., 2], np.uint32(16))
        | lax.shift_left(b[..., 3], np.uint32(24))
    )
    hi = (
        b[..., 4]
        | lax.shift_left(b[..., 5], np.uint32(8))
        | lax.shift_left(b[..., 6], np.uint32(16))
        | lax.shift_left(b[..., 7], np.uint32(24))
    )
    return hi, lo


def keccak256_blocks(blocks, active_blocks=None):
    """(..., nblocks, 136) uint8 padded message -> (..., 32) uint8 digest.

    active_blocks: optional (...,) int32 per-row live block count (rows
    with shorter messages stop updating state after their own final
    block — Keccak padding is per-message while the array shape is
    static; the sha2.sha256_blocks contract).

    Manifest kernel ``keccak256_blocks`` (jitted via
    ops/secp256k1.secp_hash_verify_batch and keccak256_device).
    """
    nblocks = blocks.shape[-2]
    hi = jnp.zeros(blocks.shape[:-2] + (25,), dtype=jnp.uint32)
    lo = jnp.zeros_like(hi)
    for blk in range(nblocks):
        lh, ll = _lanes(blocks[..., blk, :])
        ah = jnp.concatenate([hi[..., :17] ^ lh, hi[..., 17:]], axis=-1)
        al = jnp.concatenate([lo[..., :17] ^ ll, lo[..., 17:]], axis=-1)
        nh, nl = _keccak_f(ah, al)
        if active_blocks is None:
            hi, lo = nh, nl
        else:
            live = (active_blocks > blk)[..., None]
            hi = jnp.where(live, nh, hi)
            lo = jnp.where(live, nl, lo)
    return squeeze_bytes(hi, lo)


def squeeze_bytes(hi, lo):
    """First 4 state lanes -> (..., 32) uint8 digest (little-endian per
    lane, the host squeeze order).  Split out so the fused secp kernel
    can squeeze a state it permuted itself."""
    out = []
    for lane in range(4):
        for half in (lo[..., lane], hi[..., lane]):
            for s in (0, 8, 16, 24):
                out.append(
                    lax.shift_right_logical(half, np.uint32(s)).astype(jnp.uint8)
                )
    return jnp.stack(out, axis=-1)


# ------------------------------------------------------------ host bridge


_KECCAK_JIT = None
_JIT_MTX = threading.Lock()


def keccak256_device(blocks, active=None) -> np.ndarray:
    """One device dispatch of the batched Keccak kernel over padded
    host blocks; the blocking result fetch is this bridge's declared
    collect point (analysis/kernel_manifest.COLLECT_BOUNDARIES)."""
    import jax

    global _KECCAK_JIT
    if _KECCAK_JIT is None:
        with _JIT_MTX:
            if _KECCAK_JIT is None:
                _KECCAK_JIT = jax.jit(keccak256_blocks)
    if active is None:
        active = np.full(blocks.shape[:-2], blocks.shape[-2], np.int32)
    return np.asarray(_KECCAK_JIT(jnp.asarray(blocks), jnp.asarray(active)))


def pad_messages_keccak(msgs: list[bytes], max_len: int | None = None):
    """Host: variable-length messages -> (buf, active) for
    keccak256_blocks.  Original Keccak pad10*1 (0x01 ... 0x80; the two
    bytes XOR into 0x81 when the padding is a single byte)."""
    n = len(msgs)
    longest = max((len(m) for m in msgs), default=0)
    if max_len is not None:
        longest = max(longest, max_len)
    nblocks = max(1, longest // RATE + 1)
    buf = np.zeros((n, nblocks * RATE), dtype=np.uint8)
    active = np.zeros(n, dtype=np.int32)
    for i, m in enumerate(msgs):
        ln = len(m)
        nb = ln // RATE + 1
        active[i] = nb
        buf[i, :ln] = np.frombuffer(m, dtype=np.uint8)
        buf[i, ln] ^= 0x01
        buf[i, nb * RATE - 1] ^= 0x80
    return buf.reshape(n, nblocks, RATE), active
