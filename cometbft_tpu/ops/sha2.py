"""Vectorized SHA-256 / SHA-512 for TPU.

SHA-256 runs natively in uint32 (the TPU VPU's word size).  SHA-512 needs
64-bit words, which don't exist on TPU — each word is an (hi, lo) uint32
pair with explicit carry on addition.  Both kernels process a batch of
fixed-block-count padded messages with a lax.fori_loop over rounds (one
round body in the compiled graph) and a Python loop over the static block
count.

Host-side helpers pad variable-length messages into the fixed block layout
(numpy, vectorized) — message assembly is control-plane work; the digest
loop is the data plane.

Round constants are derived at import time from their public definition
(fractional parts of cube/square roots of the first primes) rather than
embedded as magic tables.

Reference workloads served by these kernels:
  - SHA-512: Ed25519 challenge hash k = H(R || A || M) per signature
    (crypto/ed25519 verification; RFC 8032 §5.1).
  - SHA-256: tmhash (crypto/tmhash/hash.go:22-37) and the RFC-6962 Merkle
    tree (crypto/merkle/tree.go:11, hash.go:21-44).
"""

from __future__ import annotations

from itertools import chain

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def _primes(n: int):
    out, c = [], 2
    while len(out) < n:
        if all(c % q for q in out):
            out.append(c)
        c += 1
    return out


def _icbrt(x: int) -> int:
    r = int(round(x ** (1 / 3)))
    while r * r * r > x:
        r -= 1
    while (r + 1) ** 3 <= x:
        r += 1
    return r


def _isqrt(x: int) -> int:
    import math

    return math.isqrt(x)


_P64 = _primes(80)
K512 = np.array(
    [[(v := _icbrt(p << 192) & ((1 << 64) - 1)) >> 32, v & 0xFFFFFFFF] for p in _P64],
    dtype=np.uint32,
)
H512 = np.array(
    [
        [(v := _isqrt(p << 128) & ((1 << 64) - 1)) >> 32, v & 0xFFFFFFFF]
        for p in _P64[:8]
    ],
    dtype=np.uint32,
)
K256 = np.array([_icbrt(p << 96) & 0xFFFFFFFF for p in _P64[:64]], dtype=np.uint32)
H256 = np.array([_isqrt(p << 64) & 0xFFFFFFFF for p in _P64[:8]], dtype=np.uint32)


# --------------------------------------------------------------- SHA-256


def _rotr32(x, n):
    return lax.shift_right_logical(x, np.uint32(n)) | lax.shift_left(
        x, np.uint32(32 - n)
    )


def sha256_blocks(blocks, active_blocks=None):
    """(..., nblocks, 64) uint8 padded message -> (..., 32) uint8 digest.

    active_blocks: optional (...,) int32 per-row live block count (rows with
    shorter messages stop updating state after their own final block, since
    SHA-2 padding is minimal per message while the array shape is static).

    Manifest kernel ``sha256_blocks`` (jitted via models//crypto callers).
    """
    nblocks = blocks.shape[-2]
    w0 = blocks.astype(jnp.uint32).reshape(blocks.shape[:-1] + (16, 4))
    # big-endian words
    words = (
        lax.shift_left(w0[..., 0], np.uint32(24))
        | lax.shift_left(w0[..., 1], np.uint32(16))
        | lax.shift_left(w0[..., 2], np.uint32(8))
        | w0[..., 3]
    )  # (..., nblocks, 16)
    state = jnp.broadcast_to(
        jnp.asarray(H256), blocks.shape[:-2] + (8,)
    ).astype(jnp.uint32)
    kt = jnp.asarray(K256)

    def round_body(t, carry):
        st, w = carry
        a, b, c, d, e, f, g, h = [st[..., i] for i in range(8)]
        s1 = _rotr32(e, 6) ^ _rotr32(e, 11) ^ _rotr32(e, 25)
        ch = (e & f) ^ (~e & g)
        wt = w[..., 0]
        t1 = h + s1 + ch + kt[t] + wt
        s0 = _rotr32(a, 2) ^ _rotr32(a, 13) ^ _rotr32(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        st = jnp.stack([t1 + t2, a, b, c, d + t1, e, f, g], axis=-1)
        # message schedule: w16 = σ1(w14) + w9 + σ0(w1) + w0
        w14, w9, w1, w0_ = w[..., 14], w[..., 9], w[..., 1], w[..., 0]
        sg0 = _rotr32(w1, 7) ^ _rotr32(w1, 18) ^ lax.shift_right_logical(
            w1, np.uint32(3)
        )
        sg1 = _rotr32(w14, 17) ^ _rotr32(w14, 19) ^ lax.shift_right_logical(
            w14, np.uint32(10)
        )
        w16 = sg1 + w9 + sg0 + w0_
        w = jnp.concatenate([w[..., 1:], w16[..., None]], axis=-1)
        return st, w

    for blk in range(nblocks):
        w = words[..., blk, :]
        st, _ = lax.fori_loop(0, 64, round_body, (state, w))
        new_state = state + st
        if active_blocks is None:
            state = new_state
        else:
            live = (active_blocks > blk)[..., None]
            state = jnp.where(live, new_state, state)

    b = jnp.stack(
        [
            lax.shift_right_logical(state, np.uint32(s)).astype(jnp.uint8)
            for s in (24, 16, 8, 0)
        ],
        axis=-1,
    )
    return b.reshape(state.shape[:-1] + (32,))


# --------------------------------------------------------------- SHA-512


def _rotr64(hi, lo, n):
    if n < 32:
        nh = np.uint32(n)
        inv = np.uint32(32 - n)
        rh = lax.shift_right_logical(hi, nh) | lax.shift_left(lo, inv)
        rl = lax.shift_right_logical(lo, nh) | lax.shift_left(hi, inv)
    elif n == 32:
        rh, rl = lo, hi
    else:
        m = np.uint32(n - 32)
        inv = np.uint32(64 - n)
        rh = lax.shift_right_logical(lo, m) | lax.shift_left(hi, inv)
        rl = lax.shift_right_logical(hi, m) | lax.shift_left(lo, inv)
    return rh, rl


def _shr64(hi, lo, n):
    nh = np.uint32(n)
    inv = np.uint32(32 - n)
    rh = lax.shift_right_logical(hi, nh)
    rl = lax.shift_right_logical(lo, nh) | lax.shift_left(hi, inv)
    return rh, rl


def _add64(ah, al, bh, bl):
    lo = al + bl
    # bool -> uint32 is the justified carry conversion of the (hi, lo)
    # pair representation (kernel_manifest.ALLOWED_CONVERSIONS)
    carry = (lo < al).astype(jnp.uint32)
    return ah + bh + carry, lo


def _add64_many(*pairs):
    h, l = pairs[0]
    for ph, pl in pairs[1:]:
        h, l = _add64(h, l, ph, pl)
    return h, l


@jax.named_scope("sha512")  # the phase's name in a profile, both verify programs
def sha512_blocks(blocks, active_blocks=None):
    """(..., nblocks, 128) uint8 padded message -> (..., 64) uint8 digest.

    active_blocks: optional (...,) int32 per-row live block count (see
    sha256_blocks).

    Manifest kernel ``sha512_blocks``.
    """
    nblocks = blocks.shape[-2]
    w0 = blocks.astype(jnp.uint32).reshape(blocks.shape[:-1] + (16, 8))

    def be32(b0, b1, b2, b3):
        return (
            lax.shift_left(b0, np.uint32(24))
            | lax.shift_left(b1, np.uint32(16))
            | lax.shift_left(b2, np.uint32(8))
            | b3
        )

    w_hi = be32(w0[..., 0], w0[..., 1], w0[..., 2], w0[..., 3])
    w_lo = be32(w0[..., 4], w0[..., 5], w0[..., 6], w0[..., 7])
    # (..., nblocks, 16) each

    state = jnp.broadcast_to(
        jnp.asarray(H512), blocks.shape[:-2] + (8, 2)
    ).astype(jnp.uint32)
    kt = jnp.asarray(K512)  # (80, 2)

    def round_body(t, carry):
        st, wh, wl = carry  # st: (..., 8, 2); wh/wl: (..., 16)
        ah, al = st[..., 0, 0], st[..., 0, 1]
        bh, bl = st[..., 1, 0], st[..., 1, 1]
        ch_, cl = st[..., 2, 0], st[..., 2, 1]
        dh, dl = st[..., 3, 0], st[..., 3, 1]
        eh, el = st[..., 4, 0], st[..., 4, 1]
        fh, fl = st[..., 5, 0], st[..., 5, 1]
        gh, gl = st[..., 6, 0], st[..., 6, 1]
        hh, hl = st[..., 7, 0], st[..., 7, 1]

        x1 = _rotr64(eh, el, 14)
        x2 = _rotr64(eh, el, 18)
        x3 = _rotr64(eh, el, 41)
        s1h, s1l = x1[0] ^ x2[0] ^ x3[0], x1[1] ^ x2[1] ^ x3[1]
        chh = (eh & fh) ^ (~eh & gh)
        chl = (el & fl) ^ (~el & gl)
        t1h, t1l = _add64_many(
            (hh, hl),
            (s1h, s1l),
            (chh, chl),
            (kt[t, 0], kt[t, 1]),
            (wh[..., 0], wl[..., 0]),
        )
        y1 = _rotr64(ah, al, 28)
        y2 = _rotr64(ah, al, 34)
        y3 = _rotr64(ah, al, 39)
        s0h, s0l = y1[0] ^ y2[0] ^ y3[0], y1[1] ^ y2[1] ^ y3[1]
        mjh = (ah & bh) ^ (ah & ch_) ^ (bh & ch_)
        mjl = (al & bl) ^ (al & cl) ^ (bl & cl)
        t2h, t2l = _add64(s0h, s0l, mjh, mjl)
        nah, nal = _add64(t1h, t1l, t2h, t2l)
        neh, nel = _add64(dh, dl, t1h, t1l)
        st = jnp.stack(
            [
                jnp.stack([nah, nal], axis=-1),
                jnp.stack([ah, al], axis=-1),
                jnp.stack([bh, bl], axis=-1),
                jnp.stack([ch_, cl], axis=-1),
                jnp.stack([neh, nel], axis=-1),
                jnp.stack([eh, el], axis=-1),
                jnp.stack([fh, fl], axis=-1),
                jnp.stack([gh, gl], axis=-1),
            ],
            axis=-2,
        )
        # schedule: w16 = σ1(w14) + w9 + σ0(w1) + w0
        a1 = _rotr64(wh[..., 14], wl[..., 14], 19)
        a2 = _rotr64(wh[..., 14], wl[..., 14], 61)
        a3 = _shr64(wh[..., 14], wl[..., 14], 6)
        sg1h, sg1l = a1[0] ^ a2[0] ^ a3[0], a1[1] ^ a2[1] ^ a3[1]
        b1 = _rotr64(wh[..., 1], wl[..., 1], 1)
        b2 = _rotr64(wh[..., 1], wl[..., 1], 8)
        b3 = _shr64(wh[..., 1], wl[..., 1], 7)
        sg0h, sg0l = b1[0] ^ b2[0] ^ b3[0], b1[1] ^ b2[1] ^ b3[1]
        w16h, w16l = _add64_many(
            (sg1h, sg1l),
            (wh[..., 9], wl[..., 9]),
            (sg0h, sg0l),
            (wh[..., 0], wl[..., 0]),
        )
        wh = jnp.concatenate([wh[..., 1:], w16h[..., None]], axis=-1)
        wl = jnp.concatenate([wl[..., 1:], w16l[..., None]], axis=-1)
        return st, wh, wl

    for blk in range(nblocks):
        st, _, _ = lax.fori_loop(
            0, 80, round_body, (state, w_hi[..., blk, :], w_lo[..., blk, :])
        )
        # state += st (64-bit lane-wise)
        sh, sl = _add64(
            state[..., 0], state[..., 1], st[..., 0], st[..., 1]
        )
        new_state = jnp.stack([sh, sl], axis=-1)
        if active_blocks is None:
            state = new_state
        else:
            live = (active_blocks > blk)[..., None, None]
            state = jnp.where(live, new_state, state)

    flat = state.reshape(state.shape[:-2] + (16,))  # hi,lo interleaved BE order
    b = jnp.stack(
        [
            lax.shift_right_logical(flat, np.uint32(s)).astype(jnp.uint8)
            for s in (24, 16, 8, 0)
        ],
        axis=-1,
    )
    return b.reshape(state.shape[:-2] + (64,))


# ------------------------------------------- device-side R||A||M assembly


def ram_blocks_from_parts(r, a, m, mlen, nblocks: int):
    """Assemble SHA-512-padded R || A || M blocks ON DEVICE.

    r, a    : (V, 32) uint8 — signature R half / compressed pubkey
    m       : (V, maxm) uint8 — messages, zero-padded to the static width
    mlen    : (V,) int32 — per-row live message length (<= maxm)
    nblocks : static block count; maxm + 81 <= nblocks*128 must hold

    Returns (blocks (V, nblocks, 128) uint8, active (V,) int32).  The host
    used to ship fully padded 128-byte blocks per row (64 bytes of R+A
    repeated, zero padding, trailers); over a ~10 MB/s device link the
    padding itself dominated the verify call, so only the tight payload
    crosses the wire and the minimal per-row SHA padding (0x80 trailer +
    128-bit big-endian bit length in the row's own final block) is
    reconstructed here with static-offset writes + iota masks.
    """
    V, maxm = m.shape
    width = nblocks * 128
    assert maxm + 64 + 17 <= width, (maxm, nblocks)
    total = mlen + 64  # live bytes before padding
    pos = jnp.arange(width, dtype=jnp.int32)[None, :]  # (1, width)
    buf = jnp.zeros((V, width), dtype=jnp.uint8)
    buf = buf.at[:, :32].set(r)
    buf = buf.at[:, 32:64].set(a)
    buf = buf.at[:, 64 : 64 + maxm].set(m)
    # zero any stale bytes beyond each row's message, then the 0x80 marker
    live = pos < total[:, None]
    buf = jnp.where(live, buf, 0)
    buf = buf | ((pos == total[:, None]) * jnp.uint8(0x80)).astype(jnp.uint8)
    # 128-bit big-endian bit length in the last 16 bytes of the row's own
    # final block; bitlen < 2^32 here so only the last 4 bytes are nonzero
    nbr = (total + 17 + 127) // 128  # (V,) per-row block count
    shift = (nbr[:, None] * 128 - 1 - pos) * 8  # BE byte shift at each col
    bitlen = (total * 8)[:, None]
    lb = jnp.where(
        (shift >= 0) & (shift < 32),
        lax.shift_right_logical(bitlen, jnp.minimum(jnp.maximum(shift, 0), 31))
        & 0xFF,
        0,
    ).astype(jnp.uint8)
    buf = buf | lb
    return buf.reshape(V, nblocks, 128), nbr


def parse_verify_payload(payload, pubs):
    """Decode the tight verify payload and assemble its SHA-512 blocks.

    payload : (V, 68 + maxm) uint8 — R(32) | s(32) | mlen(3B LE) |
              live(1B) | msg (models/comb_verifier.assemble_payload)
    pubs    : (V, 32) uint8 — device-resident compressed pubkeys

    Returns (r, s, blocks, active, live): the single source of truth for
    the payload row layout, shared by the single-device program
    (models/comb_verifier._device_verify) and the mesh-sharded one
    (parallel/verify).  active is 0 for non-live rows.

    Manifest kernel ``sha2_parse_verify_payload``.
    """
    maxm = payload.shape[1] - 68
    nblocks = (64 + maxm + 17 + 127) // 128
    r = payload[:, :32]
    s = payload[:, 32:64]
    mlen = (
        payload[:, 64].astype(jnp.int32)
        | (payload[:, 65].astype(jnp.int32) << 8)
        | (payload[:, 66].astype(jnp.int32) << 16)
    )
    live = payload[:, 67] == 1
    blocks, nbr = ram_blocks_from_parts(r, pubs, payload[:, 68:], mlen, nblocks)
    active = jnp.where(live, nbr, 0)
    return r, s, blocks, active, live


# ------------------------------------------------------- host-side padding


def pad_messages_sha512(msgs: list[bytes], max_len: int | None = None):
    """Host: variable-length messages -> (buf, active) for sha512_blocks.

    buf is (n, nblocks, 128) uint8 with *minimal* per-row SHA-512 padding
    (0x80, zeros, 128-bit big-endian bit length at the end of the row's own
    final block); active is (n,) int32 per-row live block counts.
    """
    buf, active, _ = pad_sha512_columns((), msgs, max_len)
    return buf, active


def pad_sha512_columns(heads, msgs, max_len: int | None = None):
    """Host, by columns: row i hashes ``heads[0][i] || heads[1][i] || ...
    || msgs[i]``, where each column of ``heads`` holds ``bytes`` of one
    width.  Returns (buf, active, lengths): buf and active as
    :func:`pad_messages_sha512` gives them for the concatenated rows,
    byte for byte, and ``lengths`` the number of distinct message
    lengths.

    A row's padding depends on its length alone, so each length gets one
    tail (0x80, zeros, bit length, the unused blocks) and the buffer is
    one join of the cells and tails, row after row.  Joins and C-level
    iterators hold the interpreter lock throughout, where numpy releases
    it in every large copy: while another thread runs Python (the
    background table bind), each release waits about a switch interval
    (5 ms) to get the lock back, and a dozen numpy writes cost more than
    a Python loop over the rows."""
    n = len(msgs)
    h = sum(len(col[0]) for col in heads) if n else 0
    lens = list(map(len, msgs))
    groups = set(lens)
    longest = h + max(lens, default=0)
    if max_len is not None:
        longest = max(longest, max_len)
    nblocks = max(1, (longest + 17 + 127) // 128)
    tails, nbs = {}, {}
    for ml in groups:
        ln = h + ml
        nb = nbs[ml] = (ln + 17 + 127) // 128
        tails[ml] = (b"\x80" + bytes(nb * 128 - 17 - ln)
                     + (ln * 8).to_bytes(16, "big")
                     + bytes((nblocks - nb) * 128))
    rows = zip(*heads, msgs, map(tails.__getitem__, lens))
    buf = np.frombuffer(bytearray().join(chain.from_iterable(rows)),
                        dtype=np.uint8)
    active = np.fromiter(map(nbs.__getitem__, lens), dtype=np.int32, count=n)
    return buf.reshape(n, nblocks, 128), active, len(groups)


def pad_messages_sha256(msgs: list[bytes], max_len: int | None = None):
    """Host: variable-length messages -> (buf, active) for sha256_blocks."""
    n = len(msgs)
    longest = max((len(m) for m in msgs), default=0)
    if max_len is not None:
        longest = max(longest, max_len)
    nblocks = max(1, (longest + 9 + 63) // 64)
    buf = np.zeros((n, nblocks * 64), dtype=np.uint8)
    active = np.zeros(n, dtype=np.int32)
    for i, m in enumerate(msgs):
        ln = len(m)
        nb = (ln + 9 + 63) // 64
        active[i] = nb
        buf[i, :ln] = np.frombuffer(m, dtype=np.uint8)
        buf[i, ln] = 0x80
        buf[i, nb * 64 - 8 : nb * 64] = np.frombuffer(
            (ln * 8).to_bytes(8, "big"), dtype=np.uint8
        )
    return buf.reshape(n, nblocks, 64), active
