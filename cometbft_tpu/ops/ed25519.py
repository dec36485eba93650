"""Vectorized Edwards25519 group operations for TPU.

Points are batches in extended twisted-Edwards coordinates (X:Y:Z:T),
a = -1, held as four GF(2^255-19) limb arrays in the limbs-first layout of
ops/field.py: each coordinate is (..., 22, L) with the lane/batch axis
minor (full 128-lane utilization on the VPU) and the 22 limbs on
sublanes.  The a=-1 addition law is complete on this curve, so every
operation below is branch-free — no exceptional cases, no data-dependent
control flow — exactly what XLA needs to tile the 10k-signature batch
onto the vector unit.

Scalar multiplication uses Straus/Shamir interleaving with 4-bit windows:
one shared doubling chain evaluates [s]B + [k]A' per signature with 256
doublings + 2x64 window additions.  Window lookups are one-hot
multiply-reduce (16-way select) rather than gathers — on TPU a masked
reduction vectorizes; a gather would serialize.

Verification semantics are ZIP-215 / cofactored, matching the reference
validator hot path (crypto/ed25519/ed25519.go:36-42, verified against
types/validation.go:265 verifyCommitBatch expectations):
  - non-canonical y encodings accepted (y >= p reduces mod p),
  - x = 0 with sign bit 1 accepted,
  - s < L enforced (checked in ops/scalar.py),
  - equation checked with cofactor 8: [8][s]B == [8]R + [8][k]A.

Range contract (proved by analysis/rangecheck.py, pinned in
analysis/range_fingerprints.json entry ``ed25519_verify_batch``): with
inputs at their manifest-declared ranges, every int32 intermediate of
the full verify walk stays within |x| <= 1,252,794,005 — about 0.78
bits of int32 headroom at the tightest point (the field-mul conv
partial sums).  The contract leans on two limb invariants from
ops/field.py: TIGHT (|limb0| <= 3584, others <= 2051) out of carry,
and MULIN (|limb0| <= 14336, others <= 8204) into mul — any point sum
wider than MULIN must pass through F.carry before the next mul (see
niels_to_extended for the one site where this bit).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import field as F
from ..crypto import _ref25519 as ref


class Point(NamedTuple):
    """Batched extended coordinates; each field is (..., 22, L) int32 limbs."""

    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray
    t: jnp.ndarray


# ---------------------------------------------------------------- constants

_D_L = F.to_limbs(ref.D)
_D2_L = F.to_limbs(ref.D2)
_SQRT_M1_L = F.to_limbs(ref.SQRT_M1)


def _c(limbs: np.ndarray):
    """(22,) host constant -> (22, 1) broadcastable device constant."""
    return jnp.asarray(limbs[:, None])


def identity(batch_shape=()) -> Point:
    return Point(
        F.zero(batch_shape), F.one(batch_shape), F.one(batch_shape), F.zero(batch_shape)
    )


def neg(p: Point) -> Point:
    return Point(-p.x, p.y, p.z, -p.t)


def select(cond, p: Point, q: Point) -> Point:
    """Branch-free point select: cond ? p : q (cond = batch-shaped bool)."""
    return Point(
        F.select(cond, p.x, q.x),
        F.select(cond, p.y, q.y),
        F.select(cond, p.z, q.z),
        F.select(cond, p.t, q.t),
    )


# ---------------------------------------------------------------- group law


def add(p: Point, q: Point) -> Point:
    """Unified complete addition: ten F.mul calls, the constant 2d
    among them."""
    a = F.mul(F.sub(p.y, p.x), F.sub(q.y, q.x))
    b = F.mul(F.add(p.y, p.x), F.add(q.y, q.x))
    c = F.mul(F.mul(p.t, q.t), _c(_D2_L))
    d = F.mul(p.z, q.z)
    d = F.add(d, d)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return Point(F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def double(p: Point) -> Point:
    """Dedicated doubling (4 squares + 4 muls), complete for all inputs."""
    a = F.square(p.x)
    b = F.square(p.y)
    zz = F.square(p.z)
    e = F.sub(F.sub(F.square(F.add(p.x, p.y)), a), b)
    g = F.sub(b, a)
    f = F.sub(F.sub(g, zz), zz)  # G - 2Z^2
    h = F.sub(F.neg(a), b)
    return Point(F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


class Niels(NamedTuple):
    """Precomputed affine point: (y+x, y-x, 2d*x*y); Z is implicitly 1."""

    yplusx: jnp.ndarray
    yminusx: jnp.ndarray
    t2d: jnp.ndarray


def add_niels(p: Point, n: Niels) -> Point:
    """Mixed addition with a precomputed affine point (7 field muls)."""
    a = F.mul(F.sub(p.y, p.x), n.yminusx)
    b = F.mul(F.add(p.y, p.x), n.yplusx)
    c = F.mul(p.t, n.t2d)
    d = F.add(p.z, p.z)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return Point(F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def niels_identity_like(n: Niels) -> Niels:
    """The identity in Niels form: (1, 1, 0)."""
    shape = n.yplusx.shape[:-2] + n.yplusx.shape[-1:]
    return Niels(F.one(shape), F.one(shape), F.zero(shape))


_INV_D_L = F.to_limbs(pow(ref.D, ref.P - 2, ref.P))


def niels_to_extended(n: Niels) -> Point:
    """Niels (y+x, y-x, 2dxy) -> extended (2x : 2y : 2 : 2xy).

    One field mul (t2d * d^-1); the uniform projective scale by 2 is
    free.  Lets precomputed table entries join unified additions, whose
    inputs must be full extended points.  Works for the identity
    ((1,1,0) -> (0:2:2:0)) and for sign-flipped entries
    ((y-x, y+x, -2dxy) -> (-2x : 2y : 2 : -2xy)).  The comb verify
    program adds its partials in Niels form and lifts nothing
    (ops/comb._accumulate_chains); the range regression tests use this.
    """
    # Carry the lifted sums back into the TIGHT profile: for canonical
    # table entries the raw y+x +/- y-x limbs reach +/-8190, and a
    # unified addition of two lifted points would put +/-12285 per limb
    # into its F.add(p.y, p.x), past the MULIN contract (|limb|<=8204):
    # the mul conv partial sums would clear 2^31 on adversarial
    # (attacker-chosen pubkey) tables.  One carry pass is elementwise
    # shifts (tests/test_rangecheck.py pins the proof).
    x2 = F.carry(F.sub(n.yplusx, n.yminusx))
    y2 = F.carry(F.add(n.yplusx, n.yminusx))
    batch = x2.shape[:-2] + x2.shape[-1:]
    one = F.one(batch)
    return Point(x2, y2, F.add(one, one), F.mul(n.t2d, _c(_INV_D_L)))


def tree_reduce_points(p: Point) -> Point:
    """Sum a stacked (N, ..., 22, L) Point along its leading axis with a
    binary tree of batched unified additions: ceil(log2(N)) dependent
    rounds instead of an (N-1)-deep sequential accumulation chain.  The
    addition law is complete, so identity entries and odd-level
    carry-overs are safe anywhere in the tree.  The comb verify kernel
    folds its K chain accumulators and -R with it
    (ops/comb._accumulate_chains): K + 1 points, ceil(log2(K + 1))
    rounds.
    """
    n = p.x.shape[0]
    while n > 1:
        half = n // 2
        a = Point(*(c[:half] for c in p))
        b = Point(*(c[half : 2 * half] for c in p))
        s = add(a, b)
        if n & 1:
            s = Point(
                *(
                    jnp.concatenate([cs, cp[2 * half :]], axis=0)
                    for cs, cp in zip(s, p)
                )
            )
        p = s
        n = (n + 1) // 2
    return Point(*(c[0] for c in p))


# ------------------------------------------------------------ (de)compress


def decompress(enc):
    """(..., 32) uint8 -> (Point, ok).  ZIP-215 semantics (see module doc).

    The Point's lane axis is enc's last batch axis; ok keeps enc's batch
    shape.  Invalid encodings yield ok=False and an arbitrary (but
    well-formed) point so downstream arithmetic stays branch-free.
    """
    sign = (lax.shift_right_logical(enc[..., 31].astype(jnp.int32), 7) & 1).astype(
        jnp.int32
    )
    masked = enc.at[..., 31].set(enc[..., 31] & jnp.uint8(0x7F))
    y = F.from_bytes(masked)
    batch = y.shape[:-2] + y.shape[-1:]
    yy = F.square(y)
    u = F.sub(yy, F.one(batch))
    v = F.add(F.mul(yy, _c(_D_L)), F.one(batch))
    v3 = F.mul(F.square(v), v)
    v7 = F.mul(F.square(v3), v)
    x = F.mul(F.mul(u, v3), F.pow_p58(F.mul(u, v7)))
    vxx = F.mul(v, F.square(x))
    ok_direct = F.eq(vxx, u)
    ok_flipped = F.eq(vxx, F.neg(u))
    x = F.select(ok_flipped, F.mul(x, _c(_SQRT_M1_L)), x)
    ok = ok_direct | ok_flipped
    # Match the requested sign bit (x = 0, sign = 1 stays x = 0: accepted).
    flip = F.is_negative(x) != (sign == 1)
    x = F.select(flip, F.neg(x), x)
    pt = Point(x, y, F.one(batch), F.mul(x, y))
    return pt, ok


def compress(p: Point):
    """Point -> canonical (..., L, 32) uint8 encoding (batch-first bytes)."""
    zi = F.invert(p.z)
    x = F.mul(p.x, zi)
    y = F.mul(p.y, zi)
    b = F.to_bytes(y)
    signbit = (F.freeze(x)[..., 0, :] & 1).astype(jnp.uint8)
    return b.at[..., 31].set(b[..., 31] | (signbit << 7))


def is_identity(p: Point):
    """x == 0 and y == z (projective identity test)."""
    return F.is_zero(p.x) & F.eq(p.y, p.z)


def pt_eq(p: Point, q: Point):
    """Projective equality: X1 Z2 == X2 Z1 and Y1 Z2 == Y2 Z1."""
    return F.eq(F.mul(p.x, q.z), F.mul(q.x, p.z)) & F.eq(
        F.mul(p.y, q.z), F.mul(q.y, p.z)
    )


# ----------------------------------------------------- fixed-base B tables


def _host_niels(pt) -> np.ndarray:
    """Host: reference affine point -> (3, 22) niels limbs."""
    x, y, z, _ = pt
    zi = pow(z, ref.P - 2, ref.P)
    x, y = x * zi % ref.P, y * zi % ref.P
    return np.stack(
        [
            F.to_limbs((y + x) % ref.P),
            F.to_limbs((y - x) % ref.P),
            F.to_limbs(2 * ref.D * x % ref.P * y % ref.P),
        ]
    )


def _build_base_window_table() -> np.ndarray:
    """(16, 3, 22): j*B for j = 0..15 in Niels form (j=0 -> identity)."""
    out = np.zeros((16, 3, 22), dtype=np.int32)
    out[0] = np.stack([F.to_limbs(1), F.to_limbs(1), F.to_limbs(0)])
    acc = ref.BASE
    for j in range(1, 16):
        out[j] = _host_niels(acc)
        acc = ref.pt_add(acc, ref.BASE)
    return out


_B_WINDOW = _build_base_window_table()
# (66, 16): flattened (3*22)-coord rows by entry, for the one-hot matmul
_B_WINDOW_FLAT = _B_WINDOW.reshape(16, 66).T.copy()


def lookup_niels(table_flat, idx) -> Niels:
    """One-hot select from a host table (66, 16) by (..., L) int32 idx.

    Returns Niels coords (..., 22, L): (66,16) @ onehot(..., 16, L)."""
    # int32 one-hot against the int32 host table: the lookup never
    # leaves the limb dtype (audited — only the radix-4096 B comb in
    # ops/comb.py takes the f32 MXU round trip, where it is exact)
    onehot = (
        idx[..., None, :] == jnp.arange(16, dtype=jnp.int32)[:, None]
    ).astype(jnp.int32)  # (..., 16, L)
    sel = jnp.matmul(jnp.asarray(table_flat), onehot)  # (..., 66, L)
    return Niels(sel[..., 0:22, :], sel[..., 22:44, :], sel[..., 44:66, :])


def build_var_table(a: Point) -> Point:
    """Stacked window table [0..15]*A with a new leading axis of size 16.

    1 double + 13 unified adds; entry j holds j*A.
    """
    batch = a.x.shape[:-2] + a.x.shape[-1:]
    entries = [identity(batch), a, double(a)]
    for j in range(3, 16):
        entries.append(add(entries[j - 1], a))
    return Point(
        jnp.stack([e.x for e in entries], axis=0),
        jnp.stack([e.y for e in entries], axis=0),
        jnp.stack([e.z for e in entries], axis=0),
        jnp.stack([e.t for e in entries], axis=0),
    )


def lookup_point(table: Point, idx) -> Point:
    """One-hot select from a stacked (16, ..., 22, L) point table by
    (..., L) idx."""
    onehot = (
        idx == jnp.arange(16, dtype=jnp.int32)[(...,) + (None,) * idx.ndim]
    ).astype(jnp.int32)[..., None, :]  # (16, ..., 1, L)

    def pick(coord):
        return jnp.sum(coord * onehot, axis=0)

    return Point(pick(table.x), pick(table.y), pick(table.z), pick(table.t))


# ------------------------------------------------------------ verification


def verify_prepared(a_enc, r_enc, s_windows, k_windows, s_ok):
    """Core batched verifier.

    Inputs (batch shape (..., L); byte arrays batch-first):
      a_enc, r_enc : (..., L, 32) uint8 — compressed pubkey / R point
      s_windows    : (..., 64, L) int32 — 4-bit windows of s, MSB first
      k_windows    : (..., 64, L) int32 — 4-bit windows of k = H(R,A,M) mod L
      s_ok         : (..., L) bool — s < L precondition (ops/scalar.s_lt_l)

    Returns (..., L) bool: [8]([s]B - [k]A - R) == identity, with decompress
    failures and s >= L forced to False.

    Straus interleave: acc := 16*acc + s_i*B + k_i*(-A) per window step,
    sharing one doubling chain; the per-signature (-A) window table is
    built once (1 dbl + 13 adds).  The step loop is a lax.fori_loop so the
    compiled graph is one window body regardless of scalar length.

    The ``jax.named_scope`` names are the phases a profile is read by
    (docs/observability.md); ops/comb.verify_cached uses the same ones.
    """
    with jax.named_scope("decompress"):
        a_pt, a_valid = decompress(a_enc)
        r_pt, r_valid = decompress(r_enc)
    with jax.named_scope("var_table"):
        table = build_var_table(neg(a_pt))  # windows of -A

    def step(i, acc):
        acc = double(double(double(double(acc))))
        acc = add(acc, lookup_point(table, k_at(i)))  # k_i * (-A)
        return add_niels(acc, lookup_niels(_B_WINDOW_FLAT, s_at(i)))  # s_i * B

    # fori_loop with dynamic window indexing along the window axis (-2).
    def k_at(i):
        return lax.dynamic_index_in_dim(k_windows, i, axis=-2, keepdims=False)

    def s_at(i):
        return lax.dynamic_index_in_dim(s_windows, i, axis=-2, keepdims=False)

    batch = a_enc.shape[:-1]
    with jax.named_scope("scalar_mul"):
        acc = lax.fori_loop(0, 64, step, identity(batch))
    with jax.named_scope("final_check"):
        acc = add(acc, neg(r_pt))
        acc = double(double(double(acc)))
        return is_identity(acc) & a_valid & r_valid & s_ok


def verify_batch(a_enc, r_enc, s_bytes, msg_blocks, msg_active):
    """Full on-device batch verification.

    a_enc      : (N, 32) uint8 compressed pubkeys
    r_enc      : (N, 32) uint8 R points (first half of each signature)
    s_bytes    : (N, 32) uint8 s scalars (second half of each signature)
    msg_blocks : (N, nblocks, 128) uint8 — SHA-512-padded R || A || M
                 (host-assembled; see ops/sha2.pad_messages_sha512)
    msg_active : (N,) int32 per-row live block count

    Returns (N,) bool.  The entire pipeline — challenge hash, mod-L
    reduction, window extraction, double-scalar multiplication, cofactored
    identity check — runs as one fused XLA program on device; the reference
    does the same work per signature on CPU via curve25519-voi
    (crypto/ed25519/ed25519.go:220 BatchVerifier.Verify).

    Manifest kernel ``ed25519_verify_batch`` (jitted from
    models/verifier.py — the manifest, not a per-module scan, is what
    keeps this body visible to the static checks).  Also the lane-local
    shard_map body of ``sharded_verify_batch``: the sharded census
    (analysis/shardcheck) pins it to zero collectives of its own.
    """
    from . import sha2, scalar

    # RFC 8032 interprets the 64-byte digest as a little-endian integer.
    k_digest = sha2.sha512_blocks(msg_blocks, msg_active)  # (N, 64)
    with jax.named_scope("scalar_prep"):
        k_limbs = scalar.reduce_mod_l(
            scalar.bytes_to_limbs(k_digest, scalar.NL_X)
        )
        k_windows = scalar.limbs_to_windows(k_limbs)  # (64, N)
        s_windows = scalar.bytes_to_windows(s_bytes)  # (64, N)
        s_ok = scalar.s_lt_l(s_bytes)  # (N,)
    return verify_prepared(a_enc, r_enc, s_windows, k_windows, s_ok)
