"""Vectorized GF(2^255-19) arithmetic for TPU.

Field elements are little-endian arrays of 22 signed 12-bit limbs held in
int32, shaped (..., NLIMBS, L): the limb axis is SECOND-MINOR and the
batch ("lane") axis L is minor.  TPU vector registers tile the two minor
dims as (8 sublanes x 128 lanes); with limbs on the minor axis (the
previous layout) every element-wise op used 22 of 128 lanes (83% waste).
Limbs-on-sublanes puts the big batch axis on lanes (full utilization) and
the 22 limbs on sublanes (22 of 24, 8% pad) — measured ~7x faster per
field mul on the CPU backend and the same argument applies to the VPU.
All intermediate products and accumulations fit in int32 (no int64 on
device), and every operation is element-wise/branch-free over arbitrary
leading batch axes, so a 10k-signature commit verification maps onto the
vector unit as one fused program (reference workload:
crypto/ed25519/ed25519.go:188-222 BatchVerifier — curve25519-voi's
CPU-SIMD equivalent, re-designed for TPU).

Bound contract (|limb| bounds; exercised adversarially in tests/test_field.py):

  TIGHT: output of mul/square/carry/mul_small —
         |limb 0| <= 3584, |limbs 1..21| <= 2051.
  MULIN: mul/square accept sums of up to FOUR tight elements
         (|limb 0| <= 14336, others <= 8204).

  Conv safety: for output limb k, at most one product involves a_0 and one
  involves b_0, so |conv_k| <= 22*8204^2 + 2*14336*8204 = 1.72e9 < 2^31-1.

Radix 2^12 ⇒ 22 limbs span 264 bits; 2^264 ≡ 19·2^9 = 9728 (mod p).  The
top-limb carry (weight 2^264) folds back as q·19·2^9, decomposed as
(19q mod 8)·2^9 into limb 0 plus (19q div 8) into limb 1 so the addend never
exceeds int32 range even for large q.

Two forms of the exponentiation chains (invert, pow_p58).  The array form
is what every backend but a TPU traces: each mul/square some nineteen XLA
fusions over whole (22, L) arrays.  On a TPU the 263 dependent steps of a
chain run as ONE on-chip kernel tiled over the lanes ("the row form",
below the chains): the same arithmetic, limb for limb, with every limb a
row of lanes that stays on the chip from the first squaring to the last
multiplication.  Which form a program takes is read off the backend it
is traced for (_for_tpu); no caller chooses.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

NLIMBS = 22
BITS = 12
RADIX = 1 << BITS  # 4096
MASK = RADIX - 1
FOLD = 19 << (NLIMBS * BITS - 255)  # 2^264 mod p = 19*2^9 = 9728
FOLD2_SHIFTED = 361 * 64  # 2^528 mod p = 361*2^18 = 23104 * 2^12

P = (1 << 255) - 19

_POW2 = np.array([1 << i for i in range(BITS)], dtype=np.int32)

# Limb decomposition of 2^9 * p = 2^264 - 9728 with every limb in
# [2^11, 2^13): added before the unsigned carry chain in freeze() so that
# signed limbs become non-negative without changing the value mod p.
_BIAS = np.full(NLIMBS, MASK, dtype=np.int32)  # all-4095 = 2^264 - 1
_BIAS[0] = MASK - 9727 + RADIX * 3  # borrow 3 from limb 1
_BIAS[1] = MASK - 3
assert sum(int(_BIAS[i]) << (BITS * i) for i in range(NLIMBS)) == (P << 9)

_P_LIMBS = np.zeros(NLIMBS, dtype=np.int32)
_tmp = P
for _i in range(NLIMBS):
    _P_LIMBS[_i] = _tmp & MASK
    _tmp >>= BITS


def to_limbs(x: int, batch_shape=()) -> np.ndarray:
    """Host-side: Python int -> (22,) limb vector (numpy int32); with a
    batch_shape, broadcast to batch_shape[:-1] + (22, batch_shape[-1])."""
    x %= P
    out = np.zeros(NLIMBS, dtype=np.int32)
    for i in range(NLIMBS):
        out[i] = x & MASK
        x >>= BITS
    if batch_shape:
        out = np.broadcast_to(
            out[:, None], batch_shape[:-1] + (NLIMBS, batch_shape[-1])
        ).copy()
    return out


@functools.lru_cache(maxsize=64)
def cl(x: int):
    """Device constant: (22, 1) limbs of x, broadcastable against any
    (..., 22, L) element."""
    return jnp.asarray(to_limbs(x)[:, None])


def from_limbs(limbs) -> int:
    """Host-side: (22,) limb vector -> Python int (not reduced mod p)."""
    limbs = np.asarray(limbs)
    return sum(int(limbs[i]) << (BITS * i) for i in range(limbs.shape[0]))


def _el_shape(batch_shape):
    if not batch_shape:
        return (NLIMBS, 1)
    return tuple(batch_shape[:-1]) + (NLIMBS, batch_shape[-1])


def zero(batch_shape=()):
    return jnp.zeros(_el_shape(batch_shape), dtype=jnp.int32)


def one(batch_shape=()):
    z = np.zeros(_el_shape(batch_shape), dtype=np.int32)
    z[..., 0, :] = 1
    return jnp.asarray(z)


def add(a, b):
    """Limb-wise add; no carry. Caller tracks the bound budget."""
    return a + b


def sub(a, b):
    """Limb-wise subtract; no carry (signed limbs make this exact)."""
    return a - b


def neg(a):
    return -a


def _pad_limb_axis(x, lo: int, hi: int):
    pad = [(0, 0)] * (x.ndim - 2) + [(lo, hi), (0, 0)]
    return jnp.pad(x, pad)


def _carry_round(c):
    """One parallel signed carry round over the limb axis (-2).

    q = round(c / 2^12); limbs land in [-2048, 2047] before carry-ins.
    Returns (c', top_carry) where top_carry has weight 2^(12*nlimbs).
    """
    q = lax.shift_right_arithmetic(c + (RADIX >> 1), BITS)
    c = c - lax.shift_left(q, BITS)
    carry_in = _pad_limb_axis(q[..., :-1, :], 1, 0)
    return c + carry_in, q[..., -1, :]


def _fold_top(c, q):
    """Add q * 2^264 ≡ q*19*2^9 (mod p) into limbs 0/1 without overflow.

    v = 19q (|v| < 2^26 for any carry q seen here); v*2^9 decomposes as
    (v mod 8)*2^9 at limb 0 plus (v div 8) at limb 1 — both small.
    """
    v = q * 19
    lo = (v & 7) * (1 << 9)
    hi = lax.shift_right_arithmetic(v, 3)
    c = c.at[..., 0, :].add(lo)
    c = c.at[..., 1, :].add(hi)
    return c


def carry(a, rounds: int = 3):
    """Reduce a 22-limb signed value (|limb| < 2^30.8) to TIGHT bounds."""
    c = a
    for _ in range(rounds):
        c, top = _carry_round(c)
        c = _fold_top(c, top)
    return c


def _conv(a, b, n: int, m: int):
    """Schoolbook product of n-limb a and m-limb b -> (n+m-1)-limb conv.

    Unrolled static loop: m shifted multiply-adds, each a width-n vector op
    over the lane axis.
    """
    out_len = n + m - 1
    shape = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2]) + (
        out_len,
        jnp.broadcast_shapes(a.shape[-1:], b.shape[-1:])[0],
    )
    c = jnp.zeros(shape, dtype=jnp.int32)
    for i in range(m):
        c = c.at[..., i : i + n, :].add(a * b[..., i : i + 1, :])
    return c


def _reduce_conv(c):
    """Reduce a 43-limb signed conv (|limb| <= 1.72e9) to TIGHT limbs."""
    lo = c[..., :NLIMBS, :]
    hi = c[..., NLIMBS:, :]  # 21 limbs, weight offset 2^264
    # Carry hi independently (pad so round-carries stay inside; top carry of
    # the padded array is provably zero with 3 pad limbs / 3 rounds).
    hi = _pad_limb_axis(hi, 0, 3)
    for _ in range(3):
        hi, _ = _carry_round(hi)
    # Fold: limbs 0..21 of hi (abs positions 22..43) scale by 2^264 ≡ 9728;
    # pad limbs 22/23 (abs 44/45) scale by 2^528 ≡ 23104·2^12 → limbs 1/2.
    lo = lo + hi[..., :NLIMBS, :] * FOLD
    lo = lo.at[..., 1, :].add(hi[..., NLIMBS, :] * FOLD2_SHIFTED)
    lo = lo.at[..., 2, :].add(hi[..., NLIMBS + 1, :] * FOLD2_SHIFTED)
    return carry(lo, rounds=3)


def mul(a, b):
    """Field multiply. Inputs within MULIN contract; output TIGHT."""
    return _reduce_conv(_conv(a, b, NLIMBS, NLIMBS))


def square(a):
    """Field square (XLA CSEs the shared operand in the conv)."""
    return _reduce_conv(_conv(a, a, NLIMBS, NLIMBS))


def mul_small(a, k: int):
    """Multiply by a small host constant; |a·k| limbs must stay < 2^30.8."""
    return carry(a * jnp.int32(k), rounds=3)


def _pow2k(a, k: int, square_fn):
    if k <= 4:
        for _ in range(k):
            a = square_fn(a)
        return a
    return lax.fori_loop(0, k, lambda _, x: square_fn(x), a)


def pow2k(a, k: int):
    """a^(2^k) by k squarings.

    Long runs use lax.fori_loop so the traced graph stays one square body
    regardless of k (XLA compiles once, loops on device).
    """
    return _pow2k(a, k, square)


class _Form(NamedTuple):
    """The three operations an exponentiation chain is written over."""

    square: Callable
    mul: Callable
    pow2k: Callable


_ARRAY = _Form(square, mul, pow2k)


def _chain_250(x, f: _Form = _ARRAY):
    """x^(2^250 - 1) — shared prefix of the invert and sqrt chains.

    Classic curve25519 square-and-multiply ladder (public-domain structure).
    Returns (x^(2^250-1), x^11).
    """
    z2 = f.square(x)                          # 2
    z8 = f.pow2k(z2, 2)                       # 8
    z9 = f.mul(x, z8)                         # 9
    z11 = f.mul(z2, z9)                       # 11
    z22 = f.square(z11)                       # 22
    z_5_0 = f.mul(z9, z22)                    # 2^5 - 1 = 31
    z_10_5 = f.pow2k(z_5_0, 5)
    z_10_0 = f.mul(z_10_5, z_5_0)             # 2^10 - 1
    z_20_10 = f.pow2k(z_10_0, 10)
    z_20_0 = f.mul(z_20_10, z_10_0)           # 2^20 - 1
    z_40_20 = f.pow2k(z_20_0, 20)
    z_40_0 = f.mul(z_40_20, z_20_0)           # 2^40 - 1
    z_50_10 = f.pow2k(z_40_0, 10)
    z_50_0 = f.mul(z_50_10, z_10_0)           # 2^50 - 1
    z_100_50 = f.pow2k(z_50_0, 50)
    z_100_0 = f.mul(z_100_50, z_50_0)         # 2^100 - 1
    z_200_100 = f.pow2k(z_100_0, 100)
    z_200_0 = f.mul(z_200_100, z_100_0)       # 2^200 - 1
    z_250_50 = f.pow2k(z_200_0, 50)
    z_250_0 = f.mul(z_250_50, z_50_0)         # 2^250 - 1
    return z_250_0, z11


def _invert_chain(x, f: _Form = _ARRAY):
    z_250_0, z11 = _chain_250(x, f)
    return f.mul(f.pow2k(z_250_0, 5), z11)


def _pow_p58_chain(x, f: _Form = _ARRAY):
    z_250_0, _ = _chain_250(x, f)
    return f.mul(f.pow2k(z_250_0, 2), x)


def invert(x):
    """x^(p-2);  p-2 = 2^255 - 21 = (2^250-1)·2^5 + 11."""
    return _on_chip(_invert_chain, x) if _for_tpu() else _invert_chain(x)


def pow_p58(x):
    """x^((p-5)/8);  (p-5)/8 = 2^252 - 3 = (2^250-1)·2^2 + 1."""
    return _on_chip(_pow_p58_chain, x) if _for_tpu() else _pow_p58_chain(x)


# ---------------------------------------------------------- the row form
#
# The same arithmetic over an element held as a list of its 22 limb ROWS,
# each a (S, 128) tile of lanes (one vector register at S = 8): products
# and carries are element-wise operations on whole rows, and nothing
# indexes, pads or scatters along the limb axis.  This is the body of the
# on-chip kernel (_on_chip): between its first squaring and its last
# multiplication no limb leaves the chip, where the array form above
# compiles to some nineteen fusions a multiplication, each streaming
# (22, L) arrays through HBM.  It is written with operators alone, so on
# plain arrays it is ordinary JAX: analysis/rangecheck proves its bounds
# (manifest kernels field_pow_p58_rows / field_invert_rows); and on numpy
# int32 rows it is numpy, which is how tests/test_field.py runs whole
# chains in a second (XLA:CPU takes a quarter of an hour to compile the
# 34,000 unrolled operations of one chain).  Limb for limb it returns
# what mul/square return: the conv sums are the same integers and the
# reduction is _reduce_conv's, row by row.


def _rows_carry_round(c):
    """_carry_round over rows: (rows', top carry)."""
    q = [(x + (RADIX >> 1)) >> BITS for x in c]
    r = [x - (qi << BITS) for x, qi in zip(c, q)]
    return r[:1] + [x + qi for x, qi in zip(r[1:], q[:-1])], q[-1]


def _rows_carry(c, rounds: int = 3):
    for _ in range(rounds):
        c, top = _rows_carry_round(c)
        v = top * 19  # _fold_top
        c[0] = c[0] + (v & 7) * (1 << 9)
        c[1] = c[1] + (v >> 3)
    return c


def _rows_reduce_conv(c):
    """_reduce_conv over the 43 rows of a conv."""
    hi = c[NLIMBS:]
    # _reduce_conv's three pad limbs are zero until a round's top carry
    # lands in them: append each round's top carry instead
    for _ in range(3):
        hi, top = _rows_carry_round(hi)
        hi.append(top)
    lo = [x + h * FOLD for x, h in zip(c[:NLIMBS], hi)]
    lo[1] = lo[1] + hi[NLIMBS] * FOLD2_SHIFTED
    lo[2] = lo[2] + hi[NLIMBS + 1] * FOLD2_SHIFTED
    return _rows_carry(lo)


def _rows_conv(terms):
    """Sum (k, product) terms into the 43 rows of a conv."""
    c = [None] * (2 * NLIMBS - 1)
    for k, p in terms:
        c[k] = p if c[k] is None else c[k] + p
    return c


def _rows_mul(a, b):
    return _rows_reduce_conv(_rows_conv(
        (i + j, x * y) for i, x in enumerate(a) for j, y in enumerate(b)
    ))


def _rows_square(a):
    """The 253 distinct products, the off-diagonal ones against 2a: the
    conv sums are those of _rows_mul(a, a)."""
    a2 = [x + x for x in a]
    return _rows_reduce_conv(_rows_conv(
        (i + j, (a[i] if i == j else a2[i]) * a[j])
        for i in range(NLIMBS) for j in range(i, NLIMBS)
    ))


# jitted, so that a chain's trace holds ONE squaring and ONE multiplication
# body, called 20 times, and not 34,000 row operations: tracing those in
# Python took 6 s a kernel at every start of every program, with a warm
# compile cache too (lowering inlines the calls: the kernel is the same)
_rows_square_j = jax.jit(_rows_square)
_ROWS = _Form(
    _rows_square_j, jax.jit(_rows_mul),
    lambda a, k: _pow2k(a, k, _rows_square_j),
)

LANES = 128  # a row's minor axis: the lanes of one vector register
TILE_ROWS = 8  # a row's second-minor axis at most: its sublanes


def _over_rows(chain):
    """chain as a function of the stacked rows (22, S, LANES)."""
    def stacked(x):
        return jnp.stack(chain([x[i] for i in range(NLIMBS)], _ROWS))
    return stacked


pow_p58_rows = _over_rows(_pow_p58_chain)
invert_rows = _over_rows(_invert_chain)


def _for_tpu() -> bool:
    """Whether the program being traced is compiled for a TPU (read when
    it is traced, like ops/comb.fold_chains: nothing a caller sets).  On
    every other backend (the CPU tests, the static gates, the host
    oracle) the array form runs and every traced program stays as it was."""
    return jax.default_backend() == "tpu"


def pow_form() -> str:
    """The exponentiation's form in programs traced now: "kernel" or
    "array" (the gauge cometbft_verify_comb_pow_form)."""
    return "kernel" if _for_tpu() else "array"


def _tiles(chain, rows, s: int, interpret: bool = False):
    """chain over rows (22, R, 128) as one pallas_call whose grid walks
    tiles of s rows; R is a multiple of s.  interpret is the CPU tests'."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        out = chain([x_ref[i] for i in range(NLIMBS)], _ROWS)
        for i, row in enumerate(out):
            o_ref[i] = row

    block = pl.BlockSpec((NLIMBS, s, LANES), lambda t: (0, t, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.int32),
        grid=(rows.shape[1] // s,),
        in_specs=[block],
        out_specs=block,
        interpret=interpret,
    )(rows)


def tile_rule(lanes: int) -> tuple[int, int]:
    """(rows, tile) of the kernel's layout for a lane count: the lanes as
    rows of 128, in tiles of S rows.  S is every row up to 8 (256 lanes:
    one (22, 2, 128) tile, nothing padded), else 8 with the rows padded
    up to a multiple (10,112 lanes: 80 rows in ten tiles, one of padding)."""
    rows = -(-lanes // LANES)
    tile = min(rows, TILE_ROWS)
    return -(-rows // tile) * tile, tile


def _on_chip(chain, x):
    """chain(x) as ONE on-chip kernel, tiled over the lane axis by
    tile_rule; leading batch axes of x (..., 22, L) fold into the lanes.
    Lanes never interact, so what the padding computes is dropped unread."""
    rows_first = jnp.moveaxis(x, -2, 0)  # (22, ..., L)
    flat = rows_first.reshape(NLIMBS, -1)
    n = flat.shape[1]
    rows, tile = tile_rule(n)
    flat = jnp.pad(flat, ((0, 0), (0, rows * LANES - n)))
    out = _tiles(chain, flat.reshape(NLIMBS, rows, LANES), tile)
    out = out.reshape(NLIMBS, -1)[:, :n].reshape(rows_first.shape)
    return jnp.moveaxis(out, 0, -2)


def freeze(a):
    """Fully reduce to canonical limbs in [0, 2^12), value in [0, p)."""
    c = carry(a, rounds=3)
    # Make non-negative: add 2^9 * p (limb-wise bias keeps limbs >= 0).
    c = c + jnp.asarray(_BIAS)[:, None]
    c = _unsigned_carry(c)
    # Two rounds of top-bit folding: value < 2^264 -> < 2^255 + eps -> < 2^255.
    for _ in range(2):
        hi = lax.shift_right_logical(c[..., -1, :], 3)  # bits >= 255
        c = c.at[..., -1, :].set(c[..., -1, :] & 7)
        c = c.at[..., 0, :].add(hi * 19)
        c = _unsigned_carry(c)
    # Conditional subtract p (value in [0, 2^255) -> canonical [0, p)).
    borrow = jnp.zeros(c.shape[:-2] + c.shape[-1:], dtype=jnp.int32)
    w = jnp.zeros_like(c)
    for i in range(NLIMBS):
        d = c[..., i, :] - jnp.int32(int(_P_LIMBS[i])) - borrow
        borrow = lax.shift_right_logical(d, 31) & 1  # 1 if negative
        w = w.at[..., i, :].set(d + lax.shift_left(borrow, BITS))
    ge_p = borrow == 0
    return jnp.where(ge_p[..., None, :], w, c)


def _unsigned_carry(c):
    """Sequential carry for non-negative limbs; top carry folds via 9728.

    Top carry here is < 2^4 (values < 2^268), so q*FOLD fits trivially.
    """
    out = jnp.zeros_like(c)
    k = jnp.zeros(c.shape[:-2] + c.shape[-1:], dtype=jnp.int32)
    for i in range(NLIMBS):
        t = c[..., i, :] + k
        out = out.at[..., i, :].set(t & MASK)
        k = lax.shift_right_logical(t, BITS)
    out = out.at[..., 0, :].add(k * FOLD)
    # Local ripple in case limb 0/1 overflowed (addend < 2^18).
    for i in range(2):
        ki = lax.shift_right_logical(out[..., i, :], BITS)
        out = out.at[..., i, :].set(out[..., i, :] & MASK)
        out = out.at[..., i + 1, :].add(ki)
    return out


def eq(a, b):
    """Field equality (branch-free): freeze both, compare limbs."""
    return jnp.all(freeze(a) == freeze(b), axis=-2)


def is_zero(a):
    return jnp.all(freeze(a) == 0, axis=-2)


def is_negative(a):
    """RFC 8032 sign: lowest bit of the canonical encoding."""
    return (freeze(a)[..., 0, :] & 1).astype(jnp.bool_)


def select(cond, a, b):
    """Branch-free select: cond ? a : b.  cond shape = batch shape
    (leading axes + lane axis)."""
    return jnp.where(cond[..., None, :], a, b)


def from_bytes(b):
    """(..., 32) uint8 LE -> (..., 22, L) limbs, where L is the last
    batch axis of b (a lone (32,) input yields (22, 1)).

    All 256 bits are taken; callers that need the sign bit (point
    decompression) mask it off first.  Value may exceed p — ZIP-215
    tolerates non-canonical y encodings, and the limb form handles
    values up to 2^264 transparently.
    """
    b = b.astype(jnp.int32)
    bits = jnp.stack(
        [lax.shift_right_logical(b, k) & 1 for k in range(8)], axis=-1
    )  # (..., 32, 8)
    bits = bits.reshape(bits.shape[:-2] + (256,))
    pad = [(0, 0)] * (bits.ndim - 1) + [(0, NLIMBS * BITS - 256)]
    bits = jnp.pad(bits, pad)
    bits = bits.reshape(bits.shape[:-1] + (NLIMBS, BITS))
    limbs = jnp.sum(bits * jnp.asarray(_POW2), axis=-1).astype(jnp.int32)
    if limbs.ndim == 1:
        return limbs[:, None]
    return jnp.swapaxes(limbs, -1, -2)


def to_bytes(a):
    """(..., 22, L) limbs -> canonical (..., L, 32) uint8 LE encoding."""
    c = jnp.swapaxes(freeze(a), -1, -2)  # (..., L, 22)
    bits = jnp.stack(
        [lax.shift_right_logical(c, k) & 1 for k in range(BITS)], axis=-1
    )  # (..., L, 22, 12)
    bits = bits.reshape(bits.shape[:-2] + (NLIMBS * BITS,))[..., :256]
    bits = bits.reshape(bits.shape[:-1] + (32, 8))
    return jnp.sum(
        bits * jnp.asarray([1 << k for k in range(8)], dtype=jnp.int32), axis=-1
    ).astype(jnp.uint8)
