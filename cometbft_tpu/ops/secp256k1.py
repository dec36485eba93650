"""Vectorized secp256k1 ECDSA batch verification for TPU
(ROADMAP item 4; the FPGA verification-engine staging of PAPERS.md
arXiv:2112.02229: deep batching + amortized modular inversion +
parallel point multiplication, re-targeted at the vector unit).

This generalizes the word-wise Montgomery limb arithmetic proven for
BLS12-381 in ops/bls381.py to the secp256k1 base field AND its scalar
field: p256k1 = 2^256 - 2^32 - 977 is (like p381, unlike 2^255-19)
not close enough to a power of two for the ops/field.py carry-fold, so
field elements are 22 signed 12-bit limbs in int32 (batch axis
leading, limbs minor), R = 2^264, and every op returns canonical limbs
in [0, m).  The 44-limb product is one outer-product + one constant
anti-diagonal matmul; the Montgomery reduction is whole-width (two
matmuls by constant Toeplitz matrices), and carries move by parallel
rounds and one word-wide addition, never a loop over the limbs.  int32
bounds: conv sums <= 22*4095^2 ~ 3.7e8, the reduction adds <= the same
again — peak < 7.4e8 < 2^31.  The interval interpreter
(analysis/rangecheck.py) proves the tight version of that estimate:
peak |intermediate| = 716,084,460 across all six secp kernels (1.58
bits of int32 headroom; certificate entries ``secp256k1_*`` in
analysis/range_fingerprints.json).

The ECDSA batch (one fused program per bucket shape):

* **range / low-s validation on device** — r, s enter as raw 256-bit
  limb vectors; 1 <= r < n, 1 <= s < n and the Cosmos/Ethereum low-s
  rule s <= n/2 are borrow-chain compares over the batch.
* **Montgomery batch inversion** — the per-signature s^-1 (mod n) and
  the final affine normalization z^-1 (mod p) are amortized across the
  whole batch: log-depth Hillis-Steele prefix/suffix products, ONE
  Fermat inversion chain of the total product, two muls per row —
  instead of a 256-step exponentiation ladder of full-width batched
  muls per modulus.  Rows that would poison the shared product (s = 0,
  z = 0 from invalid inputs) are sanitized to 1 BEFORE the prefix
  products — the exact latent bug PR 11 found in the ed25519 comb
  table build; a malformed row can never corrupt a valid row's
  inverse (pinned by tests/test_secp_ops.py).
* **GLV quad-scalar multiplication** (the default; ``glv=False`` keeps
  the plain Shamir chain as the bit-exactness witness, the PR-1
  witness pattern) — u1*G + u2*Q with one shared doubling chain.
  The secp256k1 endomorphism phi(x, y) = (beta*x, y) acts as
  multiplication by lambda (a cube root of 1 mod n), so each scalar
  splits as k = k1 + lambda*k2 with |k1|, |k2| < ~2^129 (lattice
  basis from the extended Euclid run on (n, lambda); the rounding is
  two 384-bit-shift multiplies by precomputed constants, Algorithm
  3.74 of Guide to ECC).  The walk then covers 33 4-bit windows over
  FOUR points (G, phi(G), Q, phi(Q) — the phi tables are one
  beta-multiply of the X rows) instead of 66 windows over two: the
  doubling chain that dominates the kernel halves (132 doublings vs
  264; adds stay 132).  Signs fold into per-row conditional Y
  negation of the table lookups.
* **Shamir's-trick double-scalar multiplication** (the witness path) —
  66 4-bit windows: per window 4 doublings + one add from the fixed G
  window table + one add from the per-signature Q table (built on
  device, 1 dbl + 13 adds).  The G table (j*G for j = 0..15, Jacobian
  Montgomery limbs) is precomputed host-side and `jax.device_put` once
  per process — the PR-11 table-residency pattern: no table-build
  program ever compiles, and the resident buffer is passed as a kernel
  argument, never re-staged per call.  Lookups are one-hot matmuls
  (gathers serialize on TPU).
* **verdict** — cosmos rows check x(R') mod n == r (x == r or
  x == r + n when r + n < p, exactly the host's `pt[0] % N == r`);
  eth rows (65-byte R||S||V signatures) check x(R') == r exactly plus
  the recovery-id parity y(R') & 1 == v, which is equivalent to
  Ecrecover(h, sig) == Q (s*R == e*G + r*Q  <=>  R == u1*G + u2*Q).
* **true ecrecover rows** (``recover=True``, a trace-time flag so
  verify-only batches never pay for it) — Ethereum txs carry no
  pubkey, only the 20-byte sender address.  Marked rows lift
  R = (r, sqrt(r^3 + 7)) with the parity v (one batched Fermat
  sqrt chain, x^((p+1)/4)), walk Q = (-e/r)*G + (s/r)*R through the
  SAME quad-scalar chain (u1 = -e*r^-1, u2 = s*r^-1, point = R), and
  compare Keccak256(x || y)[12:] of the recovered point against the
  address — bit-identical to crypto/secp256k1eth.recover_pubkey +
  address() in every edge (non-residue r, infinity, high-s, v > 1).

``secp_hash_verify_batch`` fuses the message hashing in front of all of the
above: cosmos rows through ops/sha2.sha256_blocks, eth/ecrecover rows
through ops/keccak.keccak256_blocks, digests multiplexed per row — one
device program from padded payload bytes to verdict bits, so firehose
ingest never serializes a per-tx host hash loop.

All paths are branch-free selects, so the verdict is bit-identical to
the pure-host crypto/secp256k1 / crypto/secp256k1eth lane in every
edge (tampered rows, high-s, r/s = 0, off-curve keys, infinity
results) — the host lane is the fallback verdict oracle of the
MODE_SECP verify-service lane (models/secp_verifier).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..crypto import secp256k1 as host_secp

NLIMBS = 22
BITS = 12
RADIX = 1 << BITS
MASK = RADIX - 1
NWINDOWS = NLIMBS * BITS // 4  # 66 4-bit windows span the 264 limb bits

P = host_secp.P  # 2^256 - 2^32 - 977
N = host_secp.N  # the group order (the ECDSA scalar field)
R_MONT = 1 << (NLIMBS * BITS)  # 2^264


def _int_to_limbs(x: int, n: int = NLIMBS) -> np.ndarray:
    out = np.zeros(n, dtype=np.int32)
    for i in range(n):
        out[i] = x & MASK
        x >>= BITS
    assert x == 0, "value too wide for limb count"
    return out


def _toeplitz(limbs: np.ndarray, width: int) -> np.ndarray:
    """(22, width) int32 with row i the limbs shifted up by i: a limb
    vector times it is the product's first ``width`` conv limbs."""
    out = np.zeros((NLIMBS, width), dtype=np.int32)
    for i in range(NLIMBS):
        n = min(NLIMBS, width - i)
        out[i, i : i + n] = limbs[:n]
    return out


# 2^264 as limbs of 4096 at limb 0 and 4095 above it (and the carry out
# of the top limb): added to a difference, it lifts every limb to >= 0
_ZERO_BIAS = np.full(NLIMBS, MASK, dtype=np.int32)
_ZERO_BIAS[0] = RADIX
_UNIT = _int_to_limbs(1)


def _exponent_windows(e: int) -> np.ndarray:
    """A host exponent as its 4-bit windows, most significant first:
    the digits :func:`_mont_pow` walks."""
    n = (e.bit_length() + 3) // 4
    out = np.zeros(n, dtype=np.int32)
    for i in range(n):
        out[n - 1 - i] = (e >> (4 * i)) & 15
    return out


class _Mod:
    """Host-side constant bundle for one odd modulus m < 2^264: the limb
    decompositions and Montgomery constants the device ops close over."""

    def __init__(self, m: int):
        self.m = m
        self.limbs = _int_to_limbs(m)
        self.neg = _int_to_limbs(R_MONT - m)  # 2^264 - m: the compare
        # Toeplitz matrices of the whole-width reduction: x @ toep is the
        # 44-limb product x*m, x @ toep_neg_inv the low 22 limbs of
        # x * (-m^-1 mod 2^264)
        self.toep = _toeplitz(self.limbs, 2 * NLIMBS)
        self.toep_neg_inv = _toeplitz(
            _int_to_limbs((-pow(m, -1, R_MONT)) % R_MONT), NLIMBS)
        self.r2 = _int_to_limbs(R_MONT * R_MONT % m)  # to-Montgomery mul
        self.one_plain = _int_to_limbs(1)  # from-Montgomery mul
        self.one_mont = _int_to_limbs(R_MONT % m)
        # m - 2 by 4-bit windows: the Fermat inversion chain of the ONE
        # total-product inverse in the batch-inversion trick
        self.inv_windows = _exponent_windows(m - 2)

    def to_mont(self, x: int) -> int:
        return x * R_MONT % self.m

    def from_mont(self, x: int) -> int:
        return x * pow(R_MONT, self.m - 2, self.m) % self.m


FP = _Mod(P)
FN = _Mod(N)


# ------------------------------------------------------- GLV decomposition
# The secp256k1 endomorphism: beta is a nontrivial cube root of 1 mod p,
# lambda the matching cube root of 1 mod n, with
# lambda * (x, y) = (beta * x, y) for every curve point.  All constants
# are DERIVED here from the curve parameters (not pasted): beta/lambda
# from small-base exponentiation, the short lattice basis from the
# extended Euclid run on (n, lambda), the rounding multipliers g_i from
# one 384-bit-shift division — and the pairing + decomposition bounds
# are asserted at import, so a wrong constant cannot survive to trace
# time.


def _find_glv() -> tuple[int, int]:
    beta = lam = None
    g = 2
    while beta is None:
        c = pow(g, (P - 1) // 3, P)
        if c != 1:
            beta = c
        g += 1
    g = 2
    while lam is None:
        c = pow(g, (N - 1) // 3, N)
        if c != 1:
            lam = c
        g += 1
    # the two cube roots come with an arbitrary choice each; pick the
    # pair that actually satisfies lambda*G == (beta*Gx, Gy)
    for lc in (lam, lam * lam % N):
        got = host_secp._mul(lc, host_secp.G)
        for bc in (beta, beta * beta % P):
            if got == (bc * host_secp.G[0] % P, host_secp.G[1]):
                return bc, lc
    raise AssertionError("secp256k1 GLV beta/lambda pairing not found")


_BETA, _LAM = _find_glv()


def _glv_basis() -> tuple[int, int, int, int]:
    """Two short lattice vectors (a, b) with a + b*lambda == 0 (mod n)
    (extended Euclid on (n, lambda), stopping at the sqrt(n) crossing —
    Guide to ECC, Alg. 3.74); normalized so det == +n."""
    rs, ts = [N, _LAM], [0, 1]
    while rs[-1] * rs[-1] >= N:
        q = rs[-2] // rs[-1]
        rs.append(rs[-2] - q * rs[-1])
        ts.append(ts[-2] - q * ts[-1])
    q = rs[-2] // rs[-1]
    rs.append(rs[-2] - q * rs[-1])
    ts.append(ts[-2] - q * ts[-1])
    a1, b1 = rs[-2], -ts[-2]
    cand_a = (rs[-3], -ts[-3])
    cand_b = (rs[-1], -ts[-1])
    a2, b2 = min(cand_a, cand_b, key=lambda v: v[0] * v[0] + v[1] * v[1])
    det = a1 * b2 - a2 * b1
    assert abs(det) == N
    if det < 0:
        a2, b2 = -a2, -b2
    assert (a1 + b1 * _LAM) % N == 0 and (a2 + b2 * _LAM) % N == 0
    return a1, b1, a2, b2


_A1, _B1, _A2, _B2 = _glv_basis()

# rounding multipliers: c_i = round(k * |b_j| / n) computed on device as
# (k * g_i + 2^383) >> 384 with g_i = round(2^384 * |b_j| / n) — wide
# enough that the +-1 rounding slack only nudges |k1|, |k2| within their
# ~2^129 bound, never the k1 + lambda*k2 == k identity (k1 is computed
# FROM k2, so the identity holds by construction for every k)
_G1 = ((1 << 384) * abs(_B2) + N // 2) // N
_G2 = ((1 << 384) * abs(_B1) + N // 2) // N
_S1 = 1 if _B2 > 0 else -1  # sign(b2):  c1 = _S1 * round(k*|b2|/n)
_S2 = 1 if _B1 < 0 else -1  # sign(-b1): c2 = _S2 * round(k*|b1|/n)
# k2 = -c1*b1 - c2*b2 folded into unsigned device constants:
# k2 = c1' * M1 + c2' * M2 (mod n) with c_i' the unsigned roundings
_M1 = (-_S1 * _B1) % N
_M2 = (-_S2 * _B2) % N

_G1_LIMBS = _int_to_limbs(_G1)
_G2_LIMBS = _int_to_limbs(_G2)
# Montgomery-form multipliers: mul(plain, const*R) -> plain product
_M1R = _int_to_limbs(_M1 * R_MONT % N)
_M2R = _int_to_limbs(_M2 * R_MONT % N)
_LAMR = _int_to_limbs(_LAM * R_MONT % N)
_BETA_M = _int_to_limbs(_BETA * R_MONT % P)

# signed-halves boundary: the true halves satisfy |k_i| < ~2^129, so a
# canonical k_i in [0, 2^132) is the half itself and anything else is
# k_i - n (2^132 is a clean 11-limb edge -> 33 4-bit windows)
_GLV_SIGN_BOUND = 1 << 132
NWINDOWS_GLV = 33


def _split_host(k: int) -> tuple[int, int]:
    """Host-int mirror of the device split (the import self-check and
    the tests' oracle): k -> signed (k1, k2) with k1 + lambda*k2 == k
    (mod n)."""
    c1 = (k * _G1 + (1 << 383)) >> 384
    c2 = (k * _G2 + (1 << 383)) >> 384
    k2 = (c1 * _M1 + c2 * _M2) % N
    k1 = (k - _LAM * k2) % N
    s1 = k1 if k1 < _GLV_SIGN_BOUND else k1 - N
    s2 = k2 if k2 < _GLV_SIGN_BOUND else k2 - N
    return s1, s2


def _selfcheck_glv() -> None:
    samples = [0, 1, 2, N - 1, N - 2, N // 2, _LAM, N - _LAM]
    x = 1
    for _ in range(56):
        x = x * 3 % N
        samples.append(x)
    for k in samples:
        s1, s2 = _split_host(k)
        assert (s1 + _LAM * s2) % N == k % N, k
        assert abs(s1) < 1 << 130 and abs(s2) < 1 << 130, k


_selfcheck_glv()

# anti-diagonal collector: outer(a, b).reshape @ _DIAG == conv(a, b)
_DIAG = np.zeros((NLIMBS * NLIMBS, 2 * NLIMBS), dtype=np.int32)
for _i in range(NLIMBS):
    for _j in range(NLIMBS):
        _DIAG[_i * NLIMBS + _j, _i + _j] = 1


# ------------------------------------------------------------- primitives
# Representation: canonical digits everywhere — every op returns limbs
# in [0, 2^12) with value in [0, m), so limb-wise equality IS value
# equality and window extraction reads digits directly.
#
# No op loops over the limbs: each is a fixed chain of whole-vector
# steps (a few rounds of parallel carries, then one int32 addition that
# resolves the last ripple of carries for all 22 limbs at once), so a
# device runs a field op as a dozen or so fused kernels, where a loop of
# one step a limb ran hundreds of small ones (PERF.md §6).
#
# Compile-cost note: a bucket shape compiles cold in tens of seconds;
# the persistent XLA compile cache (utils/compilecache, on by default in
# tests and bench) makes every later process a cache hit, and the
# power-of-two bucketing keeps the shape set small.

# a limb vector times _UP[w] is the vector moved one limb up (limb 0
# becomes 0, the top limb drops out), times _POW2 the word whose bit i
# is limb i (for limbs of 0 or 1).  Matmuls rather than slices: the
# compiler fuses a slice into each reader, and a chain of field ops
# would then recompute every earlier op once for each of its readers.
_UP = {w: np.eye(w, k=1, dtype=np.int32) for w in (NLIMBS, 2 * NLIMBS)}
_POW2 = (1 << np.arange(NLIMBS, dtype=np.int32)).astype(np.int32)
_LIMB_BITS = np.arange(NLIMBS, dtype=np.int32)


def _carry_save(v, rounds: int):
    """``rounds`` rounds of parallel carries over non-negative limbs:
    each limb keeps its low 12 bits and hands the rest one limb up, and
    what leaves the top limb is dropped (the value changes by a multiple
    of 2^(12 * width)).  One round takes limbs below 2^13 to at most
    4096, two take limbs below 2^24 there and three limbs below 2^31."""
    up = jnp.asarray(_UP[v.shape[-1]])
    for _ in range(rounds):
        v = (v & MASK) + (v >> BITS) @ up
    return v


def _ripple(v):
    """22 limbs in [0, 4096] -> (canonical digits of the value mod
    2^264, the carry out of the top limb).  A limb generates a carry
    when it is 4096 and passes one on when it is 4095, which is binary
    addition: with a = generate | propagate and b = generate as 22-bit
    words, a + b carries into bit i exactly where the limbs carry into
    limb i, and bit i of the sum is that carry xor propagate."""
    gen = v >> BITS
    prop = (v == MASK).astype(v.dtype)
    total = (2 * gen + prop) @ jnp.asarray(_POW2)  # a + b
    carry_in = ((total[..., None] >> _LIMB_BITS) & 1) ^ prop
    return (v + carry_in) & MASK, total >> NLIMBS


def _carry(v, rounds: int):
    """Non-negative limbs (bounded as :func:`_carry_save` says) ->
    canonical digits of the value mod 2^264."""
    return _ripple(_carry_save(v, rounds))[0]


def _cond_sub_m(a, mod: _Mod):
    """Canonical digits of a value below 2m -> canonical [0, m): a + (2^264
    - m) carries out of the top limb, in its one round of parallel
    carries or in the ripple after it, exactly when a >= m, and its
    digits are then those of a - m."""
    v = a + jnp.asarray(mod.neg)
    top = lax.index_in_dim(v, NLIMBS - 1, axis=-1, keepdims=False) >> BITS
    d, ripple_top = _ripple(_carry_save(v, 1))
    return jnp.where((top + ripple_top > 0)[..., None], d, a)


def add(a, b, mod: _Mod):
    return _cond_sub_m(_carry(a + b, 1), mod)


def sub(a, b, mod: _Mod):
    """a - b (canonical inputs): a + m - b lands in (0, 2m).  The limbs
    of a - b + m may be negative; adding ``_ZERO_BIAS`` (4096 to
    limb 0, 4095 to the others, 2^264 in all) makes each non-negative,
    and the value mod 2^264 is unchanged."""
    v = a - b + jnp.asarray(mod.limbs + _ZERO_BIAS)
    return _cond_sub_m(_carry(v, 2), mod)


def mul(a, b, mod: _Mod):
    """Montgomery product a*b*R^-1 mod m.  Canonical output; inputs may
    be any canonical-DIGIT vectors as long as a*b < R*m (both < m, or
    one < m and the other < R — the raw-input to-Montgomery case).

    Whole-width reduction: with T = a*b, Q = (T mod R) * (-m^-1) mod R
    makes T + Q*m a multiple of R, and (T + Q*m) / R < 2m.  T's limbs
    are one outer product and a constant anti-diagonal matmul; Q and
    Q*m are matmuls by constant Toeplitz matrices of -m^-1 and m.

    int32 bounds: conv limbs <= 22*4095^2 ~ 3.7e8, T + Q*m's <= twice
    that — peak < 7.4e8 < 2^31."""
    outer = (a[..., :, None] * b[..., None, :]).reshape(
        a.shape[:-1] + (NLIMBS * NLIMBS,)
    )
    t = outer @ jnp.asarray(_DIAG)  # (..., 44) conv limbs
    t_lo = _carry_save(t[..., :NLIMBS], 3)  # T mod R, limbs <= 4096
    q = _carry(t_lo @ jnp.asarray(mod.toep_neg_inv), 3)
    u = _carry_save(t + q @ jnp.asarray(mod.toep), 3)
    # the low half is now a multiple of R below 2R: it is R, and carries
    # one into the high half, exactly when one of its limbs is nonzero
    lo_carry = jnp.any(u[..., :NLIMBS] != 0, axis=-1).astype(u.dtype)
    hi = u[..., NLIMBS:] + lo_carry[..., None] * jnp.asarray(_UNIT)
    return _cond_sub_m(_carry(hi, 1), mod)


def sqr(a, mod: _Mod):
    return mul(a, a, mod)


def to_mont(a, mod: _Mod):
    """Raw canonical-limb value (< 2^264) -> Montgomery domain, reduced
    mod m (the mul's own reduction absorbs values >= m)."""
    return mul(a, jnp.asarray(mod.r2), mod)


def from_mont(a, mod: _Mod):
    """Montgomery domain -> plain canonical value in [0, m)."""
    return mul(a, jnp.asarray(mod.one_plain), mod)


def select(cond, a, b):
    return jnp.where(cond[..., None], a, b)


def is_zero(a) -> jnp.ndarray:
    """(...,) bool — canonical-input zero test (0 is 0 in Montgomery)."""
    return jnp.all(a == 0, axis=-1)


def _lt_const(a, climbs) -> jnp.ndarray:
    """(..., 22) canonical digits < host constant?  Unrolled
    borrow-chain compare."""
    borrow = jnp.zeros(a.shape[:-1], dtype=a.dtype)
    for i in range(NLIMBS):
        d = a[..., i] - jnp.int32(int(climbs[i])) - borrow
        borrow = lax.shift_right_logical(d, 31) & 1
    return borrow == 1


def _add_const(a, climbs):
    """(..., 22) + host constant, carried back to canonical digits (the
    sum must stay < 2^264; used for r + n < 2^257)."""
    return _carry(a + jnp.asarray(climbs), 1)


# ------------------------------------------------ Montgomery batch inverse


def _mont_pow(x, windows, mod: _Mod):
    """x^E in the Montgomery domain for a fixed host exponent given as
    its 4-bit windows, most significant first (:func:`_exponent_windows`):
    a table of x^0..x^15, then per window four squarings and one
    multiplication by the window's entry.  Rolled scans keep the jaxpr
    one multiplication body for the table and one window body regardless
    of the exponent's length.  Used for the batch-inversion Fermat chain
    (E = m - 2) and the ecrecover square-root chain (E = (p+1)/4)."""
    one = jnp.broadcast_to(jnp.asarray(mod.one_mont), x.shape)

    def power(p, _):
        p = mul(p, x, mod)
        return p, p

    _, pows = lax.scan(power, one, None, length=15)  # x^1..x^15
    table = jnp.concatenate([one[None], pows], axis=0)

    def step(acc, w):
        acc, _ = lax.scan(
            lambda a, _: (sqr(a, mod), None), acc, None, length=4)
        entry = lax.dynamic_index_in_dim(table, w, axis=0, keepdims=False)
        return mul(acc, entry, mod), None

    acc, _ = lax.scan(step, one, jnp.asarray(windows))
    return acc


def _mont_pow_inv(x, mod: _Mod):
    """x^(m-2) — the single Fermat chain of the batch-inversion trick."""
    return _mont_pow(x, mod.inv_windows, mod)


def _shifted(x, k: int, fill):
    """x shifted k rows toward higher indices along axis 0, `fill` rows
    entering at the top (static k: unrolled at trace time)."""
    pad = jnp.broadcast_to(fill, (k,) + x.shape[1:])
    return jnp.concatenate([pad, x[:-k]], axis=0)


def batch_inverse(x, mod: _Mod):
    """Montgomery batch inversion of a (B, 22) Montgomery-domain batch:
    every row's inverse for the price of ONE Fermat chain.

    Hillis-Steele inclusive prefix and suffix products (log2(B)
    full-width batched muls each, unrolled at trace time), one
    exponentiation of the total product, then
    inv_i = exclusive_prefix_i * exclusive_suffix_i * total^-1.

    EVERY row must be nonzero: callers sanitize poisonable rows to 1
    (with their verdict masked off) BEFORE calling — a zero row would
    zero the total product and corrupt every other row's inverse.
    """
    one = jnp.asarray(mod.one_mont)
    n = x.shape[0]
    pre = x
    suf = x[::-1]
    k = 1
    while k < n:
        pre = mul(pre, _shifted(pre, k, one), mod)
        suf = mul(suf, _shifted(suf, k, one), mod)
        k *= 2
    suf = suf[::-1]  # inclusive suffix products
    total = pre[-1]
    tinv = _mont_pow_inv(total, mod)
    left = jnp.concatenate([one[None], pre[:-1]], axis=0)
    right = jnp.concatenate([suf[1:], one[None]], axis=0)
    part = mul(left, right, mod)  # prod of all rows but i
    return mul(part, jnp.broadcast_to(tinv, x.shape), mod)


# ------------------------------------------------------------- group ops
# y^2 = x^3 + 7, a = 0: the same complete-by-selects Jacobian formulas
# as ops/bls381 (both curves are a = 0 short Weierstrass).  Infinity is
# Z = 0; all coordinates Montgomery-domain canonical limbs mod p.

_B7_M = _int_to_limbs(FP.to_mont(host_secp.B))  # curve b = 7


def pt_double(X, Y, Z):
    A = sqr(X, FP)
    Bb = sqr(Y, FP)
    Cc = sqr(Bb, FP)
    t = sqr(add(X, Bb, FP), FP)
    D = sub(t, add(A, Cc, FP), FP)
    D = add(D, D, FP)
    E = add(add(A, A, FP), A, FP)
    F = sqr(E, FP)
    X3 = sub(F, add(D, D, FP), FP)
    eight_c = add(add(Cc, Cc, FP), add(Cc, Cc, FP), FP)
    eight_c = add(eight_c, eight_c, FP)
    Y3 = sub(mul(E, sub(D, X3, FP), FP), eight_c, FP)
    Z3 = mul(add(Y, Y, FP), Z, FP)
    return X3, Y3, Z3


def pt_add(X1, Y1, Z1, X2, Y2, Z2):
    """Branch-free complete addition over the batch via selects."""
    z1z = sqr(Z1, FP)
    z2z = sqr(Z2, FP)
    U1 = mul(X1, z2z, FP)
    U2 = mul(X2, z1z, FP)
    S1 = mul(mul(Y1, Z2, FP), z2z, FP)
    S2 = mul(mul(Y2, Z1, FP), z1z, FP)
    H = sub(U2, U1, FP)
    Rr = sub(S2, S1, FP)
    h_zero = is_zero(H)
    r_zero = is_zero(Rr)
    inf1 = is_zero(Z1)
    inf2 = is_zero(Z2)

    I = sqr(add(H, H, FP), FP)
    J = mul(H, I, FP)
    r2 = add(Rr, Rr, FP)
    V = mul(U1, I, FP)
    X3 = sub(sqr(r2, FP), add(J, add(V, V, FP), FP), FP)
    Y3 = sub(
        mul(r2, sub(V, X3, FP), FP), mul(add(S1, S1, FP), J, FP), FP
    )
    Z3 = mul(mul(Z1, Z2, FP), H, FP)
    Z3 = add(Z3, Z3, FP)

    dX, dY, dZ = pt_double(X1, Y1, Z1)
    same = h_zero & r_zero & ~inf1 & ~inf2
    neg = h_zero & ~r_zero & ~inf1 & ~inf2
    X3 = select(same, dX, X3)
    Y3 = select(same, dY, Y3)
    Z3 = select(same, dZ, Z3)
    X3 = select(neg, jnp.zeros_like(X3), X3)
    Y3 = select(neg, jnp.zeros_like(Y3), Y3)
    Z3 = select(neg, jnp.zeros_like(Z3), Z3)
    X3 = select(inf1, X2, X3)
    Y3 = select(inf1, Y2, Y3)
    Z3 = select(inf1, Z2, Z3)
    X3 = select(inf2 & ~inf1, X1, X3)
    Y3 = select(inf2 & ~inf1, Y1, Y3)
    Z3 = select(inf2 & ~inf1, Z1, Z3)
    return X3, Y3, Z3


def on_curve(X_m, Y_m) -> jnp.ndarray:
    """(..., 22) affine Montgomery limbs -> (...,) bool: y^2 == x^3 + 7.
    Canonical-limb equality is value equality (both sides in [0, p))."""
    lhs = sqr(Y_m, FP)
    rhs = add(mul(sqr(X_m, FP), X_m, FP), jnp.asarray(_B7_M), FP)
    return jnp.all(lhs == rhs, axis=-1)


# --------------------------------------------------- fixed G window table


def _build_g_table() -> np.ndarray:
    """(16, 66) int32: j*G for j = 0..15 as flattened Jacobian triples
    (X | Y | Z, 22 Montgomery limbs each; j = 0 -> infinity, Z = 0).
    Pure host bigint — the PR-11 residency pattern: NO table-build
    program ever compiles; `g_table()` device_puts this once."""
    out = np.zeros((16, 3 * NLIMBS), dtype=np.int32)
    out[0, :NLIMBS] = _int_to_limbs(FP.to_mont(1))
    out[0, NLIMBS : 2 * NLIMBS] = _int_to_limbs(FP.to_mont(1))
    acc = None
    for j in range(1, 16):
        acc = host_secp._add(acc, host_secp.G)
        out[j, :NLIMBS] = _int_to_limbs(FP.to_mont(acc[0]))
        out[j, NLIMBS : 2 * NLIMBS] = _int_to_limbs(FP.to_mont(acc[1]))
        out[j, 2 * NLIMBS :] = _int_to_limbs(FP.to_mont(1))
    return out


_G_TABLE_NP = _build_g_table()
_G_TABLE_DEV = None
_G_TABLE_MTX = threading.Lock()


def g_table():
    """The resident device copy of the G window table: host-precomputed,
    `device_put` once per process, passed to the kernel as an argument
    so it is never re-staged per dispatch (PR-11 table residency)."""
    global _G_TABLE_DEV
    if _G_TABLE_DEV is None:
        with _G_TABLE_MTX:
            if _G_TABLE_DEV is None:
                _G_TABLE_DEV = jax.device_put(_G_TABLE_NP)
    return _G_TABLE_DEV


def _lookup_g(gtab, idx):
    """One-hot select from the (16, 66) flat G table by (B,) idx."""
    onehot = (
        idx[:, None] == jnp.arange(16, dtype=jnp.int32)[None, :]
    ).astype(jnp.int32)  # (B, 16)
    sel = onehot @ gtab  # (B, 66)
    return (
        sel[:, :NLIMBS],
        sel[:, NLIMBS : 2 * NLIMBS],
        sel[:, 2 * NLIMBS :],
    )


def _build_q_table(Qx, Qy, Qz):
    """Stacked (16, B, 22) Jacobian window table [0..15]*Q, built as a
    14-step lax.scan of one complete add (the addition law's own
    same-point branch makes entry 2 a doubling), so the jaxpr carries
    ONE add body instead of 13 unrolled ones.  Sanitized rows enter
    with Z = 0, so every multiple of them stays infinity."""
    one = jnp.broadcast_to(jnp.asarray(FP.one_mont), Qx.shape)
    inf = (one, one, jnp.zeros_like(Qx))

    def step(acc, _):
        nxt = pt_add(acc[0], acc[1], acc[2], Qx, Qy, Qz)
        return nxt, nxt

    _, tail = lax.scan(step, (Qx, Qy, Qz), None, length=14)  # 2Q..15Q
    return (
        jnp.concatenate([inf[0][None], Qx[None], tail[0]], axis=0),
        jnp.concatenate([inf[1][None], Qy[None], tail[1]], axis=0),
        jnp.concatenate([inf[2][None], Qz[None], tail[2]], axis=0),
    )


def _lookup_q(qtab, idx):
    """One-hot select from a stacked (16, B, 22) table by (B,) idx."""
    onehot = (
        idx[None, :] == jnp.arange(16, dtype=jnp.int32)[:, None]
    ).astype(jnp.int32)[..., None]  # (16, B, 1)
    tX, tY, tZ = qtab
    return (
        jnp.sum(tX * onehot, axis=0),
        jnp.sum(tY * onehot, axis=0),
        jnp.sum(tZ * onehot, axis=0),
    )


def _windows(a):
    """(B, 22) canonical limbs -> (66, B) int32 4-bit windows, MSB
    first (each 12-bit limb is three windows)."""
    w = jnp.stack([a & MASK, a >> 4, a >> 8], axis=-1) & 15  # (B, 22, 3)
    w = w.reshape(a.shape[0], NWINDOWS)
    return w[:, ::-1].T


# ------------------------------------------------------ GLV device half


def _carry_all(a):
    """Signed conv limbs -> canonical digits at the SAME width (the
    final carry must be provably zero: callers bound the value below
    2^(12*width))."""
    aT = jnp.moveaxis(a, -1, 0)

    def step(c, limb):
        v = limb + c
        return v >> BITS, v & MASK

    _, outT = lax.scan(step, jnp.zeros_like(aT[0]), aT)
    return jnp.moveaxis(outT, 0, -1)


def _mul_shift_384(k, glimbs):
    """round(k * g / 2^384) for a (B, 22) canonical scalar and a host
    constant g < 2^264: one outer-product conv (the mul staging, no
    reduction), +2^383 into the conv limbs (limb 31, weight 2^372,
    value 2^11), a full carry chain (product + rounder < 2^521 < 2^528
    so the 44-digit carry is exact), then the digits above bit 384
    (limb 32 up) — 12 digits, zero-padded back to a (B, 22) scalar."""
    outer = (k[..., :, None] * jnp.asarray(glimbs)[None, :]).reshape(
        k.shape[:-1] + (NLIMBS * NLIMBS,)
    )
    t = outer @ jnp.asarray(_DIAG)  # (B, 44) conv limbs
    t = t.at[..., 31].add(1 << 11)  # + 2^383 = round-half-up
    t = _carry_all(t)
    hi = t[..., 32:]  # digits of weight >= 2^384
    pad = jnp.zeros(k.shape[:-1] + (NLIMBS - hi.shape[-1],), dtype=k.dtype)
    return jnp.concatenate([hi, pad], axis=-1)


def _signed_abs(k):
    """Canonical k in [0, n) holding a signed half -> (|half|, neg):
    halves are < 2^130 in magnitude, so k < 2^132 IS the half and
    anything else encodes k - n."""
    neg = ~_lt_const(k, _int_to_limbs(_GLV_SIGN_BOUND))
    kabs = select(neg, sub(jnp.zeros_like(k), k, FN), k)
    return kabs, neg


def _glv_split(k):
    """(B, 22) plain canonical scalar mod n -> the quad-walk's signed
    halves (|k1|, k1_neg, |k2|, k2_neg) with k1 + lambda*k2 == k (mod
    n).  Mirrors :func:`_split_host` limb for limb."""
    c1 = _mul_shift_384(k, _G1_LIMBS)
    c2 = _mul_shift_384(k, _G2_LIMBS)
    k2 = add(
        mul(c1, jnp.asarray(_M1R), FN), mul(c2, jnp.asarray(_M2R), FN), FN
    )
    k1 = sub(k, mul(k2, jnp.asarray(_LAMR), FN), FN)
    k1a, k1n = _signed_abs(k1)
    k2a, k2n = _signed_abs(k2)
    return k1a, k1n, k2a, k2n


def _windows_glv(a):
    """(B, 22) canonical |half| (< 2^132, limbs 11+ all zero) ->
    (33, B) 4-bit windows, MSB first."""
    h = a[:, : NWINDOWS_GLV // 3]  # 11 limbs cover the 132 live bits
    w = jnp.stack([h & MASK, h >> 4, h >> 8], axis=-1) & 15
    w = w.reshape(a.shape[0], NWINDOWS_GLV)
    return w[:, ::-1].T


def _neg_y(Y, flag):
    """Per-row conditional point negation (Jacobian: negate Y).  Folded
    signs of the GLV halves; canonical 0 stays 0."""
    return select(flag, sub(jnp.zeros_like(Y), Y, FP), Y)


# ------------------------------------------------- the two walk variants


def _doublings(acc, k: int):
    """``k`` doublings of the accumulator, rolled: one doubling body."""
    acc, _ = lax.scan(lambda p, _: (pt_double(*p), None), acc, None,
                      length=k)
    return acc


def _add_all(acc, points):
    """The accumulator plus each of ``points`` in turn, rolled: one add
    body however many points a window adds."""
    stacked = tuple(jnp.stack(c) for c in zip(*points))
    acc, _ = lax.scan(lambda p, q: (pt_add(*p, *q), None), acc, stacked)
    return acc


def _walk_shamir(u1, u2, qtab, gtab):
    """The non-GLV bit-exactness witness: 66 shared windows, per window
    4 doublings (rolled scan) + one G-table add + one Q-table add."""
    u1w = _windows(u1)
    u2w = _windows(u2)
    one_m = jnp.broadcast_to(jnp.asarray(FP.one_mont), u1.shape)

    def step(i, acc):
        # 4 doublings, then the window's two adds, as rolled scans: one
        # doubling body and one add body in the jaxpr (compile cost,
        # not semantics)
        acc = _doublings(acc, 4)
        return _add_all(acc, [
            _lookup_g(gtab, lax.dynamic_index_in_dim(
                u1w, i, axis=0, keepdims=False)),
            _lookup_q(qtab, lax.dynamic_index_in_dim(
                u2w, i, axis=0, keepdims=False)),
        ])

    inf = (one_m, one_m, jnp.zeros_like(u1))
    return lax.fori_loop(0, NWINDOWS, step, inf)


def _walk_glv(u1, u2, qtab, gtab):
    """The GLV quad-scalar walk: both scalars split into signed halves,
    33 shared windows over G, phi(G), Q, phi(Q) — half the doubling
    chain of :func:`_walk_shamir` for the same four adds per window.
    The phi tables are one beta-multiply of the X rows (phi is
    (beta*X, Y, Z) in Jacobian too: x_aff = X/Z^2 scales by beta);
    negative halves negate the looked-up Y per row."""
    k1a, k1n, k2a, k2n = _glv_split(u1)
    l1a, l1n, l2a, l2n = _glv_split(u2)
    wg, wpg = _windows_glv(k1a), _windows_glv(k2a)
    wq, wpq = _windows_glv(l1a), _windows_glv(l2a)

    beta16 = jnp.broadcast_to(jnp.asarray(_BETA_M), (16, NLIMBS))
    pg_tab = jnp.concatenate(
        [mul(gtab[:, :NLIMBS], beta16, FP), gtab[:, NLIMBS:]], axis=-1
    )
    tX, tY, tZ = qtab
    pq_tab = (
        mul(tX, jnp.broadcast_to(jnp.asarray(_BETA_M), tX.shape), FP),
        tY,
        tZ,
    )
    one_m = jnp.broadcast_to(jnp.asarray(FP.one_mont), u1.shape)

    def step(i, acc):
        points = []
        for tab, w, neg, look in (
            (gtab, wg, k1n, _lookup_g),
            (pg_tab, wpg, k2n, _lookup_g),
            (qtab, wq, l1n, _lookup_q),
            (pq_tab, wpq, l2n, _lookup_q),
        ):
            aX, aY, aZ = look(
                tab, lax.dynamic_index_in_dim(w, i, axis=0, keepdims=False)
            )
            points.append((aX, _neg_y(aY, neg), aZ))
        return _add_all(_doublings(acc, 4), points)

    inf = (one_m, one_m, jnp.zeros_like(u1))
    return lax.fori_loop(0, NWINDOWS_GLV, step, inf)


# ------------------------------------------- ecrecover / hashing helpers

# (p+1)/4: the Fermat square-root chain of the R-lift
_SQRT_WINDOWS = _exponent_windows((P + 1) // 4)

# canonical 12-bit limbs (LE) <-> 32 big-endian bytes, as static gathers:
# BE byte j is LE byte k = 31-j, which spans limbs q = 2k//3 and q+1 at
# in-limb shift 8k - 12q in {0, 4, 8}
_BE_Q = np.array([(2 * (31 - j)) // 3 for j in range(32)], dtype=np.int32)
_BE_SH = np.array(
    [8 * (31 - j) - 12 * ((2 * (31 - j)) // 3) for j in range(32)],
    dtype=np.int32,
)
# digest bytes (BE) -> limbs: limb i spans LE bytes k0 = 12i//8 and
# k0+1 at shift 12i - 8*k0 in {0, 4} (top limb reads past byte 31 ->
# two zero pad bytes)
_E_K0 = np.array([(12 * i) // 8 for i in range(NLIMBS)], dtype=np.int32)
_E_SH = np.array(
    [12 * i - 8 * ((12 * i) // 8) for i in range(NLIMBS)], dtype=np.int32
)


def _limbs_to_bytes_be(a):
    """(B, 22) plain canonical limbs (< 2^256) -> (B, 32) uint8, big
    endian — the recovered point's coordinates as Keccak input."""
    lo = a[..., _BE_Q]
    hi = a[..., _BE_Q + 1]
    val = lo + (hi << 12)  # <= 4095 + 4095*4096 < 2^24: int32-safe
    return ((val >> jnp.asarray(_BE_SH)) & 255).astype(jnp.uint8)


def _digest_to_limbs(dig):
    """(B, 32) uint8 big-endian digest -> (B, 22) int32 canonical limbs
    (the raw 256-bit e the verify path expects)."""
    le = dig[..., ::-1].astype(jnp.int32)
    pad = jnp.zeros(dig.shape[:-1] + (2,), dtype=jnp.int32)
    le = jnp.concatenate([le, pad], axis=-1)
    val = le[..., _E_K0] + (le[..., _E_K0 + 1] << 8)
    return (val >> jnp.asarray(_E_SH)) & MASK


# Keccak block for the 64-byte x || y preimage: pad10*1 tail as a host
# constant (0x01 at offset 64, 0x80 at 135; 136-byte rate, one block)
_ADDR_PAD = np.zeros(72, dtype=np.uint8)
_ADDR_PAD[0] = 0x01
_ADDR_PAD[-1] = 0x80


def _address_from_affine(x_aff, y_aff):
    """Plain affine limbs -> (B, 20) uint8 Ethereum address:
    Keccak256(x_be || y_be)[12:], one single-block batched permutation
    (ops/keccak)."""
    from . import keccak as _keccak

    xb = _limbs_to_bytes_be(x_aff)
    yb = _limbs_to_bytes_be(y_aff)
    tail = jnp.broadcast_to(
        jnp.asarray(_ADDR_PAD), x_aff.shape[:-1] + (_ADDR_PAD.shape[0],)
    )
    block = jnp.concatenate([xb, yb, tail], axis=-1)
    dig = _keccak.keccak256_blocks(block[..., None, :])
    return dig[..., 12:32]


# ----------------------------------------------------------- verification


def secp_verify_batch(
    qx, qy, q_valid, e, r, s, is_eth, v, is_rec, addr, gtab,
    *, glv=True, recover=False,
):
    """Batched ECDSA verification, one fused device program.

    qx, qy  : (B, 22) int32 — affine pubkey coordinates, PLAIN canonical
              limbs (host decode/decompress already rejected malformed
              encodings via q_valid; garbage limbs on invalid rows are
              harmless — they feed only multiplications)
    q_valid : (B,) bool — host-side decode verdict
    e       : (B, 22) int32 — raw 256-bit message-hash value (SHA-256
              for cosmos rows, Keccak-256 for eth/ecrecover rows); the
              Montgomery conversion reduces it mod n like the host's % N
    r, s    : (B, 22) int32 — raw signature scalars
    is_eth  : (B,) bool — row wire format: eth R||S||V recovery
              semantics vs cosmos compressed-key semantics
    v       : (B,) int32 — recovery id (0/1); ignored on cosmos rows
    is_rec  : (B,) bool — true ecrecover rows (no pubkey: recover the
              signer from r/v and compare addresses).  Only honored
              under ``recover=True``; callers without such rows pass
              all-False and the cheaper program
    addr    : (B, 20) uint8 — expected sender address on ecrecover rows
    gtab    : (16, 66) int32 — the resident G window table
              (:func:`g_table`), an ARGUMENT so the device_put buffer is
              reused across dispatches instead of re-staged as a baked
              constant
    glv     : trace-time: GLV quad-scalar walk (default) vs the plain
              Shamir witness walk — bit-identical by contract
              (tests/test_secp_glv.py), by COMETBFT_TPU_SECP_GLV
    recover : trace-time: compile the R-lift sqrt chain + the on-device
              address Keccak.  False keeps verify-only batches on a
              program that never pays for either

    Returns (B,) bool, bit-identical to the host verifiers.

    The ``jax.named_scope`` names are the phases a profile is read by
    (docs/observability.md): ``secp_prep`` (on-curve, range and low-s
    checks, the scalars and their batch inversion), ``secp_walk`` (the
    Q window table and the GLV or Shamir walk) and ``secp_final`` (the
    affine normalisation and the verdict).  The XLA module is named
    after the function, ``jit_secp_verify_batch``: a prefix that no
    reader of the ed25519 programs matches.

    Manifest kernels ``secp256k1_verify_batch[_recover][ _noglv]``
    (analysis/kernel_manifest): eqn-budgeted and fingerprint-pinned per
    (glv, recover) variant; jitted by models/secp_verifier's program
    cache.
    """
    with jax.named_scope("secp_prep"):
        row_pre, q_ok, Px_m, Py_m, u1, u2 = _prep(
            qx, qy, q_valid, e, r, s, is_eth, v, is_rec, recover)
    with jax.named_scope("secp_walk"):
        # u1*G + u2*P with P the pubkey (or the lifted R on ecrecover
        # rows); invalid rows enter as infinity
        one_m = jnp.broadcast_to(jnp.asarray(FP.one_mont), qx.shape)
        Pz = select(q_ok, one_m, jnp.zeros_like(qx))
        qtab = _build_q_table(Px_m, Py_m, Pz)
        walk = _walk_glv if glv else _walk_shamir
        X, Y, Z = walk(u1, u2, qtab, gtab)
    with jax.named_scope("secp_final"):
        return row_pre & _final(X, Y, Z, r, is_eth, v, is_rec, addr, recover)


def _prep(qx, qy, q_valid, e, r, s, is_eth, v, is_rec, recover):
    """The device half of validation (on-curve, scalar ranges, low-s)
    and the walk's scalars: (row_pre, q_ok, Px_m, Py_m, u1, u2)."""
    qx_m = to_mont(qx, FP)
    qy_m = to_mont(qy, FP)
    n_l = FN.limbs
    r_ok = ~is_zero(r) & _lt_const(r, n_l)
    s_ok = (
        ~is_zero(s)
        & _lt_const(s, n_l)
        & _lt_const(s, _int_to_limbs(N // 2 + 1))  # low-s: s <= n/2
    )
    if recover:
        v_ok = jnp.where(is_eth | is_rec, v <= 1, True)
        # R-lift: x = r, y = sqrt(x^3 + 7) via x^((p+1)/4), flipped to
        # the parity v — exactly host recover_pubkey's lift (which
        # rejects r >= n before lifting, as r_ok does here)
        rx_m = to_mont(r, FP)
        y2 = add(mul(sqr(rx_m, FP), rx_m, FP), jnp.asarray(_B7_M), FP)
        y_m = _mont_pow(y2, _SQRT_WINDOWS, FP)
        lift_ok = jnp.all(sqr(y_m, FP) == y2, axis=-1)  # y2 was a QR
        y_plain = from_mont(y_m, FP)
        flip = (y_plain[:, 0] & 1) != v
        ry_m = select(flip, sub(jnp.zeros_like(y_m), y_m, FP), y_m)
        q_ok = jnp.where(is_rec, lift_ok, q_valid & on_curve(qx_m, qy_m))
        Px_m = select(is_rec, rx_m, qx_m)
        Py_m = select(is_rec, ry_m, qy_m)
    else:
        v_ok = jnp.where(is_eth, v <= 1, True)
        q_ok = q_valid & on_curve(qx_m, qy_m)
        Px_m, Py_m = qx_m, qy_m
    row_pre = q_ok & r_ok & s_ok & v_ok

    # ---- scalars, the shared denominator amortized across the batch:
    # verify rows    u1 = e/s,  u2 = r/s  (mod n)
    # ecrecover rows u1 = -e/r, u2 = s/r  (Q = r^-1 (s*R - e*G))
    # Sanitize BEFORE the shared product: a zero denominator row would
    # zero the total and poison every valid row's inverse.
    one_plain = jnp.asarray(FN.one_plain)
    if recover:
        w_in = select(is_rec, r, s)
        w_in_ok = jnp.where(is_rec, r_ok, s_ok)
    else:
        w_in = s
        w_in_ok = s_ok
    w_safe = select(w_in_ok, w_in, jnp.broadcast_to(one_plain, s.shape))
    w_m = batch_inverse(to_mont(w_safe, FN), FN)
    e_m = to_mont(e, FN)  # to-Montgomery reduces mod n (host: e % N)
    u1_m = mul(e_m, w_m, FN)
    if recover:
        u1_m = select(
            is_rec, sub(jnp.zeros_like(u1_m), u1_m, FN), u1_m
        )
        u2_src_m = select(is_rec, to_mont(s, FN), to_mont(r, FN))
    else:
        u2_src_m = to_mont(r, FN)
    u1 = from_mont(u1_m, FN)
    u2 = from_mont(mul(u2_src_m, w_m, FN), FN)
    return row_pre, q_ok, Px_m, Py_m, u1, u2


def _final(X, Y, Z, r, is_eth, v, is_rec, addr, recover):
    """The walked point's verdict, per row (row_pre not yet applied)."""
    n_l = FN.limbs
    # ---- affine normalization, z^-1 amortized across the batch (the
    # second shared inversion; Z = 0 rows sanitized exactly like s = 0)
    z_nonzero = ~is_zero(Z)
    z_safe = select(z_nonzero, Z, jnp.broadcast_to(jnp.asarray(FP.one_mont), Z.shape))
    zinv = batch_inverse(z_safe, FP)
    zi2 = sqr(zinv, FP)
    x_aff = from_mont(mul(X, zi2, FP), FP)
    y_aff = from_mont(mul(mul(Y, zi2, FP), zinv, FP), FP)

    # ---- verdict
    rn = _add_const(r, n_l)  # r + n (< 2^257, fits the limb vector)
    x_eq_r = jnp.all(x_aff == r, axis=-1)
    cosmos_ok = x_eq_r | (
        _lt_const(rn, FP.limbs) & jnp.all(x_aff == rn, axis=-1)
    )
    eth_ok = x_eq_r & ((y_aff[:, 0] & 1) == v)
    if recover:
        # the walked point IS the recovered pubkey: address-compare it
        rec_ok = jnp.all(_address_from_affine(x_aff, y_aff) == addr, axis=-1)
        verdict = jnp.where(
            is_rec, rec_ok, jnp.where(is_eth, eth_ok, cosmos_ok)
        )
    else:
        verdict = jnp.where(is_eth, eth_ok, cosmos_ok)
    return z_nonzero & verdict


def secp_hash_verify_batch(
    sha_blocks, sha_active, kec_blocks, kec_active,
    qx, qy, q_valid, r, s, is_eth, v, is_rec, addr, gtab,
    *, glv=True, recover=False,
):
    """The fused hash->verify program: padded message bytes in, verdict
    bits out — ONE dispatch, so firehose ingest never serializes a
    per-tx host hash loop (the hashing-residency seam documented in
    docs/verify_service.md).

    sha_blocks / sha_active : (B, nb, 64) uint8 + (B,) int32 — every
        row's message SHA-256-padded (ops/sha2.pad_messages_sha256)
    kec_blocks / kec_active : (B, nb', 136) uint8 + (B,) int32 — the
        SAME messages Keccak-padded (ops/keccak.pad_messages_keccak)
    remaining args/kwargs   : exactly :func:`secp_verify_batch` minus ``e``

    Both digests are computed for every row (branch-free batch; the
    loser is masked per row), then multiplexed: Keccak-256 for
    eth/ecrecover rows, SHA-256 for cosmos rows — matching the host
    hash choice bit for bit.

    The digests run under the scope ``secp_hash``; the XLA module is
    ``jit_secp_hash_verify_batch``.  Manifest kernels
    ``secp256k1_hash_verify[_recover]``, jitted by models/secp_verifier.
    """
    from . import keccak as _keccak
    from . import sha2 as _sha2

    with jax.named_scope("secp_hash"):
        sha_d = _sha2.sha256_blocks(sha_blocks, sha_active)
        kec_d = _keccak.keccak256_blocks(kec_blocks, kec_active)
        dig = jnp.where((is_eth | is_rec)[..., None], kec_d, sha_d)
        e = _digest_to_limbs(dig)
    return secp_verify_batch(
        qx, qy, q_valid, e, r, s, is_eth, v, is_rec, addr, gtab,
        glv=glv, recover=recover,
    )


# ------------------------------------------------------------ host bridge


def ints_to_limbs_np(vals) -> np.ndarray:
    """Vectorized host packer: a sequence of plain ints (< 2^264) ->
    (B, 22) int32 limb array — one numpy pass over the little-endian
    bytes (3 bytes = 2 limbs), same staging as ops/bls381."""
    n = len(vals)
    if n == 0:
        return np.zeros((0, NLIMBS), dtype=np.int32)
    raw = np.frombuffer(
        b"".join(v.to_bytes(33, "little") for v in vals), dtype=np.uint8
    ).reshape(n, 33)
    trip = raw.reshape(n, NLIMBS // 2, 3).astype(np.int32)
    out = np.empty((n, NLIMBS), dtype=np.int32)
    out[:, 0::2] = trip[..., 0] | ((trip[..., 1] & 0xF) << 8)
    out[:, 1::2] = (trip[..., 1] >> 4) | (trip[..., 2] << 4)
    return out


def from_limbs(a) -> np.ndarray:
    """Host-side limb decoder (plain, NON-Montgomery limbs) -> object
    array of Python ints; receives already-fetched device results."""
    a = np.asarray(a)
    flat = a.reshape(-1, a.shape[-1])
    out = np.empty(flat.shape[0], dtype=object)
    for i, row in enumerate(flat):
        val = 0
        for k in range(len(row) - 1, -1, -1):
            val = (val << BITS) + int(row[k])
        out[i] = val
    return out.reshape(a.shape[:-1])
