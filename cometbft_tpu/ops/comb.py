"""Comb-based cached Ed25519 verification: the validator-set fast path.

The Straus kernel (ops/ed25519.verify_prepared) spends most of its time in
the 256 shared doublings.  For commit verification the pubkeys are known
long in advance — the validator set changes rarely — so this module trades
HBM for those doublings entirely:

  - per-validator comb tables  T[i][j][v] = j * 16^i * (-A_v),  i<64,
    j<=8 (SIGNED digits: negative digits negate the entry at lookup, so
    only half the entries are stored), in affine Niels form
    (y+x, y-x, 2dxy), built once per validator set and kept
    device-resident (~152 KB/validator; a 10k-validator set is 1.5 GB
    of the chip's 16 GB HBM).  This is the TPU analogue of the reference's
    expanded-pubkey LRU (crypto/ed25519/ed25519.go:43,68), scaled to the
    whole validator set.  Layout (64, 9, 3, 22, V): the validator axis is
    MINOR so every select/add runs with full lane utilization (see
    ops/field.py module doc).
  - a shared radix-4096 comb for the base point B:
    B_TAB[i] = (66, 4096) f32 with column j holding j*4096^i*B, looked up
    with one (66, 4096) x (4096, V) matmul per position on the MXU.

verify_cached then needs NO doublings and NO per-signature table build:
   acc = sum_i T[i][k_i][v]  +  sum_i B_TAB[i][s_i]  - R,   check [8]acc = 0
64 + 22 mixed additions in K parallel chains (K from the lane count:
fold_chains), K unified ones, and one point decompression (R) per signature,
versus 256 doublings + 128 additions + 2 decompressions + table build for
the uncached kernel.

Verification semantics are identical (ZIP-215 / cofactored; see
ops/ed25519.py module doc); tests/test_comb.py checks agreement against
both the uncached kernel and the host verifier.

Range contracts (analysis/rangecheck.py; certificate entries
``comb_*`` in analysis/range_fingerprints.json): the f32 comb planes
never carry more than a single 12-bit digit per partial sum — the
one-hot table lookups are proved to select, not accumulate, so the
peak |f32 value| is 4095, leaving ~12 bits of slack under the 2^24
exact-integer envelope (docs/limb_headroom.md: that slack is what
funds wider comb digits).  The int32 plane peaks at 1,252,794,005 in
the shared field walk.  Comb tables are attacker-influenced device
inputs (a hostile validator key produces arbitrary canonical table
coords), and they reach the field multiplications as they are: every
accumulation path adds a table entry in Niels form with E.add_niels,
whose second operands are the entry's own limbs (|limb| <= 4095,
sign-flipped or not, inside the MULIN mul-input bound) and whose first
are sums of two TIGHT accumulator coordinates.  No lifted sums feed a
multiplication (a fold of lifted points needs the F.carry in
ed25519.niels_to_extended for that; the regression tests in
tests/test_rangecheck.py keep both cases).  The certificate pins the
walk; the rangecheck gate fails any regression.
"""

from __future__ import annotations

import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import ed25519 as E
from . import field as F
from . import scalar
from ..crypto import _ref25519 as ref

NPOS_A = 64  # radix-16 comb positions for the k*(-A) part
NENT_A = 9  # SIGNED digits: entries 0..8, sign applied at lookup
NPOS_B = 22  # radix-4096 comb positions for the s*B part
NENT_B = 4096

_D2_C = F.to_limbs(ref.D2)[:, None]  # (22, 1) broadcastable constant


# --------------------------------------------------- A-table construction


@jax.named_scope("table_build")  # set-up's program, by name in a profile
def build_a_tables(a_enc):
    """(V, 32) uint8 compressed pubkeys ->
       (tables (64, 9, 3, 22, V) int32 affine-Niels, valid (V,) bool).

    Runs once per validator set.  Signed-digit comb: only entries
    j = 0..8 are stored (the lookup negates for digits < 0), halving
    both the HBM footprint and the per-position build work vs a 0..15
    table.  Entries come from a scanned sequential-add chain
    (j*P = (j-1)*P + P; 16*P for the next position is one double of the
    8*P entry).  Entries are normalized to affine with a two-level
    Montgomery batch inversion (3 muls/entry amortized instead of a
    ~265-mul chain each), so the per-verify additions are the cheap
    7-multiply add_niels.

    Manifest kernel ``comb_build_a_tables``: shape/dtype/jaxpr contract
    enforced by analysis/kernelcheck, INCLUDING the compile-cost budget
    (``max_eqns``) every kernel now carries — the normalize pass is
    scan-rolled so the jaxpr stays thousands of equations, not the
    ~85k-equation unrolled build whose XLA compile ran 2m34s
    (on XLA:CPU).  Output limbs are FROZEN canonical, bit-identical
    to :func:`build_a_tables_host` (the compile-free production path).
    """
    pt, valid = E.decompress(a_enc)
    # Invalid encodings are sanitized to the identity BEFORE the chain.
    # Their table rows are never consulted (``a_valid`` masks the
    # verdict), but the garbage off-curve coordinates used to flow into
    # the shared Montgomery batch inversion below — where an
    # attacker-chosen encoding whose chain hits Z ≡ 0 (mod p) would
    # corrupt every VALID validator's inverse through the shared prefix
    # product.  Identity rows keep every Z nonzero (complete formulas on
    # curve points) and make the host build trivially bit-identical on
    # invalid rows too.
    pt = E.select(valid, pt, E.identity((a_enc.shape[0],)))
    p0 = E.neg(pt)  # tables hold multiples of -A
    V = a_enc.shape[0]

    def position_entries(p):
        """[0..8]*p as stacked extended coords (9, 22, V) per coord,
        plus 16*p for the next position.  The entry chain is a scanned
        sequential add (j*p = (j-1)*p + p) — one rolled add body instead
        of an unrolled double/add ladder, for the compile-cost budget;
        affine output is identical (representatives differ, the final
        canonical freeze does not)."""

        def astep(acc, _):
            nxt = E.add(acc, p)
            return nxt, nxt

        e8, rest = lax.scan(astep, p, None, length=NENT_A - 2)  # 2p..8p
        ident = E.identity((V,))
        stack = lambda c: jnp.concatenate(
            [getattr(ident, c)[None], getattr(p, c)[None], getattr(rest, c)]
        )
        return stack("x"), stack("y"), stack("z"), stack("t"), E.double(e8)

    def body(i, carry):
        p, tx, ty, tz, tt = carry
        ex, ey, ez, et, p16 = position_entries(p)
        tx = lax.dynamic_update_index_in_dim(tx, ex, i, axis=0)
        ty = lax.dynamic_update_index_in_dim(ty, ey, i, axis=0)
        tz = lax.dynamic_update_index_in_dim(tz, ez, i, axis=0)
        tt = lax.dynamic_update_index_in_dim(tt, et, i, axis=0)
        return p16, tx, ty, tz, tt

    shape = (NPOS_A, NENT_A, F.NLIMBS, V)
    init = (p0,) + tuple(jnp.zeros(shape, dtype=jnp.int32) for _ in range(4))
    _, tx, ty, tz, tt = lax.fori_loop(0, NPOS_A, body, init)

    niels = _normalize_to_niels(tx, ty, tz)
    # (3, NPOS_A, NENT_A, 22, V) -> (NPOS_A, NENT_A, 3, 22, V)
    tables = jnp.transpose(niels, (1, 2, 0, 3, 4))
    return tables, valid


_BUILD_A_JIT = None
_BUILD_A_MTX = threading.Lock()


def build_a_tables_jit(a_enc):
    """Process-wide jitted build_a_tables so every call site (cache build,
    incremental churn, benches) shares one compiled program per shape.

    Publication is lock-guarded (the parallel/verify._publish_program
    discipline): two threads racing the first verify used to each
    install their OWN ``jax.jit`` wrapper here, guaranteeing two traces
    (and two multi-minute XLA compiles before the scan-rolled rework) of
    the same table build.  The dispatch itself runs outside the lock."""
    global _BUILD_A_JIT
    fn = _BUILD_A_JIT
    if fn is None:
        with _BUILD_A_MTX:
            if _BUILD_A_JIT is None:
                _BUILD_A_JIT = jax.jit(build_a_tables)
            fn = _BUILD_A_JIT
    return fn(a_enc)


def _normalize_to_niels(tx, ty, tz):
    """Extended (pos, ent, 22, V) coords -> stacked affine Niels
    (3, pos, ent, 22, V): (y+x, y-x, 2dxy), limbs FROZEN canonical.

    Batch inversion: Montgomery's trick over the entry axis, then over the
    position axis, so only (22, V) values go through the full inversion
    chain.  Zero Z never occurs (Z=2 after add, Z>0 always on this
    curve's complete formulas; invalid rows are sanitized to identity
    chains before this runs), except entry 0 (identity, Z=1) — safe.

    Every prefix/unwind pass is a ``lax.scan`` — the pre-PR-11 Python
    loops unrolled ~460 field multiplies into ~85k flat jaxpr equations,
    the direct cause of the 2m34s ``jit_build_a_tables`` XLA compile.
    The scans compute the SAME products in the same order; the final
    :func:`ops.field.freeze` canonicalizes the limb representation, so
    the restructure is invisible downstream and the device tables agree
    bit-for-bit with the host-precomputed ones
    (:func:`build_a_tables_host`).
    """

    def mul_carry(c, z):
        p = F.mul(c, z)
        return p, p

    def unwind(running, xs):
        pref_prev, z = xs
        return F.mul(running, z), F.mul(running, pref_prev)

    # level 1: prefix products over the entry axis (batched over pos)
    zs = jnp.moveaxis(tz, 1, 0)  # (ent, pos, 22, V)
    _, pref1_rest = lax.scan(mul_carry, zs[0], zs[1:])
    prefix1 = jnp.concatenate([zs[:1], pref1_rest], axis=0)
    tot1 = prefix1[-1]  # (pos, 22, V)

    # level 2: prefix products over the position axis
    _, pref2_rest = lax.scan(mul_carry, tot1[0], tot1[1:])
    prefix2 = jnp.concatenate([tot1[:1], pref2_rest], axis=0)

    inv_tot2 = F.invert(prefix2[-1])  # (22, V)

    # unwind level 2: inv_tot1[i] = inverse of tot1[i] (reverse scan over
    # positions NPOS_A-1 .. 1; outputs land at their original indices)
    running, inv1_rest = lax.scan(
        unwind, inv_tot2, (prefix2[:-1], tot1[1:]), reverse=True
    )
    inv_tot1 = jnp.concatenate([running[None], inv1_rest], axis=0)

    # unwind level 1: entry-axis inverses, batched over all positions
    run, invz_rest = lax.scan(
        unwind, inv_tot1, (prefix1[:-1], zs[1:]), reverse=True
    )
    inv_z = jnp.moveaxis(
        jnp.concatenate([run[None], invz_rest], axis=0), 0, 1
    )  # (pos, ent, 22, V)

    x = F.mul(tx, inv_z)
    y = F.mul(ty, inv_z)
    xy = F.mul(x, y)
    return F.freeze(
        jnp.stack([F.add(y, x), F.sub(y, x), F.mul(xy, jnp.asarray(_D2_C))])
    )


# ------------------------------------------- host A-table precomputation


def _host_decompress_zip215(pk: bytes):
    """ZIP-215 decompression on host ints with EXACTLY the device
    kernel's semantics (ops/ed25519.decompress): non-canonical y
    accepted, x = 0 with sign 1 accepted, validity = the on-curve check.
    Returns ((x, y, 1, x*y) extended coords, ok)."""
    P = ref.P
    enc = int.from_bytes(pk, "little")
    sign = (enc >> 255) & 1
    y = (enc & ((1 << 255) - 1)) % P
    u = (y * y - 1) % P
    v = (ref.D * y % P * y + 1) % P
    x = u * pow(v, 3, P) % P * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P) % P
    vxx = v * x % P * x % P
    flipped = vxx == (P - u) % P
    ok = vxx == u or flipped
    if flipped:
        x = x * ref.SQRT_M1 % P
    if (x & 1) != sign:
        x = (P - x) % P
    return (x, y, 1, x * y % P), ok


def build_a_tables_host(a_enc: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host-precomputed A-tables: exact-bigint build of the same
    (64, 9, 3, 22, V) int32 tables + (V,) valid that
    :func:`build_a_tables` produces — bit-identical (the device path
    freezes its output to canonical limbs; affine coordinates are
    projective invariants, so both paths land on the same canonical
    field elements), with NO XLA program anywhere.

    This is the cold-start fix of ROADMAP item 1: the jitted build's
    XLA compile ran 2m34s (on XLA:CPU) before the scan-rolled
    rework, and even compile-cached it costs a device round trip per
    new shape.  The host build is pure Python/NumPy — a few ms per
    validator — and its output is ``device_put`` with the entry's
    ``NamedSharding`` (models/comb_verifier._finish_entry), so the
    tables land already sharded over the mesh without tracing anything.
    models/comb_verifier routes builds of up to
    ``COMETBFT_TPU_COMB_HOST_BUILD_MAX`` validators here; the jitted
    kernel remains for bigger sets and as the bit-exactness witness
    (tests/test_comb_hostbuild.py).

    Invalid pubkey rows build from the identity, mirroring the device
    kernel's sanitization (their rows are masked by ``valid``
    downstream).
    """
    P = ref.P
    a_enc = np.ascontiguousarray(np.asarray(a_enc, dtype=np.uint8))
    V = int(a_enc.shape[0])
    valid = np.zeros((V,), dtype=bool)
    p0: list[tuple] = []
    for vrow in range(V):
        pt, ok = _host_decompress_zip215(a_enc[vrow].tobytes())
        valid[vrow] = ok
        p0.append(ref.pt_neg(pt) if ok else ref.IDENT)

    # entries[i][j][v] = j * 16^i * (-A_v) in extended coords
    ext: list[list[list[tuple]]] = [
        [[None] * V for _ in range(NENT_A)] for _ in range(NPOS_A)
    ]
    for vrow in range(V):
        base = p0[vrow]
        for i in range(NPOS_A):
            row = ext[i]
            row[0][vrow] = ref.IDENT
            acc = base
            row[1][vrow] = acc
            for j in range(2, NENT_A):
                acc = ref.pt_add(acc, base)
                row[j][vrow] = acc
            for _ in range(4):
                base = ref.pt_add(base, base)

    # one flat Montgomery batch inversion over every Z (all nonzero:
    # identity Z=1, on-curve chains Z != 0 by completeness)
    flat = [p for row in ext for col in row for p in col]
    prefix = [1]
    for p in flat:
        prefix.append(prefix[-1] * p[2] % P)
    inv = pow(prefix[-1], P - 2, P)
    inv_z = [0] * len(flat)
    for k in range(len(flat) - 1, -1, -1):
        inv_z[k] = inv * prefix[k] % P
        inv = inv * flat[k][2] % P

    # canonical Niels values, serialized LE then decoded to limbs in one
    # vectorized pass (33 bytes cover the 22x12-bit limb span)
    buf = bytearray()
    k = 0
    for i in range(NPOS_A):
        for j in range(NENT_A):
            vals = [bytearray(), bytearray(), bytearray()]
            for vrow in range(V):
                X, Y, _, _ = ext[i][j][vrow]
                iz = inv_z[k]
                k += 1
                x = X * iz % P
                y = Y * iz % P
                vals[0] += ((y + x) % P).to_bytes(33, "little")
                vals[1] += ((y - x) % P).to_bytes(33, "little")
                vals[2] += (x * y % P * ref.D2 % P).to_bytes(33, "little")
            for c in vals:
                buf += c
    raw = np.frombuffer(bytes(buf), dtype=np.uint8).reshape(
        NPOS_A, NENT_A, 3, V, 33
    )
    bits = np.unpackbits(raw, axis=-1, bitorder="little")  # (..., V, 264)
    limbs = bits.reshape(NPOS_A, NENT_A, 3, V, F.NLIMBS, F.BITS).astype(
        np.int32
    )
    limbs = (limbs * (1 << np.arange(F.BITS, dtype=np.int32))).sum(axis=-1)
    # (pos, ent, 3, V, 22) -> (pos, ent, 3, 22, V)
    tables = np.ascontiguousarray(
        limbs.transpose(0, 1, 2, 4, 3), dtype=np.int32
    )
    return tables, valid


# --------------------------------------------------- B-table construction

_B_TABLES = None  # device (NPOS_B, 66, NENT_B) f32, built lazily
_B_TABLES_MTX = threading.Lock()


def build_b_tables() -> np.ndarray:
    """(22, 66, 4096) f32: column j of slab i holds j * 4096^i * B in
    flattened affine Niels.

    Built on HOST with exact integer arithmetic: the table is a pure
    constant (~24 MB), and building it as an XLA program constant-folds
    multi-gigabyte scatters on the CPU backend (minutes of compile).  The
    host build is ~90k extended-coordinate additions plus one Montgomery
    batch inversion over all entries — a couple of seconds of Python,
    once per process.  f32 because the one-hot lookup is an MXU matmul;
    limb values < 2^12 are exact in f32.
    """
    P = ref.P
    out = np.zeros((NPOS_B, NENT_B, 3, F.NLIMBS), dtype=np.int32)
    pts: list[list[tuple]] = []
    base = ref.BASE
    for _ in range(NPOS_B):
        row = [(0, 1, 1, 0), base]
        for j in range(2, NENT_B):
            row.append(ref.pt_add(row[-1], base))
        pts.append(row)
        for _ in range(12):
            base = ref.pt_add(base, base)

    # Montgomery batch inversion of every Z at once
    flat = [p for row in pts for p in row]
    prefix = [1]
    for p in flat:
        prefix.append(prefix[-1] * p[2] % P)
    inv = pow(prefix[-1], P - 2, P)
    inv_z = [0] * len(flat)
    for i in range(len(flat) - 1, -1, -1):
        inv_z[i] = inv * prefix[i] % P
        inv = inv * flat[i][2] % P

    for i in range(NPOS_B):
        for j in range(NENT_B):
            X, Y, _, _ = pts[i][j]
            iz = inv_z[i * NENT_B + j]
            x, y = X * iz % P, Y * iz % P
            out[i, j, 0] = F.to_limbs((y + x) % P)
            out[i, j, 1] = F.to_limbs((y - x) % P)
            out[i, j, 2] = F.to_limbs(x * y % P * ref.D2 % P)
    # (pos, ent, 3, 22) -> (pos, 66, ent): coords flattened, entry minor
    return (
        out.reshape(NPOS_B, NENT_B, 3 * F.NLIMBS)
        .transpose(0, 2, 1)
        .astype(np.float32)
        .copy()
    )


def get_b_tables():
    global _B_TABLES
    if _B_TABLES is None:
        # publish under a lock (same discipline as build_a_tables_jit):
        # two first-verify threads would otherwise both run the ~2s host
        # build and the 24 MB transfer.  The device constant is cached
        # process-wide, so it must never be born inside somebody's jit
        # trace (a stored tracer poisons every later program); force
        # eager creation even when first called under tracing.
        with _B_TABLES_MTX:
            if _B_TABLES is None:
                with jax.ensure_compile_time_eval():
                    _B_TABLES = jnp.asarray(_b_tables_cached())
    return _B_TABLES


def _b_tables_cached() -> np.ndarray:
    """Disk-cache the constant table next to the JAX compile cache."""
    import os

    from ..utils import envknobs

    cache = envknobs.get_str(envknobs.BTAB_CACHE)
    if cache and not cache.endswith(".npy"):
        cache += ".npy"  # np.save appends it; np.load would miss the file
    if cache:
        try:
            tab = np.load(cache)
            # reject stale caches from an older table layout
            if tab.shape == (NPOS_B, 3 * F.NLIMBS, NENT_B) and tab.dtype == np.float32:
                return tab
        except (OSError, ValueError):
            pass
    tab = build_b_tables()
    if cache:
        try:
            os.makedirs(os.path.dirname(cache) or ".", exist_ok=True)
            np.save(cache, tab)
        except OSError:
            pass
    return tab


# ------------------------------------------------------------ verification


NPART = NPOS_A + NPOS_B  # Niels partials a lane sums, before -R

# For each K, the widest batch (K chains x lanes) of one field
# multiplication at which a chain step was measured running out of on-chip
# memory on the chip: up to it a step is bound by the launches of its ~290
# short fusions, so more chains a step are nearly free; past it every
# fusion streams through HBM, time follows the bytes (two to five times
# more a lane), and the fewest multiplications win.  Measured on a TPU v5e
# (PERF.md section 6, PR 30, the K sweep over fifteen lane counts): 8
# chains fast at 384 lanes and slow at 512, 4 fast at 768 and slow at
# 1,024, 2 fast at 2,560 and slow at 3,072.  No K = 16: it pads the 86
# partials to 96, and read 5% under K = 8 at 128 lanes and three times
# over it at 256.
CHAIN_WIDTHS = ((8, 3072), (4, 3072), (2, 5120))


def fold_chains(lanes: int) -> int:
    """K, the number of parallel add_niels chains the accumulation runs
    at this lane count: the most chains whose batch K * lanes stays
    within the width measured fast for that K (CHAIN_WIDTHS), else 1.
    Read off k_dig's lane axis at trace time, so under shard_map it sees
    the shard's own lanes."""
    for k, width in CHAIN_WIDTHS:
        if k * lanes <= width:
            return k
    return 1


def verify_cached(tables, a_valid, r_enc, s_bytes, k_digest, b_tables):
    """Batched cofactored verification against cached comb tables.

    tables   : (64, 9, 3, 22, V) int32 — build_a_tables output
    a_valid  : (V,) bool — per-row pubkey decompression success
    r_enc    : (V, 32) uint8 — signature R halves
    s_bytes  : (V, 32) uint8 — signature s halves
    k_digest : (V, 64) uint8 — SHA-512(R || A || M)
    b_tables : (22, 66, 4096) f32 — get_b_tables()

    Returns (V,) bool.  Rows whose validator did not sign carry dummy
    inputs; callers mask the result.

    Manifest kernel ``comb_verify_cached``.  As the
    shard_map body of ``sharded_verify_cached`` this must stay
    lane-local over the validator axis: any collective it grows is
    caught by the sharded census (analysis/shardcheck,
    docs/sharding_contracts.md).
    """
    # the scope names are the uncached program's (ops/ed25519): one
    # reading of a profile serves both
    with jax.named_scope("scalar_prep"):
        k_limbs = scalar.reduce_mod_l(
            scalar.bytes_to_limbs(k_digest, scalar.NL_X)
        )
        # signed radix-16 digits in [-8, 7]: |d| selects the entry, the
        # sign flips the Niels point ((y+x, y-x, 2dxy) -> (y-x, y+x, -2dxy))
        k_dig = scalar.signed_digits_radix16(k_limbs, NPOS_A)  # (64, V)
        s_ok = scalar.s_lt_l(s_bytes)
        # s as 22 x 12-bit digits, LSB first: exactly its base-2^12 limbs
        s_dig = scalar.bytes_to_limbs(s_bytes, NPOS_B)  # (22, V)

    with jax.named_scope("decompress"):
        r_pt, r_valid = E.decompress(r_enc)

    with jax.named_scope("scalar_mul"):
        acc = _accumulate_chains(tables, k_dig, s_dig, b_tables, r_pt)

    # ---- clear cofactor, check identity
    with jax.named_scope("final_check"):
        acc = E.double(E.double(E.double(acc)))
        return E.is_identity(acc) & a_valid & r_valid & s_ok


def _lookup_partials(tables, k_dig, s_dig, b_tables):
    """Every position's partial point at once, in Niels form: the 64
    sign-adjusted A selections and the 22 B selections, coords
    (64, 22, V) and (22, 22, V).  The selects carry no loop dependence."""
    # ---- A part: all 64 sign-adjusted selections in one shot
    with jax.named_scope("comb_lookup_a"):
        neg_d = k_dig < 0
        absd = jnp.abs(k_dig)
        ents_a = jnp.arange(NENT_A, dtype=jnp.int32)[None, :, None]
        onehot_a = (ents_a == absd[:, None, :]).astype(jnp.int32)  # (64, 9, V)
        sel = jnp.sum(
            tables * onehot_a[:, :, None, None, :], axis=1
        )  # (64, 3, 22, V)
        na = E.Niels(
            F.select(neg_d, sel[:, 1], sel[:, 0]),
            F.select(neg_d, sel[:, 0], sel[:, 1]),
            F.select(neg_d, -sel[:, 2], sel[:, 2]),
        )

    # ---- B part: 22 independent one-hot MXU matmuls (no add chain);
    # unrolled so each keeps a (4096, V) onehot transient instead of one
    # (22, 4096, V) monster
    # f32 one-hot for the MXU path: int32 -> float32 -> int32 is exact
    # for the 12-bit Niels limbs (both conversions are in the manifest's
    # justified ALLOWED_CONVERSIONS set; HIGHEST forbids bf16 passes)
    with jax.named_scope("comb_lookup_b"):
        ents_b = jnp.arange(NENT_B, dtype=jnp.int32)[:, None]
        sels = []
        for i in range(NPOS_B):
            onehot = (ents_b == s_dig[i][None, :]).astype(jnp.float32)
            sels.append(
                jnp.matmul(
                    b_tables[i], onehot, precision=lax.Precision.HIGHEST
                ).astype(jnp.int32)
            )  # (66, V)
        selb = jnp.stack(sels)  # (22, 66, V)
        nb = E.Niels(selb[:, 0:22], selb[:, 22:44], selb[:, 44:66])
    return na, nb


def _accumulate_chains(tables, k_dig, s_dig, b_tables, r_pt, chains=None):
    """K parallel chains of mixed additions, then a short fold.

    The 86 looked-up partials stay in Niels form (no lift), are padded
    with the Niels identity to K * steps and viewed as (steps, K, 22, V);
    K accumulators start at the identity and take steps = ceil(86 / K)
    dependent E.add_niels steps (7 field muls each, one rolled body),
    and the K accumulators fold with -R by unified additions
    (E.tree_reduce_points, ten muls each).  Field muls a lane: K = 1:
    612 (one add_niels a position and the R fold, the fewest), 2: 622,
    4: 656, 8: 696.  K
    comes from the lane count (fold_chains); chains overrides it for
    tests and sweeps only.
    """
    V = k_dig.shape[-1]
    K = fold_chains(V) if chains is None else chains
    steps = -(-NPART // K)
    na, nb = _lookup_partials(tables, k_dig, s_dig, b_tables)

    # the benchmark's per-layer readers match on this scope's name: the
    # whole accumulation, chains and fold, reads under it
    with jax.named_scope("tree_reduce"):
        npad = K * steps - NPART
        pad = E.niels_identity_like(E.Niels(*(c[:npad] for c in na)))
        xs = E.Niels(
            *(
                jnp.concatenate([a, b, i], axis=0).reshape(
                    steps, K, F.NLIMBS, V
                )
                for a, b, i in zip(na, nb, pad)
            )
        )
        accs, _ = lax.scan(
            lambda acc, n: (E.add_niels(acc, n), None),
            E.identity((K, V)),
            xs,
        )
        nr = E.neg(r_pt)
        return E.tree_reduce_points(
            E.Point(
                *(jnp.concatenate([a, r[None]], axis=0) for a, r in zip(accs, nr))
            )
        )
