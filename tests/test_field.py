"""Differential tests for GF(2^255-19) limb arithmetic vs Python bigints.

All device ops go through module-level jitted wrappers: eager JAX would
dispatch thousands of tiny XLA ops (the limb kernels are written for one
big fused program), making the suite needlessly slow.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cometbft_tpu.ops import field as F

rng = np.random.default_rng(1234)

mul_j = jax.jit(F.mul)
square_j = jax.jit(F.square)
carry_j = jax.jit(F.carry)
freeze_j = jax.jit(F.freeze)
invert_j = jax.jit(F.invert)
pow_p58_j = jax.jit(F.pow_p58)
to_bytes_j = jax.jit(F.to_bytes)
from_bytes_j = jax.jit(F.from_bytes)
addmul_j = jax.jit(lambda a, b: F.mul(F.add(a, b), F.sub(a, b)))
mul_small_121666_j = jax.jit(lambda a: F.mul_small(a, 121666))


def rand_ints(n):
    return [int.from_bytes(rng.bytes(32), "little") % F.P for _ in range(n)]


def limbs_of(vals):
    """Values -> (22, n) limbs-first batch (lane axis minor)."""
    return jnp.asarray(np.stack([F.to_limbs(v) for v in vals], axis=-1))


def ints_of(limbs):
    """Freeze a batch on device, convert each lane to a Python int."""
    fz = np.asarray(freeze_j(limbs))
    return [F.from_limbs(fz[:, i]) for i in range(fz.shape[-1])]


def test_roundtrip():
    vals = rand_ints(16) + [0, 1, F.P - 1, F.P - 19, (1 << 255) - 20]
    assert ints_of(limbs_of(vals)) == [v % F.P for v in vals]


def test_add_sub_mul_square():
    va, vb = rand_ints(64), rand_ints(64)
    a, b = limbs_of(va), limbs_of(vb)
    assert ints_of(carry_j(F.add(a, b))) == [(x + y) % F.P for x, y in zip(va, vb)]
    assert ints_of(carry_j(F.sub(a, b))) == [(x - y) % F.P for x, y in zip(va, vb)]
    assert ints_of(mul_j(a, b)) == [(x * y) % F.P for x, y in zip(va, vb)]
    assert ints_of(square_j(a)) == [(x * x) % F.P for x in va]


def test_mul_of_uncarried_sums():
    """The MULIN contract: 4-term tight sums go straight into mul."""
    vs = [rand_ints(32) for _ in range(8)]
    ones = limbs_of([1] * 32)
    t = [mul_j(limbs_of(v), ones) for v in vs]  # outputs are TIGHT
    m = mul_j(t[0] + t[1] + t[2] + t[3], t[4] + t[5] + t[6] + t[7])
    want = [
        (sum(vs[j][i] for j in range(4)) * sum(vs[j][i] for j in range(4, 8))) % F.P
        for i in range(32)
    ]
    assert ints_of(m) == want


def test_worst_case_bounds_no_overflow():
    """Adversarial limbs at the documented magnitude bounds."""
    a = np.full((F.NLIMBS, 1), 8204, dtype=np.int32)
    a[0, 0] = 14336
    b = -a.copy()
    for x, y in [(a, a), (a, b), (b, b)]:
        m = mul_j(jnp.asarray(x), jnp.asarray(y))
        want = (F.from_limbs(x[:, 0]) * F.from_limbs(y[:, 0])) % F.P
        assert ints_of(m) == [want]


def test_freeze_and_bytes():
    vals = rand_ints(16) + [0, 1, F.P - 1]
    a = limbs_of(vals)
    bts = np.asarray(to_bytes_j(a))
    for i, v in enumerate(vals):
        assert bts[i].tobytes() == (v % F.P).to_bytes(32, "little")
    assert ints_of(from_bytes_j(jnp.asarray(bts))) == [v % F.P for v in vals]


def test_from_bytes_noncanonical():
    """ZIP-215: y encodings >= p must be accepted and reduce mod p."""
    raw = [F.P + 3, (1 << 255) - 1, (1 << 256) - 1]
    b = jnp.asarray(
        np.stack(
            [np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8) for v in raw]
        )
    )
    assert ints_of(from_bytes_j(b)) == [v % F.P for v in raw]


def test_invert_and_pow_p58():
    vals = rand_ints(8) + [1, 2, F.P - 1]
    a = limbs_of(vals)
    assert ints_of(invert_j(a)) == [pow(v, F.P - 2, F.P) for v in vals]
    e = (F.P - 5) // 8
    assert ints_of(pow_p58_j(a)) == [pow(v, e, F.P) for v in vals]


def test_predicates():
    vals = [0, 1, 2, F.P - 1]
    a = limbs_of(vals)
    assert list(np.asarray(jax.jit(F.is_zero)(a))) == [True, False, False, False]
    assert list(np.asarray(jax.jit(F.is_negative)(a))) == [False, True, False, False]
    eq_j = jax.jit(F.eq)
    assert bool(np.asarray(eq_j(a[..., :1], a[..., :1]))[0])
    assert not bool(np.asarray(eq_j(a[..., 0:1], a[..., 1:2]))[0])


def test_mul_small():
    vals = rand_ints(8)
    assert ints_of(mul_small_121666_j(limbs_of(vals))) == [
        (v * 121666) % F.P for v in vals
    ]


def test_fused_expression():
    va, vb = rand_ints(4), rand_ints(4)
    out = addmul_j(limbs_of(va), limbs_of(vb))
    assert ints_of(out) == [
        ((x + y) * (x - y)) % F.P for x, y in zip(va, vb)
    ]


# ------------------------------------------------ the row form (PR 35)
#
# ops/field's exponentiation chains over limb ROWS: the body of the
# on-chip kernel that pow_p58 / invert run as on a TPU.  The row
# functions are written with operators, so on numpy int32 rows they are
# numpy: whole chains run here in about a second, with a Python loop
# where the kernel has a fori_loop (XLA:CPU needs a quarter of an hour
# to compile one chain's 34,000 unrolled operations, so no test jits it).

E58 = (F.P - 5) // 8
_NUMPY = F._Form(
    F._rows_square, F._rows_mul,
    lambda a, k: functools.reduce(lambda x, _: F._rows_square(x), range(k), a),
)


def numpy_rows(chain, limbs):
    """chain over a (22, n) numpy limb array, row form, numpy arithmetic."""
    rows, _ = F.tile_rule(limbs.shape[1])
    x = np.zeros((F.NLIMBS, rows * F.LANES), np.int32)
    x[:, : limbs.shape[1]] = limbs
    out = chain(list(x.reshape(F.NLIMBS, rows, F.LANES)), _NUMPY)
    return np.stack(out).reshape(F.NLIMBS, -1)[:, : limbs.shape[1]]


def raw_limbs(v):
    """v < 2^264 as 22 digits, NOT reduced mod p (to_limbs reduces)."""
    return np.array([(v >> (F.BITS * i)) & F.MASK for i in range(F.NLIMBS)],
                    dtype=np.int32)


def adversarial_limbs(lanes):
    """(22, lanes) limb columns and their values: 0, 1, p - 1,
    non-canonical values up to 2^264, signed limb vectors at the MULIN
    bounds (the cases test_worst_case_bounds_no_overflow builds for
    mul), random field elements for the rest."""
    top = np.full(F.NLIMBS, 8204, dtype=np.int32)
    top[0] = 14336
    zigzag = top * np.where(np.arange(F.NLIMBS) % 2, -1, 1).astype(np.int32)
    cols = [raw_limbs(v) for v in (
        0, 1, F.P - 1, F.P, F.P + 3, (1 << 255) - 1, (1 << 256) - 1,
        (1 << 264) - 1, (1 << 264) - 9728,
    )] + [top, -top, zigzag, -zigzag]
    cols += [F.to_limbs(v) for v in rand_ints(lanes - len(cols))]
    limbs = np.stack(cols, axis=-1)
    return limbs, [F.from_limbs(limbs[:, i]) % F.P for i in range(lanes)]


@pytest.mark.parametrize("lanes", [128, 256, 384, 1152])
@pytest.mark.parametrize(
    "chain,array_j,exponent",
    [(F._pow_p58_chain, pow_p58_j, E58), (F._invert_chain, invert_j, F.P - 2)],
    ids=["pow_p58", "invert"],
)
def test_row_form_matches_bigint_and_array_form(chain, array_j, exponent, lanes):
    limbs, vals = adversarial_limbs(lanes)
    got = numpy_rows(chain, limbs)
    assert ints_of(jnp.asarray(got)) == [pow(v, exponent, F.P) for v in vals]
    # limb for limb what the array form returns, not only the same value
    assert np.array_equal(got, np.asarray(array_j(jnp.asarray(limbs))))


def test_row_form_as_traced_matches_array_form():
    """The function the manifest traces and the kernel body calls,
    fori_loops and all, evaluated eagerly (one dispatch an operation)."""
    limbs, _ = adversarial_limbs(128)
    with jax.disable_jit():
        got = F.pow_p58_rows(jnp.asarray(limbs).reshape(F.NLIMBS, 1, F.LANES))
    assert np.array_equal(
        np.asarray(got).reshape(F.NLIMBS, -1),
        np.asarray(pow_p58_j(jnp.asarray(limbs))),
    )


@pytest.mark.parametrize(
    "lanes,rule",
    [(1, (1, 1)), (128, (1, 1)), (256, (2, 2)), (384, (3, 3)), (1024, (8, 8)),
     (1152, (16, 8)), (2560, (24, 8)), (10112, (80, 8))],
)
def test_tile_rule(lanes, rule):
    assert F.tile_rule(lanes) == rule


def _cube(x, f):
    """A chain short enough for XLA:CPU to compile under interpret."""
    return f.mul(f.square(x), x)


@pytest.mark.parametrize("shape", [(130,), (1157,), (3, 70)])
def test_kernel_interpreted_matches_array_form(shape, monkeypatch):
    """pallas_call(interpret=True) through the shipped layout code: one
    ragged tile, two tiles with padded rows, a leading batch axis."""
    monkeypatch.setattr(F, "_tiles", functools.partial(F._tiles, interpret=True))
    limbs, _ = adversarial_limbs(int(np.prod(shape)))
    x = jnp.asarray(limbs)
    if len(shape) == 2:  # (22, 210) -> (3, 22, 70)
        x = jnp.moveaxis(x.reshape(F.NLIMBS, *shape), 1, 0)
    got = jax.jit(lambda v: F._on_chip(_cube, v))(x)
    want = jax.jit(lambda v: F.mul(F.square(v), v))(x)
    assert got.shape == x.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_decompress_through_the_kernel_form(monkeypatch):
    """E.decompress with pow_p58 routed as on a TPU (layout code and row
    body; numpy stands in for the pallas_call) equals the array form
    point for point and ok for ok on the ZIP-215 vectors of
    tests/test_ed25519_ops.py."""
    from cometbft_tpu.crypto import _ref25519 as ref
    from cometbft_tpu.ops import ed25519 as E

    encs = [ref.compress(ref.pt_mul(k, ref.BASE)) for k in (1, 2, 7, 2**200 + 5)]
    encs += [y.to_bytes(32, "little") for y in range(ref.P, ref.P + 19)]  # y >= p
    encs += [
        (1 | 1 << 255).to_bytes(32, "little"),  # x = 0 with sign 1
        (ref.P - 1 | 1 << 255).to_bytes(32, "little"),
        (2).to_bytes(32, "little"),  # u/v is no square
        bytes(rng.bytes(31)) + b"\x00",
        b"\xff" * 32,
    ]
    enc = jnp.asarray(np.stack([np.frombuffer(b, np.uint8) for b in encs]))
    want_pt, want_ok = jax.jit(E.decompress)(enc)

    def numpy_tiles(chain, rows, tile):
        assert rows.shape[1] % tile == 0
        return jnp.asarray(np.stack(chain(list(np.asarray(rows)), _NUMPY)))

    monkeypatch.setattr(F, "_for_tpu", lambda: True)
    monkeypatch.setattr(F, "_tiles", numpy_tiles)
    with jax.disable_jit():
        got_pt, got_ok = E.decompress(enc)
    assert np.array_equal(np.asarray(got_ok), np.asarray(want_ok))
    assert {bool(v) for v in np.asarray(want_ok)} == {True, False}
    for got, want in zip(got_pt, want_pt):
        assert np.array_equal(np.asarray(got), np.asarray(want))
