"""The comb's chains as one on-chip kernel (ops/comb._chains_on_chip, PR 37)
against the array form it replaces on a TPU, on the CPU.

The kernel's body is ops/field's row form (a coordinate as 22 limb rows of
lanes) written with operators, so on numpy rows it is numpy: the shipped
layout code runs with numpy standing in for the pallas_call, a tile and a
step at a time, and must return what the array form's scan and fold
return, limb for limb, for every K the rule can give.  One short chain
goes through pallas_call(interpret=True) itself.  Mosaic's lowering is
tests/test_tpu_compile.py's; the chip's answer is chip_smoke.py's.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cometbft_tpu.ops import comb, ed25519 as E, field as F

rng = np.random.default_rng(20261015)


@pytest.mark.parametrize(
    "lanes,k", [(128, 8), (256, 4), (384, 2), (1024, 1), (2500, 1), (10112, 1)]
)
def test_chain_rule(lanes, k):
    """The most chains one tile's sublanes hold: K x rows <= 8, a power
    of two, at least 1; 2,500 is a shard of 10,000 lanes on four chips."""
    assert F.chain_rule(lanes) == k
    rows = -(-lanes // F.LANES)
    assert k == 1 or k * rows <= F.TILE_ROWS < 2 * k * rows


def _limbs(shape, lo, hi):
    return rng.integers(lo, hi + 1, size=shape, dtype=np.int32)


def partials(lanes):
    """Niels partials (64 and 22 positions) and -R, at the bounds the
    program feeds them: canonical table limbs, sign-flipped entries (y+x
    and y-x swapped, t2d negated), lanes whose every partial is the Niels
    identity, and limbs at 4095; -R with TIGHT limbs of either sign."""
    def niels(n):
        yp, ym, t2d = (_limbs((n, F.NLIMBS, lanes), 0, F.MASK) for _ in range(3))
        flip = rng.random((n, 1, lanes)) < 0.5
        yp, ym, t2d = np.where(flip, ym, yp), np.where(flip, yp, ym), np.where(flip, -t2d, t2d)
        for a in (yp, ym, t2d):
            a[:, :, 3] = F.MASK  # a lane at the top of every limb
        t2d[:, :, 4] = -F.MASK
        for a, v in ((yp, 1), (ym, 1), (t2d, 0)):  # lane 5 all identity
            a[:, :, 5] = 0
            a[:, 0, 5] = v
        return E.Niels(*map(jnp.asarray, (yp, ym, t2d)))

    nr = [_limbs((F.NLIMBS, lanes), -2048, 2047) for _ in range(4)]
    for c in nr:
        c[0] = _limbs(lanes, -2048, 3584)
    return niels(comb.NPOS_A), niels(comb.NPOS_B), E.Point(*map(jnp.asarray, nr))


def numpy_chain_tiles(x, nr, k, tile, mul=F._rows_mul):
    """comb._chain_tiles' grid in numpy: every tile, every step, the
    shipped row functions on numpy rows."""
    x, nr = np.asarray(x), np.asarray(nr)
    _, _, steps, rows, _ = x.shape
    per = tile // k
    out = np.zeros((4, F.NLIMBS, rows // k, F.LANES), np.int32)
    for t in range(-(-rows // tile)):
        def coords(a, sl):
            return tuple([a[c, i, sl] for i in range(F.NLIMBS)] for c in range(len(a)))

        n = min(tile, rows - t * tile)  # the last tile may be ragged
        zero = np.zeros((n, F.LANES), np.int32)
        one = np.ones((n, F.LANES), np.int32)
        acc = tuple([one if c in (1, 2) and i == 0 else zero for i in range(F.NLIMBS)]
                    for c in range(4))
        for s in range(steps):
            acc = comb._add_niels_rows(acc, coords(x[:, :, s], slice(t * tile, (t + 1) * tile)),
                                       mul=mul)
        got = comb._fold_rows(acc, coords(nr, slice(t * per, (t + 1) * per)), k, mul=mul)
        out[:, :, t * per:t * per + n // k] = np.stack([np.stack(c) for c in got])
    return jnp.asarray(out)


@pytest.mark.parametrize("k,lanes", [(1, 1100), (2, 384), (4, 256), (8, 128)])
def test_row_chains_match_array_form(k, lanes, monkeypatch):
    """The kernel's layout and row body (numpy for the pallas_call) sum
    the partials to the array form's point limb for limb: K = 1 over two
    tiles, the second ragged, with lanes padded to a row, K = 2 in a tile
    of six rows, K = 4 and K = 8 with identity padding in the last step."""
    na, nb, nr = partials(lanes)
    want = jax.jit(lambda *a: comb._sum_partials(*a, chains=k))(na, nb, nr)
    monkeypatch.setattr(F, "_for_tpu", lambda: True)
    monkeypatch.setattr(comb, "_chain_tiles", numpy_chain_tiles)
    with jax.disable_jit():
        got = comb._sum_partials(na, nb, nr, chains=k)
    for g, w in zip(got, want):
        assert g.shape == w.shape == (F.NLIMBS, lanes)
        assert np.array_equal(np.asarray(g), np.asarray(w))


def _cheap_mul(a, b):
    """A limb-exact stand-in for the multiplication (XLA:CPU needs many
    minutes to compile the interpreted kernel with the real one: 27
    unrolled multiplications of 484 products)."""
    return [(x * y + i) & F.MASK for i, (x, y) in enumerate(zip(a, b))]


def test_short_chain_through_interpreted_kernel(monkeypatch):
    """pallas_call(interpret=True): the grid, the blocks, the identity at
    the first step, the fold's halves and its store at the last, for
    three steps of two chains in one tile, against the same grid in
    numpy; the multiplication is a cheap stand-in on both sides, the real
    one being test_row_chains_match_array_form's."""
    k, steps = 2, 3
    na, _, nr = partials(k * F.LANES)
    x = jnp.stack(na)[:, :steps].reshape(3, steps, F.NLIMBS, k, F.LANES)
    x = jnp.swapaxes(x, 1, 2)  # (3, 22, steps, rows, 128)
    r = jnp.stack(nr)[:, :, :F.LANES, None].reshape(4, F.NLIMBS, 1, F.LANES)
    want = numpy_chain_tiles(x, r, k, k, mul=_cheap_mul)
    monkeypatch.setattr(comb, "_add_niels_rows_j", jax.jit(
        functools.partial(comb._add_niels_rows, mul=_cheap_mul)))
    monkeypatch.setattr(comb, "_fold_rows", functools.partial(
        comb._fold_rows, mul=_cheap_mul))
    got = jax.jit(lambda x, r: comb._chain_tiles(x, r, k, k, interpret=True))(x, r)
    assert got.shape == (4, F.NLIMBS, 1, F.LANES)
    assert np.array_equal(np.asarray(got), np.asarray(want))
