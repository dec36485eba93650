"""Limb-range abstract interpreter tests: per-primitive transfer
functions, the scan strategy ladder (unroll / fixpoint / declared
invariant / affine counters), fixture kernels tripping each contract,
certificate round-trip + drift + regen-refusal, and the fast clean gate
over the hash-plane kernels (the full-manifest pass is the slow gate)."""

from __future__ import annotations

import sys
import types

import numpy as np
import pytest

from cometbft_tpu.analysis import kernel_manifest as manifest
from cometbft_tpu.analysis import kernelcheck, rangecheck as rc

kernelcheck._ensure_cpu_backend()

import jax  # noqa: E402  (after the backend pin, the repo convention)
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402


# --------------------------------------------------------------- helpers


def _iv(lo, hi, shape=(), dtype=np.int32):
    return rc.IVal(
        np.full(shape, lo, np.int64),
        np.full(shape, hi, np.int64),
        np.dtype(dtype),
    )


def _interp(fn, ivals):
    """Trace fn at the ivals' shapes/dtypes and interpret abstractly.
    Returns (findings, out_ivals, ctx)."""
    structs = [jax.ShapeDtypeStruct(v.lo.shape, v.dtype) for v in ivals]
    closed = jax.make_jaxpr(fn)(*structs)
    ctx = rc._Ctx("unit")
    outs = rc._interp_jaxpr(ctx, closed.jaxpr, closed.consts, list(ivals))
    findings = [e[1] for e in ctx.events if e[0] == "finding"]
    return findings, outs, ctx


def _bounds(v):
    return int(v.lo.min()), int(v.hi.max())


# ------------------------------------------- per-primitive transfer fns


def test_add_sub_mul_interval_arithmetic():
    findings, outs, _ = _interp(
        lambda x, y: (x + y, x - y, x * y),
        [_iv(-3, 5), _iv(2, 4)],
    )
    assert findings == []
    assert _bounds(outs[0]) == (-1, 9)
    assert _bounds(outs[1]) == (-7, 3)
    assert _bounds(outs[2]) == (-12, 20)


def test_select_n_joins_branches():
    findings, outs, _ = _interp(
        lambda c, x, y: jnp.where(c, x, y),
        [_iv(0, 1, (4,), np.bool_), _iv(0, 5, (4,)), _iv(10, 20, (4,))],
    )
    assert findings == []
    assert _bounds(outs[0]) == (0, 20)


def test_static_shift_scales_bounds():
    findings, outs, _ = _interp(
        lambda x: jnp.left_shift(x, 3), [_iv(1, 4)]
    )
    assert findings == []
    assert _bounds(outs[0]) == (8, 32)


def test_dot_general_abs_sum_contraction():
    # (8,) . (8,): partial sums bounded by depth * |a| * |b| = 800
    findings, outs, ctx = _interp(
        lambda a, b: a @ b, [_iv(0, 10, (8,)), _iv(-10, 10, (8,))]
    )
    assert findings == []
    assert _bounds(outs[0]) == (-800, 800)
    peaks = [e[2] for e in ctx.events if e[0] == "stat" and e[1] == "int32"]
    assert max(peaks) == 800


def test_int32_overflow_is_a_finding():
    findings, _, _ = _interp(
        lambda x: x * x, [_iv(-(2**31) + 1, 2**31 - 1)]
    )
    assert any("int32 overflow" in f for f in findings)


def test_f32_dot_general_exactness_contract():
    # 8 * 2^22 = 2^25 partial sums: past the f32 exact-integer envelope
    findings, _, _ = _interp(
        lambda a, b: a @ b,
        [_iv(0, 1 << 22, (8,), np.float32), _iv(0, 1, (8,), np.float32)],
    )
    assert any("f32" in f and "2^24" in f for f in findings)


def test_unsigned_wraps_instead_of_flagging():
    findings, outs, _ = _interp(
        lambda x: x + jnp.uint8(200), [_iv(100, 150, (), np.uint8)]
    )
    assert findings == []  # wrap is defined behavior, not overflow
    assert _bounds(outs[0]) == (44, 94)  # [300, 350] wraps mod 256


# ------------------------------------------------- one-hot provenance


def test_onehot_dot_general_keeps_table_bound():
    # 16-way one-hot lookup: the contraction must NOT multiply the
    # table bound by the table size (the lookup_niels shape).
    def f(tbl, idx):
        onehot = (
            jnp.arange(16, dtype=jnp.int32)[:, None] == idx[None, :]
        ).astype(jnp.int32)
        return lax.dot_general(tbl, onehot, (((1,), (0,)), ((), ())))

    findings, outs, _ = _interp(
        f, [_iv(0, 4095, (22, 16)), _iv(0, 15, (4,))]
    )
    assert findings == []
    assert _bounds(outs[0])[1] <= 4095, "one-hot lookup inflated 16x"


def test_onehot_masked_reduce_sum_keeps_bound():
    # sum(tbl * onehot, axis) is the other lookup spelling
    def f(tbl, idx):
        onehot = (
            jnp.arange(16, dtype=jnp.int32)[:, None] == idx[None, :]
        ).astype(jnp.int32)
        return jnp.sum(tbl[:, :, None] * onehot[None, :, :], axis=1)

    findings, outs, _ = _interp(
        f, [_iv(0, 4095, (22, 16)), _iv(0, 15, (4,))]
    )
    assert findings == []
    assert _bounds(outs[0])[1] <= 4095


# ------------------------------------------------- scan strategy ladder


def test_short_fori_unrolls_exactly():
    findings, outs, _ = _interp(
        lambda x: lax.fori_loop(0, 10, lambda i, s: s + jnp.int32(2), x),
        [_iv(0, 0)],
    )
    assert findings == []
    assert _bounds(outs[0]) == (20, 20)  # unrolled: exact, not widened


def test_affine_counter_is_pinned_not_widened():
    # 200 > UNROLL_MAX forces the fixpoint rung; both fori carries are
    # `c + literal` counters, so the final value must be exact and no
    # false int32-overflow finding may appear (the i + 1 trap).
    assert 200 > rc.UNROLL_MAX
    findings, outs, _ = _interp(
        lambda x: lax.fori_loop(0, 200, lambda i, s: s + jnp.int32(1), x),
        [_iv(0, 0)],
    )
    assert findings == []
    assert _bounds(outs[0]) == (200, 200)


def test_long_fori_converges_by_fixpoint():
    # carry saturates at 4: join-fixpoint must converge inside
    # FIXPOINT_MAX_ITERS and keep the bound, with no widening
    def body(i, s):
        return jnp.minimum(s + jnp.int32(1), jnp.int32(4))

    findings, outs, _ = _interp(
        lambda x: lax.fori_loop(0, 200, body, x), [_iv(0, 0)]
    )
    assert findings == []
    assert _bounds(outs[0])[1] <= 4


def test_declared_invariant_rescues_slow_fixpoint(tmp_path):
    # saturation at 50 needs ~50 joins, past FIXPOINT_MAX_ITERS: only
    # the declared (scan, carry, lo, hi) invariant keeps the bound.
    m = types.ModuleType("_rc_inv_fixture")

    def slow_sat(x):
        return lax.fori_loop(
            0, 200, lambda i, s: jnp.minimum(s + jnp.int32(1), jnp.int32(50)), x
        )

    m.slow_sat = slow_sat
    sys.modules["_rc_inv_fixture"] = m

    def kernel(invariants):
        return manifest.Kernel(
            name="fix_inv", fn="_rc_inv_fixture:slow_sat",
            args=(manifest.i32(),), out=(manifest.i32(),),
            arg_ranges=((0, 0),), loop_invariants=invariants,
            max_eqns=1_000_000,
        )

    # fori carries are (i, s): i is an affine counter (auto-pinned), s
    # is carry ordinal 1 and needs the declared bound
    good = rc.check_kernel(kernel(((0, 1, 0, 50),)))
    assert good.ok, good.messages

    # a non-inductive declaration must be rejected, not trusted
    bad = rc.check_kernel(kernel(((0, 1, 0, 3),)))
    assert not bad.ok


# ------------------------------------------- fixture kernels, contracts


def _fixture_module():
    m = types.ModuleType("_rc_fixtures")

    def clean_add(x):
        return x + jnp.int32(1)

    def square(x):
        return x * x

    def f32_dot(a, b):
        return a @ b

    m.clean_add, m.square, m.f32_dot = clean_add, square, f32_dot
    sys.modules["_rc_fixtures"] = m
    return m


def _kernel(fn, args, out, name="fix", **kw):
    return manifest.Kernel(
        name=name, fn=f"_rc_fixtures:{fn}", args=args, out=out,
        max_eqns=1_000_000, **kw,
    )


def test_clean_kernel_report_and_declared_output_range():
    _fixture_module()
    rep = rc.check_kernel(_kernel(
        "clean_add", (manifest.i32(4),), (manifest.i32(4),),
        arg_ranges=((0, 10),), out_ranges=((1, 11),),
    ))
    assert rep.ok and rep.messages == []
    assert rep.peak_int32 == 11 and rep.eqns >= 1
    assert rep.headroom_int32_bits > 25


def test_undeclared_inputs_default_to_full_dtype_range():
    _fixture_module()
    rep = rc.check_kernel(_kernel(
        "square", (manifest.i32(4),), (manifest.i32(4),),
    ))
    assert not rep.ok
    assert any("int32 overflow" in m for m in rep.messages)


def test_f32_partial_sum_contract_trips():
    _fixture_module()
    rep = rc.check_kernel(_kernel(
        "f32_dot", (manifest.f32(4, 8), manifest.f32(8, 4)),
        (manifest.f32(4, 4),),
        arg_ranges=((0, 1 << 22), (0, 2)),
    ))
    assert not rep.ok
    assert any("2^24" in m for m in rep.messages)


def test_escaping_declared_output_range_is_a_finding():
    _fixture_module()
    rep = rc.check_kernel(_kernel(
        "clean_add", (manifest.i32(4),), (manifest.i32(4),),
        arg_ranges=((0, 10),), out_ranges=((0, 5),),
    ))
    assert not rep.ok
    assert any("escapes the declared" in m for m in rep.messages)


def test_manifest_spec_shape_errors_are_manifest_findings():
    _fixture_module()
    arity = _kernel(
        "clean_add", (manifest.i32(4),), (manifest.i32(4),),
        arg_ranges=((0, 1), (0, 1)),  # two entries, one arg
    )
    empty = _kernel(
        "clean_add", (manifest.i32(4),), (manifest.i32(4),),
        arg_ranges=((5, 2),),  # lo > hi
    )
    found = rc._manifest_findings([arity, empty])
    assert len(found) == 2
    assert all(f.check == "range-manifest" for f in found)


# ------------------------------- the comb-tree overflow, pinned (PR 18)


def test_comb_tree_fold_carries_lifted_niels_points():
    """Regression for the live overflow this gate found: the comb TREE
    accumulation lifts Niels table entries to extended points and sums
    two of them before the first field mul.  Table coords are attacker
    chosen (derived from validator pubkeys), so the adversarial input is
    every limb at its canonical maximum — with the F.carry in
    niels_to_extended the whole fold must prove overflow-free."""
    from cometbft_tpu.ops import ed25519 as E

    def fold(yplusx, yminusx, t2d):
        p = E.niels_to_extended(E.Niels(yplusx, yminusx, t2d))
        return E.add(p, p).x  # the first tree round: lifted + lifted

    maximal = [_iv(0, 4095, (22, 4)) for _ in range(3)]
    findings, _, _ = _interp(fold, maximal)
    assert findings == [], findings


def test_comb_tree_fold_uncarried_lift_overflows():
    """The tripwire: re-create the pre-fix shape (lifted sums fed to
    E.add uncarried) and prove the interpreter still catches it — the
    raw y+x / y-x limbs reach +-8190, add's y+x sums hit +-12285 past
    MULIN, and the mul conv partial sums clear 2^31."""
    from cometbft_tpu.ops import ed25519 as E
    from cometbft_tpu.ops import field as F

    def uncarried_fold(yplusx, yminusx, t2d):
        x2 = F.sub(yplusx, yminusx)  # no carry: the pre-fix lift
        y2 = F.add(yplusx, yminusx)
        one = F.one(yplusx.shape[:-2] + yplusx.shape[-1:])
        p = E.Point(
            x2, y2, F.add(one, one), F.mul(t2d, E._c(E._INV_D_L))
        )
        return E.add(p, p).x

    maximal = [_iv(0, 4095, (22, 4)) for _ in range(3)]
    findings, _, _ = _interp(uncarried_fold, maximal)
    assert any(
        "overflow" in f or "exceeds" in f for f in findings
    ), findings


@pytest.mark.parametrize(
    "t2d_range", [(0, 4095), (-4095, 0)], ids=["canonical", "sign_flipped"]
)
def test_comb_chain_step_takes_maximal_table_entries(t2d_range):
    """What the comb's chains do at every step (PR 30): E.add_niels of a
    TIGHT accumulator with a table entry as it is, no lift and no carry.
    The adversarial entry is every limb at its canonical maximum, in the
    stored form and in the sign-flipped one the lookup makes of it
    ((y-x, y+x, -2dxy)); both must prove overflow-free, and the bounds
    the interpreter gives the step's outputs must carry a second step to
    outputs no wider: the chain's loop invariant."""
    from cometbft_tpu.ops import ed25519 as E

    tight = np.full((22, 4), 2051, np.int64)
    tight[0] = 3584
    coord = lambda: rc.IVal(-tight, tight.copy(), np.dtype(np.int32))

    def two_steps(x, y, z, t, yplusx, yminusx, t2d):
        n = E.Niels(yplusx, yminusx, t2d)
        first = E.add_niels(E.Point(x, y, z, t), n)
        return tuple(first) + tuple(E.add_niels(first, n))

    entry = [_iv(0, 4095, (22, 4)), _iv(0, 4095, (22, 4)),
             _iv(*t2d_range, (22, 4))]
    findings, outs, _ = _interp(two_steps, [coord() for _ in range(4)] + entry)
    assert findings == [], findings
    for first, second in zip(outs[:4], outs[4:]):
        assert (second.lo >= first.lo).all() and (second.hi <= first.hi).all()


# ------------------------------------------------------- certificates


def test_certificate_round_trip(tmp_path):
    _fixture_module()
    rep = rc.check_kernel(_kernel(
        "clean_add", (manifest.i32(4),), (manifest.i32(4),),
        arg_ranges=((0, 10),),
    ))
    path = str(tmp_path / "ranges.json")
    rc.write_fingerprints([rep], path)
    golden = rc.load_fingerprints(path)
    assert rc.compare_fingerprints([rep], golden) == []


def test_certificate_drift_missing_and_stale(tmp_path):
    _fixture_module()
    rep = rc.check_kernel(_kernel(
        "clean_add", (manifest.i32(4),), (manifest.i32(4),),
        arg_ranges=((0, 10),),
    ))
    drifted = rep.fingerprint()
    drifted["peak_int32"] += 1
    golden = {
        "fix": drifted,
        manifest.KERNELS[0].name: {"ok": True},  # untraced, real: silent
        "ghost": {"ok": True},  # names no kernel: stale
    }
    found = rc.compare_fingerprints([rep], golden)
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 2
    assert "drifted from its range certificate" in msgs
    assert "regen-ranges" in msgs
    assert "'ghost'" in msgs and "stale" in msgs
    # no certificate at all: its own finding
    missing = rc.compare_fingerprints([rep], {})
    assert len(missing) == 1
    assert "no checked-in range certificate" in missing[0].message


def test_regenerate_refuses_on_open_finding(tmp_path, monkeypatch):
    _fixture_module()
    path = str(tmp_path / "ranges.json")
    bad = _kernel("square", (manifest.i32(4),), (manifest.i32(4),))
    monkeypatch.setattr(manifest, "KERNELS", (bad,))
    findings, _ = rc.regenerate(path)
    assert findings, "overflow must block regeneration"
    assert rc.load_fingerprints(path) == {}, "refusal must not write"

    good = _kernel(
        "clean_add", (manifest.i32(4),), (manifest.i32(4),),
        arg_ranges=((0, 10),),
    )
    monkeypatch.setattr(manifest, "KERNELS", (good,))
    findings, reports = rc.regenerate(path)
    assert findings == [] and len(reports) == 1
    assert set(rc.load_fingerprints(path)) == {"fix"}


def test_summary_shape():
    _fixture_module()
    rep = rc.check_kernel(_kernel(
        "clean_add", (manifest.i32(4),), (manifest.i32(4),),
        arg_ranges=((0, 10),),
    ))
    s = rc.summary([], [rep])
    assert s["ok"] is True and s["kernels"] == 1
    assert s["headroom"]["fix"]["peak_int32"] == 11


# --------------------------------------------------- headroom scaling


def test_max_safe_limb_width_scaling_law():
    # at the current width the measured peak itself must be admitted
    assert rc.max_safe_limb_width(10**9, 256, 12, rc.INT32_MAX) >= 12
    # near-saturated int32 conv: widening is NOT safe
    assert rc.max_safe_limb_width(2 * 10**9, 256, 12, rc.INT32_MAX) == 12
    # tiny peak against the f32 envelope: wide limbs unlock
    assert rc.max_safe_limb_width(4095, 255, 12, rc.F32_EXACT) > 12


def test_field_headroom_groups_and_picks_tightest():
    mk = rc.RangeReport(
        kernel="secp256k1_verify_batch", ok=True, messages=[],
        peak_int32=716255216, peak_int32_at=".:add", peak_f32=0,
        peak_f32_at="", headroom_int32_bits=1.58, headroom_f32_bits=24.0,
        eqns=10,
    )
    out = rc.field_headroom([mk])
    assert out["secp256k1"]["peak"] == 716255216
    assert out["secp256k1"]["max_safe_limb_width"] >= 1
    assert out["ed25519"]["peak"] == 0  # no ed25519 kernels in the list


# ------------------------------------------------------------ the gates


def test_range_gate_fast_hash_plane_clean():
    """Certificates + live interpretation agree on the cheap kernels
    (the full manifest is the slow gate below)."""
    by_name = manifest.by_name()
    fast = [by_name[n] for n in (
        "sha256_blocks", "keccak256_blocks", "merkle_root_from_leaves",
    )]
    findings, reports = rc.run_check(
        kernels=fast, allowlist=rc.default_allowlist()
    )
    assert not findings, "\n".join(f.render() for f in findings)
    assert all(r.ok for r in reports)


@pytest.mark.parametrize("name", ["field_pow_p58_rows", "field_invert_rows"])
def test_field_row_kernels_prove_finding_free(name):
    """The body of the on-chip exponentiation kernel (ops/field, PR 35),
    every limb row its own variable: MULIN rows in, no int32 overflow in
    any of a chain's 263 steps, CARRIED rows out, and the certificate agrees."""
    kernel = manifest.by_name()[name]
    assert kernel.arg_ranges == (manifest.MULIN,)
    assert kernel.out_ranges == (manifest.CARRIED,)
    findings, (report,) = rc.run_check(
        kernels=[kernel], allowlist=rc.default_allowlist()
    )
    assert not findings, "\n".join(f.render() for f in findings)
    assert report.ok and report.peak_int32 < 2**31


def test_per_row_ranges_bound_each_limb_row():
    """A declared range may be one (lo, hi) per index of the leading
    axis; an output row outside its own pair is a finding even when it
    lies inside a wider row's."""
    m = _fixture_module()
    m.identity = lambda x: x + jnp.int32(0)
    m.swap_rows = lambda x: x[::-1]
    rows = ((-7, 7), (-1, 1))
    clean = _kernel("identity", (manifest.i32(2, 3),), (manifest.i32(2, 3),),
                    arg_ranges=(rows,), out_ranges=(rows,))
    assert rc.check_kernel(clean).ok
    swapped = _kernel("swap_rows", (manifest.i32(2, 3),), (manifest.i32(2, 3),),
                      arg_ranges=(rows,), out_ranges=(rows,))
    report = rc.check_kernel(swapped)
    assert not report.ok and "escapes the declared [-1, 1]" in report.messages[0]
    ragged = _kernel("identity", (manifest.i32(3, 3),), (manifest.i32(3, 3),),
                     arg_ranges=(rows,))
    assert "per-row ranges" in rc.check_kernel(ragged).messages[0]


@pytest.mark.slow
def test_range_certificates_match_full_manifest():
    """The acceptance gate, in-process: interpret every manifest kernel
    and hold it to the checked-in certificates (same pass as
    ``python scripts/lint.py --check range cometbft_tpu``)."""
    findings, reports = rc.run_check(allowlist=rc.default_allowlist())
    assert len(reports) == len(manifest.KERNELS)
    assert not findings, "range findings:\n" + "\n".join(
        f.render() for f in findings
    )
