"""Limb-range abstract interpreter: prove every field-arithmetic
intermediate overflow-free.

An interval abstract interpreter over the jaxprs of every manifest
kernel (the PR-4 ``kernel_manifest`` trace machinery), propagating
per-element ``[lo, hi]`` bounds through the primitive vocabulary the
kernels actually use.  Two contracts per kernel:

1. **No intermediate exceeds its dtype's safe range** — signed int32
   magnitude (a wrapped carry chain is a wrong verdict), and the 2^24
   exact-integer threshold for every float32 value (the MXU one-hot
   matmul trick is exact only below 2^24, including each partial sum
   of a dot_general contraction).  Unsigned dtypes wrap by design
   (SHA/Keccak mod-2^32 adds) and are modelled, not flagged.
2. **Declared output ranges hold** — canonical limb digits out means
   limb-equality-is-value-equality stays true downstream.

Abstract domain: per-element int64 interval arrays saturating at
``SAT``.  Per-element (not whole-array) bounds are load-bearing: the
ed25519 conv bound is provable only because limb 0's larger fold bound
(<= 14336) multiplies into at most one product per output limb — a
uniform whole-array interval would claim 22*14336^2 ~ 4.5e9 and
falsely flag the kernel.

Loop strategy ladder, per ``scan`` (all repo loops lower to scan —
there is no ``while`` in the vocabulary):

* **fixpoint** — join-iterate the carry until it stabilizes (with
  widening to the dtype range after ``FIXPOINT_MAX_ITERS`` joins);
  accepted when the converged body evaluates finding-free.  Handles
  the long chains (the 255-bit BLS subgroup walk) whose carries are
  re-normalized to canonical digits every iteration.
* **exact unroll** — for static lengths <= ``UNROLL_MAX``: loop
  counters become concrete carries, so dynamic_slice starts concretize
  and Montgomery accumulator windows are tracked exactly (join-fixpoint
  diverges on them by construction).
* **declared invariant** — assume-guarantee via
  ``Kernel.loop_invariants``: seed the carry with the declared bound
  and verify one body evaluation preserves it.
* otherwise the loop is a ``range-contract`` finding.

A small provenance-pattern layer recovers the correlations plain
intervals lose: the carry-round residue ``x - (((x + c) >> k) << k)``
is ``[-c, 2^k - 1 - c]``, and conditional add/sub through a comparison
on the same variable (``d - 16 * (d >= 8)``, ``v + 4096 * (v < 0)``,
``d + (borrow(d) << k)``) evaluates piecewise.

Results are pinned as checked-in certificates
(``analysis/range_fingerprints.json``, kernelcheck drift-gate style:
``scripts/lint.py regen-ranges`` refuses while findings are open) plus
a per-kernel headroom report — bits of slack at the tightest
intermediate and the computed max safe limb width per field (the
ROADMAP item-4 instrument, docs/limb_headroom.md).
"""

from __future__ import annotations

import hashlib
import json
import math
import os
from dataclasses import dataclass

import numpy as np

from . import kernel_manifest as km
from .linter import Finding

#: Finding check ids this pass emits (scripts/lint.py uses these for
#: stale-allowlist accounting, mirroring kernelcheck.FINDING_CHECK_IDS).
FINDING_CHECK_IDS = frozenset(
    {"range-contract", "range-fingerprint", "range-manifest"}
)

RANGE_FINGERPRINTS_PATH = os.path.join(
    os.path.dirname(__file__), "range_fingerprints.json"
)

INT32_MIN, INT32_MAX = -(2**31), 2**31 - 1
F32_EXACT = 2**24  # last exactly-representable contiguous integer in f32
#: Interval saturation cap: far above every contract threshold (2^31,
#: 2^24) and low enough that sums of saturated products stay inside
#: int64 (4096 * 2^40 = 2^52).
SAT = 1 << 40
FIXPOINT_MAX_ITERS = 8
UNROLL_MAX = 96  # sha512's 80-round fori must stay unrollable
DSLICE_ENUM_MAX = 128  # dynamic_slice start-enumeration cap
_MAX_FINDINGS_PER_KERNEL = 8


# ------------------------------------------------------------- intervals


def _np64(x) -> np.ndarray:
    return np.asarray(x, dtype=np.int64)


class IVal:
    """One abstract value: elementwise [lo, hi] int64 bounds + dtype."""

    __slots__ = ("lo", "hi", "dtype")

    def __init__(self, lo, hi, dtype):
        self.lo = _np64(lo)
        self.hi = _np64(hi)
        self.dtype = np.dtype(dtype)

    @property
    def shape(self):
        return self.lo.shape

    def concrete(self) -> bool:
        return bool(np.all(self.lo == self.hi))

    def max_abs(self) -> int:
        if self.lo.size == 0:
            return 0
        return int(max(abs(int(self.lo.min())), abs(int(self.hi.max()))))


def _const_ival(arr, dtype) -> IVal:
    a = np.asarray(arr)
    if a.dtype.kind == "b":
        a = a.astype(np.int64)
    elif a.dtype.kind == "f":
        # float consts in these kernels are integral (one-hot tables);
        # round outward so a non-integral constant stays sound
        lo = _np64(np.floor(a))
        hi = _np64(np.ceil(a))
        return IVal(lo, hi, dtype)
    v = _np64(a)
    return IVal(v, v, dtype)


def _join(a: IVal, b: IVal) -> IVal:
    return IVal(np.minimum(a.lo, b.lo), np.maximum(a.hi, b.hi), a.dtype)


def _contains(outer: IVal, inner: IVal) -> bool:
    return bool(np.all(outer.lo <= inner.lo) and np.all(outer.hi >= inner.hi))


def _dtype_range(dtype) -> tuple[int, int]:
    dt = np.dtype(dtype)
    if dt.kind == "b":
        return 0, 1
    if dt.kind == "u":
        return 0, (1 << (8 * dt.itemsize)) - 1
    if dt.kind == "i":
        b = 8 * dt.itemsize
        return -(1 << (b - 1)), (1 << (b - 1)) - 1
    # floats: the exactness envelope is the only meaningful default
    return -F32_EXACT, F32_EXACT


def _bithull(h: np.ndarray) -> np.ndarray:
    """Smallest all-ones mask >= h (elementwise, h >= 0)."""
    v = _np64(np.maximum(h, 0))
    for s in (1, 2, 4, 8, 16, 32):
        v = v | (v >> s)
    return v


def _sat_mul(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Exact product where |x*y| < SAT, +-SAT beyond (elementwise)."""
    pf = x.astype(np.float64) * y.astype(np.float64)
    big = np.abs(pf) >= float(SAT)
    xs = np.where(big, 0, x)
    ys = np.where(big, 0, y)
    exact = xs * ys
    return np.where(big, np.where(pf > 0, SAT, -SAT), exact)


def _mul_bounds(a: IVal, b: IVal) -> tuple[np.ndarray, np.ndarray]:
    c1 = _sat_mul(a.lo, b.lo)
    c2 = _sat_mul(a.lo, b.hi)
    c3 = _sat_mul(a.hi, b.lo)
    c4 = _sat_mul(a.hi, b.hi)
    return (
        np.minimum(np.minimum(c1, c2), np.minimum(c3, c4)),
        np.maximum(np.maximum(c1, c2), np.maximum(c3, c4)),
    )


# ------------------------------------------------------- interpreter state


class _Frame:
    """Per-jaxpr scope: values + defining eqns (for pattern matching)."""

    __slots__ = ("env", "defs")

    def __init__(self):
        self.env: dict = {}
        self.defs: dict = {}


class _Ctx:
    """One kernel interpretation: journal of (stat|finding) events with
    rollback (speculative scan strategies must not leak findings), the
    scan-strategy cache, and the active shard_map mesh sizes."""

    def __init__(self, kernel_name: str, invariants=()):
        self.kernel = kernel_name
        self.events: list = []  # ("finding", msg) | ("stat", cls, v, label)
        self.path: list[str] = []
        self.mesh_sizes: dict[str, int] = {}
        self.cache: dict = {}
        self.cache_refs: list = []  # keep jaxprs alive so id() keys stay valid
        self.eqn_count = 0
        self.scan_ordinal = 0
        self.invariants = {(i[0], i[1]): (i[2], i[3]) for i in invariants}
        self._best = {"int32": 0, "f32": 0}

    def mark(self) -> int:
        return len(self.events)

    def rollback(self, mark: int) -> None:
        del self.events[mark:]
        for cls in self._best:
            self._best[cls] = 0
        for ev in self.events:
            if ev[0] == "stat" and ev[2] > self._best[ev[1]]:
                self._best[ev[1]] = ev[2]

    def finding(self, msg: str) -> None:
        self.events.append(("finding", msg))

    def stat(self, cls: str, value: int, prim: str) -> None:
        if value > self._best[cls]:
            self._best[cls] = value
            self.events.append(
                ("stat", cls, value, f"{'/'.join(self.path) or '.'}:{prim}")
            )

    def label(self, prim: str) -> str:
        return f"{'/'.join(self.path) or '.'}:{prim}"


def _settle(ctx: _Ctx, lo, hi, dtype, prim: str) -> IVal:
    """Normalize a raw transfer result: wrap unsigned, flag+clamp signed
    overflow and f32 exactness loss, saturate, record headroom stats."""
    dt = np.dtype(dtype)
    lo = _np64(lo)
    hi = _np64(hi)
    if dt.kind == "b":
        return IVal(np.clip(lo, 0, 1), np.clip(hi, 0, 1), dt)
    if dt.kind == "u":
        m = 1 << (8 * dt.itemsize)
        span = hi - lo
        lom = lo % m
        him = lom + span
        wide = (span >= m) | (him >= m)
        return IVal(
            np.where(wide, 0, lom), np.where(wide, m - 1, him), dt
        )
    if dt.kind == "f":
        v = int(max(abs(int(lo.min())), abs(int(hi.max())))) if lo.size else 0
        ctx.stat("f32", v, prim)
        if v > F32_EXACT:
            ctx.finding(
                f"f32 exactness: |bound| {v} > 2^24 at {ctx.label(prim)}"
            )
        return IVal(np.clip(lo, -SAT, SAT), np.clip(hi, -SAT, SAT), dt)
    # signed int
    dmin, dmax = _dtype_range(dt)
    v = int(max(abs(int(lo.min())), abs(int(hi.max())))) if lo.size else 0
    ctx.stat("int32", v, prim)
    if lo.size and (int(lo.min()) < dmin or int(hi.max()) > dmax):
        ctx.finding(
            f"{dt.name} overflow: bounds [{int(lo.min())}, {int(hi.max())}] "
            f"exceed [{dmin}, {dmax}] at {ctx.label(prim)}"
        )
        lo = np.clip(lo, dmin, dmax)
        hi = np.clip(hi, dmin, dmax)
    return IVal(lo, hi, dt)


def _out_dtype(eqn):
    return eqn.outvars[0].aval.dtype


def _read(frame: _Frame, atom) -> IVal:
    if hasattr(atom, "val"):  # Literal
        return _const_ival(atom.val, atom.aval.dtype)
    return frame.env[atom]


def _concrete_scalar(frame: _Frame, atom):
    """The concrete integer value of a scalar-or-uniform atom, or None."""
    if hasattr(atom, "val"):
        v = np.asarray(atom.val)
        if v.size and np.all(v.flat[0] == v):
            return int(np.asarray(v.flat[0]).astype(np.int64))
        return None
    iv = frame.env.get(atom)
    if iv is None or not iv.concrete() or iv.lo.size == 0:
        return None
    if np.all(iv.lo.flat[0] == iv.lo):
        return int(iv.lo.flat[0])
    return None


def _peel(frame: _Frame, atom):
    """Follow an atom back through broadcast_in_dim/copy wrappers to the
    var the provenance patterns care about.  Literals (unhashable) are
    returned as-is."""
    seen = 0
    while not hasattr(atom, "val") and atom in frame.defs and seen < 4:
        eqn = frame.defs[atom]
        if eqn.primitive.name in ("broadcast_in_dim", "copy", "squeeze"):
            atom = eqn.invars[0]
            seen += 1
        else:
            break
    return atom

# ------------------------------------------------- provenance patterns
#
# Plain intervals lose correlations between a variable and functions of
# itself.  Three idioms in the field kernels need them back; each match
# INTERSECTS its piecewise bound with the plain transfer (sound both
# ways, tighter together).

_CMP_PRIMS = {"lt", "le", "ge", "gt"}


def _match_def(frame: _Frame, atom, names):
    """The defining eqn of atom when its primitive is in names."""
    atom = _peel(frame, atom)
    if hasattr(atom, "val"):  # Literal: no defining eqn
        return None
    eqn = frame.defs.get(atom)
    if eqn is not None and eqn.primitive.name in names:
        return eqn
    return None


def _const_axes(frame: _Frame, atom, depth: int = 0) -> set:
    """Axes of `atom` along which the value provably does not vary
    (size-1 axes, broadcast-introduced axes, or concrete constants that
    happen to be uniform along the axis)."""
    if hasattr(atom, "val"):
        v = np.asarray(atom.val)
        return {
            i
            for i, s in enumerate(v.shape)
            if s == 1 or (v == np.take(v, [0], axis=i)).all()
        }
    shape = tuple(atom.aval.shape)
    axes = {i for i, s in enumerate(shape) if s == 1}
    iv = frame.env.get(atom)
    if iv is not None and iv.concrete():
        for i, s in enumerate(shape):
            if s > 1 and (iv.lo == np.take(iv.lo, [0], axis=i)).all():
                axes.add(i)
    eqn = frame.defs.get(atom)
    if eqn is not None and depth < 4:
        prim = eqn.primitive.name
        if prim in ("convert_element_type", "copy"):
            axes |= _const_axes(frame, eqn.invars[0], depth + 1)
        elif prim == "broadcast_in_dim":
            bd = eqn.params["broadcast_dimensions"]
            src = eqn.invars[0]
            src_shape = (
                np.shape(src.val)
                if hasattr(src, "val")
                else tuple(src.aval.shape)
            )
            inner = _const_axes(frame, src, depth + 1)
            for d in range(len(shape)):
                if d not in bd:
                    axes.add(d)
                else:
                    i = bd.index(d)
                    if src_shape[i] == 1 or i in inner:
                        axes.add(d)
    return axes


def _distinct_axes(frame: _Frame, atom) -> set:
    """Axes of a CONCRETE `atom` along which every fiber has pairwise-
    distinct values (an iota/arange ramp, possibly broadcast)."""
    if hasattr(atom, "val"):
        v = np.asarray(atom.val)
    else:
        iv = frame.env.get(atom)
        if iv is None or not iv.concrete():
            return set()
        v = iv.lo
    out = set()
    for d, s in enumerate(v.shape):
        if s > 1:
            srt = np.sort(v, axis=d)
            if (np.diff(srt, axis=d) != 0).all():
                out.add(d)
    return out


def _onehot_axes(frame: _Frame, atom, depth: int = 0) -> set:
    """Axes along which `atom` provably has at most one nonzero element,
    all elements in {0, 1}: the one-hot-select idiom
    ``eq(distinct-constant, axis-constant)``, traced through
    convert_element_type and non-replicating broadcast_in_dim.

    This is the relational fact plain intervals lose at every table
    lookup: without it, a 16-entry one-hot matmul is bounded by the
    16x-inflated contraction abs-sum instead of the table entry hull,
    and every downstream conv appears to overflow int32."""
    if hasattr(atom, "val") or depth > 5:
        return set()
    eqn = frame.defs.get(atom)
    if eqn is None:
        return set()
    prim = eqn.primitive.name
    if prim in ("convert_element_type", "copy"):
        return _onehot_axes(frame, eqn.invars[0], depth + 1)
    if prim == "broadcast_in_dim":
        bd = eqn.params["broadcast_dimensions"]
        src = eqn.invars[0]
        src_shape = (
            np.shape(src.val) if hasattr(src, "val") else tuple(src.aval.shape)
        )
        inner = _onehot_axes(frame, src, depth + 1)
        return {
            bd[i]
            for i in inner
            if eqn.params["shape"][bd[i]] == src_shape[i]
        }
    if prim == "eq":
        a, b = eqn.invars
        out = set()
        for x, y in ((a, b), (b, a)):
            out |= _distinct_axes(frame, x) & _const_axes(frame, y)
        return out
    return set()


def _carry_round_bound(frame: _Frame, eqn):
    """sub(x, shl(shra(add(x, c), k), k)) -> [-c, 2^k - 1 - c]."""
    x_atom, y_atom = eqn.invars
    shl = _match_def(frame, y_atom, ("shift_left",))
    if shl is None:
        return None
    k = _concrete_scalar(frame, shl.invars[1])
    if k is None or not (0 < k < 62):
        return None
    shra = _match_def(frame, shl.invars[0], ("shift_right_arithmetic",))
    if shra is None or _concrete_scalar(frame, shra.invars[1]) != k:
        return None
    add = _match_def(frame, shra.invars[0], ("add",))
    if add is None:
        return None
    x_var = _peel(frame, x_atom)
    for xi, ci in ((0, 1), (1, 0)):
        if _peel(frame, add.invars[xi]) is x_var:
            c = _concrete_scalar(frame, add.invars[ci])
            if c is not None:
                return -c, (1 << k) - 1 - c
    return None


def _cond_delta_bound(frame: _Frame, eqn, sign: int):
    """add/sub(v, K * [v cmp C]) evaluated piecewise on the comparison.

    Covers ``d - 16 * (d >= 8)`` (signed radix-16 digits),
    ``v + 4096 * (v < 0)`` (borrow re-add via a compare), and
    ``d + (borrow << k)`` where borrow = shrl(d, 31) [& 1] (borrow
    re-add via the sign bit).  sign is +1 for add, -1 for sub.
    """
    v_atom, w_atom = eqn.invars
    v_var = _peel(frame, v_atom)
    if hasattr(v_var, "val"):  # Literal base: nothing correlated to find
        return None
    v = frame.env.get(v_var)
    if v is None:
        return None

    k_val = None
    cmp_prim = None
    cmp_c = None
    # form A: w = mul(K, convert(cmp(v, C)))  (either operand order)
    mul = _match_def(frame, w_atom, ("mul",))
    if mul is not None:
        for gi, ki in ((0, 1), (1, 0)):
            g = _match_def(frame, mul.invars[gi], ("convert_element_type",))
            kc = _concrete_scalar(frame, mul.invars[ki])
            if g is None or kc is None:
                continue
            cmp_eqn = _match_def(frame, g.invars[0], _CMP_PRIMS)
            if cmp_eqn is None:
                continue
            if _peel(frame, cmp_eqn.invars[0]) is not v_var:
                continue
            c = _concrete_scalar(frame, cmp_eqn.invars[1])
            if c is None:
                continue
            k_val, cmp_prim, cmp_c = kc, cmp_eqn.primitive.name, c
            break
    # form B: w = shift_left(borrow, k), borrow = [and(.,1) of] shrl(v, 31)
    if k_val is None:
        shl = _match_def(frame, w_atom, ("shift_left",))
        if shl is not None:
            ks = _concrete_scalar(frame, shl.invars[1])
            b_atom = shl.invars[0]
            band = _match_def(frame, b_atom, ("and",))
            if band is not None and (
                _concrete_scalar(frame, band.invars[1]) == 1
                or _concrete_scalar(frame, band.invars[0]) == 1
            ):
                b_atom = (
                    band.invars[0]
                    if _concrete_scalar(frame, band.invars[1]) == 1
                    else band.invars[1]
                )
            shrl = _match_def(frame, b_atom, ("shift_right_logical",))
            if (
                ks is not None
                and shrl is not None
                and _peel(frame, shrl.invars[0]) is v_var
                and _concrete_scalar(frame, shrl.invars[1]) == 31
                and np.dtype(v.dtype).itemsize == 4
            ):
                k_val, cmp_prim, cmp_c = 1 << ks, "lt", 0
    if k_val is None:
        return None

    # piecewise: true branch gets +sign*K, false branch +0, on the
    # restriction of v to each side of the comparison
    if cmp_prim == "lt":
        t_lo, t_hi = v.lo, np.minimum(v.hi, cmp_c - 1)
        f_lo, f_hi = np.maximum(v.lo, cmp_c), v.hi
    elif cmp_prim == "le":
        t_lo, t_hi = v.lo, np.minimum(v.hi, cmp_c)
        f_lo, f_hi = np.maximum(v.lo, cmp_c + 1), v.hi
    elif cmp_prim == "ge":
        t_lo, t_hi = np.maximum(v.lo, cmp_c), v.hi
        f_lo, f_hi = v.lo, np.minimum(v.hi, cmp_c - 1)
    else:  # gt
        t_lo, t_hi = np.maximum(v.lo, cmp_c + 1), v.hi
        f_lo, f_hi = v.lo, np.minimum(v.hi, cmp_c)
    d = sign * k_val
    big = np.int64(1) << 62
    t_valid = t_lo <= t_hi
    f_valid = f_lo <= f_hi
    lo = np.minimum(
        np.where(t_valid, t_lo + d, big), np.where(f_valid, f_lo, big)
    )
    hi = np.maximum(
        np.where(t_valid, t_hi + d, -big), np.where(f_valid, f_hi, -big)
    )
    if not bool(np.all(t_valid | f_valid)):
        return None
    return lo, hi


# --------------------------------------------------------------- rules

_RULES: dict = {}


def _rule(name):
    def deco(fn):
        _RULES[name] = fn
        return fn

    return deco


@_rule("add")
def _r_add(ctx, frame, eqn, ins):
    a, b = ins
    lo, hi = a.lo + b.lo, a.hi + b.hi
    pw = _cond_delta_bound(frame, eqn, +1)
    if pw is not None:
        lo, hi = np.maximum(lo, pw[0]), np.minimum(hi, pw[1])
    return [_settle(ctx, lo, hi, _out_dtype(eqn), "add")]


@_rule("sub")
def _r_sub(ctx, frame, eqn, ins):
    a, b = ins
    lo, hi = a.lo - b.hi, a.hi - b.lo
    cr = _carry_round_bound(frame, eqn)
    if cr is not None:
        lo, hi = np.maximum(lo, cr[0]), np.minimum(hi, cr[1])
    pw = _cond_delta_bound(frame, eqn, -1)
    if pw is not None:
        lo, hi = np.maximum(lo, pw[0]), np.minimum(hi, pw[1])
    return [_settle(ctx, lo, hi, _out_dtype(eqn), "sub")]


@_rule("mul")
def _r_mul(ctx, frame, eqn, ins):
    lo, hi = _mul_bounds(*ins)
    return [_settle(ctx, lo, hi, _out_dtype(eqn), "mul")]


@_rule("neg")
def _r_neg(ctx, frame, eqn, ins):
    (a,) = ins
    return [_settle(ctx, -a.hi, -a.lo, _out_dtype(eqn), "neg")]


@_rule("abs")
def _r_abs(ctx, frame, eqn, ins):
    (a,) = ins
    crosses = (a.lo <= 0) & (a.hi >= 0)
    lo = np.where(crosses, 0, np.minimum(np.abs(a.lo), np.abs(a.hi)))
    hi = np.maximum(np.abs(a.lo), np.abs(a.hi))
    return [_settle(ctx, lo, hi, _out_dtype(eqn), "abs")]


@_rule("sign")
def _r_sign(ctx, frame, eqn, ins):
    (a,) = ins
    return [
        _settle(ctx, np.sign(a.lo), np.sign(a.hi), _out_dtype(eqn), "sign")
    ]


@_rule("max")
def _r_max(ctx, frame, eqn, ins):
    a, b = ins
    return [
        _settle(
            ctx,
            np.maximum(a.lo, b.lo),
            np.maximum(a.hi, b.hi),
            _out_dtype(eqn),
            "max",
        )
    ]


@_rule("min")
def _r_min(ctx, frame, eqn, ins):
    a, b = ins
    return [
        _settle(
            ctx,
            np.minimum(a.lo, b.lo),
            np.minimum(a.hi, b.hi),
            _out_dtype(eqn),
            "min",
        )
    ]


@_rule("div")
def _r_div(ctx, frame, eqn, ins):
    a, b = ins

    def tdiv(x, y):
        y = np.where(y == 0, 1, y)
        return (np.abs(x) // np.abs(y)) * np.sign(x) * np.sign(y)

    if bool(np.any((b.lo <= 0) & (b.hi >= 0))):
        # divisor may be zero somewhere: conservative
        m = np.maximum(np.abs(a.lo), np.abs(a.hi))
        return [_settle(ctx, -m, m, _out_dtype(eqn), "div")]
    cands = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            cands.append(tdiv(x, y))
    # a sign change inside the dividend interval adds the 0 quotient
    if bool(np.any((a.lo < 0) & (a.hi > 0))):
        cands.append(np.zeros_like(a.lo))
    lo = cands[0]
    hi = cands[0]
    for c in cands[1:]:
        lo = np.minimum(lo, c)
        hi = np.maximum(hi, c)
    return [_settle(ctx, lo, hi, _out_dtype(eqn), "div")]


@_rule("rem")
def _r_rem(ctx, frame, eqn, ins):
    a, b = ins
    cap = np.maximum(np.maximum(np.abs(b.lo), np.abs(b.hi)) - 1, 0)
    lo = np.where(a.lo >= 0, 0, np.maximum(a.lo, -cap))
    hi = np.where(a.hi <= 0, 0, np.minimum(a.hi, cap))
    return [_settle(ctx, lo, hi, _out_dtype(eqn), "rem")]

def _cmp_bounds(a: IVal, b: IVal, lo_true, hi_true):
    """Generic comparison: lo = 1 when it MUST hold, hi = 1 when it CAN."""
    return _np64(lo_true(a, b)), _np64(hi_true(a, b))


@_rule("lt")
def _r_lt(ctx, frame, eqn, ins):
    a, b = ins
    lo = (a.hi < b.lo).astype(np.int64)
    hi = (a.lo < b.hi).astype(np.int64)
    return [IVal(lo, hi, _out_dtype(eqn))]


@_rule("le")
def _r_le(ctx, frame, eqn, ins):
    a, b = ins
    lo = (a.hi <= b.lo).astype(np.int64)
    hi = (a.lo <= b.hi).astype(np.int64)
    return [IVal(lo, hi, _out_dtype(eqn))]


@_rule("gt")
def _r_gt(ctx, frame, eqn, ins):
    a, b = ins
    lo = (a.lo > b.hi).astype(np.int64)
    hi = (a.hi > b.lo).astype(np.int64)
    return [IVal(lo, hi, _out_dtype(eqn))]


@_rule("ge")
def _r_ge(ctx, frame, eqn, ins):
    a, b = ins
    lo = (a.lo >= b.hi).astype(np.int64)
    hi = (a.hi >= b.lo).astype(np.int64)
    return [IVal(lo, hi, _out_dtype(eqn))]


@_rule("eq")
def _r_eq(ctx, frame, eqn, ins):
    a, b = ins
    both_fixed = (a.lo == a.hi) & (b.lo == b.hi)
    lo = (both_fixed & (a.lo == b.lo)).astype(np.int64)
    overlap = (a.lo <= b.hi) & (b.lo <= a.hi)
    return [IVal(lo, overlap.astype(np.int64), _out_dtype(eqn))]


@_rule("ne")
def _r_ne(ctx, frame, eqn, ins):
    a, b = ins
    both_fixed = (a.lo == a.hi) & (b.lo == b.hi)
    overlap = (a.lo <= b.hi) & (b.lo <= a.hi)
    lo = (~overlap).astype(np.int64)
    hi = (~(both_fixed & (a.lo == b.lo))).astype(np.int64)
    return [IVal(lo, hi, _out_dtype(eqn))]


def _is_boolish(dt) -> bool:
    return np.dtype(dt).kind == "b"


@_rule("and")
def _r_and(ctx, frame, eqn, ins):
    a, b = ins
    dt = _out_dtype(eqn)
    if _is_boolish(dt):
        return [IVal(a.lo & b.lo, a.hi & b.hi, dt)]
    # x & y <= min(x, y) and >= 0 when either side is provably >= 0
    a_nn = a.lo >= 0
    b_nn = b.lo >= 0
    dmin, dmax = _dtype_range(dt)
    lo = np.where(a_nn | b_nn, 0, dmin)
    hi = np.where(
        a_nn & b_nn,
        np.minimum(a.hi, b.hi),
        np.where(b_nn, b.hi, np.where(a_nn, a.hi, dmax)),
    )
    return [IVal(lo, hi, dt)]


@_rule("or")
def _r_or(ctx, frame, eqn, ins):
    a, b = ins
    dt = _out_dtype(eqn)
    if _is_boolish(dt):
        return [IVal(a.lo | b.lo, a.hi | b.hi, dt)]
    a_nn = a.lo >= 0
    b_nn = b.lo >= 0
    dmin, dmax = _dtype_range(dt)
    both = a_nn & b_nn
    lo = np.where(both, np.maximum(a.lo, b.lo), dmin)
    hi = np.where(both, _bithull(np.maximum(a.hi, b.hi)), dmax)
    return [IVal(lo, np.minimum(hi, dmax), dt)]


@_rule("xor")
def _r_xor(ctx, frame, eqn, ins):
    a, b = ins
    dt = _out_dtype(eqn)
    if _is_boolish(dt):
        fixed = (a.lo == a.hi) & (b.lo == b.hi)
        v = a.lo ^ b.lo
        return [IVal(np.where(fixed, v, 0), np.where(fixed, v, 1), dt)]
    a_nn = a.lo >= 0
    b_nn = b.lo >= 0
    dmin, dmax = _dtype_range(dt)
    both = a_nn & b_nn
    lo = np.where(both, 0, dmin)
    hi = np.where(both, _bithull(np.maximum(a.hi, b.hi)), dmax)
    return [IVal(lo, np.minimum(hi, dmax), dt)]


@_rule("not")
def _r_not(ctx, frame, eqn, ins):
    (a,) = ins
    dt = np.dtype(_out_dtype(eqn))
    if dt.kind == "b":
        return [IVal(1 - a.hi, 1 - a.lo, dt)]
    if dt.kind == "u":
        m = (1 << (8 * dt.itemsize)) - 1
        return [IVal(m - a.hi, m - a.lo, dt)]
    return [IVal(-a.hi - 1, -a.lo - 1, dt)]


@_rule("shift_left")
def _r_shl(ctx, frame, eqn, ins):
    a, s = ins
    slo = np.clip(s.lo, 0, 62)
    shi = np.clip(s.hi, 0, 62)
    f = IVal(np.int64(1) << slo, np.int64(1) << shi, a.dtype)
    lo, hi = _mul_bounds(a, f)
    return [_settle(ctx, lo, hi, _out_dtype(eqn), "shift_left")]


@_rule("shift_right_arithmetic")
def _r_shra(ctx, frame, eqn, ins):
    a, s = ins
    slo = np.clip(s.lo, 0, 62)
    shi = np.clip(s.hi, 0, 62)
    c = (a.lo >> slo, a.lo >> shi, a.hi >> slo, a.hi >> shi)
    lo = np.minimum(np.minimum(c[0], c[1]), np.minimum(c[2], c[3]))
    hi = np.maximum(np.maximum(c[0], c[1]), np.maximum(c[2], c[3]))
    return [
        _settle(ctx, lo, hi, _out_dtype(eqn), "shift_right_arithmetic")
    ]


@_rule("shift_right_logical")
def _r_shrl(ctx, frame, eqn, ins):
    a, s = ins
    dt = np.dtype(a.dtype)
    bits = 8 * dt.itemsize
    slo = np.clip(s.lo, 0, bits)
    shi = np.clip(s.hi, 0, bits)
    # nonneg elements behave arithmetically; possibly-negative elements
    # reinterpret two's-complement: value in [2^bits + lo, 2^bits - 1]
    m = np.int64(1) << bits
    nn_lo = np.minimum(a.lo >> shi, a.lo >> slo)
    nn_hi = np.maximum(a.hi >> slo, a.hi >> shi)
    neg_any = a.lo < 0
    all_neg = a.hi < 0
    wrap_lo = np.where(all_neg, (m + a.lo) >> shi, 0)
    wrap_hi = np.where(
        all_neg, (m + a.hi) >> slo, (m - 1) >> slo
    )
    lo = np.where(neg_any, wrap_lo, nn_lo)
    hi = np.where(neg_any, wrap_hi, nn_hi)
    return [_settle(ctx, lo, hi, _out_dtype(eqn), "shift_right_logical")]


@_rule("convert_element_type")
def _r_convert(ctx, frame, eqn, ins):
    (a,) = ins
    dst = np.dtype(eqn.params["new_dtype"])
    if dst.kind == "b":
        nonzero = (a.lo > 0) | (a.hi < 0)
        fixed_zero = (a.lo == 0) & (a.hi == 0)
        return [
            IVal(
                nonzero.astype(np.int64),
                (~fixed_zero).astype(np.int64),
                dst,
            )
        ]
    return [_settle(ctx, a.lo, a.hi, dst, "convert_element_type")]


@_rule("select_n")
def _r_select_n(ctx, frame, eqn, ins):
    pred, *cases = ins
    big = np.int64(1) << 62
    lo = np.full(cases[0].lo.shape, big, dtype=np.int64)
    hi = np.full(cases[0].hi.shape, -big, dtype=np.int64)
    for idx, c in enumerate(cases):
        m = (pred.lo <= idx) & (pred.hi >= idx)
        lo = np.where(m, np.minimum(lo, c.lo), lo)
        hi = np.where(m, np.maximum(hi, c.hi), hi)
    return [IVal(lo, hi, _out_dtype(eqn))]


@_rule("iota")
def _r_iota(ctx, frame, eqn, ins):
    p = eqn.params
    shape, dim = p["shape"], p["dimension"]
    ar = np.arange(shape[dim], dtype=np.int64)
    view = [1] * len(shape)
    view[dim] = shape[dim]
    arr = np.broadcast_to(ar.reshape(view), shape)
    return [IVal(arr, arr, p["dtype"])]

def _both(fn, a: IVal, dtype) -> IVal:
    return IVal(fn(a.lo), fn(a.hi), dtype)


@_rule("broadcast_in_dim")
def _r_broadcast(ctx, frame, eqn, ins):
    (a,) = ins
    p = eqn.params
    shape, bd = p["shape"], p["broadcast_dimensions"]

    def go(x):
        view = [1] * len(shape)
        for i, d in enumerate(bd):
            view[d] = x.shape[i] if x.ndim else 1
        return np.broadcast_to(x.reshape(view), shape)

    return [_both(go, a, _out_dtype(eqn))]


@_rule("reshape")
def _r_reshape(ctx, frame, eqn, ins):
    (a,) = ins
    p = eqn.params
    dims = p.get("dimensions")

    def go(x):
        if dims is not None:
            x = np.transpose(x, dims)
        return np.reshape(x, p["new_sizes"])

    return [_both(go, a, _out_dtype(eqn))]


@_rule("transpose")
def _r_transpose(ctx, frame, eqn, ins):
    (a,) = ins
    perm = eqn.params["permutation"]
    return [_both(lambda x: np.transpose(x, perm), a, _out_dtype(eqn))]


@_rule("rev")
def _r_rev(ctx, frame, eqn, ins):
    (a,) = ins
    dims = tuple(eqn.params["dimensions"])
    return [_both(lambda x: np.flip(x, dims), a, _out_dtype(eqn))]


@_rule("squeeze")
def _r_squeeze(ctx, frame, eqn, ins):
    (a,) = ins
    dims = tuple(eqn.params["dimensions"])
    return [_both(lambda x: np.squeeze(x, dims), a, _out_dtype(eqn))]


@_rule("slice")
def _r_slice(ctx, frame, eqn, ins):
    (a,) = ins
    p = eqn.params
    strides = p["strides"] or (1,) * len(p["start_indices"])
    sl = tuple(
        slice(s, l, st)
        for s, l, st in zip(p["start_indices"], p["limit_indices"], strides)
    )
    return [_both(lambda x: x[sl], a, _out_dtype(eqn))]


@_rule("concatenate")
def _r_concat(ctx, frame, eqn, ins):
    dim = eqn.params["dimension"]
    lo = np.concatenate([i.lo for i in ins], axis=dim)
    hi = np.concatenate([i.hi for i in ins], axis=dim)
    return [IVal(lo, hi, _out_dtype(eqn))]


@_rule("pad")
def _r_pad(ctx, frame, eqn, ins):
    a, pv = ins
    cfg = eqn.params["padding_config"]
    out_shape = tuple(
        lo + hi + d + max(d - 1, 0) * interior
        for d, (lo, hi, interior) in zip(a.shape, cfg)
    )

    def go(x, fill):
        out = np.full(out_shape, np.asarray(fill).reshape(()), dtype=np.int64)
        idx = []
        src = []
        for d, (lo, _hi, interior) in zip(x.shape, cfg):
            pos = lo + np.arange(d, dtype=np.int64) * (interior + 1)
            ok = (pos >= 0) & (pos < out.shape[len(idx)])
            idx.append(pos[ok])
            src.append(np.arange(d)[ok])
        if x.size and all(len(i) for i in idx):
            out[np.ix_(*idx)] = x[np.ix_(*src)]
        elif not cfg:
            out = _np64(x).reshape(out_shape)
        return out

    return [
        IVal(go(a.lo, pv.lo), go(a.hi, pv.hi), _out_dtype(eqn))
    ]


@_rule("reduce_sum")
def _r_reduce_sum(ctx, frame, eqn, ins):
    (a,) = ins
    axes = tuple(eqn.params["axes"])
    # one-hot select: sum(x * onehot, axis) picks at most one term along
    # the one-hot axis -- hull that axis (joined with 0) instead of
    # summing it
    oh_ax = None
    src = eqn.invars[0]
    d = None if hasattr(src, "val") else frame.defs.get(src)
    if d is not None and d.primitive.name == "mul":
        for f in d.invars:
            fiv = None if hasattr(f, "val") else frame.env.get(f)
            if (
                fiv is None
                or not (np.all(fiv.lo >= 0) and np.all(fiv.hi <= 1))
            ):
                continue
            cand = _onehot_axes(frame, f) & set(axes)
            if cand:
                oh_ax = min(cand)
                break
    if oh_ax is not None:
        lo = np.minimum(0, a.lo.min(axis=oh_ax))
        hi = np.maximum(0, a.hi.max(axis=oh_ax))
        rest = tuple(ax - (ax > oh_ax) for ax in axes if ax != oh_ax)
        if rest:
            lo, hi = lo.sum(axis=rest), hi.sum(axis=rest)
        return [_settle(ctx, lo, hi, _out_dtype(eqn), "reduce_sum")]
    return [
        _settle(
            ctx, a.lo.sum(axis=axes), a.hi.sum(axis=axes),
            _out_dtype(eqn), "reduce_sum",
        )
    ]


@_rule("reduce_and")
def _r_reduce_and(ctx, frame, eqn, ins):
    (a,) = ins
    axes = tuple(eqn.params["axes"])
    return [
        IVal(a.lo.min(axis=axes), a.hi.min(axis=axes), _out_dtype(eqn))
    ]


@_rule("reduce_or")
def _r_reduce_or(ctx, frame, eqn, ins):
    (a,) = ins
    axes = tuple(eqn.params["axes"])
    return [
        IVal(a.lo.max(axis=axes), a.hi.max(axis=axes), _out_dtype(eqn))
    ]


@_rule("reduce_max")
def _r_reduce_max(ctx, frame, eqn, ins):
    (a,) = ins
    axes = tuple(eqn.params["axes"])
    return [
        IVal(a.lo.max(axis=axes), a.hi.max(axis=axes), _out_dtype(eqn))
    ]


@_rule("reduce_min")
def _r_reduce_min(ctx, frame, eqn, ins):
    (a,) = ins
    axes = tuple(eqn.params["axes"])
    return [
        IVal(a.lo.min(axis=axes), a.hi.min(axis=axes), _out_dtype(eqn))
    ]


@_rule("device_put")
def _r_device_put(ctx, frame, eqn, ins):
    return list(ins)


@_rule("copy")
def _r_copy(ctx, frame, eqn, ins):
    return list(ins)


@_rule("psum")
def _r_psum(ctx, frame, eqn, ins):
    factor = 1
    for ax in eqn.params["axes"]:
        factor *= ctx.mesh_sizes.get(ax, 1)
    out = []
    for a, ov in zip(ins, eqn.outvars):
        out.append(
            _settle(ctx, a.lo * factor, a.hi * factor, ov.aval.dtype, "psum")
        )
    return out


@_rule("all_gather")
def _r_all_gather(ctx, frame, eqn, ins):
    (a,) = ins
    p = eqn.params
    dim = p["all_gather_dimension"]
    n = p["axis_size"]

    def go(x):
        if p["tiled"]:
            reps = [1] * x.ndim
            reps[dim] = n
            return np.tile(x, reps)
        return np.repeat(np.expand_dims(x, dim), n, axis=dim)

    return [_both(go, a, _out_dtype(eqn))]

@_rule("dot_general")
def _r_dot_general(ctx, frame, eqn, ins):
    a, b = ins
    (ca, cb), (ba, bb) = eqn.params["dimension_numbers"]
    out_dt = _out_dtype(eqn)

    def canon(x, contract, batch):
        free = [
            d for d in range(x.ndim) if d not in contract and d not in batch
        ]
        perm = list(batch) + free + list(contract)
        y = np.transpose(x, perm)
        nb = len(batch)
        nf = len(free)
        bshape = y.shape[:nb]
        fshape = y.shape[nb:nb + nf]
        k = int(np.prod(y.shape[nb + nf:], dtype=np.int64)) if x.ndim else 1
        return (
            y.reshape(
                (int(np.prod(bshape, dtype=np.int64)) if nb else 1,
                 int(np.prod(fshape, dtype=np.int64)) if nf else 1,
                 k)
            ),
            bshape,
            fshape,
        )

    alo, bsh, afsh = canon(a.lo, ca, ba)
    ahi, _, _ = canon(a.hi, ca, ba)
    blo, _, bfsh = canon(b.lo, cb, bb)
    bhi, _, _ = canon(b.hi, cb, bb)
    A_lo = alo[:, :, None, :]
    A_hi = ahi[:, :, None, :]
    B_lo = blo[:, None, :, :]
    B_hi = bhi[:, None, :, :]
    c1 = _sat_mul(A_lo, B_lo)
    c2 = _sat_mul(A_lo, B_hi)
    c3 = _sat_mul(A_hi, B_lo)
    c4 = _sat_mul(A_hi, B_hi)
    pmin = np.minimum(np.minimum(c1, c2), np.minimum(c3, c4))
    pmax = np.maximum(np.maximum(c1, c2), np.maximum(c3, c4))
    # one-hot contraction: when an operand is provably one-hot along its
    # (single) contracted axis, the sum selects at most one product term
    # -- bound by the term hull (joined with 0 for the no-match row)
    # instead of the contraction abs-sum
    onehot = any(
        len(cd) == 1
        and cd[0] in _onehot_axes(frame, atom)
        and np.all(v.lo >= 0)
        and np.all(v.hi <= 1)
        for atom, v, cd in (
            (eqn.invars[0], a, ca),
            (eqn.invars[1], b, cb),
        )
    )
    if onehot:
        lo = np.minimum(0, pmin.min(axis=-1))
        hi = np.maximum(0, pmax.max(axis=-1))
        absum = np.maximum(np.abs(lo), np.abs(hi))
    else:
        lo = pmin.sum(axis=-1)
        hi = pmax.sum(axis=-1)
        # the exactness contract is on PARTIAL sums too: bound them by
        # the sum of absolute product bounds over the contraction
        absum = np.maximum(np.abs(pmin), np.abs(pmax)).sum(axis=-1)
    peak = int(absum.max()) if absum.size else 0
    out_shape = tuple(bsh) + tuple(afsh) + tuple(bfsh)
    lo = lo.reshape(out_shape)
    hi = hi.reshape(out_shape)
    dt = np.dtype(out_dt)
    if dt.kind == "f":
        ctx.stat("f32", peak, "dot_general")
        if peak > F32_EXACT:
            ctx.finding(
                f"f32 dot_general partial sums: |bound| {peak} > 2^24 "
                f"at {ctx.label('dot_general')}"
            )
    elif dt.kind == "i":
        ctx.stat("int32", peak, "dot_general")
        dmin, dmax = _dtype_range(dt)
        if peak > dmax:
            ctx.finding(
                f"{dt.name} dot_general partial sums: |bound| {peak} "
                f"exceeds {dmax} at {ctx.label('dot_general')}"
            )
    return [_settle(ctx, lo, hi, out_dt, "dot_general")]


def _jnp():
    # deferred: the interpreter itself never traces, but the gather /
    # scatter index-map trick executes the primitive eagerly (tiny int32
    # id arrays) to recover the exact index mapping
    import jax  # noqa: F401
    import jax.numpy as jnp

    return jnp


@_rule("gather")
def _r_gather(ctx, frame, eqn, ins):
    op, idx = ins
    p = eqn.params
    out_aval = eqn.outvars[0].aval
    if idx.concrete() and op.lo.size < (1 << 24):
        from jax import lax

        ids = np.arange(op.lo.size, dtype=np.int32).reshape(op.shape)
        jnp = _jnp()
        mode = p["mode"]
        try:
            mapped = np.asarray(
                lax.gather(
                    jnp.asarray(ids),
                    jnp.asarray(idx.lo.astype(np.int32)),
                    dimension_numbers=p["dimension_numbers"],
                    slice_sizes=p["slice_sizes"],
                    unique_indices=p["unique_indices"],
                    indices_are_sorted=p["indices_are_sorted"],
                    mode="fill",
                    fill_value=-1,
                )
            )
            in_b = mapped >= 0
            safe = np.where(in_b, mapped, 0)
            lo = np.where(in_b, op.lo.reshape(-1)[safe], 0)
            hi = np.where(in_b, op.hi.reshape(-1)[safe], 0)
            return [IVal(lo, hi, out_aval.dtype)]
        except Exception:
            # eager replay can reject shapes jax accepted at trace time;
            # the operand hull below is the sound fallback either way
            return _gather_hull(op, out_aval)
        finally:
            del mode
    return _gather_hull(op, out_aval)


def _gather_hull(op: IVal, out_aval):
    # non-concrete (or un-replayable) indices: hull of the operand,
    # joined with the out-of-bounds fill value 0
    lo = np.minimum(int(op.lo.min()) if op.lo.size else 0, 0)
    hi = np.maximum(int(op.hi.max()) if op.hi.size else 0, 0)
    return [
        IVal(
            np.full(out_aval.shape, lo, np.int64),
            np.full(out_aval.shape, hi, np.int64),
            out_aval.dtype,
        )
    ]


def _scatter_map(ctx, p, op_shape, idx, upd_shape):
    """Update-element id landing on each operand element (-1 = none),
    recovered by running an overwrite scatter of ids eagerly."""
    from jax import lax

    jnp = _jnp()
    base = np.full(op_shape, -1, dtype=np.int32)
    uids = np.arange(
        int(np.prod(upd_shape, dtype=np.int64)), dtype=np.int32
    ).reshape(upd_shape)
    return np.asarray(
        lax.scatter(
            jnp.asarray(base),
            jnp.asarray(idx.lo.astype(np.int32)),
            jnp.asarray(uids),
            dimension_numbers=p["dimension_numbers"],
            indices_are_sorted=p["indices_are_sorted"],
            unique_indices=p["unique_indices"],
            mode="drop",
        )
    )


@_rule("scatter")
def _r_scatter(ctx, frame, eqn, ins):
    op, idx, upd = ins
    p = eqn.params
    if idx.concrete() and p["unique_indices"]:
        try:
            rid = _scatter_map(ctx, p, op.shape, idx, upd.shape)
            hit = rid >= 0
            safe = np.where(hit, rid, 0)
            lo = np.where(hit, upd.lo.reshape(-1)[safe], op.lo)
            hi = np.where(hit, upd.hi.reshape(-1)[safe], op.hi)
            return [IVal(lo, hi, _out_dtype(eqn))]
        except Exception:
            # index-map replay rejected: the hull below is sound anyway
            return _scatter_hull(op, upd, _out_dtype(eqn))
    return _scatter_hull(op, upd, _out_dtype(eqn))


def _scatter_hull(op: IVal, upd: IVal, dt):
    # unknown indices: any element may keep the operand or take any update
    u_lo = int(upd.lo.min()) if upd.lo.size else 0
    u_hi = int(upd.hi.max()) if upd.hi.size else 0
    return [IVal(np.minimum(op.lo, u_lo), np.maximum(op.hi, u_hi), dt)]


@_rule("scatter-add")
def _r_scatter_add(ctx, frame, eqn, ins):
    op, idx, upd = ins
    p = eqn.params
    dt = _out_dtype(eqn)
    if idx.concrete() and p["unique_indices"]:
        try:
            rid = _scatter_map(ctx, p, op.shape, idx, upd.shape)
            hit = rid >= 0
            safe = np.where(hit, rid, 0)
            lo = op.lo + np.where(hit, upd.lo.reshape(-1)[safe], 0)
            hi = op.hi + np.where(hit, upd.hi.reshape(-1)[safe], 0)
            return [_settle(ctx, lo, hi, dt, "scatter-add")]
        except Exception:
            # index-map replay rejected: the all-collide hull is sound
            return _scatter_add_hull(ctx, op, upd, dt)
    return _scatter_add_hull(ctx, op, upd, dt)


def _scatter_add_hull(ctx, op: IVal, upd: IVal, dt):
    # unknown indices: every update may land on the same element
    add_lo = int(np.minimum(upd.lo, 0).sum()) if upd.lo.size else 0
    add_hi = int(np.maximum(upd.hi, 0).sum()) if upd.hi.size else 0
    return [_settle(ctx, op.lo + add_lo, op.hi + add_hi, dt, "scatter-add")]


def _start_candidates(starts, sizes, op_shape):
    """Clamped candidate start tuples for dynamic slice/update; None when
    the enumeration would exceed DSLICE_ENUM_MAX combinations."""
    axes = []
    total = 1
    for s, size, dim in zip(starts, sizes, op_shape):
        lo = int(np.clip(s.lo, 0, dim - size))
        hi = int(np.clip(s.hi, 0, dim - size))
        n = hi - lo + 1
        total *= n
        if total > DSLICE_ENUM_MAX:
            return None
        axes.append(range(lo, hi + 1))
    import itertools

    return list(itertools.product(*axes))


@_rule("dynamic_slice")
def _r_dynamic_slice(ctx, frame, eqn, ins):
    op = ins[0]
    starts = ins[1:]
    sizes = eqn.params["slice_sizes"]
    cands = _start_candidates(starts, sizes, op.shape)
    out_aval = eqn.outvars[0].aval
    if cands is not None:
        lo = None
        hi = None
        for tup in cands:
            sl = tuple(
                slice(s, s + z) for s, z in zip(tup, sizes)
            )
            clo, chi = op.lo[sl], op.hi[sl]
            lo = clo if lo is None else np.minimum(lo, clo)
            hi = chi if hi is None else np.maximum(hi, chi)
        return [IVal(lo, hi, out_aval.dtype)]
    # too many possible windows: hull of the whole operand
    lo = int(op.lo.min()) if op.lo.size else 0
    hi = int(op.hi.max()) if op.hi.size else 0
    return [
        IVal(
            np.full(out_aval.shape, lo, np.int64),
            np.full(out_aval.shape, hi, np.int64),
            out_aval.dtype,
        )
    ]


@_rule("dynamic_update_slice")
def _r_dynamic_update_slice(ctx, frame, eqn, ins):
    op, upd = ins[0], ins[1]
    starts = ins[2:]
    sizes = upd.shape
    cands = _start_candidates(starts, sizes, op.shape)
    if cands is not None and len(cands) == 1:
        sl = tuple(slice(s, s + z) for s, z in zip(cands[0], sizes))
        lo = op.lo.copy()
        hi = op.hi.copy()
        lo[sl] = upd.lo
        hi[sl] = upd.hi
        return [IVal(lo, hi, _out_dtype(eqn))]
    # uncertain start: every covered position may keep op or take the
    # update's hull
    lo = op.lo.copy()
    hi = op.hi.copy()
    u_lo = int(upd.lo.min()) if upd.lo.size else 0
    u_hi = int(upd.hi.max()) if upd.hi.size else 0
    if cands is not None:
        region = tuple(
            slice(min(t[d] for t in cands),
                  max(t[d] for t in cands) + sizes[d])
            for d in range(len(sizes))
        )
    else:
        region = tuple(slice(None) for _ in sizes)
    lo[region] = np.minimum(lo[region], u_lo)
    hi[region] = np.maximum(hi[region], u_hi)
    return [IVal(lo, hi, _out_dtype(eqn))]


# ------------------------------------------------------ composite prims


def _bounds_digest(ins) -> str:
    h = hashlib.sha256()
    for v in ins:
        h.update(v.dtype.str.encode())
        h.update(str(v.shape).encode())
        h.update(v.lo.tobytes())
        h.update(v.hi.tobytes())
    return h.hexdigest()


def _replay(ctx, events) -> None:
    ctx.events.extend(events)
    for ev in events:
        if ev[0] == "stat" and ev[2] > ctx._best[ev[1]]:
            ctx._best[ev[1]] = ev[2]


def _cached_call(ctx, jaxpr, consts, ins, runner):
    """Memoize sub-jaxpr interpretation on (jaxpr identity, input
    bounds); replays the journal events the original run produced."""
    key = (id(jaxpr), _bounds_digest(ins))
    hit = ctx.cache.get(key)
    if hit is not None:
        outs, events = hit
        _replay(ctx, events)
        return [IVal(o.lo, o.hi, o.dtype) for o in outs]
    start = len(ctx.events)
    outs = runner()
    ctx.cache[key] = (
        [IVal(o.lo, o.hi, o.dtype) for o in outs],
        list(ctx.events[start:]),
    )
    ctx.cache_refs.append(jaxpr)
    return outs


@_rule("jit")
def _r_jit(ctx, frame, eqn, ins):
    closed = eqn.params["jaxpr"]
    name = eqn.params.get("name") or "jit"
    ctx.path.append(name)
    try:
        return _cached_call(
            ctx, closed.jaxpr, closed.consts, ins,
            lambda: _interp_closed(ctx, closed, ins),
        )
    finally:
        ctx.path.pop()


@_rule("shard_map")
def _r_shard_map(ctx, frame, eqn, ins):
    """Interpret the per-shard body on per-shard bounds: split each
    sharded axis (k, inner), hull over the shard axis in, tile back out.
    Saves mesh axis sizes so psum knows its multiplier."""
    p = eqn.params
    jaxpr = p["jaxpr"]  # open jaxpr (no consts) in current jax
    mesh = p["mesh"]
    sizes = dict(mesh.shape)

    def axes_by_dim(spec) -> dict[int, tuple]:
        """A PartitionSpec as {dim: (axis, ...)} over its sharded dims."""
        return {
            dim: ax if isinstance(ax, tuple) else (ax,)
            for dim, ax in enumerate(spec) if ax is not None
        }

    in_names = [axes_by_dim(s) for s in p["in_specs"]]
    out_names = [axes_by_dim(s) for s in p["out_specs"]]

    def shard_in(v, names):
        lo, hi = v.lo, v.hi
        for dim in sorted(names):
            k = 1
            for ax in names[dim]:
                k *= sizes[ax]
            if k == 1:
                continue
            n = lo.shape[dim]
            newshape = lo.shape[:dim] + (k, n // k) + lo.shape[dim + 1:]
            lo = lo.reshape(newshape).min(axis=dim)
            hi = hi.reshape(newshape).max(axis=dim)
        return IVal(lo, hi, v.dtype)

    def unshard_out(v, names):
        lo, hi = v.lo, v.hi
        for dim in sorted(names):
            k = 1
            for ax in names[dim]:
                k *= sizes[ax]
            if k == 1:
                continue
            reps = [1] * lo.ndim
            reps[dim] = k
            lo = np.tile(lo, reps)
            hi = np.tile(hi, reps)
        return IVal(lo, hi, v.dtype)

    body_ins = [shard_in(v, n) for v, n in zip(ins, in_names)]
    saved = ctx.mesh_sizes
    ctx.mesh_sizes = sizes
    ctx.path.append("shard_map")
    try:
        if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr in some jax versions
            outs = _cached_call(
                ctx, jaxpr.jaxpr, jaxpr.consts, body_ins,
                lambda: _interp_closed(ctx, jaxpr, body_ins),
            )
        else:
            outs = _cached_call(
                ctx, jaxpr, (), body_ins,
                lambda: _interp_jaxpr(ctx, jaxpr, (), body_ins),
            )
    finally:
        ctx.path.pop()
        ctx.mesh_sizes = saved
    return [unshard_out(v, n) for v, n in zip(outs, out_names)]


# ---------------------------------------------------------------- scan


def _widen_to_dtype(v: IVal) -> IVal:
    lo, hi = _dtype_range(v.dtype)
    return IVal(
        np.full(v.shape, lo, np.int64), np.full(v.shape, hi, np.int64), v.dtype
    )


def _run_scan_body(ctx, closed, consts_iv, carry_iv, xs_slice_iv):
    ins = list(consts_iv) + list(carry_iv) + list(xs_slice_iv)
    return _cached_call(
        ctx, closed.jaxpr, closed.consts, ins,
        lambda: _interp_closed(ctx, closed, ins),
    )


def _xs_hull_slices(xs_ivs):
    """Per-step hull of each scanned input (axis 0 removed)."""
    out = []
    for v in xs_ivs:
        out.append(
            IVal(v.lo.min(axis=0), v.hi.max(axis=0), v.dtype)
            if v.lo.size
            else IVal(
                np.zeros(v.shape[1:], np.int64),
                np.zeros(v.shape[1:], np.int64),
                v.dtype,
            )
        )
    return out


def _affine_counters(closed, n_consts: int, n_carry: int) -> dict:
    """Carry slots whose body update is exactly ``carry + literal``
    (the fori_loop counter shape) -> {carry_ordinal: step}.

    Detected statically from the body jaxpr, so the bound is sound by
    induction: the value at iteration t is exactly ``init + t*step``,
    which lets the fixpoint rung pin the counter to its trip-count hull
    instead of widening it to the full dtype range (the widened counter's
    ``i + 1`` would otherwise surface as a false int32-overflow finding
    on every long fori_loop).
    """
    jx = closed.jaxpr
    carry_invars = jx.invars[n_consts:n_consts + n_carry]
    out: dict = {}
    for j, ov in enumerate(jx.outvars[:n_carry]):
        if hasattr(ov, "val"):
            continue
        eqn = next(
            (e for e in jx.eqns if any(o is ov for o in e.outvars)), None
        )
        if eqn is None or eqn.primitive.name != "add":
            continue
        a, b = eqn.invars
        for x, y in ((a, b), (b, a)):
            if (
                hasattr(x, "val")
                and np.ndim(x.val) == 0
                and np.issubdtype(np.asarray(x.val).dtype, np.integer)
                and not hasattr(y, "val")
                and y is carry_invars[j]
            ):
                out[j] = int(x.val)
                break
    return out


@_rule("scan")
def _r_scan(ctx, frame, eqn, ins):
    p = eqn.params
    closed = p["jaxpr"]
    n_consts = p["num_consts"]
    n_carry = p["num_carry"]
    length = p["length"]
    reverse = p["reverse"]
    ordinal = ctx.scan_ordinal
    ctx.scan_ordinal += 1

    consts_iv = ins[:n_consts]
    carry0 = ins[n_consts:n_consts + n_carry]
    xs_iv = ins[n_consts + n_carry:]
    n_ys = len(eqn.outvars) - n_carry
    label = ctx.label(f"scan#{ordinal}")
    counters = _affine_counters(closed, n_consts, n_carry)

    def _pin_counters(carry):
        """In-loop hull for counter carries: init + [0, step*(length-1)]."""
        for j, step in counters.items():
            c0 = carry0[j]
            span = step * (length - 1)
            carry[j] = IVal(
                c0.lo + min(0, span), c0.hi + max(0, span), c0.dtype
            )
        return carry

    def _counter_finals(carry):
        """Exact post-loop counter value: init + step*length."""
        for j, step in counters.items():
            c0 = carry0[j]
            carry[j] = IVal(
                c0.lo + step * length, c0.hi + step * length, c0.dtype
            )
        return carry

    # ladder rung 1: join-iterate to a fixpoint on the per-step hull.
    # Intermediate (non-converged) body runs are rolled back so their
    # transient bounds never surface as findings; only the converged
    # run's events remain in the journal.
    def try_fixpoint():
        xs_hull = _xs_hull_slices(xs_iv)
        carry = _pin_counters([IVal(c.lo, c.hi, c.dtype) for c in carry0])
        for _ in range(FIXPOINT_MAX_ITERS):
            m = ctx.mark()
            outs = _run_scan_body(ctx, closed, consts_iv, carry, xs_hull)
            new_carry = list(outs[:n_carry])
            for j in counters:  # pinned: exact by induction, never joined
                new_carry[j] = carry[j]
            if all(_contains(c, nc) for c, nc in zip(carry, new_carry)):
                return _counter_finals(list(carry)), outs[n_carry:]
            ctx.rollback(m)
            carry = [_join(c, nc) for c, nc in zip(carry, new_carry)]
        # widen every still-moving carry to its dtype range, re-check once
        m = ctx.mark()
        outs = _run_scan_body(ctx, closed, consts_iv, carry, xs_hull)
        widened = [
            c if j in counters or _contains(c, nc) else _widen_to_dtype(c)
            for j, (c, nc) in enumerate(zip(carry, outs[:n_carry]))
        ]
        ctx.rollback(m)
        m = ctx.mark()
        final = _run_scan_body(ctx, closed, consts_iv, widened, xs_hull)
        new_carry = list(final[:n_carry])
        for j in counters:
            new_carry[j] = widened[j]
        if all(_contains(c, nc) for c, nc in zip(widened, new_carry)):
            return _counter_finals(list(widened)), final[n_carry:]
        ctx.rollback(m)
        return None

    # ladder rung 2: exact unroll (concretizes loop counters; the only
    # strategy that tracks Montgomery accumulator windows)
    def try_unroll():
        if length == 0 or length > UNROLL_MAX:
            return None
        carry = [IVal(c.lo, c.hi, c.dtype) for c in carry0]
        ys_steps: list[list[IVal]] = []
        steps = range(length - 1, -1, -1) if reverse else range(length)
        for t in steps:
            xs_t = [IVal(v.lo[t], v.hi[t], v.dtype) for v in xs_iv]
            outs = _run_scan_body(ctx, closed, consts_iv, carry, xs_t)
            carry = outs[:n_carry]
            ys_steps.append(outs[n_carry:])
        if reverse:
            ys_steps.reverse()
        ys = []
        for j in range(n_ys):
            lo = np.stack([st[j].lo for st in ys_steps])
            hi = np.stack([st[j].hi for st in ys_steps])
            ys.append(IVal(lo, hi, ys_steps[0][j].dtype))
        return carry, ys

    # ladder rung 3: declared invariant (assume-guarantee)
    def try_invariant():
        decl = {
            co: bound
            for (so, co), bound in ctx.invariants.items()
            if so == ordinal
        }
        if not decl:
            return None
        carry = []
        for i, c in enumerate(carry0):
            if i in decl:
                lo, hi = decl[i]
                carry.append(
                    IVal(
                        np.full(c.shape, lo, np.int64),
                        np.full(c.shape, hi, np.int64),
                        c.dtype,
                    )
                )
            else:
                carry.append(c)
        _pin_counters_undecl = {
            j: s for j, s in counters.items() if j not in decl
        }
        for j, step in _pin_counters_undecl.items():
            c0 = carry0[j]
            span = step * (length - 1)
            carry[j] = IVal(
                c0.lo + min(0, span), c0.hi + max(0, span), c0.dtype
            )
        if not all(_contains(inv, c0) for inv, c0 in zip(carry, carry0)):
            ctx.finding(
                f"loop invariant at {label} does not cover the initial "
                f"carry"
            )
            return None
        xs_hull = _xs_hull_slices(xs_iv)
        outs = _run_scan_body(ctx, closed, consts_iv, carry, xs_hull)
        new_carry = list(outs[:n_carry])
        for j in _pin_counters_undecl:
            new_carry[j] = carry[j]
        if not all(_contains(inv, nc) for inv, nc in zip(carry, new_carry)):
            ctx.finding(
                f"declared loop invariant at {label} is not inductive"
            )
            return None
        final = list(carry)
        for j, step in _pin_counters_undecl.items():
            c0 = carry0[j]
            final[j] = IVal(
                c0.lo + step * length, c0.hi + step * length, c0.dtype
            )
        return final, outs[n_carry:]

    # unroll FIRST: for short scans it dominates the fixpoint — exact
    # per-step xs bounds (the fixpoint's per-step hull smears one loose
    # limb's bound over every step of a carry chain) and concrete loop
    # counters.  The fixpoint rung exists for the long chains (the
    # 255-bit subgroup walk) that exceed UNROLL_MAX.
    best = None  # (n_findings, (carry, ys), events-suffix)
    for attempt in (try_unroll, try_fixpoint, try_invariant):
        mark = ctx.mark()
        res = attempt()
        if res is None:
            ctx.rollback(mark)
            continue
        events = list(ctx.events[mark:])
        nf = sum(1 for ev in events if ev[0] == "finding")
        if nf == 0:
            # clean strategy: its events stay in the journal as-is
            return _finish_scan(res, eqn, n_carry)
        if best is None or nf < best[0]:
            best = (nf, res, events)
        ctx.rollback(mark)
    if best is not None:
        # every strategy had findings: surface the least-bad set
        _replay(ctx, best[2])
        return _finish_scan(best[1], eqn, n_carry)
    ctx.finding(f"scan at {label}: no strategy converged")
    carry = [_widen_to_dtype(c) for c in carry0]
    ys = []
    for ov in eqn.outvars[n_carry:]:
        lo, hi = _dtype_range(ov.aval.dtype)
        ys.append(
            IVal(
                np.full(ov.aval.shape, lo, np.int64),
                np.full(ov.aval.shape, hi, np.int64),
                ov.aval.dtype,
            )
        )
    return _finish_scan((carry, ys), eqn, n_carry)


def _finish_scan(res, eqn, n_carry):
    carry, ys = res
    fixed = []
    for v, ov in zip(list(carry) + list(ys), eqn.outvars):
        shape = ov.aval.shape
        if v.shape != shape:
            v = IVal(
                np.broadcast_to(v.lo, shape),
                np.broadcast_to(v.hi, shape),
                ov.aval.dtype,
            )
        fixed.append(v)
    return fixed


# ------------------------------------------------------- interpreter loop


def _interp_jaxpr(ctx, jaxpr, consts, ins):
    frame = _Frame()
    for var, c in zip(jaxpr.constvars, consts):
        frame.env[var] = _const_ival(np.asarray(c), np.asarray(c).dtype)
    for var, v in zip(jaxpr.invars, ins):
        frame.env[var] = v
    for eqn in jaxpr.eqns:
        ctx.eqn_count += 1
        prim = eqn.primitive.name
        rule = _RULES.get(prim)
        in_vals = [_read(frame, a) for a in eqn.invars]
        if rule is None:
            ctx.finding(
                f"no transfer rule for primitive {prim!r} at "
                f"{ctx.label(prim)}"
            )
            outs = []
            for ov in eqn.outvars:
                lo, hi = _dtype_range(ov.aval.dtype)
                outs.append(
                    IVal(
                        np.full(ov.aval.shape, lo, np.int64),
                        np.full(ov.aval.shape, hi, np.int64),
                        ov.aval.dtype,
                    )
                )
        else:
            outs = rule(ctx, frame, eqn, in_vals)
        for ov, val in zip(eqn.outvars, outs):
            if type(ov).__name__ == "DropVar":
                continue
            shape = ov.aval.shape
            if val.shape != shape:
                val = IVal(
                    np.broadcast_to(val.lo, shape),
                    np.broadcast_to(val.hi, shape),
                    val.dtype,
                )
            frame.env[ov] = val
            frame.defs[ov] = eqn
    return [_read(frame, a) for a in jaxpr.outvars]


def _interp_closed(ctx, closed, ins):
    return _interp_jaxpr(ctx, closed.jaxpr, closed.consts, ins)


def _input_ivals(kernel) -> list[IVal]:
    """Abstract inputs from the manifest row: the declared arg_ranges
    entry when present, else the full dtype range (f32 defaults to the
    exactness envelope +-2^24)."""
    ranges = getattr(kernel, "arg_ranges", None) or (None,) * len(kernel.args)
    if len(ranges) != len(kernel.args):
        raise ValueError(
            f"{kernel.name}: arg_ranges has {len(ranges)} entries for "
            f"{len(kernel.args)} args"
        )
    out = []
    for arg, rng in zip(kernel.args, ranges):
        dt = np.dtype(arg.dtype)
        lo, hi = _range_bounds(
            rng if rng is not None else _dtype_range(dt), arg.shape
        )
        out.append(IVal(lo, hi, dt))
    return out


def _range_bounds(rng, shape) -> tuple[np.ndarray, np.ndarray]:
    """A declared range as (lo, hi) arrays of ``shape``: one ``(lo, hi)``
    pair for the whole array, or one pair per index of the leading axis
    (a field element's limb rows, whose first limb is bounded wider)."""
    pairs = np.asarray(rng, dtype=np.int64)
    if pairs.ndim == 2:
        if len(pairs) != shape[0]:
            raise ValueError(
                f"{len(pairs)} per-row ranges for a leading axis of "
                f"{shape[0]}"
            )
        pairs = pairs.T.reshape((2, shape[0]) + (1,) * (len(shape) - 1))
    return (np.broadcast_to(pairs[0], shape).copy(),
            np.broadcast_to(pairs[1], shape).copy())


# ---------------------------------------------------------- kernel check


@dataclass
class RangeReport:
    """Interpretation result for one kernel."""

    kernel: str
    ok: bool
    messages: list  # finding strings (deduped, capped)
    peak_int32: int
    peak_int32_at: str
    peak_f32: int
    peak_f32_at: str
    headroom_int32_bits: float
    headroom_f32_bits: float
    eqns: int

    def fingerprint(self) -> dict:
        return {
            "ok": self.ok,
            "findings": list(self.messages),
            "peak_int32": self.peak_int32,
            "peak_int32_at": self.peak_int32_at,
            "peak_f32": self.peak_f32,
            "peak_f32_at": self.peak_f32_at,
            "headroom_int32_bits": self.headroom_int32_bits,
            "headroom_f32_bits": self.headroom_f32_bits,
        }


def _headroom_bits(peak: int, limit: int) -> float:
    if peak <= 0:
        return float(math.log2(limit))
    return round(math.log2(limit / peak), 2) if peak <= limit else 0.0


def _trace_closed(kernel):
    """The kernel's ClosedJaxpr under the PR-4 deterministic trace
    environment (CPU backend pinned)."""
    from . import kernelcheck

    kernelcheck._ensure_cpu_backend()
    import jax

    fn = kernelcheck._resolve(kernel)
    return jax.make_jaxpr(fn)(*kernelcheck._arg_structs(kernel))


def check_kernel(kernel) -> RangeReport:
    """Trace one manifest kernel and interpret its jaxpr abstractly."""
    ctx = _Ctx(kernel.name, getattr(kernel, "loop_invariants", ()) or ())
    outs = []
    try:
        closed = _trace_closed(kernel)
        ins = _input_ivals(kernel)
        outs = _interp_jaxpr(ctx, closed.jaxpr, closed.consts, ins)
    except Exception as e:  # an interpreter crash is a finding, not a pass
        ctx.finding(f"interpreter error: {type(e).__name__}: {e}")

    # contract 2: declared output ranges hold
    out_ranges = getattr(kernel, "out_ranges", None)
    if out_ranges is not None and outs:
        if len(out_ranges) != len(outs):
            ctx.finding(
                f"out_ranges has {len(out_ranges)} entries for "
                f"{len(outs)} outputs"
            )
        else:
            for i, (rng, v) in enumerate(zip(out_ranges, outs)):
                if rng is None:
                    continue
                lo, hi = _range_bounds(rng, v.shape)
                outside = (v.lo < lo) | (v.hi > hi)
                if outside.any():
                    where = np.argmax(outside)
                    ctx.finding(
                        f"output {i} range [{int(v.lo.min())}, "
                        f"{int(v.hi.max())}] escapes the declared "
                        f"[{int(lo.flat[where])}, {int(hi.flat[where])}]"
                    )

    messages: list[str] = []
    for ev in ctx.events:
        if ev[0] == "finding" and ev[1] not in messages:
            messages.append(ev[1])
    extra = len(messages) - _MAX_FINDINGS_PER_KERNEL
    if extra > 0:
        messages = messages[:_MAX_FINDINGS_PER_KERNEL]
        messages.append(f"... and {extra} more")

    peaks = {"int32": (0, ""), "f32": (0, "")}
    for ev in ctx.events:
        if ev[0] == "stat" and ev[2] > peaks[ev[1]][0]:
            peaks[ev[1]] = (ev[2], ev[3])
    pi, pi_at = peaks["int32"]
    pf, pf_at = peaks["f32"]
    return RangeReport(
        kernel=kernel.name,
        ok=not messages,
        messages=messages,
        peak_int32=pi,
        peak_int32_at=pi_at,
        peak_f32=pf,
        peak_f32_at=pf_at,
        headroom_int32_bits=_headroom_bits(pi, INT32_MAX),
        headroom_f32_bits=_headroom_bits(pf, F32_EXACT),
        eqns=ctx.eqn_count,
    )


# ----------------------------------------------------------- certificates


def load_fingerprints(path: str = RANGE_FINGERPRINTS_PATH) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        return {}


def write_fingerprints(
    reports: list, path: str = RANGE_FINGERPRINTS_PATH
) -> None:
    data = {r.kernel: r.fingerprint() for r in reports}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


def _diff_report(name: str, golden: dict, fresh: dict) -> str:
    lines = [f"kernel {name!r} drifted from its range certificate:"]
    for key in (
        "ok",
        "peak_int32",
        "peak_int32_at",
        "peak_f32",
        "peak_f32_at",
        "headroom_int32_bits",
        "headroom_f32_bits",
        "findings",
    ):
        b, a = golden.get(key), fresh.get(key)
        if b != a:
            lines.append(f"  {key}: {b!r} -> {a!r}")
    lines.append(
        "  deliberate change? regenerate with "
        "`python scripts/lint.py regen-ranges`"
    )
    return "\n".join(lines)


def compare_fingerprints(reports: list, golden: dict) -> list[Finding]:
    """Certificate drift findings for reports against the golden file."""
    findings: list[Finding] = []
    fresh_names = set()
    for r in reports:
        fresh_names.add(r.kernel)
        kernel = km.by_name().get(r.kernel)
        path = (
            km.module_path(kernel)
            if kernel is not None
            else "cometbft_tpu/analysis/kernel_manifest.py"
        )
        fresh = r.fingerprint()
        have = golden.get(r.kernel)
        if have is None:
            findings.append(Finding(
                "range-fingerprint", path, 1, 0,
                f"kernel {r.kernel!r} has no checked-in range certificate"
                " — run `python scripts/lint.py regen-ranges`",
            ))
        elif have != fresh:
            findings.append(Finding(
                "range-fingerprint", path, 1, 0,
                _diff_report(r.kernel, have, fresh),
            ))
    # stale = certificate names neither checked this run nor in the
    # manifest (targeted runs must not call unchecked goldens stale)
    known = fresh_names | set(km.by_name())
    for name in sorted(set(golden) - known):
        findings.append(Finding(
            "range-fingerprint",
            "cometbft_tpu/analysis/range_fingerprints.json", 1, 0,
            f"range certificate {name!r} names no manifest kernel — "
            "stale entry; regenerate the certificates",
        ))
    return findings


def _manifest_findings(kernels) -> list[Finding]:
    """Declared-spec shape errors (arity mismatches) are manifest bugs,
    not kernel findings."""
    findings: list[Finding] = []
    for k in kernels:
        ranges = getattr(k, "arg_ranges", None)
        if ranges is not None and len(ranges) != len(k.args):
            findings.append(Finding(
                "range-manifest",
                "cometbft_tpu/analysis/kernel_manifest.py", 1, 0,
                f"kernel {k.name!r}: arg_ranges has {len(ranges)} entries "
                f"for {len(k.args)} args",
            ))
        for rng in (ranges or ()):  # each entry None or (lo, hi)
            if rng is not None and rng[0] > rng[1]:
                findings.append(Finding(
                    "range-manifest",
                    "cometbft_tpu/analysis/kernel_manifest.py", 1, 0,
                    f"kernel {k.name!r}: empty declared range {rng}",
                ))
    return findings


def default_allowlist():
    from .linter import Allowlist, default_allowlist_path

    return Allowlist.load(default_allowlist_path())


def run_check(
    fingerprints_path: str = RANGE_FINGERPRINTS_PATH,
    kernels=None,
    allowlist=None,
) -> tuple[list[Finding], list]:
    """The full range pass: interpret every manifest kernel, enforce
    both contracts, and diff against the checked-in certificates.
    Returns (findings, reports); empty findings is the green gate.

    ``allowlist`` filters findings when given (the kernelcheck policy:
    raw by default so scripts/lint.py can track stale entries)."""
    kernels = tuple(kernels) if kernels is not None else km.KERNELS
    findings = _manifest_findings(kernels)
    reports = [check_kernel(k) for k in kernels]
    for r in reports:
        kernel = km.by_name().get(r.kernel)
        path = (
            km.module_path(kernel)
            if kernel is not None
            else "cometbft_tpu/analysis/kernel_manifest.py"
        )
        for msg in r.messages:
            findings.append(Finding(
                "range-contract", path, 1, 0, f"[{r.kernel}] {msg}"
            ))
    findings.extend(
        compare_fingerprints(reports, load_fingerprints(fingerprints_path))
    )
    if allowlist is not None:
        findings = [f for f in findings if not allowlist.suppresses(f)]
    return findings, reports


def regenerate(
    fingerprints_path: str = RANGE_FINGERPRINTS_PATH,
) -> tuple[list[Finding], list]:
    """Re-interpret everything and rewrite the certificate file.
    Contract findings still fail — regeneration only blesses drift,
    never an open overflow (the PR-6 policy)."""
    findings = _manifest_findings(km.KERNELS)
    reports = [check_kernel(k) for k in km.KERNELS]
    for r in reports:
        kernel = km.by_name().get(r.kernel)
        path = (
            km.module_path(kernel)
            if kernel is not None
            else "cometbft_tpu/analysis/kernel_manifest.py"
        )
        for msg in r.messages:
            findings.append(Finding(
                "range-contract", path, 1, 0, f"[{r.kernel}] {msg}"
            ))
    allow = default_allowlist()
    findings = [f for f in findings if not allow.suppresses(f)]
    if not findings:
        write_fingerprints(reports, fingerprints_path)
    return findings, reports


def summary(findings: list[Finding], reports: list) -> dict:
    """Machine-readable result: ``range`` in ``lint.py --json``."""
    return {
        "ok": not findings,
        "kernels": len(reports),
        "headroom": {
            r.kernel: {
                "ok": r.ok,
                "peak_int32": r.peak_int32,
                "peak_f32": r.peak_f32,
                "headroom_int32_bits": r.headroom_int32_bits,
                "headroom_f32_bits": r.headroom_f32_bits,
            }
            for r in reports
        },
        "findings": [
            {"check": f.check, "path": f.path, "message": f.message}
            for f in findings
        ],
    }


# ------------------------------------------------------- field headroom


#: Per-field conv structure for the max-safe-limb-width scaling law:
#: (bits, current limb width, dtype limit for the conv partial sums).
_FIELDS = {
    "ed25519": {"bits": 255, "width": 12, "limit": F32_EXACT},
    "secp256k1": {"bits": 256, "width": 12, "limit": INT32_MAX},
    "bls12-381": {"bits": 381, "width": 12, "limit": INT32_MAX},
}


def max_safe_limb_width(
    peak: int, bits: int, width: int = 12, limit: int = INT32_MAX
) -> int:
    """Widest limb w for which the measured conv peak, rescaled from
    ``width``-bit digits to w-bit digits, still fits ``limit``.

    The conv peak scales as the per-product magnitude (2^w - 1)^2 times
    the contraction depth ceil(bits / w): widening limbs grows each
    product quadratically but shrinks the number of products linearly.
    """
    if peak <= 0:
        return width
    depth0 = math.ceil(bits / width)
    per0 = ((1 << width) - 1) ** 2
    best = 0
    for w in range(1, 32):
        scale = (((1 << w) - 1) ** 2 / per0) * (math.ceil(bits / w) / depth0)
        if peak * scale <= limit:
            best = w
    return best


def field_headroom(reports: list) -> dict:
    """Per-field tightest-intermediate table: the max conv peak across
    that field's kernels, bits of slack, and the computed max safe limb
    width (the docs/limb_headroom.md payload)."""
    groups = {
        "ed25519": ("ed25519", "comb"),
        "secp256k1": ("secp",),
        "bls12-381": ("bls381",),
    }
    out = {}
    for fieldname, prefixes in groups.items():
        cfg = _FIELDS[fieldname]
        peak = 0
        at = ""
        for r in reports:
            if not any(p in r.kernel for p in prefixes):
                continue
            p, where = (
                (r.peak_f32, r.peak_f32_at)
                if cfg["limit"] == F32_EXACT
                else (r.peak_int32, r.peak_int32_at)
            )
            if p > peak:
                peak, at = p, f"{r.kernel} {where}"
        out[fieldname] = {
            "peak": peak,
            "at": at,
            "limit": cfg["limit"],
            "headroom_bits": _headroom_bits(peak, cfg["limit"]),
            "limb_width": cfg["width"],
            "max_safe_limb_width": max_safe_limb_width(
                peak, cfg["bits"], cfg["width"], cfg["limit"]
            ),
        }
    return out
