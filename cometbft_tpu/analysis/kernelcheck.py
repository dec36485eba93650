"""Kernel contract checker: static shape/dtype/jaxpr analysis for the
TPU verify plane.

Abstract-interprets every kernel declared in ``kernel_manifest.py`` via
``jax.make_jaxpr`` (no device execution — runs on CPU-only hosts with
``JAX_PLATFORMS=cpu``) and enforces three contracts:

1. **dtype closure** — no 64-bit/complex dtype anywhere in the traced
   program, no weak-typed KERNEL OUTPUT (a weak output means the public
   contract's dtype is at the mercy of promotion rules), no weak-typed
   FLOATING intermediate (the signature of a bare float literal leaking
   into integer kernel arithmetic — the dtype-changing kind of
   promotion; weak int/bool intermediates from loop counters and index
   math are idiomatic, dtype-preserving against any strong operand, and
   deliberately NOT findings), and every ``convert_element_type`` drawn
   from the justified allowlist in the manifest.
2. **purity** — no host-callback primitive (``pure_callback``,
   ``io_callback``, ``debug_callback``, infeed/outfeed) anywhere in the
   jaxpr, including nested control-flow bodies.
3. **drift gate** — the traced signature (input/output avals) and the
   primitive census of each kernel must match the checked-in golden
   (``analysis/kernel_fingerprints.json``).  A mismatch fails with a
   readable before/after report: accidental jaxpr drift is how silent
   recompiles (seconds of wall clock per shape) and numeric changes land.

Regenerate goldens after a DELIBERATE kernel change with::

    python scripts/lint.py regen-fingerprints

JAX imports are deferred to call time so the analysis package itself
stays importable everywhere the stdlib runs.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from dataclasses import dataclass, field

from . import kernel_manifest as manifest
from .linter import Finding

FINGERPRINTS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "kernel_fingerprints.json"
)

#: Every Finding.check id this module emits — scripts/lint.py's
#: stale-entry filter for --check kernel imports this instead of
#: duplicating the set.
FINDING_CHECK_IDS = frozenset(
    {"kernel-contract", "kernel-fingerprint", "kernel-manifest"}
)

#: Sentinel signature for a kernel that failed to trace: the failure is
#: its own finding, and the drift gate skips it.
UNTRACEABLE_SIG = "<untraceable>"

# Host-callback / host-transfer primitives that must never appear inside
# a verify-plane kernel.  Matched on the primitive NAME so new jax
# spellings of the same escape hatch (e.g. versioned callback prims)
# still trip the substring rules below.
_FORBIDDEN_PRIMS = frozenset(
    {"infeed", "outfeed", "host_local_array_to_global_array"}
)
_FORBIDDEN_PRIM_SUBSTRINGS = ("callback",)


def _ensure_cpu_backend() -> None:
    """Force the CPU backend when jax has not been imported yet — even
    over an ambient JAX_PLATFORMS=tpu: the gate must run (and stay
    deterministic) on hosts with no TPU, and must never touch a real
    accelerator when one exists (it belongs to one process at a time,
    and one that hangs would hang backend init indefinitely).  When jax is already initialized (pytest's
    conftest), the caller owns the platform choice."""
    if "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"


def _aval_str(aval) -> str:
    shape = ",".join(str(d) for d in aval.shape)
    return f"{aval.dtype}[{shape}]"


@dataclass
class Trace:
    """One kernel's abstract interpretation."""

    kernel: manifest.Kernel
    signature: str  # "(in avals) -> (out avals)"
    primitives: dict[str, int]
    findings: list[Finding] = field(default_factory=list)
    eqns: int = 0  # total jaxpr equations, nested bodies included
    # jax.named_scope names met on the equations' name stacks: the
    # phases a profile is read by (docs/observability.md).  Outside the
    # fingerprint: a scope adds no primitive and moves no digest.
    scopes: frozenset[str] = frozenset()

    def fingerprint(self) -> dict:
        payload = {
            "signature": self.signature,
            "primitives": dict(sorted(self.primitives.items())),
        }
        digest = hashlib.sha256(
            json.dumps(payload, sort_keys=True).encode()
        ).hexdigest()
        # eqns ride along for operators reading the golden but stay out
        # of the digest: they are budget-gated (manifest max_eqns hard
        # ceilings), not drift-gated — the shardcheck "costs" policy
        return {**payload, "digest": digest, "costs": {"eqns": self.eqns}}


def _resolve(kernel: manifest.Kernel):
    """Manifest fn ref -> the traceable callable (factories get a
    1-device CPU mesh; static kwargs are bound as Python constants)."""
    import functools
    import importlib

    mod_name, _, fn_name = kernel.fn.partition(":")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    if kernel.needs_mesh:
        from ..parallel.mesh import make_mesh

        return fn(make_mesh(1), **dict(kernel.static_kwargs))
    if kernel.static_kwargs:
        return functools.partial(fn, **dict(kernel.static_kwargs))
    return fn


def _arg_structs(kernel: manifest.Kernel):
    import jax
    import numpy as np

    return [
        jax.ShapeDtypeStruct(a.shape, np.dtype(a.dtype)) for a in kernel.args
    ]


def _walk_jaxprs(jaxpr):
    """Yield jaxpr and every nested jaxpr (pjit/scan/while/cond bodies,
    shard_map, custom-call sub-programs) exactly once each."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    seen: set[int] = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if isinstance(j, ClosedJaxpr):
            j = j.jaxpr
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for p in eqn.params.values():
                if isinstance(p, (ClosedJaxpr, Jaxpr)):
                    stack.append(p)
                elif isinstance(p, (list, tuple)):
                    stack.extend(
                        q for q in p if isinstance(q, (ClosedJaxpr, Jaxpr))
                    )


def trace_kernel(kernel: manifest.Kernel) -> Trace:
    """Trace one manifest kernel and run the dtype-closure and purity
    passes over its jaxpr."""
    _ensure_cpu_backend()
    import jax

    path = manifest.module_path(kernel)
    findings: list[Finding] = []

    def add(msg: str) -> None:
        findings.append(Finding("kernel-contract", path, 1, 0,
                                f"[{kernel.name}] {msg}"))

    try:
        fn = _resolve(kernel)
        closed = jax.make_jaxpr(fn)(*_arg_structs(kernel))
    except Exception as e:  # noqa: BLE001 - a kernel that fails to trace IS the finding
        add(f"failed to trace: {type(e).__name__}: {e}")
        return Trace(kernel, UNTRACEABLE_SIG, {}, findings)

    in_sig = ", ".join(_aval_str(a) for a in closed.in_avals)
    out_sig = ", ".join(_aval_str(a) for a in closed.out_avals)
    signature = f"({in_sig}) -> ({out_sig})"

    for a in closed.out_avals:
        if getattr(a, "weak_type", False):
            add(
                f"weak-typed kernel output {_aval_str(a)} — the contract "
                "dtype is at the mercy of promotion rules; pin it "
                "(jnp.int32(...)/.astype(...)) at the return"
            )

    # output spec: declared in the manifest, checked before fingerprints
    def leaf_strs(leaves):
        return [
            d + "[" + ",".join(str(x) for x in s) + "]" for s, d in leaves
        ]

    got = [(tuple(a.shape), str(a.dtype)) for a in closed.out_avals]
    want = [(a.shape, a.dtype) for a in kernel.out]
    if got != want:
        add(
            "output spec mismatch: manifest declares "
            f"{leaf_strs(want)}, trace produced {leaf_strs(got)}"
        )

    prims: dict[str, int] = {}
    total_eqns = 0
    scopes: set[str] = set()
    for jaxpr in _walk_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            total_eqns += 1
            name = eqn.primitive.name
            prims[name] = prims.get(name, 0) + 1

            scopes.update(
                el.name for el in eqn.source_info.name_stack.stack
                if type(el).__name__ == "Scope"
            )

            if name in _FORBIDDEN_PRIMS or any(
                s in name for s in _FORBIDDEN_PRIM_SUBSTRINGS
            ):
                add(
                    f"impure primitive {name!r} in the jaxpr — host "
                    "callbacks/transfers are forbidden inside verify-plane "
                    "kernels"
                )

            if name == "convert_element_type":
                src = str(eqn.invars[0].aval.dtype)
                dst = str(eqn.params.get("new_dtype"))
                if src != dst and (src, dst) not in manifest.ALLOWED_CONVERSIONS:
                    add(
                        f"unjustified convert_element_type {src} -> {dst} — "
                        "add the pair to kernel_manifest.ALLOWED_CONVERSIONS "
                        "with a justification, or fix the promotion"
                    )

            for v in eqn.outvars:
                aval = v.aval
                dt = str(getattr(aval, "dtype", ""))
                if dt in manifest.FORBIDDEN_DTYPES:
                    add(
                        f"{dt} value produced by {name!r} — 64-bit/complex "
                        "dtypes are outside the kernel contract"
                    )
                if getattr(aval, "weak_type", False) and dt.startswith(
                    ("float", "complex", "bfloat")
                ):
                    # weak int/bool intermediates (loop counters, index
                    # math) are dtype-preserving and not findings; a weak
                    # FLOAT is a bare float literal changing dtypes
                    add(
                        f"weak-typed {dt} output of {name!r} — a bare float "
                        "literal leaked into kernel arithmetic; pin it "
                        "(np.float32(...)/jnp.float32(...)) so promotion "
                        "cannot drift"
                    )

    # compile-cost budget: the static face of a minutes-long XLA compile
    # (the pre-PR-11 comb table build hit 2m34s at ~84k eqns).  A kernel
    # with no declared budget skips the gate here but fails the manifest
    # consistency pass below — no production kernel rides unbudgeted.
    if kernel.max_eqns > 0 and total_eqns > kernel.max_eqns:
        add(
            f"compile-cost budget: {total_eqns} jaxpr equations exceeds "
            f"the budget of {kernel.max_eqns} "
            f"({total_eqns - kernel.max_eqns:+d}) — an unrolled loop or "
            "table build lands here in milliseconds instead of as a "
            "minutes-long XLA compile; restructure the kernel (roll the "
            "loop with lax.scan / precompute host-side) or raise the "
            "budget with justification"
        )
    return Trace(kernel, signature, prims, findings, total_eqns,
                 frozenset(scopes))


# -------------------------------------------------------------- drift gate


def load_fingerprints(path: str = FINGERPRINTS_PATH) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        return {}


def write_fingerprints(traces: list[Trace], path: str = FINGERPRINTS_PATH) -> None:
    data = {t.kernel.name: t.fingerprint() for t in traces}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


def _diff_report(name: str, golden: dict, fresh: dict) -> str:
    """Readable before/after for one drifted kernel."""
    lines = [f"kernel {name!r} drifted from its checked-in fingerprint:"]
    if golden.get("signature") != fresh.get("signature"):
        lines.append(f"  signature before: {golden.get('signature')}")
        lines.append(f"  signature after : {fresh.get('signature')}")
    gp = golden.get("primitives", {})
    fp = fresh.get("primitives", {})
    for prim in sorted(set(gp) | set(fp)):
        b, a = gp.get(prim, 0), fp.get(prim, 0)
        if b != a:
            lines.append(f"  {prim}: {b} -> {a} ({a - b:+d})")
    lines.append(
        "  deliberate change? regenerate with "
        "`python scripts/lint.py regen-fingerprints`"
    )
    return "\n".join(lines)


def compare_fingerprints(
    traces: list[Trace], golden: dict
) -> list[Finding]:
    """Fingerprint drift findings for traces against the golden file."""
    findings: list[Finding] = []
    fresh_names = set()
    for t in traces:
        fresh_names.add(t.kernel.name)
        if t.signature == UNTRACEABLE_SIG:
            # 'failed to trace' is already the finding; an every-prim
            # "N -> 0" drift diff (with its regen hint) would only bury it
            continue
        path = manifest.module_path(t.kernel)
        fresh = t.fingerprint()
        have = golden.get(t.kernel.name)
        if have is None:
            findings.append(Finding(
                "kernel-fingerprint", path, 1, 0,
                f"kernel {t.kernel.name!r} has no checked-in fingerprint — "
                "run `python scripts/lint.py regen-fingerprints`",
            ))
        elif have.get("digest") != fresh["digest"]:
            findings.append(Finding(
                "kernel-fingerprint", path, 1, 0,
                _diff_report(t.kernel.name, have, fresh),
            ))
    # stale = names neither traced THIS run nor declared in the manifest:
    # a targeted run_check(kernels=<subset>) must not call the untraced
    # manifest kernels' goldens stale
    known = fresh_names | set(manifest.by_name())
    for name in sorted(set(golden) - known):
        findings.append(Finding(
            "kernel-fingerprint", "cometbft_tpu/analysis/kernel_fingerprints.json",
            1, 0,
            f"golden fingerprint {name!r} names no manifest kernel — "
            "stale entry; regenerate the goldens",
        ))
    return findings


def _manifest_findings() -> list[Finding]:
    """Internal consistency: every JIT_SITES value must name a kernel,
    and every kernel must carry a positive compile-cost budget — the
    grandfather clause that let ``comb_build_a_tables`` ride unbudgeted
    into a 2m34s XLA compile is deleted."""
    findings: list[Finding] = []
    names = manifest.by_name()
    for site, kernel in manifest.JIT_SITES.items():
        if kernel not in names:
            findings.append(Finding(
                "kernel-manifest",
                "cometbft_tpu/analysis/kernel_manifest.py", 1, 0,
                f"JIT_SITES[{site!r}] names unknown kernel {kernel!r}",
            ))
    for k in manifest.KERNELS:
        if k.max_eqns <= 0:
            findings.append(Finding(
                "kernel-manifest",
                "cometbft_tpu/analysis/kernel_manifest.py", 1, 0,
                f"kernel {k.name!r} declares no compile-cost budget "
                "(max_eqns) — unbudgeted kernels are how multi-minute "
                "XLA compiles land; declare a measured ceiling",
            ))
    return findings


def default_allowlist():
    """The checked-in repo allowlist (``analysis/allowlist.txt``)."""
    from .linter import Allowlist, default_allowlist_path

    return Allowlist.load(default_allowlist_path())


def run_check(
    fingerprints_path: str = FINGERPRINTS_PATH,
    kernels: tuple[manifest.Kernel, ...] | None = None,
    allowlist=None,
) -> tuple[list[Finding], list[Trace]]:
    """The full static pass: trace every manifest kernel, enforce the
    contracts, and diff against the checked-in fingerprints.  Returns
    (findings, traces); an empty findings list is the green gate.

    ``allowlist`` (an :class:`analysis.linter.Allowlist`) filters the
    findings when given.  The default is raw so callers that do their
    own allowlist bookkeeping (scripts/lint.py tracks stale entries)
    see every finding exactly once; standalone consumers (the tests)
    pass :func:`default_allowlist` so a justified entry reads green
    everywhere the gate does."""
    traces = [trace_kernel(k) for k in (kernels or manifest.KERNELS)]
    findings = _manifest_findings()
    for t in traces:
        findings.extend(t.findings)
    findings.extend(
        compare_fingerprints(traces, load_fingerprints(fingerprints_path))
    )
    if allowlist is not None:
        findings = [f for f in findings if not allowlist.suppresses(f)]
    return findings, traces


def regenerate(fingerprints_path: str = FINGERPRINTS_PATH) -> tuple[list[Finding], list[Trace]]:
    """Re-trace everything and rewrite the golden file.  Contract
    findings (dtype/purity) still fail — regeneration only blesses
    DRIFT, never a broken contract.  Findings suppressed by a justified
    entry in the checked-in allowlist don't block: a blessed state that
    passes the lint gate must stay regenerable."""
    traces = [trace_kernel(k) for k in manifest.KERNELS]
    findings = _manifest_findings()
    for t in traces:
        findings.extend(t.findings)
    allow = default_allowlist()
    findings = [f for f in findings if not allow.suppresses(f)]
    if not findings:
        write_fingerprints(traces, fingerprints_path)
    return findings, traces


def summary(findings: list[Finding], traces: list[Trace]) -> dict:
    """Machine-readable result: ``kernel`` in ``lint.py --json``."""
    return {
        "ok": not findings,
        "kernels": len(traces),
        "primitive_total": sum(
            sum(t.primitives.values()) for t in traces
        ),
        # per-kernel eqn counts next to their budgets: the acceptance
        # surface for "the table path fits the budget"
        "eqns": {
            t.kernel.name: {"eqns": t.eqns, "max_eqns": t.kernel.max_eqns}
            for t in traces
        },
        "findings": [
            {"check": f.check, "path": f.path, "message": f.message}
            for f in findings
        ],
    }
