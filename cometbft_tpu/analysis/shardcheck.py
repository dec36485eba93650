"""Sharded-program contract checker: multi-device abstract tracing,
collective census, and a compile-cost budget gate for the mesh plane.

``kernelcheck.py`` pins every kernel's numeric contract on a 1-device
trace; this module is its sharded sibling.  Every mesh-parameterized
kernel declared in ``kernel_manifest.SHARDED_KERNELS`` is traced under a
**real 8-way CPU mesh** — a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and
``JAX_PLATFORMS=cpu`` — so CPU-only CI exercises the genuine sharded
program (shard_map + collectives), not a 1-device stand-in.  On the
traced/lowered program three contracts hold:

1. **sharding closure** — the shard_map's in/out names must match the
   manifest's declared ``in_specs``/``out_specs``, and the collective
   census (psum / all_gather / all_to_all / ppermute / resharding
   ``sharding_constraint`` copies, ...) must match the declared census
   exactly.  An undeclared collective is how silent reshard-per-stage
   lands: a pipelined stage that should hand off device-resident shards
   quietly grows a gather+scatter.
2. **compile-cost budget** — per-kernel ceilings on total jaxpr
   equation count, nested-loop depth, and a per-device peak-bytes
   estimate from the shard_map body's (already per-device) avals.  This
   is the static gate that flags a ``jit_build_a_tables``-class
   unrolled table build in milliseconds instead of a 2m34s XLA compile.
3. **donation discipline** — arguments the manifest declares donated
   must actually be donated in the lowered program (``donated_invars``
   on the jit), and nothing else may be; the companion AST check
   (``donated_read.py``) keeps host code from reading a donated buffer
   after dispatch.

Alongside the contracts, a drift gate: the traced signature, shardings,
donation vector, and collective census are held to the checked-in
golden ``analysis/shard_fingerprints.json``.  Regenerate after a
DELIBERATE change with::

    python scripts/lint.py regen-shardings

which refuses while any contract finding is open — regeneration blesses
drift, never a broken contract (the PR-4 fingerprint policy).

JAX imports are deferred to call time; the module is importable
anywhere the stdlib runs.  In-process tracing requires the host to
already expose ``SHARD_MESH_DEVICES`` devices (the test suite forces 8
host devices); every other consumer goes through :func:`run_subprocess`.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import subprocess
import sys
import time
from dataclasses import dataclass, field

from . import kernel_manifest as manifest
from .kernelcheck import UNTRACEABLE_SIG, _aval_str, _walk_jaxprs
from .linter import Finding

SHARD_FINGERPRINTS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "shard_fingerprints.json"
)

#: Every Finding.check id this module emits — scripts/lint.py's
#: stale-entry filter for --check sharding imports this.
FINDING_CHECK_IDS = frozenset(
    {"shard-contract", "shard-fingerprint", "shard-manifest"}
)

# Collective / cross-device primitives counted by the census.  Matched
# on exact names plus family prefixes so versioned spellings
# (all_gather_invariant, ...) still land in the census rather than
# slipping past it.
_COLLECTIVE_PRIMS = frozenset(
    {
        "psum", "pmax", "pmin", "pgather", "pbroadcast", "ppermute",
        "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
        "collective_permute", "sharding_constraint",
    }
)
_COLLECTIVE_PREFIXES = (
    "all_gather", "all_to_all", "reduce_scatter", "psum", "ppermute",
    "collective_permute",
)

# Control-flow primitives whose body nesting the loop-depth budget
# counts (jit/shard_map wrappers add structure, not iteration).
_LOOP_PRIMS = frozenset({"scan", "while", "cond"})


def is_collective(prim_name: str) -> bool:
    return prim_name in _COLLECTIVE_PRIMS or prim_name.startswith(
        _COLLECTIVE_PREFIXES
    )


# ------------------------------------------------------------ normalization


def declared_spec_map(spec) -> dict[str, str]:
    """Spec tuple -> {"dim": "axis"} with unsharded dims dropped — the
    canonical, JSON-able form both sides compare in.  Serves the
    manifest's declared tuples and the ``PartitionSpec`` entries of a
    traced shard_map's ``in_specs``/``out_specs`` alike."""
    out: dict[str, str] = {}
    for dim, name in enumerate(spec):
        if name is None:
            continue
        if isinstance(name, (tuple, list)):
            name = "+".join(name)
        out[str(dim)] = name
    return out


def _fmt_spec(m: dict[str, str]) -> str:
    if not m:
        return "replicated"
    return "{" + ", ".join(f"{d}:{a}" for d, a in sorted(m.items())) + "}"


# ----------------------------------------------------------------- tracing


@dataclass
class ShardTrace:
    """One sharded kernel's 8-way abstract interpretation."""

    sharded: manifest.ShardedKernel
    signature: str
    collectives: dict[str, int]
    in_specs: list[dict[str, str]]  # observed, canonical form
    out_specs: list[dict[str, str]]
    donated: list[int]  # observed donated arg indices
    eqns: int
    loop_depth: int
    device_bytes: int
    findings: list[Finding] = field(default_factory=list)

    def fingerprint(self) -> dict:
        payload = {
            "signature": self.signature,
            "collectives": dict(sorted(self.collectives.items())),
            "in_specs": self.in_specs,
            "out_specs": self.out_specs,
            "donated": list(self.donated),
        }
        digest = hashlib.sha256(
            json.dumps(payload, sort_keys=True).encode()
        ).hexdigest()
        # costs ride along for operators reading the golden but stay out
        # of the digest: they are budget-gated (hard ceilings in the
        # manifest), not drift-gated, so an innocuous +1 eqn never forces
        # a regen ceremony
        return {
            **payload,
            "digest": digest,
            "costs": {
                "eqns": self.eqns,
                "loop_depth": self.loop_depth,
                "device_bytes": self.device_bytes,
            },
        }


def _aval_bytes(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    dt = getattr(aval, "dtype", None)
    return n * (dt.itemsize if dt is not None else 1)


def _resolve_sharded(sk: manifest.ShardedKernel, row: manifest.Kernel, mesh):
    import importlib

    mod_name, _, fn_name = row.fn.partition(":")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    return fn(mesh, **dict(row.static_kwargs))


def _loop_depth(jaxpr) -> int:
    """Deepest nesting of scan/while/cond bodies, iteratively (the comb
    jaxpr nests thousands deep in eqns but shallow in control flow)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    best = 0
    stack = [(jaxpr, 0)]
    seen: set[tuple[int, int]] = set()
    while stack:
        j, depth = stack.pop()
        if isinstance(j, ClosedJaxpr):
            j = j.jaxpr
        if (id(j), depth) in seen:
            continue
        seen.add((id(j), depth))
        best = max(best, depth)
        for eqn in j.eqns:
            inc = 1 if eqn.primitive.name in _LOOP_PRIMS else 0
            for p in eqn.params.values():
                if isinstance(p, (ClosedJaxpr, Jaxpr)):
                    stack.append((p, depth + inc))
                elif isinstance(p, (list, tuple)):
                    stack.extend(
                        (q, depth + inc)
                        for q in p
                        if isinstance(q, (ClosedJaxpr, Jaxpr))
                    )
    return best


def _device_peak_bytes(body_jaxpr) -> int:
    """Per-device peak-bytes estimate from the shard_map body's avals.

    Inside shard_map every aval is already the LOCAL (per-device) shape,
    so no division by mesh size is needed.  The estimate is
    max(resident inputs+consts, largest single equation's in+out) — a
    floor on true peak liveness, cheap and deterministic; the budget is
    a blowup tripwire, not an allocator."""
    resident = 0
    for v in list(body_jaxpr.invars) + list(body_jaxpr.constvars):
        resident += _aval_bytes(v.aval)
    peak_eqn = 0
    for j in _walk_jaxprs(body_jaxpr):
        for eqn in j.eqns:
            b = sum(
                _aval_bytes(v.aval)
                for v in list(eqn.invars) + list(eqn.outvars)
                if hasattr(v, "aval")
            )
            peak_eqn = max(peak_eqn, b)
    return max(resident, peak_eqn)


def trace_sharded(
    sk: manifest.ShardedKernel, row: manifest.Kernel, mesh
) -> ShardTrace:
    """Trace one sharded kernel under ``mesh`` and run the three
    contract passes over its jaxpr."""
    import jax

    path = manifest.module_path(row)
    findings: list[Finding] = []

    def add(msg: str) -> None:
        findings.append(
            Finding("shard-contract", path, 1, 0, f"[{sk.name}] {msg}")
        )

    def structs():
        import numpy as np

        return [
            jax.ShapeDtypeStruct(a.shape, np.dtype(a.dtype)) for a in sk.args
        ]

    try:
        fn = _resolve_sharded(sk, row, mesh)
        closed = jax.make_jaxpr(fn)(*structs())
    except Exception as e:  # noqa: BLE001 - failing to trace IS the finding
        add(f"failed to trace under the {mesh.devices.size}-way mesh: "
            f"{type(e).__name__}: {e}")
        return ShardTrace(sk, UNTRACEABLE_SIG, {}, [], [], [], 0, 0, 0, findings)

    in_sig = ", ".join(_aval_str(a) for a in closed.in_avals)
    out_sig = ", ".join(_aval_str(a) for a in closed.out_avals)
    signature = f"({in_sig}) -> ({out_sig})"

    got = [(tuple(a.shape), str(a.dtype)) for a in closed.out_avals]
    want = [(a.shape, a.dtype) for a in sk.out]
    if got != want:
        add(f"output spec mismatch: manifest declares {want}, trace "
            f"produced {got}")

    # ---- census + budgets over the whole program
    prims: dict[str, int] = {}
    total_eqns = 0
    shard_maps = []
    jit_eqn = None
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "jit" and jit_eqn is None:
            jit_eqn = eqn
    for j in _walk_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            total_eqns += 1
            name = eqn.primitive.name
            prims[name] = prims.get(name, 0) + 1
            if name == "shard_map":
                shard_maps.append(eqn)

    census = {k: v for k, v in prims.items() if is_collective(k)}
    declared = {k: v for k, v in sk.collectives}
    for prim in sorted(set(census) | set(declared)):
        have, want_n = census.get(prim, 0), declared.get(prim, 0)
        if have > want_n:
            add(
                f"undeclared collective {prim!r}: traced program contains "
                f"{have}, census declares {want_n} ({have - want_n:+d}) — "
                "a silent reshard/new collective; update the manifest "
                "census only if the extra communication is intended"
            )
        elif have < want_n:
            add(
                f"stale collective census: {prim!r} declared {want_n} but "
                f"the traced program contains {have} — shrink the census"
            )

    # ---- donation discipline on the lowered jit
    donated_idx: list[int] = []
    if jit_eqn is not None:
        donated = jit_eqn.params.get("donated_invars", ())
        donated_idx = [i for i, d in enumerate(donated) if d]
    declared_don = set(sk.donate_argnums)
    if jit_eqn is None and declared_don:
        add(
            "program is not jitted at the top level — declared donations "
            f"{sorted(declared_don)} cannot be honored"
        )
    else:
        for i in sorted(declared_don - set(donated_idx)):
            add(
                f"donation contract: arg {i} is declared donated but the "
                "lowered program does not donate it (missing "
                "donate_argnums on the jit?)"
            )
        for i in sorted(set(donated_idx) - declared_don):
            add(
                f"donation contract: arg {i} is donated by the lowered "
                "program but not declared in the manifest — an undeclared "
                "donation invalidates a buffer host code may still hold"
            )

    # ---- sharding closure on the shard_map
    in_specs_obs: list[dict[str, str]] = []
    out_specs_obs: list[dict[str, str]] = []
    device_bytes = 0
    if not shard_maps:
        add(
            "no shard_map in the traced program — the kernel does not "
            "actually run under the mesh; per-device budgets and the "
            "sharding closure are unverifiable"
        )
        device_bytes = max(
            (_aval_bytes(a) for a in list(closed.in_avals) + list(closed.out_avals)),
            default=0,
        )
    else:
        if len(shard_maps) > 1:
            add(
                f"{len(shard_maps)} shard_map applications in one program "
                "— the contract covers exactly one mesh entry per kernel"
            )
        sm = shard_maps[0]
        # closed-over constants (SHA round tables, the basepoint comb)
        # are hoisted as LEADING shard_map operands; the user arguments
        # are the trailing len(sk.args) entries.  Constants must be
        # replicated — a sharded closure constant would be a hidden
        # resharding input the manifest cannot describe.
        all_in = [declared_spec_map(s) for s in sm.params["in_specs"]]
        n_args = len(sk.args)
        n_const = max(0, len(all_in) - n_args)
        for i, obs in enumerate(all_in[:n_const]):
            if obs:
                add(
                    f"sharding closure: closed-over constant {i} is "
                    f"{_fmt_spec(obs)} — closure constants must be "
                    "replicated; pass sharded values as arguments"
                )
        in_specs_obs = all_in[n_const:]
        out_specs_obs = [declared_spec_map(s) for s in sm.params["out_specs"]]
        in_specs_decl = [declared_spec_map(s) for s in sk.in_specs]
        out_specs_decl = [declared_spec_map(s) for s in sk.out_specs]
        if in_specs_obs != in_specs_decl:
            for i, (obs, decl) in enumerate(
                zip(in_specs_obs, in_specs_decl)
            ):
                if obs != decl:
                    add(
                        f"sharding closure: input {i} is {_fmt_spec(obs)} "
                        f"but the manifest declares {_fmt_spec(decl)} — a "
                        "respec here means a silent reshard at every call"
                    )
            if len(in_specs_obs) != len(in_specs_decl):
                add(
                    f"sharding closure: program takes {len(in_specs_obs)} "
                    f"inputs, manifest declares {len(in_specs_decl)}"
                )
        if out_specs_obs != out_specs_decl:
            for i, (obs, decl) in enumerate(
                zip(out_specs_obs, out_specs_decl)
            ):
                if obs != decl:
                    add(
                        f"sharding closure: output {i} is {_fmt_spec(obs)} "
                        f"but the manifest declares {_fmt_spec(decl)}"
                    )
            if len(out_specs_obs) != len(out_specs_decl):
                add(
                    f"sharding closure: program returns {len(out_specs_obs)} "
                    f"outputs, manifest declares {len(out_specs_decl)}"
                )
        device_bytes = _device_peak_bytes(sm.params["jaxpr"])

    # ---- compile-cost budget
    depth = _loop_depth(closed.jaxpr)
    if total_eqns > sk.max_eqns:
        add(
            f"compile-cost budget: {total_eqns} jaxpr equations exceeds "
            f"the budget of {sk.max_eqns} ({total_eqns - sk.max_eqns:+d}) "
            "— an unrolled loop or table build lands here in milliseconds "
            "instead of as a minutes-long XLA compile; restructure the "
            "kernel (roll the loop / precompute host-side) or raise the "
            "budget with justification"
        )
    if depth > sk.max_loop_depth:
        add(
            f"compile-cost budget: control-flow nesting depth {depth} "
            f"exceeds the budget of {sk.max_loop_depth} "
            f"({depth - sk.max_loop_depth:+d})"
        )
    if device_bytes > sk.max_device_bytes:
        add(
            f"compile-cost budget: per-device peak-bytes estimate "
            f"{device_bytes} exceeds the budget of {sk.max_device_bytes} "
            f"({device_bytes - sk.max_device_bytes:+d})"
        )

    return ShardTrace(
        sk, signature, census, in_specs_obs, out_specs_obs, donated_idx,
        total_eqns, depth, device_bytes, findings,
    )


# -------------------------------------------------------------- drift gate


def load_fingerprints(path: str = SHARD_FINGERPRINTS_PATH) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        return {}


def write_fingerprints(
    traces: list[ShardTrace], path: str = SHARD_FINGERPRINTS_PATH
) -> None:
    data = {t.sharded.name: t.fingerprint() for t in traces}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


def _diff_report(name: str, golden: dict, fresh: dict) -> str:
    lines = [f"sharded kernel {name!r} drifted from its checked-in golden:"]
    for key in ("signature", "in_specs", "out_specs", "donated"):
        if golden.get(key) != fresh.get(key):
            lines.append(f"  {key} before: {golden.get(key)}")
            lines.append(f"  {key} after : {fresh.get(key)}")
    gc = golden.get("collectives", {})
    fc = fresh.get("collectives", {})
    for prim in sorted(set(gc) | set(fc)):
        b, a = gc.get(prim, 0), fc.get(prim, 0)
        if b != a:
            lines.append(f"  collective {prim}: {b} -> {a} ({a - b:+d})")
    lines.append(
        "  deliberate change? regenerate with "
        "`python scripts/lint.py regen-shardings`"
    )
    return "\n".join(lines)


def compare_fingerprints(
    traces: list[ShardTrace], golden: dict
) -> list[Finding]:
    findings: list[Finding] = []
    fresh_names = set()
    for t in traces:
        fresh_names.add(t.sharded.name)
        if t.signature == UNTRACEABLE_SIG:
            continue  # 'failed to trace' is already the finding
        row = manifest.by_name().get(t.sharded.name)
        path = manifest.module_path(row) if row else "cometbft_tpu/parallel/verify.py"
        fresh = t.fingerprint()
        have = golden.get(t.sharded.name)
        if have is None:
            findings.append(Finding(
                "shard-fingerprint", path, 1, 0,
                f"sharded kernel {t.sharded.name!r} has no checked-in "
                "golden — run `python scripts/lint.py regen-shardings`",
            ))
        elif have.get("digest") != fresh["digest"]:
            findings.append(Finding(
                "shard-fingerprint", path, 1, 0,
                _diff_report(t.sharded.name, have, fresh),
            ))
    known = fresh_names | set(manifest.sharded_by_name())
    for name in sorted(set(golden) - known):
        findings.append(Finding(
            "shard-fingerprint",
            "cometbft_tpu/analysis/shard_fingerprints.json", 1, 0,
            f"golden {name!r} names no sharded manifest kernel — stale "
            "entry; regenerate the goldens",
        ))
    return findings


# ------------------------------------------------------- manifest findings


def _manifest_findings() -> list[Finding]:
    """Internal consistency of the sharding extension itself."""
    findings: list[Finding] = []
    mpath = "cometbft_tpu/analysis/kernel_manifest.py"

    def add(msg: str) -> None:
        findings.append(Finding("shard-manifest", mpath, 1, 0, msg))

    rows = manifest.by_name()
    seen: set[str] = set()
    for sk in manifest.SHARDED_KERNELS:
        if sk.name in seen:
            add(f"duplicate ShardedKernel {sk.name!r}")
        seen.add(sk.name)
        row = rows.get(sk.name)
        if row is None:
            add(f"ShardedKernel {sk.name!r} names no manifest Kernel row")
            continue
        if not row.needs_mesh:
            add(f"ShardedKernel {sk.name!r}: Kernel row is not needs_mesh")
        if len(sk.in_specs) != len(sk.args):
            add(
                f"ShardedKernel {sk.name!r}: {len(sk.in_specs)} in_specs "
                f"for {len(sk.args)} args"
            )
        if len(sk.out_specs) != len(sk.out):
            add(
                f"ShardedKernel {sk.name!r}: {len(sk.out_specs)} out_specs "
                f"for {len(sk.out)} outputs"
            )
        for spec, arg in zip(sk.in_specs, sk.args):
            if len(spec) > len(arg.shape):
                add(
                    f"ShardedKernel {sk.name!r}: in_spec {spec} longer "
                    f"than the arg rank {len(arg.shape)}"
                )
        for i in sk.donate_argnums:
            if not (0 <= i < len(sk.args)):
                add(f"ShardedKernel {sk.name!r}: donate_argnums {i} out of range")
        for pname, pos in sk.entry_donated_params:
            if not pname or pos < 0:
                add(
                    f"ShardedKernel {sk.name!r}: bad entry_donated_params "
                    f"({pname!r}, {pos})"
                )
        if sk.entry_donated_params and not sk.donate_argnums:
            add(
                f"ShardedKernel {sk.name!r}: entry_donated_params declared "
                "but no donate_argnums"
            )
        if min(sk.max_eqns, sk.max_loop_depth, sk.max_device_bytes) <= 0:
            add(f"ShardedKernel {sk.name!r}: budgets must be positive")
    return findings


# ----------------------------------------------------------------- driver


def _build_mesh():
    """The real 8-way mesh, or a shard-manifest finding when the host
    cannot provide it (callers then go through run_subprocess)."""
    import jax

    from ..parallel.mesh import make_mesh

    have = len(jax.devices())
    if have < manifest.SHARD_MESH_DEVICES:
        return None, [Finding(
            "shard-manifest", "cometbft_tpu/analysis/shardcheck.py", 1, 0,
            f"host exposes {have} device(s); the sharded gate needs "
            f"{manifest.SHARD_MESH_DEVICES} — run via "
            "shardcheck.run_subprocess (forced host devices)",
        )]
    return make_mesh(manifest.SHARD_MESH_DEVICES, axis=manifest.SHARD_AXIS), []


def run_check(
    fingerprints_path: str = SHARD_FINGERPRINTS_PATH,
    sharded: tuple[manifest.ShardedKernel, ...] | None = None,
    kernel_rows: dict[str, manifest.Kernel] | None = None,
    allowlist=None,
    skip_goldens: bool = False,
) -> tuple[list[Finding], list[ShardTrace]]:
    """The full sharded static pass.  Returns (findings, traces); empty
    findings is the green gate.  ``sharded``/``kernel_rows`` swap in a
    fixture manifest (tests); manifest-consistency findings only run
    against the real manifest.  ``skip_goldens`` limits the run to the
    contract passes (fixture runs have no checked-in golden)."""
    fixture_run = sharded is not None
    sharded = sharded if sharded is not None else manifest.SHARDED_KERNELS
    rows = kernel_rows if kernel_rows is not None else manifest.by_name()
    findings = [] if fixture_run else _manifest_findings()
    mesh, mesh_findings = _build_mesh()
    if mesh is None:
        return findings + mesh_findings, []
    traces: list[ShardTrace] = []
    for sk in sharded:
        row = rows.get(sk.name)
        if row is None:
            findings.append(Finding(
                "shard-manifest", "cometbft_tpu/analysis/kernel_manifest.py",
                1, 0, f"ShardedKernel {sk.name!r} has no Kernel row to trace",
            ))
            continue
        traces.append(trace_sharded(sk, row, mesh))
    for t in traces:
        findings.extend(t.findings)
    if not skip_goldens:
        findings.extend(
            compare_fingerprints(traces, load_fingerprints(fingerprints_path))
        )
    if allowlist is not None:
        findings = [f for f in findings if not allowlist.suppresses(f)]
    return findings, traces


def regenerate(
    fingerprints_path: str = SHARD_FINGERPRINTS_PATH,
    sharded: tuple[manifest.ShardedKernel, ...] | None = None,
    kernel_rows: dict[str, manifest.Kernel] | None = None,
) -> tuple[list[Finding], list[ShardTrace]]:
    """Re-trace and rewrite the golden file.  Contract findings
    (closure/census/budget/donation) still fail — regeneration only
    blesses DRIFT, never a broken contract.  Justified allowlist entries
    don't block, so a blessed state stays regenerable."""
    from .kernelcheck import default_allowlist

    findings, traces = run_check(
        fingerprints_path, sharded=sharded, kernel_rows=kernel_rows,
        skip_goldens=True,
    )
    allow = default_allowlist()
    findings = [f for f in findings if not allow.suppresses(f)]
    if not findings:
        write_fingerprints(traces, fingerprints_path)
    return findings, traces


def summary(findings: list[Finding], traces: list[ShardTrace]) -> dict:
    """Machine-readable result: ``sharding`` in ``lint.py --json``."""
    return {
        "ok": not findings,
        "kernels": {
            t.sharded.name: {
                "eqns": t.eqns,
                "loop_depth": t.loop_depth,
                "device_bytes": t.device_bytes,
                "collectives": dict(sorted(t.collectives.items())),
            }
            for t in traces
        },
        "findings": [
            {"check": f.check, "path": f.path, "line": f.line,
             "col": f.col, "message": f.message}
            for f in findings
        ],
    }


# ------------------------------------------------------------- subprocess
#
# The production entry: CPU-only CI (and any host whose jax is already
# initialized with the wrong device count) runs the gate in a child
# interpreter with the 8-device CPU environment forced BEFORE jax's
# first import, so the traced program is the genuine sharded one and a
# real accelerator — which may belong to the parent process — is never
# touched.

_DEV_FLAG_RE = re.compile(r"--xla_force_host_platform_device_count=\d+")


def _forced_env(base: dict) -> dict:
    env = dict(base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = _DEV_FLAG_RE.sub("", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags
        + f" --xla_force_host_platform_device_count={manifest.SHARD_MESH_DEVICES}"
    ).strip()
    return env


def _repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def run_subprocess(
    *,
    regen: bool = False,
    fixtures: str | None = None,
    only: tuple[str, ...] = (),
    fingerprints_path: str | None = None,
    skip_goldens: bool = False,
    timeout: float = 1800.0,
) -> tuple[list[Finding], dict]:
    """Run the gate in a forced-environment child; returns
    (findings, summary).  A child that dies or emits unparseable output
    is itself a finding — the gate must never silently read green."""
    repo = _repo_root()
    env = _forced_env(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    argv = [sys.executable, "-m", "cometbft_tpu.analysis.shardcheck", "--json"]
    if regen:
        argv.append("--regen")
    if fixtures:
        argv += ["--fixtures", fixtures]
    for name in only:
        argv += ["--only", name]
    if fingerprints_path:
        argv += ["--fingerprints", fingerprints_path]
    if skip_goldens:
        argv.append("--no-goldens")
    try:
        proc = subprocess.run(
            argv, capture_output=True, text=True, env=env, cwd=repo,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        f = Finding(
            "shard-contract", "cometbft_tpu/analysis/shardcheck.py", 1, 0,
            f"sharded trace child timed out after {timeout:.0f}s — a "
            "compile-cost blowup or a hung backend; the gate is RED",
        )
        return [f], {"ok": False, "error": "timeout", "findings": []}
    for line in reversed(proc.stdout.strip().splitlines() or [""]):
        line = line.strip()
        if line.startswith("{"):
            try:
                data = json.loads(line)
            except json.JSONDecodeError:
                continue
            findings = [
                Finding(d["check"], d["path"], d["line"], d["col"], d["message"])
                for d in data.get("findings", ())
            ]
            return findings, data
    f = Finding(
        "shard-contract", "cometbft_tpu/analysis/shardcheck.py", 1, 0,
        f"sharded trace child failed (rc={proc.returncode}) with no "
        f"parseable report; stderr tail: {proc.stderr[-400:]!r}",
    )
    return [f], {"ok": False, "error": f"child rc={proc.returncode}",
                 "findings": []}


def _child_main(argv: list[str] | None = None) -> int:
    """The forced-environment child body (``python -m
    cometbft_tpu.analysis.shardcheck``).  Pins the CPU platform and the
    8-device flag BEFORE jax's first import so direct invocations work
    without the wrapper too."""
    import argparse

    for k, v in _forced_env(
        {"XLA_FLAGS": os.environ.get("XLA_FLAGS", "")}
    ).items():
        os.environ[k] = v
    if "jax" in sys.modules:  # pragma: no cover - defensive
        import jax

        if len(jax.devices()) < manifest.SHARD_MESH_DEVICES:
            print(json.dumps({
                "ok": False,
                "error": "jax already initialized with too few devices",
                "findings": [],
            }))
            return 2

    ap = argparse.ArgumentParser(description="sharded-program contract gate")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--regen", action="store_true")
    ap.add_argument("--fixtures", default=None,
                    help="module exporting SHARDED_KERNELS + KERNEL_ROWS")
    ap.add_argument("--only", action="append", default=[])
    ap.add_argument("--fingerprints", default=None)
    ap.add_argument("--no-goldens", action="store_true")
    args = ap.parse_args(argv)

    sharded = None
    rows = None
    if args.fixtures:
        import importlib

        mod = importlib.import_module(args.fixtures)
        sharded = tuple(mod.SHARDED_KERNELS)
        rows = dict(mod.KERNEL_ROWS)
    if args.only:
        pool = sharded if sharded is not None else manifest.SHARDED_KERNELS
        sharded = tuple(s for s in pool if s.name in set(args.only))
        if not sharded:
            # a typo'd --only tracing zero kernels must not read as a
            # clean pass (the PR-3 nonexistent-lint-path rule)
            print(json.dumps({
                "ok": False,
                "error": f"--only {args.only} matched no sharded kernel",
                "findings": [{
                    "check": "shard-manifest",
                    "path": "cometbft_tpu/analysis/kernel_manifest.py",
                    "line": 1, "col": 0,
                    "message": f"--only {args.only} matched no sharded "
                    "kernel — nothing was checked",
                }],
            }))
            return 2
    fp = args.fingerprints or SHARD_FINGERPRINTS_PATH

    t0 = time.monotonic()
    if args.regen:
        findings, traces = regenerate(fp, sharded=sharded, kernel_rows=rows)
        written = not findings
    else:
        # check runs report RAW findings: the CALLER owns allowlist
        # policy (scripts/lint.py applies its --allowlist/--config
        # choice and tracks stale entries; bench applies the default) —
        # filtering here too would hide a live finding from the
        # parent's used-entry bookkeeping.  Only regen (above) consults
        # the checked-in allowlist itself, for its refusal semantics.
        findings, traces = run_check(
            fp, sharded=sharded, kernel_rows=rows,
            skip_goldens=args.no_goldens,
        )
        written = False

    import jax

    result = {
        **summary(findings, traces),
        "device_count": len(jax.devices()),
        "elapsed_s": round(time.monotonic() - t0, 1),
        "regen_written": written,
    }
    if args.json:
        print(json.dumps(result))
    else:
        for f in findings:
            print(f.render())
        print(
            f"traced {len(traces)} sharded kernel(s) on "
            f"{result['device_count']} devices in {result['elapsed_s']}s"
            + (" (goldens written)" if written else "")
        )
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(_child_main())
