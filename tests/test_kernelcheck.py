"""Kernel contract checker tests: fixtures tripping each kernel-plane
AST check, the manifest-exhaustiveness gate (every ``jax.jit`` site in
the repo registered, no stale registrations), the fingerprint
round-trip + deliberate-drift failure report, and dtype-closure /
purity negative cases traced through real (tiny) jaxprs.

The full-manifest trace gate (every checked-in fingerprint against a
fresh trace of every kernel) is ~2.5 min of CPU tracing and marked
``slow``; the acceptance command ``python scripts/lint.py --check
kernel cometbft_tpu`` runs the same pass.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

from cometbft_tpu.analysis import (
    _jitscan,
    host_sync,
    kernel_manifest as manifest,
    kernelcheck,
    linter,
    untracked_jit,
    weak_type_literal,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mod(src: str, path: str = "cometbft_tpu/ops/fake.py") -> linter.Module:
    return linter.Module(path, src)


# ------------------------------------------------------- untracked-jit

def test_untracked_jit_flags_each_site_form():
    src = '''
import jax
from functools import partial

@jax.jit
def deco(x):                      # decorator site
    return x

@partial(jax.jit, static_argnums=(1,))
def deco2(x, n):                  # partial-decorator site
    return x

def named(x):
    return x

_J = jax.jit(named)               # by-name call site

def factory(mesh):
    return jax.jit(wrap(local))   # composed: attributed to the factory
'''
    found = untracked_jit.check(_mod(src))
    targets = sorted(f.message.split(" ")[2] for f in found)
    assert targets == [
        "cometbft_tpu/ops/fake.py::deco",
        "cometbft_tpu/ops/fake.py::deco2",
        "cometbft_tpu/ops/fake.py::factory",
        "cometbft_tpu/ops/fake.py::named",
    ]
    assert all(f.check == "untracked-jit" for f in found)


def test_untracked_jit_accepts_registered_site_and_scope():
    # a real JIT_SITES entry (suffix-matched like the allowlist)
    src = "import jax\ndef build_a_tables(x):\n    return x\n_J = jax.jit(build_a_tables)\n"
    assert untracked_jit.check(_mod(src, "cometbft_tpu/ops/comb.py")) == []
    # out of the kernel plane: not this check's business
    assert untracked_jit.check(_mod(src, "cometbft_tpu/utils/foo.py")) == []


# ----------------------------------------------- host-sync-in-hot-path

def test_host_sync_flags_each_sync_kind():
    src = '''
import jax
import numpy as np

def hot(x):
    x.block_until_ready()
    jax.device_get(x)
    v = x.item()
    a = np.asarray(x)
    b = np.array(x)
'''
    found = host_sync.check(_mod(src))
    assert len(found) == 5
    kinds = " | ".join(f.message for f in found)
    for needle in ("block_until_ready", "device_get", ".item()",
                   "np.asarray", "np.array"):
        assert needle in kinds


def test_host_sync_exempts_literals_boundaries_and_scope():
    # module-level host constants from literals: never a sync
    src = (
        "import numpy as np\n"
        "K = np.array([1, 2, 3])\n"
        "W = np.asarray([1 << i for i in range(8)])\n"
    )
    assert host_sync.check(_mod(src)) == []
    # a declared collect boundary (kernel_manifest.COLLECT_BOUNDARIES)
    src = (
        "import numpy as np\n"
        "def from_limbs(a):\n"
        "    a = np.asarray(a)\n"
        "    return a\n"
    )
    assert host_sync.check(_mod(src, "cometbft_tpu/ops/field.py")) == []
    # same code outside a boundary function: a finding
    assert len(host_sync.check(_mod(src.replace("from_limbs", "other")))) == 1
    # models/ is the host orchestration layer — out of scope
    src = "import numpy as np\ndef f(x):\n    return np.asarray(x)\n"
    assert host_sync.check(_mod(src, "cometbft_tpu/models/foo.py")) == []


def test_host_sync_exempts_device_list_construction():
    # the parallel/mesh.py factory shapes: np.array over devices()
    # dataflow is host-list wrapping, not a device fetch — but an
    # arbitrary non-literal argument in the same function still flags
    src = '''
import jax
import numpy as np

def make_mesh(n):
    devs = jax.devices()
    devs = devs[:n]
    return np.array(devs)

def make_mesh_2d(a, b):
    return np.array(jax.devices()[: a * b]).reshape(a, b)

def leak(x):
    return np.array(x)
'''
    found = host_sync.check(_mod(src, "cometbft_tpu/parallel/fake.py"))
    assert len(found) == 1 and "'leak'" in found[0].message


def test_host_sync_device_name_reassigned_loses_exemption():
    src = '''
import jax
import numpy as np

def f(x):
    devs = jax.devices()
    devs = x
    return np.array(devs)
'''
    assert len(host_sync.check(_mod(src))) == 1


# --------------------------------------------------- weak-type-literal

def test_weak_type_literal_flags_float_div_and_wide_int():
    src = '''
import jax

@jax.jit
def k(x):
    a = x * 0.5
    b = x / x
    c = x + 4294967296
    return a
'''
    found = weak_type_literal.check(_mod(src))
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 3
    assert "bare float literal 0.5" in msgs
    assert "true division" in msgs
    assert "exceeds int32" in msgs


def test_weak_type_literal_float_division_reports_once():
    # x / 0.5 is one offending line: the float-literal finding pins it;
    # no second true-division finding for the same BinOp
    src = "import jax\n\n@jax.jit\ndef k(x):\n    return x / 0.5\n"
    found = weak_type_literal.check(_mod(src))
    assert len(found) == 1
    assert "bare float literal 0.5" in found[0].message


def test_weak_type_literal_exemptions():
    # in-range int literal arithmetic is idiomatic and NOT a finding;
    # host (non-jitted) code and ensure_compile_time_eval are exempt
    src = '''
import jax

@jax.jit
def k(x):
    i = x + 1
    j = (x * 8) // 128
    with jax.ensure_compile_time_eval():
        c = x * 0.5
    return i + j

def host_only(x):
    return x * 0.5
'''
    assert weak_type_literal.check(_mod(src)) == []


def test_weak_type_literal_seeds_roots_from_manifest():
    # sha2.sha512_blocks is jitted from models/, not in its own module:
    # only the manifest makes its body visible to a per-module scan
    src = "def sha512_blocks(blocks, active):\n    return blocks * 0.5\n"
    found = weak_type_literal.check(_mod(src, "cometbft_tpu/ops/sha2.py"))
    assert len(found) == 1 and "sha512_blocks" in found[0].message
    # same body under an unmanifested name: no roots, no findings
    src2 = src.replace("sha512_blocks", "helper")
    assert weak_type_literal.check(_mod(src2, "cometbft_tpu/ops/sha2.py")) == []


# ------------------------------------------- manifest exhaustiveness

def _repo_kernel_plane_files():
    for root, dirs, files in os.walk(os.path.join(REPO, "cometbft_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f).replace(os.sep, "/")


def test_every_jit_site_in_repo_is_registered():
    """THE exhaustiveness gate: a new ``jax.jit`` site anywhere in the
    kernel plane fails here until it lands in JIT_SITES (and therefore
    in the manifest + fingerprints)."""
    findings, _ = linter.lint_paths(
        [os.path.join(REPO, "cometbft_tpu")],
        checks={"untracked-jit": untracked_jit},
    )
    assert not findings, "unregistered jit site(s):\n" + "\n".join(
        f.render() for f in findings
    )


def test_jit_sites_registry_is_not_stale():
    """The reverse direction: every JIT_SITES entry must still name a
    real site, so the registry cannot rot as code moves."""
    found: set[tuple[str, str]] = set()
    for path in _repo_kernel_plane_files():
        with open(path, encoding="utf-8") as f:
            mod = linter.Module(path, f.read())
        for site in _jitscan.iter_jit_sites(mod.tree):
            if site.target:
                found.add((mod.path, site.target))
    for site in manifest.JIT_SITES:
        rpath, _, rtarget = site.partition("::")
        assert any(
            t == rtarget and (p == rpath or p.endswith("/" + rpath))
            for p, t in found
        ), f"stale JIT_SITES entry: {site!r} matches no jax.jit site"


def test_manifest_internal_consistency():
    names = manifest.by_name()
    assert len(names) == len(manifest.KERNELS), "duplicate kernel name"
    for site, kernel in manifest.JIT_SITES.items():
        assert kernel in names, f"JIT_SITES[{site!r}] -> unknown {kernel!r}"
    for k in manifest.KERNELS:
        mod_file = os.path.join(REPO, manifest.module_path(k))
        assert os.path.exists(mod_file), f"{k.name}: no module {mod_file}"
    assert "verify_cached" in manifest.traced_roots("cometbft_tpu/ops/comb.py")
    assert kernelcheck._manifest_findings() == []


# --------------------------------------------- fingerprint round trip

def _fake_trace(name="k1", prims=None, sig="(int32[4]) -> (int32[4])"):
    k = manifest.Kernel(
        name=name, fn="cometbft_tpu.ops.comb:whatever",
        args=(manifest.i32(4),), out=(manifest.i32(4),),
    )
    return kernelcheck.Trace(k, sig, dict(prims or {"add": 2, "mul": 1}))


def test_fingerprint_round_trip(tmp_path):
    p = str(tmp_path / "fp.json")
    t = _fake_trace()
    kernelcheck.write_fingerprints([t], p)
    golden = kernelcheck.load_fingerprints(p)
    assert golden["k1"]["digest"] == t.fingerprint()["digest"]
    assert kernelcheck.compare_fingerprints([t], golden) == []


def test_fingerprint_drift_fails_with_readable_report(tmp_path):
    p = str(tmp_path / "fp.json")
    kernelcheck.write_fingerprints([_fake_trace()], p)
    drifted = _fake_trace(
        prims={"add": 3, "mul": 1, "jit": 1},
        sig="(int32[4]) -> (float32[4])",
    )
    found = kernelcheck.compare_fingerprints(
        [drifted], kernelcheck.load_fingerprints(p)
    )
    assert len(found) == 1 and found[0].check == "kernel-fingerprint"
    msg = found[0].message
    assert "drifted" in msg
    assert "signature before: (int32[4]) -> (int32[4])" in msg
    assert "signature after : (int32[4]) -> (float32[4])" in msg
    assert "add: 2 -> 3 (+1)" in msg and "jit: 0 -> 1 (+1)" in msg
    assert "regen-fingerprints" in msg  # the operator hint


def test_fingerprint_missing_and_stale_entries(tmp_path):
    t = _fake_trace()
    found = kernelcheck.compare_fingerprints([t], {})
    assert len(found) == 1 and "no checked-in fingerprint" in found[0].message
    golden = {"k1": t.fingerprint(), "ghost": t.fingerprint()}
    found = kernelcheck.compare_fingerprints([t], golden)
    assert len(found) == 1 and "names no manifest kernel" in found[0].message


def test_compare_fingerprints_subset_keeps_untraced_goldens():
    """A targeted run over a kernel subset must not call the other
    manifest kernels' goldens stale — only names in neither the traces
    nor the manifest are."""
    t = _fake_trace()
    golden = {
        "k1": t.fingerprint(),
        manifest.KERNELS[0].name: {"digest": "whatever"},  # untraced, real
        "ghost": {"digest": "whatever"},  # in neither: stale
    }
    found = kernelcheck.compare_fingerprints([t], golden)
    assert len(found) == 1 and "'ghost'" in found[0].message


# ------------------------------------- dtype closure / purity negatives

def _fixture_module():
    import jax
    import jax.numpy as jnp

    m = types.ModuleType("_kc_fixtures")

    def clean(x):
        return x + jnp.int32(1)

    def weak_float(x):
        return x * 1.5

    def bad_convert(x):
        return x.astype(jnp.int8)

    def impure(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    def boom(x):
        raise RuntimeError("untraceable by design")

    def mesh_factory(mesh, scale=1):
        assert scale == 3, "static_kwargs must reach the mesh factory"

        def run(x):
            return x + jnp.int32(scale)

        return run

    m.clean, m.weak_float, m.bad_convert = clean, weak_float, bad_convert
    m.impure, m.boom, m.mesh_factory = impure, boom, mesh_factory
    sys.modules["_kc_fixtures"] = m
    return m


def _kernel(fn, out, name="fix"):
    # budgeted like every production kernel: tests that swap the
    # manifest (regenerate/run_check end-to-end) must not trip the
    # unbudgeted-kernel manifest finding
    return manifest.Kernel(
        name=name, fn=f"_kc_fixtures:{fn}", args=(manifest.i32(4),), out=out,
        max_eqns=1_000_000,
    )


def test_trace_clean_kernel_has_no_contract_findings():
    _fixture_module()
    t = kernelcheck.trace_kernel(_kernel("clean", (manifest.i32(4),)))
    assert t.findings == []
    assert t.signature == "(int32[4]) -> (int32[4])"
    assert t.primitives.get("add") == 1


def test_trace_flags_weak_float_and_weak_output():
    _fixture_module()
    t = kernelcheck.trace_kernel(_kernel("weak_float", (manifest.f32(4),)))
    msgs = " | ".join(f.message for f in t.findings)
    assert "weak-typed float32" in msgs  # the bare 1.5 intermediate
    assert "weak-typed kernel output" in msgs  # and it escapes the contract


def test_trace_flags_unjustified_conversion():
    _fixture_module()
    t = kernelcheck.trace_kernel(
        _kernel("bad_convert", (manifest.Arg((4,), "int8"),))
    )
    assert any(
        "unjustified convert_element_type int32 -> int8" in f.message
        for f in t.findings
    )


def test_trace_flags_host_callback_as_impure():
    _fixture_module()
    t = kernelcheck.trace_kernel(_kernel("impure", (manifest.i32(4),)))
    assert any("impure primitive" in f.message for f in t.findings)


def test_trace_reports_output_spec_mismatch_and_trace_failure():
    _fixture_module()
    t = kernelcheck.trace_kernel(_kernel("clean", (manifest.u8(4),)))
    assert any("output spec mismatch" in f.message for f in t.findings)
    t = kernelcheck.trace_kernel(_kernel("boom", (manifest.i32(4),)))
    assert t.signature == "<untraceable>"
    assert any("failed to trace" in f.message for f in t.findings)


def test_untraceable_kernel_produces_no_drift_noise(tmp_path):
    """An untraceable kernel reports 'failed to trace' only — never an
    every-primitive 'N -> 0' drift diff with a bogus regen hint."""
    p = str(tmp_path / "fp.json")
    good = _fake_trace()
    kernelcheck.write_fingerprints([good], p)
    broken = kernelcheck.Trace(good.kernel, kernelcheck.UNTRACEABLE_SIG, {})
    found = kernelcheck.compare_fingerprints(
        [broken], kernelcheck.load_fingerprints(p)
    )
    assert found == []


def test_resolve_applies_static_kwargs_to_mesh_factory():
    _fixture_module()
    k = manifest.Kernel(
        name="fix_mesh", fn="_kc_fixtures:mesh_factory",
        args=(manifest.i32(4),), out=(manifest.i32(4),),
        static_kwargs=(("scale", 3),), needs_mesh=True,
    )
    t = kernelcheck.trace_kernel(k)
    assert t.findings == [], [f.message for f in t.findings]


def test_ensure_cpu_backend_overrides_ambient_platform():
    """The gate must pin cpu even over an exported JAX_PLATFORMS=tpu —
    static analysis never asks for (or waits on) an accelerator."""
    code = (
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "os.environ['JAX_PLATFORMS'] = 'tpu'\n"
        "from cometbft_tpu.analysis import kernelcheck\n"
        "kernelcheck._ensure_cpu_backend()\n"
        "print(os.environ['JAX_PLATFORMS'])\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "cpu"


def test_regenerate_and_drift_end_to_end(tmp_path, monkeypatch):
    """regen writes goldens for the (monkeypatched) manifest, a clean
    re-check passes, and editing the kernel fails the gate with the
    readable report — the whole workflow on a fast fixture kernel."""
    m = _fixture_module()
    k = _kernel("clean", (manifest.i32(4),), name="fix_e2e")
    monkeypatch.setattr(manifest, "KERNELS", (k,))
    monkeypatch.setattr(manifest, "JIT_SITES", {})
    p = str(tmp_path / "fp.json")
    findings, traces = kernelcheck.regenerate(p)
    assert findings == [] and len(traces) == 1
    findings, _ = kernelcheck.run_check(p)
    assert findings == []
    # a "deliberate" kernel change: one more add
    import jax.numpy as jnp

    m.clean = lambda x: x + jnp.int32(1) + jnp.int32(2)
    findings, _ = kernelcheck.run_check(p)
    assert len(findings) == 1 and "drifted" in findings[0].message


def test_untracked_jit_refuses_allowlist_suppression(tmp_path):
    """The manifest is the only way out: an allowlist entry for
    untracked-jit does not suppress (and reads back as stale)."""
    f = tmp_path / "ops" / "fake.py"
    f.parent.mkdir()
    f.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x\n")
    allow = linter.Allowlist.parse("untracked-jit fake.py  # must not work\n")
    findings, stale = linter.lint_paths(
        [str(f)], checks={"untracked-jit": untracked_jit}, allowlist=allow
    )
    assert len(findings) == 1 and findings[0].check == "untracked-jit"
    assert [e.check for e in stale] == ["untracked-jit"]


def test_run_check_applies_provided_allowlist(tmp_path, monkeypatch):
    """A justified allowlist entry reads green through run_check too
    and lets regenerate() re-bless the goldens."""
    _fixture_module()
    k = _kernel("weak_float", (manifest.f32(4),), name="fix_allow")
    monkeypatch.setattr(manifest, "KERNELS", (k,))
    monkeypatch.setattr(manifest, "JIT_SITES", {})
    p = str(tmp_path / "fp.json")
    raw, _ = kernelcheck.run_check(p)
    assert raw, "fixture must produce contract findings unfiltered"
    allow = linter.Allowlist.parse(
        "kernel-contract _kc_fixtures.py  # blessed for the test\n"
        "kernel-fingerprint _kc_fixtures.py  # blessed for the test\n"
    )
    filtered, traces = kernelcheck.run_check(p, allowlist=allow)
    assert filtered == [] and len(traces) == 1
    # regenerate honors the checked-in allowlist the same way
    monkeypatch.setattr(kernelcheck, "default_allowlist", lambda: allow)
    findings, _ = kernelcheck.regenerate(p)
    assert findings == [] and os.path.exists(p)


def test_regenerate_refuses_broken_contract(tmp_path, monkeypatch):
    _fixture_module()
    k = _kernel("weak_float", (manifest.f32(4),), name="fix_bad")
    monkeypatch.setattr(manifest, "KERNELS", (k,))
    monkeypatch.setattr(manifest, "JIT_SITES", {})
    p = str(tmp_path / "fp.json")
    findings, _ = kernelcheck.regenerate(p)
    assert findings, "contract violation must refuse regeneration"
    assert not os.path.exists(p)


# ------------------------------------------------------------ CLI wiring

def test_lint_cli_check_selector(tmp_path):
    bad = tmp_path / "ops" / "fake.py"
    bad.parent.mkdir()
    bad.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x\n")
    cli = [sys.executable, os.path.join(REPO, "scripts", "lint.py")]
    proc = subprocess.run(
        cli + [str(bad), "--check", "untracked-jit", "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert {f["check"] for f in data["findings"]} == {"untracked-jit"}
    proc = subprocess.run(
        cli + [str(bad), "--check", "no-such-check"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 2


# ------------------------------------------- the phases a profile reads

def test_scopes_are_collected_from_nested_bodies_and_outside_the_digest():
    """jax.named_scope names come out of the existing walk (loop bodies
    included) and move no fingerprint: a scope adds no primitive."""
    import jax
    import jax.numpy as jnp

    m = _fixture_module()

    def body(x):
        def step(i, a):
            return a + jnp.int32(1)

        return jax.lax.fori_loop(0, 3, step, x) * jnp.int32(2)

    def scoped(x):
        with jax.named_scope("outer_phase"):
            def step(i, a):
                with jax.named_scope("inner_phase"):
                    return a + jnp.int32(1)

            x = jax.lax.fori_loop(0, 3, step, x)
        with jax.named_scope("last_phase"):
            return x * jnp.int32(2)

    m.body, m.scoped = body, scoped
    plain = kernelcheck.trace_kernel(_kernel("body", (manifest.i32(4),)))
    named = kernelcheck.trace_kernel(_kernel("scoped", (manifest.i32(4),)))
    assert plain.scopes == frozenset()
    assert named.scopes == {"outer_phase", "inner_phase", "last_phase"}
    assert named.fingerprint() == plain.fingerprint()
    assert "scopes" not in named.fingerprint()


# the names of docs/observability.md "Names inside the device program":
# the same in both verify programs, so one metric file of benchmarks/
# reads either
_PHASES = {
    "ed25519_verify_batch": {
        "sha512", "scalar_prep", "decompress", "var_table", "scalar_mul",
        "final_check",
    },
    "comb_device_verify": {
        "payload_parse", "sha512", "scalar_prep", "decompress", "scalar_mul",
        "comb_lookup_a", "comb_lookup_b", "tree_reduce", "final_check",
        "pack_result",
    },
    # the secp256k1 programs' own names (a prefix of their own, so that
    # no reader of the ed25519 phases counts them)
    "secp256k1_verify_batch": {"secp_prep", "secp_walk", "secp_final"},
    "secp256k1_hash_verify": {
        "secp_hash", "secp_prep", "secp_walk", "secp_final",
    },
}


@pytest.mark.parametrize("name", sorted(_PHASES))
def test_verify_programs_name_their_phases(name):
    """One trace of the manifest kernel, contracts and names together:
    the per-kernel metrics of the benchmark find the device time of a
    phase by these names."""
    t = kernelcheck.trace_kernel(manifest.by_name()[name])
    assert t.findings == []
    assert t.scopes == _PHASES[name]


# ------------------------------------------------------- the slow gate

@pytest.mark.slow
def test_checked_in_fingerprints_match_fresh_trace():
    """The acceptance gate, in-process: trace every manifest kernel on
    the CPU backend and hold it to the checked-in goldens (same pass as
    ``python scripts/lint.py --check kernel cometbft_tpu``)."""
    allowlist = linter.Allowlist.load(linter.default_allowlist_path())
    findings, traces = kernelcheck.run_check()
    findings = [f for f in findings if not allowlist.suppresses(f)]
    assert len(traces) == len(manifest.KERNELS)
    assert not findings, "kernel contract findings:\n" + "\n".join(
        f.render() for f in findings
    )
