"""Sharded-program contract checker tests.

Fast tier: the contract passes exercised in-process over the fixture
kernels in ``tests/shardcheck_fixtures.py`` (the suite already runs
with 8 forced host devices, so the genuine 8-way mesh is available
without a child interpreter), the golden round-trip/drift machinery,
the ``donated-read-after-dispatch`` AST check, the per-equivalent-mesh
program cache regression, and the CLI wiring.  One subprocess
smoke proves the forced-environment child end to end.

Slow tier: the full golden-match pass — every real sharded kernel
traced in the child and held to the checked-in
``analysis/shard_fingerprints.json`` (the same pass as
``python scripts/lint.py --check sharding``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import shardcheck_fixtures as fx
from cometbft_tpu.analysis import (
    donated_read,
    kernel_manifest as manifest,
    linter,
    shardcheck,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _findings_for(name, findings):
    return [f for f in findings if f"[{name}]" in f.message]


def _trace_one(sk, *, rows=fx.KERNEL_ROWS):
    findings, traces = shardcheck.run_check(
        sharded=(sk,), kernel_rows=rows, skip_goldens=True
    )
    assert len(traces) <= 1
    return findings, (traces[0] if traces else None)


# ------------------------------------------------- manifest consistency


def test_sharding_manifest_is_internally_consistent():
    assert shardcheck._manifest_findings() == []
    rows = manifest.by_name()
    for sk in manifest.SHARDED_KERNELS:
        row = rows[sk.name]
        assert row.needs_mesh, sk.name
        assert len(sk.in_specs) == len(sk.args)
        assert len(sk.out_specs) == len(sk.out)
    assert set(manifest.sharded_by_name()) == {
        "sharded_verify_batch", "sharded_verify_cached", "sharded_merkle_root",
        "sharded_merkle_proofs",
    }
    # the donated-entrypoint worklist the AST check consumes: since
    # PR 11 every per-call staging slab of every sharded program is
    # donated ("finish the set"), not just the comb payload
    assert manifest.donated_entrypoints() == {
        "sharded_verify_batch": (
            ("a_enc", 1), ("r_enc", 2), ("s_bytes", 3),
            ("msg_blocks", 4), ("msg_active", 5),
        ),
        "sharded_verify_cached": (("payload", 4),),
        "sharded_merkle_root": (("leaf_blocks", 1), ("leaf_active", 2)),
        "sharded_merkle_proofs": (("indices", 3), ("sib_pos", 4)),
    }


def test_spec_normalization():
    assert shardcheck.declared_spec_map(("sig",)) == {"0": "sig"}
    assert shardcheck.declared_spec_map((None, None, "sig")) == {"2": "sig"}
    assert shardcheck.declared_spec_map(()) == {}
    # a traced shard_map's PartitionSpec entries take the same road
    from jax.sharding import PartitionSpec as P

    assert shardcheck.declared_spec_map(P("sig")) == {"0": "sig"}
    assert shardcheck.declared_spec_map(P()) == {}
    assert shardcheck.declared_spec_map(P(None, ("a", "b"))) == {"1": "a+b"}
    assert shardcheck._fmt_spec({}) == "replicated"
    assert "0:sig" in shardcheck._fmt_spec({"0": "sig"})


def test_collective_prim_matcher():
    for name in ("psum", "all_gather", "all_to_all", "ppermute",
                 "sharding_constraint", "all_gather_invariant"):
        assert shardcheck.is_collective(name), name
    for name in ("add", "scan", "shard_map", "jit", "convert_element_type"):
        assert not shardcheck.is_collective(name), name


# ----------------------------------------- contract passes (fixtures)


def test_clean_fixture_traces_green():
    findings, t = _trace_one(fx.CLEAN)
    assert findings == [], [f.message for f in findings]
    assert t.collectives == {"psum": 1}
    assert t.in_specs == [{"0": "sig"}] and t.out_specs == [{}]
    assert t.donated == [] and t.eqns > 0


def test_undeclared_collective_is_a_finding():
    findings, _ = _trace_one(fx.BAD_CENSUS)
    assert len(findings) == 1
    msg = findings[0].message
    assert "undeclared collective 'ppermute'" in msg and "(+1)" in msg
    assert findings[0].check == "shard-contract"


def test_blown_equation_budget_is_a_finding():
    """The jit_build_a_tables class: an unrolled table build fails the
    static budget with the kernel name and the delta in the report."""
    findings, t = _trace_one(fx.BAD_BUDGET)
    assert len(findings) == 1
    msg = findings[0].message
    assert "[shardfix_budget]" in msg and "compile-cost budget" in msg
    assert f"{t.eqns} jaxpr equations exceeds the budget of 64" in msg
    assert f"(+{t.eqns - 64})" in msg


def test_blown_loop_depth_is_a_finding():
    findings, _ = _trace_one(fx.BAD_DEPTH)
    assert len(findings) == 1
    assert "control-flow nesting depth 2 exceeds the budget of 1" in (
        findings[0].message
    )


def test_violated_donation_is_a_finding():
    findings, _ = _trace_one(fx.BAD_DONATION)
    assert len(findings) == 1
    assert "declared donated but the lowered program does not donate" in (
        findings[0].message
    )


def test_undeclared_donation_is_a_finding():
    findings, _ = _trace_one(fx.SNEAKY_DONATION)
    assert len(findings) == 1
    assert "donated by the lowered program but not declared" in (
        findings[0].message
    )


def test_spec_mismatch_is_a_finding():
    findings, _ = _trace_one(fx.BAD_SPEC)
    assert len(findings) == 1
    msg = findings[0].message
    assert "sharding closure" in msg
    assert "replicated" in msg and "{0:sig}" in msg


def test_inter_stage_reshard_trips_census():
    """PR-11 regression: a pipelined stage handoff that inserts a
    resharding sharding_constraint is a census finding — the
    no-reshard stage-handoff contract of docs/sharding_contracts.md."""
    findings, t = _trace_one(fx.BAD_PIPELINE)
    msgs = " | ".join(f.message for f in findings)
    assert "undeclared collective 'sharding_constraint'" in msgs
    assert t.collectives.get("sharding_constraint", 0) >= 1
    # the two-stage shape also violates the one-mesh-entry contract
    assert "shard_map applications in one program" in msgs


def test_real_sharded_programs_census_is_reshard_free():
    """The checked-in goldens carry the production censuses: zero
    sharding_constraint anywhere — pipelined stages hand off
    device-resident buffers without a resharding copy — and the
    donation vectors match the manifest's finished set (PR 11: every
    per-call staging slab donated).  The slow golden gate proves these
    goldens match a fresh 8-way trace."""
    golden = shardcheck.load_fingerprints()
    by_name = manifest.sharded_by_name()
    assert set(golden) == set(by_name)
    for name, fp in golden.items():
        assert "sharding_constraint" not in fp["collectives"], name
        assert fp["donated"] == sorted(by_name[name].donate_argnums), name
    assert golden["sharded_verify_batch"]["donated"] == [0, 1, 2, 3, 4]
    assert golden["sharded_merkle_root"]["donated"] == [0, 1]
    assert golden["sharded_verify_cached"]["donated"] == [3]


def test_untraceable_fixture_reports_trace_failure_only(tmp_path):
    findings, t = _trace_one(fx.UNTRACEABLE)
    assert len(findings) == 1
    assert "failed to trace under the 8-way mesh" in findings[0].message
    # and produces no drift noise against any golden
    assert shardcheck.compare_fingerprints([t], {"shardfix_boom": {}}) == []


def test_budget_fixture_donation_still_checked_via_pjit_alignment():
    """The real comb kernel's shape: donation index must align with the
    USER args even though the shard_map sees hoisted constants first —
    pinned here by the real manifest golden carrying donated=[3]."""
    golden = shardcheck.load_fingerprints()
    assert golden["sharded_verify_cached"]["donated"] == [3]
    assert golden["sharded_verify_cached"]["in_specs"][0] == {"4": "sig"}


# --------------------------------------------------- golden round trip


def test_golden_round_trip_and_signature_drift(tmp_path):
    p = str(tmp_path / "shard_fp.json")
    findings, traces = shardcheck.regenerate(
        p, sharded=(fx.CLEAN,), kernel_rows=fx.KERNEL_ROWS
    )
    assert findings == [] and os.path.exists(p)
    findings, _ = shardcheck.run_check(
        p, sharded=(fx.CLEAN,), kernel_rows=fx.KERNEL_ROWS
    )
    assert findings == []
    # the same kernel traced at a different width: signature drift only
    findings, _ = shardcheck.run_check(
        p, sharded=(fx.CLEAN_WIDE,), kernel_rows=fx.KERNEL_ROWS
    )
    assert len(findings) == 1
    msg = findings[0].message
    assert findings[0].check == "shard-fingerprint" and "drifted" in msg
    assert "signature before" in msg and "signature after" in msg
    assert "regen-shardings" in msg  # the operator hint


def test_regenerate_refuses_contract_findings(tmp_path):
    p = str(tmp_path / "shard_fp.json")
    findings, _ = shardcheck.regenerate(
        p, sharded=(fx.BAD_CENSUS,), kernel_rows=fx.KERNEL_ROWS
    )
    assert findings and not os.path.exists(p)


def test_missing_and_stale_goldens(tmp_path):
    _, traces = shardcheck.run_check(
        sharded=(fx.CLEAN,), kernel_rows=fx.KERNEL_ROWS, skip_goldens=True
    )
    found = shardcheck.compare_fingerprints(traces, {})
    assert len(found) == 1 and "no checked-in golden" in found[0].message
    golden = {
        "shardfix_clean": traces[0].fingerprint(),
        "ghost": {"digest": "whatever"},
    }
    found = shardcheck.compare_fingerprints(traces, golden)
    assert len(found) == 1 and "'ghost'" in found[0].message


def test_costs_ride_the_golden_but_not_the_digest(tmp_path):
    _, traces = shardcheck.run_check(
        sharded=(fx.CLEAN,), kernel_rows=fx.KERNEL_ROWS, skip_goldens=True
    )
    fp = traces[0].fingerprint()
    assert fp["costs"]["eqns"] == traces[0].eqns
    mutated = dict(fp)
    mutated["costs"] = {"eqns": 10**6, "loop_depth": 99, "device_bytes": 0}
    assert shardcheck.compare_fingerprints(
        traces, {"shardfix_clean": mutated}
    ) == []  # budget numbers are manifest-gated, not drift-gated


# -------------------------------------------- per-equivalent-mesh cache


def test_one_program_per_equivalent_mesh():
    """The PR-6 cache fix: two make_mesh calls over the same devices
    hand out the SAME program object — one trace, one compile — while a
    different axis name keys a different program."""
    from cometbft_tpu.parallel import verify as PV
    from cometbft_tpu.parallel.mesh import make_mesh, mesh_cache_key

    m1, m2 = make_mesh(1), make_mesh(1)
    assert m1 is not m2 or mesh_cache_key(m1) == mesh_cache_key(m2)
    assert PV._verify_fn(m1) is PV._verify_fn(m2)
    assert PV._merkle_fn(m1) is PV._merkle_fn(m2)
    assert PV._comb_verify_fn(m1) is PV._comb_verify_fn(m2)
    # the axis name is part of the key
    other = make_mesh(1, axis="other")
    assert PV._verify_fn(other) is not PV._verify_fn(m1)


def test_mesh_cache_key_is_stable_and_distinguishing():
    from cometbft_tpu.parallel.mesh import make_mesh, mesh_cache_key

    k1 = mesh_cache_key(make_mesh(1))
    k2 = mesh_cache_key(make_mesh(1))
    assert k1 == k2 and hash(k1) == hash(k2)
    assert mesh_cache_key(make_mesh(1, axis="x")) != k1


# ------------------------------------------ donated-read-after-dispatch


def _mod(src: str, path: str = "cometbft_tpu/models/fake.py") -> linter.Module:
    return linter.Module(path, src)


def test_donated_read_flags_read_after_dispatch():
    src = '''
def go(mesh, tables, valid, pubs):
    payload = build()
    out = sharded_verify_cached(mesh, tables, valid, pubs, payload)
    return out, payload.sum()
'''
    found = donated_read.check(_mod(src))
    assert len(found) == 1
    assert "'payload' was donated to sharded_verify_cached()" in found[0].message
    assert found[0].check == "donated-read-after-dispatch"


def test_donated_read_keyword_form_and_rebinding():
    src = '''
def kw(mesh, t, v, p):
    payload = build()
    sharded_verify_cached(mesh, t, v, p, payload=payload)
    return payload  # finding: kwarg donation

def rebound(mesh, t, v, p):
    payload = build()
    sharded_verify_cached(mesh, t, v, p, payload)
    payload = build()  # fresh buffer: taint cleared
    return payload
'''
    found = donated_read.check(_mod(src))
    assert len(found) == 1 and found[0].line == 5


def test_donated_read_flags_rhs_of_rebinding_assignment():
    """`payload = payload.sum()` reads the donated buffer BEFORE the
    rebind — Python evaluation order, not AST field order."""
    src = '''
def rebind(mesh, t, v, p):
    payload = build()
    sharded_verify_cached(mesh, t, v, p, payload)
    payload = payload.sum()  # finding: RHS reads the donated buffer
    return payload           # no finding: rebound above

def aug(mesh, t, v, p):
    payload = build()
    sharded_verify_cached(mesh, t, v, p, payload)
    payload += 1  # finding: augmented assignment reads, then rebinds
    return payload
'''
    found = donated_read.check(_mod(src))
    assert [f.line for f in found] == [5, 11], [f.render() for f in found]


def test_donated_read_exempts_prior_reads_inline_args_and_other_fns():
    src = '''
def ok(mesh, t, v, p):
    payload = build()
    use(payload)  # before dispatch: fine
    return sharded_verify_cached(mesh, t, v, p, payload)

def inline(mesh, t, v, p, slab):
    # the production pattern: the donated value is never bound
    return sharded_verify_cached(mesh, t, v, p, jnp.asarray(slab))

def unrelated(payload):
    other_call(payload)
    return payload.sum()
'''
    assert donated_read.check(_mod(src)) == []


def test_donated_read_tracks_same_scope_partial_alias():
    """The production binding shape: a functools.partial over the
    entrypoint shifts the donated position by the bound args."""
    src = '''
import functools

def aliased(mesh, t, v, p):
    fn = functools.partial(sharded_verify_cached, mesh)
    payload = build()
    fn(t, v, p, payload)
    return payload.sum()  # finding: donated via the alias

def alias_rebound(mesh, t, v, p):
    fn = functools.partial(sharded_verify_cached, mesh)
    fn = host_verify  # alias rebound: later calls are not dispatches
    payload = build()
    fn(t, v, p, payload)
    return payload.sum()
'''
    found = donated_read.check(_mod(src))
    assert len(found) == 1 and found[0].line == 8
    assert "sharded_verify_cached" in found[0].message


def test_donated_read_scopes_taints_per_function():
    src = '''
def a(mesh, t, v, p):
    payload = build()
    sharded_verify_cached(mesh, t, v, p, payload)

def b(payload):
    return payload.sum()  # different scope: no taint
'''
    assert donated_read.check(_mod(src)) == []


def test_donated_read_module_level_dispatch():
    src = (
        "payload = build()\n"
        "sharded_verify_cached(mesh, t, v, p, payload)\n"
        "print(payload.sum())\n"
    )
    found = donated_read.check(_mod(src))
    assert len(found) == 1 and found[0].line == 3


def test_donated_read_sweeps_repo_clean():
    findings, _ = linter.lint_paths(
        [os.path.join(REPO, "cometbft_tpu")],
        checks={"donated-read-after-dispatch": donated_read},
    )
    assert findings == [], "\n".join(f.render() for f in findings)


# ------------------------------------------------- child + CLI wiring


def test_subprocess_smoke_forced_8_device_child():
    """The production entry: the child really runs under 8 forced host
    devices and reports the genuine sharded trace."""
    findings, data = shardcheck.run_subprocess(
        fixtures="tests.shardcheck_fixtures",
        only=("shardfix_clean", "shardfix_census"),
        skip_goldens=True,
        timeout=300,
    )
    assert data["device_count"] == 8
    assert not data["ok"]
    msgs = [f.message for f in findings]
    assert any("undeclared collective 'ppermute'" in m for m in msgs)
    assert not any("shardfix_clean" in m for m in msgs)
    assert data["kernels"]["shardfix_clean"]["collectives"] == {"psum": 1}


def test_child_refuses_vacuous_only_filter():
    """A typo'd --only must not read as a clean pass (the PR-3
    nonexistent-lint-path rule)."""
    findings, data = shardcheck.run_subprocess(
        fixtures="tests.shardcheck_fixtures",
        only=("no_such_kernel",),
        skip_goldens=True,
        timeout=300,
    )
    assert data["ok"] is False
    assert len(findings) == 1
    assert "matched no sharded kernel" in findings[0].message


def test_run_subprocess_surfaces_child_crash(monkeypatch):
    monkeypatch.setattr(
        shardcheck.subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(a, 3, "", "boom"),
    )
    findings, data = shardcheck.run_subprocess()
    assert len(findings) == 1 and "rc=3" in findings[0].message
    assert data["ok"] is False


def test_lint_registers_sharding_checks():
    checks = linter.all_checks()
    assert set(linter.SHARDING_CHECK_IDS) <= set(checks)
    assert checks["donated-read-after-dispatch"] is donated_read


def test_lint_cli_sharding_ast_check(tmp_path):
    bad = tmp_path / "models" / "fake.py"
    bad.parent.mkdir()
    bad.write_text(
        "def go(mesh, t, v, p):\n"
        "    payload = build()\n"
        "    sharded_verify_cached(mesh, t, v, p, payload)\n"
        "    return payload\n"
    )
    cli = [sys.executable, os.path.join(REPO, "scripts", "lint.py")]
    proc = subprocess.run(
        cli + [str(bad), "--check", "donated-read-after-dispatch", "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stderr
    data = json.loads(proc.stdout)
    assert {f["check"] for f in data["findings"]} == {
        "donated-read-after-dispatch"
    }


# ------------------------------------------------- compile-cache rule


def test_compile_cache_rule(tmp_path, monkeypatch):
    """utils/compilecache: with JAX_COMPILATION_CACHE_DIR set the helper
    sets no directory at all (jax reads the variable by itself); unset,
    it sets the in-checkout default.  Either way the key holds the
    program's metadata (an executable cached before a jax.named_scope
    existed must not be served without the name), and nothing else is
    set."""
    import jax

    from cometbft_tpu.utils import compilecache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    in_key = ("jax_compilation_cache_include_metadata_in_key", True)
    monkeypatch.setenv(compilecache.ENV_VAR, str(tmp_path / "placed"))
    compilecache.enable()
    assert calls == [in_key]
    monkeypatch.delenv(compilecache.ENV_VAR)
    del calls[:]
    compilecache.enable()
    assert calls == [
        ("jax_compilation_cache_dir", compilecache.DEFAULT_DIR), in_key]
    assert compilecache.DEFAULT_DIR == os.path.join(REPO, "tests", ".jax_cache")


def test_compile_cache_default_is_the_same_path_from_any_process(tmp_path):
    """The directory is part of what makes a cache warm: computed from
    the module's own path, never from the cwd, a pid or the time."""
    code = (
        f"import sys; sys.path.insert(0, {REPO!r}); "
        "from cometbft_tpu.utils import compilecache as c; "
        "print(c.DEFAULT_DIR)"
    )
    seen = set()
    for cwd in (REPO, str(tmp_path)):
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=60, cwd=cwd,
        )
        assert proc.returncode == 0, proc.stderr
        seen.add(proc.stdout.strip())
    assert seen == {os.path.join(REPO, "tests", ".jax_cache")}


# ------------------------------------------------------- the slow gate


@pytest.mark.slow
def test_checked_in_shard_goldens_match_fresh_trace():
    """The acceptance gate: every real sharded kernel traced in the
    forced 8-device child and held to the checked-in goldens (same pass
    as ``python scripts/lint.py --check sharding`` — the child reports
    raw findings; the allowlist is the caller's job, applied here like
    the lint gate does)."""
    allowlist = linter.Allowlist.load(linter.default_allowlist_path())
    findings, data = shardcheck.run_subprocess(timeout=1200)
    findings = [f for f in findings if not allowlist.suppresses(f)]
    assert data.get("device_count") == 8, data
    assert findings == [], "\n".join(f.render() for f in findings)
    assert set(data["kernels"]) == set(manifest.sharded_by_name())
