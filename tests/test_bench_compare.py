"""scripts/bench_compare.py — the round-over-round perf diff.

Proven against rounds in the driver's wrapper shape, written here: two
valid rounds (100.0 ms @ 2.75x vs 107.99 ms @ 2.55x, a +7.99% headline
regression), one that crashed (rc=1, no JSON), and one that failed with
a structured line (value null + "error") — the exclusion shapes the
comparator must refuse to treat as numbers."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "bench_compare.py")

_METRIC = "verify_commit_p50_10k_ms"
_ROUNDS = {
    "a": {"rc": 0, "parsed": {"metric": _METRIC, "value": 100.0,
                              "unit": "ms", "vs_baseline": 2.75}},
    "b": {"rc": 0, "parsed": {"metric": _METRIC, "value": 107.99,
                              "unit": "ms", "vs_baseline": 2.55}},
    "crashed": {"rc": 1, "parsed": None},
    "failed": {"rc": 0, "parsed": {
        "metric": _METRIC, "value": None, "unit": "ms", "phases": {},
        "error": "backend-unavailable: probe exited 1"}},
}


@pytest.fixture
def rounds(tmp_path):
    """name -> path of a driver round wrapper."""
    paths = {}
    for name, doc in _ROUNDS.items():
        path = tmp_path / f"round_{name}.json"
        path.write_text(json.dumps(
            {"n": 1, "cmd": "python bench.py", "tail": "", **doc}
        ))
        paths[name] = str(path)
    return paths


def _load_mod():
    spec = importlib.util.spec_from_file_location("bench_compare", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, SCRIPT, *args],
        capture_output=True, text=True, timeout=60, cwd=REPO,
    )


# ------------------------------------------------------- wrapped rounds


def test_a_vs_b_within_default_threshold(rounds):
    """+7.99% sits under the default 10% gate: reported, not fatal."""
    r = _run(rounds["a"], rounds["b"], "--json")
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout)
    assert rep["headline"]["delta_pct"] == pytest.approx(7.99, abs=0.01)
    assert rep["vs_baseline"]["delta"] == pytest.approx(-0.2)
    assert rep["regressions"] == []


def test_a_vs_b_trips_tighter_threshold(rounds):
    r = _run("--threshold", "0.05", rounds["a"], rounds["b"])
    assert r.returncode == 1
    assert "REGRESSION" in r.stderr and "+8.0%" in r.stderr
    # the improvement direction never trips: new faster than old
    assert _run(
        "--threshold", "0.05", rounds["b"], rounds["a"]
    ).returncode == 0


@pytest.mark.parametrize("name,why", [
    ("crashed", "rc=1"),              # bench crashed, no JSON at all
    ("failed", "backend-unavailable"),  # value null + error
])
def test_failed_rounds_excluded(rounds, name, why):
    r = _run(rounds["a"], rounds[name])
    assert r.returncode == 2
    assert "excluded" in r.stderr and why in r.stderr
    # symmetric: a failed BASELINE is just as unusable
    assert _run(rounds[name], rounds["a"]).returncode == 2


def test_unreadable_and_mismatched_inputs_exit_2(rounds, tmp_path):
    r = _run(rounds["a"], str(tmp_path / "missing.json"))
    assert r.returncode == 2
    other = tmp_path / "other_metric.json"
    other.write_text(json.dumps(
        {"metric": "something_else_ms", "value": 10.0}
    ))
    r = _run(rounds["a"], str(other))
    assert r.returncode == 2 and "metric mismatch" in r.stderr


# ------------------------------------------------------------- unit level


def test_lane_and_phase_share_diffs():
    """Per-lane p50/p95 each gate independently; phase wall-share
    shifts are reported in percentage points but never trip the exit
    (attribution drift is a smell, not a regression by itself)."""
    mod = _load_mod()
    old = {
        "metric": "verify_mixed_consensus_p50_ms", "value": 100.0,
        "classes": {
            "consensus": {"p50_ms": 100.0, "p95_ms": 200.0},
            "mempool": {"p50_ms": 50.0, "p95_ms": 80.0},
            "old_only": {"p50_ms": 1.0, "p95_ms": 2.0},
        },
        "phase_attribution": {
            "hash": {"p50_ms": 10.0, "share_of_wall": 0.30},
            "verify": {"p50_ms": 60.0, "share_of_wall": 0.50},
        },
    }
    new = {
        "metric": "verify_mixed_consensus_p50_ms", "value": 101.0,
        "classes": {
            "consensus": {"p50_ms": 102.0, "p95_ms": 300.0},  # p95 +50%
            "mempool": {"p50_ms": 49.0, "p95_ms": None},      # unmeasured
        },
        "phase_attribution": {
            "hash": {"p50_ms": 9.0, "share_of_wall": 0.55},   # +25 pp
            "verify": {"p50_ms": 61.0, "share_of_wall": 0.25},
        },
    }
    rep = mod.compare(old, new, threshold=0.10)
    assert set(rep["lanes"]) == {"consensus", "mempool"}  # intersection
    assert rep["lanes"]["consensus"]["p95_ms"]["delta_pct"] == 50.0
    assert "p95_ms" not in rep["lanes"]["mempool"]  # null side skipped
    assert rep["phase_shares"]["hash"]["shift_pp"] == pytest.approx(25.0)
    assert rep["regressions"] == [
        "lane consensus p95_ms: 200.0 -> 300.0 (+50.0%)"
    ]


def test_proofs_sweep_checked_in_rounds():
    """The checked-in BENCH_WORKLOAD=proofs sample rounds (tests/data/
    bench_proofs_r0{1,2}.json): r02 is slightly faster at every size, so
    the comparison passes under the default gate and the text output
    carries the per-K proofs rows, including the dedup line."""
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    old = os.path.join(data, "bench_proofs_r01.json")
    new = os.path.join(data, "bench_proofs_r02.json")
    r = _run(old, new, "--json")
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout)
    assert rep["metric"] == "proof_gen_tpu_batch_p50_ms"
    assert rep["regressions"] == []
    sweep = rep["proofs_sweep"]
    assert set(sweep) == {"64", "256", "1024", "4096"}
    assert sweep["4096"]["tpu_p50_ms"]["delta_pct"] == pytest.approx(-3.34)
    # dedup factor is reported (delta), never a latency gate
    assert sweep["4096"]["multiproof_dedup_factor"]["delta"] == 0.0
    # text mode prints the per-K rows
    r2 = _run(old, new)
    assert r2.returncode == 0
    assert "proofs K=   64 tpu_p50_ms" in r2.stdout
    assert "proofs K= 4096 dedup: 6.4 -> 6.4 (+0.0)" in r2.stdout


def test_proofs_sweep_gates_each_lane_and_skips_dedup():
    """Unit level: every tpu/host p50/p95 series gates independently at
    the threshold; the dedup factor and a size present on only one side
    never gate; non-proofs rounds never grow a proofs_sweep."""
    mod = _load_mod()
    base = {
        "64": {"tpu_p50_ms": 1.0, "tpu_p95_ms": 1.2,
               "host_p50_ms": 4.0, "host_p95_ms": 4.4,
               "multiproof_dedup_factor": 3.4},
        "1024": {"tpu_p50_ms": 2.0, "tpu_p95_ms": 2.4,
                 "host_p50_ms": 9.0, "host_p95_ms": 10.0,
                 "multiproof_dedup_factor": 5.4},
        "8192": {"tpu_p50_ms": 5.0},  # old-only size: skipped
    }
    cand = {
        "64": {"tpu_p50_ms": 1.0, "tpu_p95_ms": 1.8,   # p95 +50%
               "host_p50_ms": 4.1, "host_p95_ms": None,  # unmeasured
               "multiproof_dedup_factor": 2.0},           # reported only
        "1024": {"tpu_p50_ms": 2.5, "tpu_p95_ms": 2.5,  # p50 +25%
                 "host_p50_ms": 9.1, "host_p95_ms": 10.2,
                 "multiproof_dedup_factor": 5.4},
    }
    old = {"metric": "proof_gen_tpu_batch_p50_ms", "workload": "proofs",
           "value": 2.0, "sweep": base}
    new = {"metric": "proof_gen_tpu_batch_p50_ms", "workload": "proofs",
           "value": 2.1, "sweep": cand}
    rep = mod.compare(old, new, threshold=0.10)
    assert set(rep["proofs_sweep"]) == {"64", "1024"}
    assert rep["proofs_sweep"]["64"]["multiproof_dedup_factor"]["delta"] == -1.4
    assert "host_p95_ms" not in rep["proofs_sweep"]["64"]  # null side skipped
    assert rep["regressions"] == [
        "proofs K=64 tpu_p95_ms: 1.2 -> 1.8 (+50.0%)",
        "proofs K=1024 tpu_p50_ms: 2.0 -> 2.5 (+25.0%)",
    ]
    # a non-proofs round with a stray "sweep" key (e.g. the bls
    # crossover sweep) must not be diffed as a proofs sweep
    rep2 = mod.compare(
        {"metric": "m", "value": 1.0, "workload": "bls", "sweep": base},
        {"metric": "m", "value": 1.0, "workload": "bls", "sweep": cand},
        threshold=0.10,
    )
    assert "proofs_sweep" not in rep2 and rep2["regressions"] == []


def test_rangecheck_summary_passes_through_unchanged():
    """Backend-less rounds embed a "rangecheck" block (bench.py); the
    comparator must neither diff it nor choke on it — it only reads
    metric/value/classes/phase_attribution."""
    mod = _load_mod()
    rng = {
        "ok": True, "mode": "certificates+spot", "certificates": 23,
        "headroom": {"ed25519_verify_batch": {"peak_int32": 1252794005}},
    }
    old = {"metric": "m", "value": 100.0, "rangecheck": rng}
    new = {"metric": "m", "value": 104.0, "rangecheck": rng}
    ok, reason = mod.classify(old, "x")
    assert reason is None and ok["rangecheck"] == rng
    rep = mod.compare(old, new, threshold=0.10)
    assert rep["headline"]["delta_pct"] == pytest.approx(4.0)
    assert rep["regressions"] == []
    assert "rangecheck" not in rep  # not a perf surface: passed over


def test_classify_shapes():
    mod = _load_mod()
    # bare bench JSON (no driver wrapper) is accepted directly
    ok, reason = mod.classify({"metric": "m", "value": 1.0}, "x")
    assert reason is None and ok["value"] == 1.0
    for doc, frag in [
        ({"rc": 1, "parsed": {"value": 1.0}}, "rc=1"),
        ({"rc": 0, "parsed": None}, "no parsed"),
        ({"rc": 0, "parsed": {"value": None}}, "null"),
        ({"metric": "m", "value": 2.0, "error": "boom"}, "degraded"),
    ]:
        obj, reason = mod.classify(doc, "x")
        assert obj is None and frag in reason, (doc, reason)
