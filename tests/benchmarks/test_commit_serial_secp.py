"""The cell commit-175-secp on the CPU, through ``harness.run_cell`` at a
tiny width: eight validators are a device batch of the secp lane (bucket
8, the hash on the host), so an honest run is correct here, and each
control is not: a run whose batches the host answers, a tampered commit
in the pool, a verifier that accepts everything after set-up, a program
without the lower-S rule, and a program without the announced warm-up.  Beside them the plain reference
(benchmarks/reference_secp.py) and the names the cell's device metrics
read.  One compile for the file: the first run's warm-up compiles the
bucket-8 program and the process keeps it."""

import copy
import glob
import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import checks, data, data_secp, harness, reference_secp, spec  # noqa: E402
from benchmarks.drivers import commit_serial_secp  # noqa: E402

CELL = "commit-175-secp"
SECP_MODULES = ["jit_secp_hash_verify_batch", "jit_secp_verify_batch"]


@pytest.fixture
def fresh(monkeypatch):
    """A fresh metrics hub, a fresh global verify service and an empty
    span ring around a run_cell."""
    from cometbft_tpu.utils import metrics, tracing
    from cometbft_tpu.verifysvc import service as svc_mod

    monkeypatch.setattr(metrics, "_HUB", metrics.Hub())
    svc_mod.reset_global_service()
    was_on = tracing.enabled()
    yield
    svc_mod.reset_global_service()
    tracing.set_enabled(was_on)
    tracing.reset()


def tiny_cell(width: int, pool: int = 3, warm_s: float = 0.05):
    cell = spec.resolve(CELL)
    cell.config = dict(cell.config, validators=width)
    cell.traffic = dict(cell.traffic, pool=pool, warm_s=warm_s)
    return cell


def run_tiny(cell, seconds=1.5, seed=(1 << 31) + 77):
    import jax

    return harness.run_cell(
        cell, seed, seconds, False, time.monotonic(), jax.devices())


def test_the_cell_resolves_to_its_driver_and_configuration():
    cell = spec.resolve(CELL)
    assert cell.driver is commit_serial_secp
    assert cell.config["key_type"] == "secp256k1"
    assert cell.config["validators"] == 175 and cell.chips == 1
    assert cell.config["reduced"] == []
    assert cell.traffic["warm_s"] == 5 and cell.traffic["pool"] == 8


def test_an_honest_tiny_run_is_correct(fresh):
    result, facts = run_tiny(tiny_cell(8))
    assert facts["problems"] == []
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert all(c["value"] == 0 for c in result["compared"].values())
    assert set(result["metrics"]) == {"verdict_p50_ms", "verdict_p90_ms", "setup_s"}


def test_a_host_routed_run_is_right_and_not_correct(fresh):
    """Four validators are under COMETBFT_TPU_SECP_DEVICE_MIN (8): the
    host answers every batch, with right verdicts, and the run says so
    through the host-route counter and the lane's own."""
    result, facts = run_tiny(tiny_cell(4), seconds=0.6)
    assert result["correct"] is False
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert facts["route"]["verify_host_route"] > 0
    assert any("rows verified on the host in the window" in p
               for p in facts["problems"])


def test_a_tampered_commit_in_the_pool_fails_its_requests(fresh, monkeypatch):
    setup = commit_serial_secp.setup

    def setup_then_tamper(cell, seed, log):
        state = setup(cell, seed, log)
        sc = state.pool[2]
        sc.commit, _ = checks.tampered(sc.commit, 8)
        return state

    monkeypatch.setattr(commit_serial_secp, "setup", setup_then_tamper)
    result, _ = run_tiny(tiny_cell(8, warm_s=0), seconds=2.0)
    assert result["correct"] is False
    assert result["attempted"] >= 3
    assert result["compared"]["requests_failed"]["value"] == result["failed"] > 0


def test_a_program_that_stops_verifying_is_caught(fresh, monkeypatch):
    """After set-up's checks verify_commit accepts whatever it is given:
    no request fails, and the run is still not correct, twice over: the
    window ran no batch of the lane, and after it a tampered commit is
    accepted."""
    from cometbft_tpu.types import validation

    setup = commit_serial_secp.setup

    def setup_then_break(cell, seed, log):
        state = setup(cell, seed, log)
        monkeypatch.setattr(validation, "verify_commit", lambda *a, **kw: None)
        return state

    monkeypatch.setattr(commit_serial_secp, "setup", setup_then_break)
    result, facts = run_tiny(tiny_cell(8), seconds=0.4)
    assert result["failed"] == 0 and result["attempted"] > 3
    assert result["correct"] is False
    assert any("not one batch of 8 a request" in p for p in facts["problems"])
    assert any(p.startswith("after the window:") and "accepted" in p
               for p in facts["problems"])


def test_a_program_without_the_lower_s_rule_is_caught(fresh, monkeypatch):
    """After set-up the program takes a high-S signature for its twin in
    the lower half, as a program without the lower-S rule would.  Every
    window verdict stands, and the run is still not correct: after the
    window the tampered commit's high-S row reads valid where the
    reference refuses it, and the commit whose one bad row is a high-S
    twin is accepted."""
    from cometbft_tpu.models import secp_verifier

    assemble = secp_verifier._assemble
    half = reference_secp.N // 2

    def fold(items):
        return [
            (pub, msg, commit_serial_secp.high_s_twin(sig)
             if len(sig) == 64 and int.from_bytes(sig[32:], "big") > half
             else sig)
            for pub, msg, sig in items
        ]

    setup = commit_serial_secp.setup

    def setup_then_drop_the_rule(cell, seed, log):
        state = setup(cell, seed, log)
        monkeypatch.setattr(secp_verifier, "_assemble",
                            lambda items, hash_dev: assemble(fold(items), hash_dev))
        return state

    monkeypatch.setattr(commit_serial_secp, "setup", setup_then_drop_the_rule)
    result, facts = run_tiny(tiny_cell(8), seconds=0.6)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["correct"] is False
    high_s = int(commit_serial_secp.HIGH_S * 8)
    assert any(p.startswith("after the window:")
               and f"differ from the reference at rows [{high_s}]" in p
               for p in facts["problems"])


def test_the_checked_commit_is_bad_where_the_reference_says(fresh):
    """tampered's commit: the flipped rows, the high-S twin and the other
    set's signature, each refused by the reference and by the program's
    host oracle, and the rest of the commit as it was."""
    from cometbft_tpu.crypto import secp256k1 as program_secp

    cell = tiny_cell(8)
    valset = data_secp.make_valset(cell.config, (1 << 31) + 9)
    sc = data.make_commits(valset, 1)[0]
    bad, idxs = commit_serial_secp.tampered(valset, sc)
    assert idxs == sorted({0, 6, 7, int(commit_serial_secp.HIGH_S * 8),
                           int(commit_serial_secp.FOREIGN * 8)})
    for i, v in enumerate(valset.vals.validators):
        sig = bad.signatures[i].signature
        want = i not in idxs
        assert (sig == sc.commit.signatures[i].signature) is want
        assert reference_secp.verify(v.pub_key.bytes(), sc.sign_bytes[i], sig) is want
        assert program_secp.PubKey(v.pub_key.bytes()).verify_signature(
            sc.sign_bytes[i], sig) is want


def test_a_program_without_the_announced_warm_up_fails_at_once(
        fresh, monkeypatch, capsys):
    """The parent of this cell's program: no models/secp_verifier.warm.
    Set-up stops before it signs or verifies anything, with no result
    line, rather than compile under the service's clocks."""
    import types

    import jax

    from cometbft_tpu.models import secp_verifier

    monkeypatch.delattr(secp_verifier, "warm")
    signed = []
    monkeypatch.setattr(data_secp, "make_valset",
                        lambda *a: signed.append(a) or None)
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                memory_stats=lambda: {})
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    t0 = time.monotonic()
    args = ["--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0"]
    assert harness.main(args, t0) == 1
    assert time.monotonic() - t0 < 30 and signed == []
    out = capsys.readouterr()
    assert out.out == ""
    assert "models/secp_verifier.warm" in out.err


def test_same_seed_same_commits():
    cell = tiny_cell(5)
    a = data.make_commits(data_secp.make_valset(cell.config, 11), 2)
    b = data.make_commits(data_secp.make_valset(cell.config, 11), 2)
    c = data.make_commits(data_secp.make_valset(cell.config, (1 << 31) + 5), 2)
    sigs = lambda pool: [s.signature for sc in pool for s in sc.commit.signatures]
    assert sigs(a) == sigs(b) and sigs(a) != sigs(c)
    assert {len(m) for sc in a + c for m in sc.sign_bytes} == {
        spec.resolve(CELL).config["commit_shape"]["sign_bytes_length"]}


@pytest.mark.parametrize("seed", [0, (1 << 31) + 1])
def test_reference_agrees_with_the_programs_host_verifier(seed):
    """reference_secp against crypto/secp256k1's own gauntlet (the
    program's host oracle), on what each case changes."""
    from cometbft_tpu.crypto import secp256k1 as program_secp

    key = reference_secp.SigningKey(seed, b"t", 3)
    msg = b"precommit" * 12
    sig = key.sign(msg)
    assert len(key.public) == 33 and len(sig) == 64
    assert key.sign(msg) == sig  # RFC 6979: the seed fixes the signature
    s = int.from_bytes(sig[32:], "big")
    assert 0 < s <= reference_secp.N // 2
    high = sig[:32] + (reference_secp.N - s).to_bytes(32, "big")
    wrong_prefix = bytes([key.public[0] ^ 1]) + key.public[1:]
    cases = [
        (key.public, msg, sig, True),
        (key.public, msg + b"x", sig, False),
        (key.public, msg, sig[:-1] + bytes([sig[-1] ^ 1]), False),
        (key.public, msg, high, False),
        (key.public, msg, bytes(32) + sig[32:], False),
        (key.public, msg, sig[:32] + bytes(32), False),
        (wrong_prefix, msg, sig, False),
    ]
    for pub, m, sg, want in cases:
        assert reference_secp.verify(pub, m, sg) is want
        assert program_secp.PubKey(pub).verify_signature(m, sg) is want
    assert not reference_secp.verify(b"\x04" + key.public[1:], msg, sig)
    assert not reference_secp.verify(b"\x02" + bytes(32), msg, sig)  # x = 0: no point
    p_bytes = reference_secp.P.to_bytes(32, "big")
    assert not reference_secp.verify(b"\x02" + p_bytes, msg, sig)


def _module_prefixes():
    """Every metric file's XLA module prefixes, by metric."""
    out = {}
    for path in glob.glob(os.path.join(REPO, "benchmarks", "layer_metrics", "*.json")):
        with open(path) as f:
            args = json.load(f).get("args", {})
        if "modules" in args:
            out[os.path.basename(path)[:-5]] = args["modules"]
    return out


def test_no_reader_of_another_program_counts_the_secp_modules():
    """The secp programs' XLA modules are read by the secp metrics alone,
    and those read no module of the ed25519 programs."""
    prefixes = _module_prefixes()
    secp = {n for n in prefixes if n.startswith("secp_")}
    assert secp == {"secp_verify_device_ms", "secp_walk_device_ms",
                    "secp_prep_device_ms", "secp_hash_device_ms"}
    others = {m for n, ms in prefixes.items() if n not in secp for m in ms}
    assert others  # the ed25519 programs' modules
    for name in SECP_MODULES:
        assert not name.startswith(tuple(others)), name
    for n in secp:
        assert not any(m.startswith(tuple(prefixes[n])) for m in others), n
    assert prefixes["secp_verify_device_ms"] == SECP_MODULES


def test_the_secp_programs_are_named_as_the_metrics_read_them():
    """The jitted functions' names make the module names the metrics
    match (jax names a module jit_<function>)."""
    from cometbft_tpu.ops import secp256k1

    assert [f"jit_{f.__name__}" for f in (
        secp256k1.secp_hash_verify_batch, secp256k1.secp_verify_batch)
    ] == SECP_MODULES


def test_the_cell_is_on_its_metrics_and_off_the_ed25519_programs():
    bench = spec.load_benchmark()
    on = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
          if CELL in m.get("workloads", [CELL])}
    assert {"verdict_p50_ms", "verdict_p90_ms", "setup_s", "secp_verify_device_ms",
            "secp_walk_device_ms", "secp_prep_device_ms", "secp_hash_device_ms",
            "secp_assemble_ms"} <= on
    for name in ("verify_device_ms", "scalar_mul_device_ms", "sha512_device_ms",
                 "verifier_host_ms", "idle_verifier_host_ms", "idle_handoff_ms"):
        assert name not in on
    assert copy.deepcopy(spec.resolve(CELL).per_layer)  # every reader found
