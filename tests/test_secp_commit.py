"""A secp256k1 validator set on the normal commit path: verify_commit ->
crypto/batch -> verifysvc (consensus class, MODE_SECP, the host worker)
-> models/secp_verifier.TpuSecpBatchVerifier -> ops/secp256k1, on the
CPU backend at 8 validators (bucket 8, hash on the host), held to the
plain reference of the benchmark (benchmarks/reference_secp.py: OpenSSL
ECDSA) on seeded keys.  One compile for the whole file: the module
fixture warms the program (models/secp_verifier.warm) and every test
after it is a program-cache hit."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import checks, data, data_secp, reference_secp  # noqa: E402
from cometbft_tpu.models import secp_verifier  # noqa: E402
from cometbft_tpu.types.validation import (  # noqa: E402
    CommitVerificationError, verify_commit,
)
from cometbft_tpu.utils import tracing  # noqa: E402
from cometbft_tpu.utils.metrics import hub  # noqa: E402
from cometbft_tpu.verifysvc.service import reset_global_service  # noqa: E402

WIDTH = 8
CONFIG = {
    "name": "valset-8-secp256k1", "key_type": "secp256k1",
    "validators": WIDTH,
    "assumed": {"chain_id": "bench-valset-175", "voting_power": 10},
}


@pytest.fixture(scope="module")
def chain():
    """Eight validators, two commits, and the program warmed once."""
    valset = data_secp.make_valset(CONFIG, (1 << 31) + 42)
    pool = data.make_commits(valset, 2)
    width = max(map(len, pool[0].sign_bytes))
    assert secp_verifier.warm(WIDTH, width) == (8, "host")
    yield valset, pool
    reset_global_service()


def _program_counts():
    c = hub().verify_secp_program_cache
    return c.value(result="hit"), c.value(result="compile")


def _verify(valset, sc, commit=None):
    verify_commit(valset.chain_id, valset.vals, sc.block_id, sc.height,
                  commit if commit is not None else sc.commit)


def _device_vector(valset, commit):
    """The vector the node's batch verifier gives for ``commit``, beside
    the reference's, row by row (benchmarks/drivers/commit_serial_secp
    holds the chip run to the same comparison)."""
    from cometbft_tpu.crypto import batch as crypto_batch

    program_bytes = commit.vote_sign_bytes_fn(valset.chain_id)
    bv = crypto_batch.create_batch_verifier(
        "secp256k1", pubkeys=valset.vals.pub_keys_bytes())
    rows = [(v.pub_key.bytes(), program_bytes(i), commit.signatures[i].signature)
            for i, v in enumerate(valset.vals.validators)]
    for row in rows:
        bv.add(*row)
    _, vec = bv.verify()
    return vec, [reference_secp.verify(*row) for row in rows]


def test_honest_commit_verifies_as_the_reference_does(chain):
    valset, pool = chain
    for sc in pool:
        vec, oracle = _device_vector(valset, sc.commit)
        assert vec == oracle == [True] * WIDTH
        _verify(valset, sc)


def test_flipped_signatures_are_refused_at_the_first(chain):
    valset, pool = chain
    sc = pool[0]
    bad, flipped = checks.tampered(sc.commit, WIDTH)
    assert len(flipped) == 3
    vec, oracle = _device_vector(valset, bad)
    assert vec == oracle
    assert [i for i, ok in enumerate(vec) if not ok] == flipped
    with pytest.raises(CommitVerificationError, match=rf"\(#{flipped[0]}\)"):
        _verify(valset, sc, bad)


def test_a_high_s_signature_is_refused(chain):
    """s and n - s sign alike; crypto/secp256k1 takes the lower half
    only, and so do the program and the reference."""
    import copy

    valset, pool = chain
    sc = pool[1]
    bad = copy.deepcopy(sc.commit)
    sig = bad.signatures[5].signature
    s = int.from_bytes(sig[32:], "big")
    bad.signatures[5].signature = sig[:32] + (reference_secp.N - s).to_bytes(32, "big")
    vec, oracle = _device_vector(valset, bad)
    assert vec == oracle
    assert [i for i, ok in enumerate(vec) if not ok] == [5]
    with pytest.raises(CommitVerificationError, match=r"\(#5\)"):
        _verify(valset, sc, bad)


def test_a_signature_by_a_key_of_another_set_is_refused(chain):
    import copy

    valset, pool = chain
    sc = pool[0]
    other = reference_secp.SigningKey(7, b"another set", 0)
    msg = sc.sign_bytes[2]
    bad = copy.deepcopy(sc.commit)
    bad.signatures[2].signature = other.sign(msg)
    assert reference_secp.verify(other.public, msg, bad.signatures[2].signature)
    vec, oracle = _device_vector(valset, bad)
    assert vec == oracle
    assert [i for i, ok in enumerate(vec) if not ok] == [2]
    with pytest.raises(CommitVerificationError, match=r"\(#2\)"):
        _verify(valset, sc, bad)


def test_the_second_verdict_is_a_program_cache_hit(chain):
    valset, pool = chain
    _verify(valset, pool[0])
    hit, compiled = _program_counts()
    _verify(valset, pool[1])
    assert _program_counts() == (hit + 1, compiled)


def test_a_verdict_runs_on_the_device_with_its_spans(chain):
    """One batch of the set's width, on the device, hashed on the host
    (8 rows are under COMETBFT_TPU_SECP_HASH_DEVICE_MIN), with the
    assembly and one device wait in the span ring."""
    valset, pool = chain
    rows = hub().verify_secp_rows
    before = rows.value(where="device", hash="host")
    host_before = rows.value(where="host", hash="host")
    was_on = tracing.enabled()
    tracing.set_enabled(True)
    tracing.reset()
    try:
        _verify(valset, pool[1])
        events = [e for e in tracing.chrome_trace_events() if e.get("ph") == "X"]
    finally:
        tracing.set_enabled(was_on)
        tracing.reset()
    assert rows.value(where="device", hash="host") == before + WIDTH
    assert rows.value(where="host", hash="host") == host_before
    names = [e["name"] for e in events]
    assert names.count("verify.secp_assemble") == 1
    assert names.count("verify.secp_batch") == 1
    assert names.count("verify.device_wait") == 1
    assemble = next(e for e in events if e["name"] == "verify.secp_assemble")
    assert {k: assemble["args"][k] for k in ("rows", "bucket", "hash")} == {
        "rows": WIDTH, "bucket": 8, "hash": "host"}
    batch = next(e for e in events if e["name"] == "verify.secp_batch")
    assert batch["args"]["bucket"] == 8


def test_warm_announces_a_compile_and_only_a_compile(chain, monkeypatch):
    """warm() runs the program a batch of that shape takes: a shape the
    process holds is a hit and says nothing; one it does not hold is
    announced to on_compile (True, then False) and counted once (with
    the held programs set aside, the compile is the persistent cache's
    load of the module fixture's)."""
    told = []
    hit, compiled = _program_counts()
    rows = hub().verify_secp_rows.value(where="device", hash="host")
    was_on = tracing.enabled()
    tracing.set_enabled(True)
    tracing.reset()
    try:
        assert secp_verifier.warm(WIDTH, 112, told.append) == (8, "host")
        # no verdict: no span (a traced run matches device waits to the
        # service's dispatches) and no row counted
        assert [e for e in tracing.chrome_trace_events()
                if e.get("ph") in ("X", "i")] == []
    finally:
        tracing.set_enabled(was_on)
        tracing.reset()
    assert told == [] and _program_counts() == (hit + 1, compiled)
    assert hub().verify_secp_rows.value(where="device", hash="host") == rows
    monkeypatch.setattr(secp_verifier, "_PROGRAMS", {})
    assert secp_verifier.warm(WIDTH, 112, told.append) == (8, "host")
    assert told == [True, False]
    assert _program_counts() == (hit + 1, compiled + 1)


def test_a_batch_under_the_device_minimum_is_a_counted_host_route(chain):
    valset, pool = chain
    route = hub().verify_host_route
    before = route.value(lane="secp", reason="below_batch_min")
    items = [(v.pub_key.bytes(), pool[0].sign_bytes[i],
              pool[0].commit.signatures[i].signature)
             for i, v in enumerate(valset.vals.validators)][:3]
    assert secp_verifier._verify_items(items, use_device=True) == (
        True, [True] * 3)
    assert route.value(lane="secp", reason="below_batch_min") == before + 1
    assert secp_verifier.warm(3, 112) is None
