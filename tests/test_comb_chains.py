"""The comb accumulation by K parallel chains of mixed additions
(ops/comb._accumulate_chains) against its witness, the sequential
reference kept in tests/test_comb_tree.py, and the pure-Python ZIP-215
host verifier, at the 128-lane bucket the fast tier compiles: the verdict vector and the accumulated point itself
for every K the rule can return and for K = 16 (4, 8 and 16 do not
divide 86, so they pad with the Niels identity), and the rule that reads
K off the lane count.

The corpus is that of tests/test_comb_tree.py (slow tier) in one batch:
tampered rows, non-signer zero rows, and the ZIP-215 edge encodings
x = 0 with sign 1 and non-canonical y >= p.  Scalar prep and the
decompression of R are one shared program; each schedule is one small
program of its own (lookups, accumulation, cofactor check, compress).
"""

import hashlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cometbft_tpu.crypto import _ref25519 as ref
from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.ops import comb, ed25519 as E

from test_comb_tree import (
    _accumulate_sequential, _edge_r_encodings, _edge_sig, _scalar_prep,
)

V = 128
CHAINS = (1, 2, 4, 8, 16)


@pytest.mark.parametrize(
    "lanes,k",
    [(128, 8), (256, 8), (384, 8), (512, 4), (768, 4), (896, 2),
     (1024, 2), (2528, 2), (2560, 2), (2688, 1), (10112, 1)],
)
def test_chains_come_from_the_lane_count(lanes, k):
    """Pure: the most chains whose batch K * lanes stays within the
    width measured fast for that K, else one; 2,528 is one shard of
    10,112 lanes over four chips."""
    assert comb.fold_chains(lanes) == k
    widths = dict(comb.CHAIN_WIDTHS)
    assert k == 1 or k * lanes <= widths[k]
    assert all(c * lanes > w for c, w in comb.CHAIN_WIDTHS if c > k)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(20261002)
    seeds = [rng.bytes(32) for _ in range(V)]
    keys = [host.PrivKey.from_seed(sd) for sd in seeds]
    pubs = [k.pub_key().data for k in keys]
    a_arr = np.frombuffer(b"".join(pubs), dtype=np.uint8).reshape(V, 32)
    tables, valid = comb.build_a_tables_host(a_arr)
    assert valid.all()

    edges = _edge_r_encodings()
    edge_rows = {1: edges[0], 4: edges[1], 77: edges[0], 127: edges[1]}
    zero_rows = {0, 9, 64, 126}
    tampered = {2, 3, 31, 32, 100}
    r = np.zeros((V, 32), np.uint8)
    s = np.zeros((V, 32), np.uint8)
    dig = np.zeros((V, 64), np.uint8)
    want = []
    for i in range(V):
        msg = rng.bytes(int(rng.integers(0, 40)))
        if i in zero_rows:  # non-signer dummy row
            msg, sig = b"", b"\x00" * 64
        elif i in edge_rows:
            msg = b"zip215-edge-%d" % i
            sig = _edge_sig(seeds[i], edge_rows[i], pubs[i], msg)
        else:
            sig = keys[i].sign(msg)
            if i in tampered:
                msg += b"!"
        r[i] = np.frombuffer(sig[:32], np.uint8)
        s[i] = np.frombuffer(sig[32:], np.uint8)
        dig[i] = np.frombuffer(
            hashlib.sha512(sig[:32] + pubs[i] + msg).digest(), np.uint8
        )
        want.append(ref.verify(pubs[i], msg, sig))
    assert all(want[i] for i in edge_rows), "ZIP-215 edge rows must verify"
    assert not any(want[i] for i in tampered)

    k_dig, s_dig, r_pt, rs_ok = jax.jit(_scalar_prep)(r, s, dig)
    args = (jnp.asarray(tables), k_dig, s_dig, comb.get_b_tables(), r_pt)

    def run(accumulate):
        """(compressed accumulated point (V, 32), verdict vector)."""

        def program(*x):
            acc = accumulate(*x)
            ok = E.is_identity(E.double(E.double(E.double(acc))))
            return E.compress(acc), ok & rs_ok

        enc, ok = jax.jit(program)(*args)
        return np.asarray(enc), np.asarray(ok).tolist()

    seq_enc, seq_ok = run(_accumulate_sequential)
    return {"want": want, "seq_enc": seq_enc, "seq_ok": seq_ok, "run": run}


def test_sequential_witness_matches_host(corpus):
    assert corpus["seq_ok"] == corpus["want"]


@pytest.fixture(scope="module", params=CHAINS, ids=lambda k: f"K{k}")
def chained(request, corpus):
    k = request.param
    return corpus["run"](
        lambda *x: comb._accumulate_chains(*x, chains=k)
    )


def test_chains_verdicts_match_sequential_and_host(corpus, chained):
    _, ok = chained
    assert ok == corpus["seq_ok"], "chains != sequential"
    assert ok == corpus["want"], "chains != host ZIP-215"


def test_chains_accumulate_the_sequential_point(corpus, chained):
    """The same group element, not only the same verdict: the canonical
    encodings of the accumulated points are equal lane by lane."""
    enc, _ = chained
    assert (enc == corpus["seq_enc"]).all()
