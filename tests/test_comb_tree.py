"""Property: the default comb accumulation (ops/comb._accumulate_chains,
K parallel chains of mixed additions; ``tree=True``) is bit-identical to
the sequential comb path AND to the Straus fallback kernel on randomized
vectors — including non-signer zero rows and ZIP-215 edge encodings —
with the pure-Python host verifier as ground truth.  (The name of this
file is the older fold's, a binary tree over all 87 points; the fast
tier's tests/test_comb_chains.py holds the per-K checks.)

The chains are the engine default (COMETBFT_TPU_COMB_TREE); the
sequential fori_loop path is kept exactly as the cross-check this module
runs.  The mesh-sharded program runs the same verify_cached body
(parallel/verify.sharded_verify_cached) and is cross-checked in a fresh
interpreter by tests/test_parallel.py::test_sharded_comb_path_matches_host
(tests/sharded_comb_check.py), which exercises the default path.
"""

import hashlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = [
    pytest.mark.slow,  # kernel compiles take minutes on the CPU backend
    pytest.mark.usefixtures("tiny_device_batches"),
]

from cometbft_tpu.crypto import _ref25519 as ref
from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.ops import comb, ed25519 as E, sha2

V = 8


def _edge_r_encodings():
    """ZIP-215 edge encodings of the identity point, both decoding to
    R = identity so that s = k*a (mod L) makes (R, s) a VALID signature:
      - x = 0 with sign bit 1 (canonical y=1, non-canonical sign)
      - non-canonical y = p + 1 (reduces to y = 1, x = 0)
    A strict (RFC 8032 canonical) verifier rejects both; ZIP-215 — the
    validator consensus rule — accepts both."""
    x0_sign1 = bytearray((1).to_bytes(32, "little"))
    x0_sign1[31] |= 0x80
    y_noncanon = (ref.P + 1).to_bytes(32, "little")
    return [bytes(x0_sign1), y_noncanon]


def _edge_sig(seed: bytes, r_enc: bytes, pub: bytes, msg: bytes) -> bytes:
    """Signature whose R half is the given identity encoding: R = 0 so
    the equation needs exactly s = k * a (mod L)."""
    a, _ = ref.secret_expand(seed)
    k = int.from_bytes(hashlib.sha512(r_enc + pub + msg).digest(), "little") % ref.L
    s = k * a % ref.L
    return r_enc + s.to_bytes(32, "little")


def test_tree_matches_sequential_straus_and_host():
    rng = np.random.default_rng(20260803)
    seeds = [rng.bytes(32) for _ in range(V)]
    keys = [host.PrivKey.from_seed(sd) for sd in seeds]
    pubs = [k.pub_key().data for k in keys]
    a_arr = np.frombuffer(b"".join(pubs), dtype=np.uint8).reshape(V, 32)

    tables, valid = comb.build_a_tables_jit(jnp.asarray(a_arr))
    assert np.asarray(valid).all()
    bt = comb.get_b_tables()

    tree_fn = jax.jit(lambda *x: comb.verify_cached(*x, tree=True))
    seq_fn = jax.jit(lambda *x: comb.verify_cached(*x, tree=False))
    straus_fn = jax.jit(E.verify_batch)

    edges = _edge_r_encodings()
    for trial in range(6):
        r = np.zeros((V, 32), np.uint8)
        s = np.zeros((V, 32), np.uint8)
        dig = np.zeros((V, 64), np.uint8)
        msgs = []
        mlen = int(rng.integers(0, 40))
        for i in range(V):
            # mix equal-length (commit-shaped) and ragged trials
            ln = mlen if trial % 2 == 0 else int(rng.integers(0, 40))
            msgs.append(rng.bytes(ln))
        edge_rows = {} if trial else {1: edges[0], 4: edges[1]}
        zero_rows = set(
            int(z) for z in rng.choice(V, size=rng.integers(0, 3), replace=False)
        ) - set(edge_rows)
        tampered = (
            set(
                int(t)
                for t in rng.choice(V, size=rng.integers(0, 4), replace=False)
            )
            - zero_rows
            - set(edge_rows)
        )

        sigs = []
        for i in range(V):
            if i in zero_rows:
                # non-signer dummy row: all-zero signature, empty message
                msgs[i] = b""
                sig = b"\x00" * 64
            elif i in edge_rows:
                msgs[i] = b"zip215-edge-%d" % i
                sig = _edge_sig(seeds[i], edge_rows[i], pubs[i], msgs[i])
            else:
                sig = keys[i].sign(msgs[i])
                if i in tampered:
                    msgs[i] = msgs[i] + b"!"
            sigs.append(sig)
            r[i] = np.frombuffer(sig[:32], np.uint8)
            s[i] = np.frombuffer(sig[32:], np.uint8)
            dig[i] = np.frombuffer(
                hashlib.sha512(sig[:32] + pubs[i] + msgs[i]).digest(), np.uint8
            )

        want = [ref.verify(pubs[i], msgs[i], sigs[i]) for i in range(V)]
        if trial == 0:
            # the edge constructions must actually exercise acceptance
            assert want[1] and want[4], "ZIP-215 edge signatures must verify"
        for i in tampered:
            assert not want[i]

        ra, sa, da = jnp.asarray(r), jnp.asarray(s), jnp.asarray(dig)
        got_tree = np.asarray(tree_fn(tables, valid, ra, sa, da, bt)).tolist()
        got_seq = np.asarray(seq_fn(tables, valid, ra, sa, da, bt)).tolist()
        blocks, active = sha2.pad_messages_sha512(
            [sigs[i][:32] + pubs[i] + msgs[i] for i in range(V)]
        )
        got_straus = np.asarray(
            straus_fn(
                jnp.asarray(a_arr), ra, sa, jnp.asarray(blocks), jnp.asarray(active)
            )
        ).tolist()

        assert got_tree == got_seq, f"trial {trial}: tree != sequential"
        assert got_tree == got_straus, f"trial {trial}: tree != Straus"
        assert got_tree == want, f"trial {trial}: kernel != host ZIP-215"


def test_tree_reduce_points_matches_serial_fold():
    """Direct check of the shared helper: tree fold of a small random
    point stack equals the serial add chain (odd and even counts)."""
    rng = np.random.default_rng(7)
    pts_host = []
    p = ref.BASE
    for _ in range(6):
        pts_host.append(p)
        p = ref.pt_add(p, ref.pt_add(ref.BASE, ref.BASE))

    def enc(pt):
        return np.frombuffer(ref.compress(pt), np.uint8)

    for n in (1, 2, 5, 6):
        encs = np.stack([enc(pt) for pt in pts_host[:n]])[:, None, :]  # (n,1,32)
        want = pts_host[0]
        for pt in pts_host[1:n]:
            want = ref.pt_add(want, pt)

        def fold(e):
            pts, ok = E.decompress(e)
            return E.compress(E.tree_reduce_points(pts)), ok

        got, ok = jax.jit(fold)(jnp.asarray(encs))
        assert np.asarray(ok).all()
        assert bytes(np.asarray(got)[0]) == ref.compress(want), f"n={n}"
