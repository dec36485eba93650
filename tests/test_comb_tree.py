"""Property: the comb accumulation (ops/comb._accumulate_chains, K
parallel chains of mixed additions) is bit-identical to the sequential
reference below AND to the Straus fallback kernel on randomized vectors
— including non-signer zero rows and ZIP-215 edge encodings — with the
pure-Python host verifier as ground truth.  (The name of this file is
the older fold's, a binary tree over all 87 points; the fast tier's
tests/test_comb_chains.py holds the per-K checks.)

The sequential reference, _accumulate_sequential, is test code: it left
ops/comb when the chip had judged it (PERF.md section 6, PR 30) and is
kept here as the witness both files compare against.  The mesh-sharded
program runs the same verify_cached body
(parallel/verify.sharded_verify_cached) and is cross-checked in a fresh
interpreter by tests/test_parallel.py::test_sharded_comb_path_matches_host
(tests/sharded_comb_check.py), which exercises the default path.
"""

import hashlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import lax

pytestmark = [
    pytest.mark.slow,  # kernel compiles take minutes on the CPU backend
    pytest.mark.usefixtures("tiny_device_batches"),
]

from cometbft_tpu.crypto import _ref25519 as ref
from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.ops import comb, ed25519 as E, field as F, scalar, sha2

V = 8


def _accumulate_sequential(tables, k_dig, s_dig, b_tables, r_pt):
    """The reference accumulation: 64 + 22 dependent position adds in two
    fori_loops, each looking its own partial up, then the R fold — an
    87-step serial chain.  Same arguments and result as
    ops/comb._accumulate_chains, which it witnesses bit for bit."""
    V = k_dig.shape[-1]

    # ---- A part: acc += T[i][|k_i|][v] (sign-adjusted), 64 adds
    ents_a = jnp.arange(comb.NENT_A, dtype=jnp.int32)[:, None]

    def a_body(i, acc):
        slab = lax.dynamic_index_in_dim(tables, i, axis=0, keepdims=False)
        dig = lax.dynamic_index_in_dim(k_dig, i, axis=0, keepdims=False)
        neg = dig < 0
        absd = jnp.abs(dig)
        # int32 one-hot: the select stays in the tables' own dtype end to
        # end (no float round trip; dtype-closure audited, no promotion)
        onehot = (ents_a == absd[None, :]).astype(jnp.int32)  # (9, V)
        sel = jnp.sum(slab * onehot[:, None, None, :], axis=0)  # (3, 22, V)
        yplusx = F.select(neg, sel[1], sel[0])
        yminusx = F.select(neg, sel[0], sel[1])
        t2d = F.select(neg, -sel[2], sel[2])
        return E.add_niels(acc, E.Niels(yplusx, yminusx, t2d))

    acc = lax.fori_loop(0, comb.NPOS_A, a_body, E.identity((V,)))

    # ---- B part: acc += B_TAB[i][:, s_i], 22 adds, MXU one-hot matmul
    ents_b = jnp.arange(comb.NENT_B, dtype=jnp.int32)[:, None]

    def b_body(i, acc):
        slab = lax.dynamic_index_in_dim(b_tables, i, axis=0, keepdims=False)
        dig = lax.dynamic_index_in_dim(s_dig, i, axis=0, keepdims=False)
        onehot = (ents_b == dig[None, :]).astype(jnp.float32)  # (4096, V)
        # HIGHEST: the TPU MXU default is bf16 passes (8 mantissa bits);
        # the Niels limbs are 12-bit values and must come through exact.
        sel = jnp.matmul(
            slab, onehot, precision=lax.Precision.HIGHEST
        ).astype(jnp.int32)  # (66, V)
        return E.add_niels(
            acc, E.Niels(sel[0:22], sel[22:44], sel[44:66])
        )

    acc = lax.fori_loop(0, comb.NPOS_B, b_body, acc)
    return E.add(acc, E.neg(r_pt))


def _scalar_prep(r_enc, s_bytes, k_digest):
    """What ops/comb.verify_cached does before it accumulates: the
    digits of k and s, R, and whether R and s are admissible."""
    k_dig = scalar.signed_digits_radix16(
        scalar.reduce_mod_l(scalar.bytes_to_limbs(k_digest, scalar.NL_X)),
        comb.NPOS_A,
    )
    r_pt, r_valid = E.decompress(r_enc)
    return (
        k_dig,
        scalar.bytes_to_limbs(s_bytes, comb.NPOS_B),
        r_pt,
        r_valid & scalar.s_lt_l(s_bytes),
    )


def _verify_sequential(tables, a_valid, r_enc, s_bytes, k_digest, b_tables):
    """ops/comb.verify_cached with the reference accumulation."""
    k_dig, s_dig, r_pt, rs_ok = _scalar_prep(r_enc, s_bytes, k_digest)
    acc = _accumulate_sequential(tables, k_dig, s_dig, b_tables, r_pt)
    acc = E.double(E.double(E.double(acc)))
    return E.is_identity(acc) & a_valid & rs_ok


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

    tree_fn = jax.jit(comb.verify_cached)
    seq_fn = jax.jit(_verify_sequential)
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
