"""GLV endomorphism decomposition pins (ops/secp256k1).

Fast tier works the HOST half of the split (pure bigint — free): the
lattice-basis identities, the half-width bound, and k = k1 + λ·k2 over
adversarial scalars.  The device half is pinned two ways: the traced
jaxpr of the device split against the host split (make_jaxpr runs in
milliseconds, no compile), and — in the slow tier, where the witness
programs' compiles belong — full-batch GLV-vs-Shamir-witness verdict
bit-identity over the adversarial corpus.
"""

import numpy as np
import pytest

from cometbft_tpu.crypto import secp256k1 as host_secp
from cometbft_tpu.ops import secp256k1 as dev

P, N, G = host_secp.P, host_secp.N, host_secp.G


def test_glv_constants_are_the_endomorphism():
    # beta is a nontrivial cube root of 1 mod p, lambda mod n, and they
    # pair: lambda * (x, y) == (beta * x, y) for every curve point
    assert pow(dev._BETA, 3, P) == 1 and dev._BETA != 1
    assert pow(dev._LAM, 3, N) == 1 and dev._LAM != 1
    got = host_secp._mul(dev._LAM, G)
    assert got == (dev._BETA * G[0] % P, G[1])
    # and not just on G: an unrelated point
    Q = host_secp._mul(0xDEADBEEF, G)
    assert host_secp._mul(dev._LAM, Q) == (dev._BETA * Q[0] % P, Q[1])


def test_glv_lattice_basis_identities():
    a1, b1, a2, b2 = dev._A1, dev._B1, dev._A2, dev._B2
    assert abs(a1 * b2 - a2 * b1) == N
    assert (a1 + b1 * dev._LAM) % N == 0
    assert (a2 + b2 * dev._LAM) % N == 0
    # basis vectors are genuinely half-width
    for c in (a1, b1, a2, b2):
        assert abs(c) < 1 << 129


def test_host_split_reconstructs_and_bounds():
    samples = [0, 1, 2, N - 1, N - 2, N // 2, dev._LAM, N - dev._LAM,
               dev._BETA % N, (1 << 255) % N]
    x = 7
    for _ in range(500):
        x = x * x * 1103515245 % N
        samples.append(x)
    for k in samples:
        s1, s2 = dev._split_host(k)
        assert (s1 + dev._LAM * s2) % N == k % N, k
        assert abs(s1) < 1 << 130 and abs(s2) < 1 << 130, k


def test_device_split_matches_host_split_traced():
    """The jitted _glv_split, evaluated eagerly on CPU (no jit, no
    compile): |k1|, |k2| limbs + negation flags must equal the host
    split exactly — the device walk consumes exactly these."""
    samples = [0, 1, N - 1, dev._LAM, N // 3, (1 << 200) % N]
    rng = np.random.default_rng(16)
    samples += [int.from_bytes(rng.bytes(32), "big") % N for _ in range(10)]
    k = np.stack([dev._int_to_limbs(s) for s in samples]).astype(np.int32)
    import jax.numpy as jnp

    k1, n1, k2, n2 = dev._glv_split(jnp.asarray(k))
    for i, s in enumerate(samples):
        h1, h2 = dev._split_host(s)
        assert dev.from_limbs(np.asarray(k1[i])) == abs(h1), s
        assert dev.from_limbs(np.asarray(k2[i])) == abs(h2), s
        assert bool(n1[i]) == (h1 < 0), s
        assert bool(n2[i]) == (h2 < 0), s


def test_sign_bound_splits_negatives_correctly():
    # a scalar just above the sign boundary must come back negative
    for k in range(3):
        s1, s2 = dev._split_host(N - 1 - k)
        assert s1 <= 0 or s1 < dev._GLV_SIGN_BOUND


# ------------------------------------------------------------ slow tier


def _rec_corpus():
    """The PR-15 adversarial builder extended with ecrecover rows —
    every invalid class, poison rows before AND after victims, all
    three wire shapes in one dispatch."""
    from cometbft_tpu.crypto import secp256k1eth as heth
    from tests.test_secp_ops import _corpus as base

    items = base()
    rpk = heth.RecoverPrivKey.from_seed(b"glv-rec")
    addr = rpk.pub_key().data
    msg = b"rec ok"
    items.append((addr, msg, rpk.sign(msg)))
    # tampered sig, wrong address, high-s + flipped v, r >= n, non-QR r
    sig = bytearray(rpk.sign(b"rec t1"))
    sig[3] ^= 1
    items.append((addr, b"rec t1", bytes(sig)))
    items.append((b"\x42" * 20, b"rec t2", rpk.sign(b"rec t2")))
    s0 = rpk.sign(b"rec t3")
    r_ = int.from_bytes(s0[:32], "big")
    s_ = int.from_bytes(s0[32:64], "big")
    items.append((addr, b"rec t3",
                  r_.to_bytes(32, "big") + (N - s_).to_bytes(32, "big")
                  + bytes([s0[64] ^ 1])))
    items.append((addr, b"rec t4",
                  (N + 1).to_bytes(32, "big") + s0[32:64] + b"\x00"))
    x = 5
    while True:
        y2 = (pow(x, 3, P) + host_secp.B) % P
        if pow(y2, (P + 1) // 4, P) ** 2 % P != y2:
            break
        x += 1
    items.append((addr, b"rec t5",
                  x.to_bytes(32, "big") + s0[32:64] + b"\x00"))
    # a second valid rec row AFTER the poison, same 64-bucket
    items.append((addr, b"rec ok 2", rpk.sign(b"rec ok 2")))
    return items


def _witness_pin(items, hash_min):
    import os

    from cometbft_tpu.models import secp_verifier as sv

    want = [sv._host_verify_one(p, m, s) for (p, m, s) in items]
    assert True in want and False in want
    os.environ["COMETBFT_TPU_SECP_HASH_DEVICE_MIN"] = hash_min
    try:
        os.environ["COMETBFT_TPU_SECP_GLV"] = "1"
        _, glv = sv._verify_items(items, use_device=True)
        os.environ["COMETBFT_TPU_SECP_GLV"] = "0"
        _, wit = sv._verify_items(items, use_device=True)
    finally:
        os.environ.pop("COMETBFT_TPU_SECP_GLV", None)
        os.environ.pop("COMETBFT_TPU_SECP_HASH_DEVICE_MIN", None)
    assert glv == wit == want


@pytest.mark.slow
def test_glv_bit_identical_to_shamir_witness_device():
    """THE witness pin: the GLV program and the non-GLV Shamir program
    produce bit-identical verdicts — equal to the host gauntlet — over
    the rec-extended adversarial corpus (all three wire shapes, every
    invalid class, poison rows both sides of victims) in one dispatch:
    the witness walk pins the default one."""
    _witness_pin(_rec_corpus(), hash_min="0")


@pytest.mark.slow
def test_glv_bit_identical_fused_hash_program():
    """Same witness pin through the fused hash->verify dispatch (the
    on-device SHA-256/Keccak-256 digests feed the same verdicts)."""
    _witness_pin(_rec_corpus(), hash_min="1")
