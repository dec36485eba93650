"""Device secp256k1 ECDSA kernel vs the pure-host lane: bit-identity
over adversarial corpora, plus the batch-inversion poison test.

The whole corpus rides in ONE padded bucket -> one compiled program
(warm via the persistent XLA compile cache tests/.jax_cache, the same
mitigation the ed25519/comb kernels rely on), so the fast tier pays a
dispatch, not a compile, per run.  Each field op is held to Python
integers on operands that make long carry chains; the batch inversion's
differential and the multi-bucket sweep are heavier and live in the
slow tier.
"""

import hashlib

import numpy as np
import pytest

from cometbft_tpu.crypto import secp256k1 as host
from cometbft_tpu.crypto import secp256k1eth as heth
from cometbft_tpu.models import secp_verifier as mv

rng = np.random.default_rng(1234)


def _corpus():
    """One adversarial corpus: valid cosmos + eth rows interleaved with
    every invalid-edge class the host gauntlet rejects."""
    items = []

    def cosmos(seed, msg=b"ok", tamper=None):
        sk = host.PrivKey.from_seed(seed)
        sig = sk.sign(msg)
        pub = sk.pub_key().data
        if tamper:
            pub, msg, sig = tamper(sk, pub, msg, sig)
        items.append((pub, msg, sig))

    def ether(seed, msg=b"ok-eth", tamper=None):
        sk = heth.PrivKey.from_seed(seed)
        sig = sk.sign(msg)
        pub = sk.pub_key().data
        if tamper:
            pub, msg, sig = tamper(sk, pub, msg, sig)
        items.append((pub, msg, sig))

    for i in range(5):
        cosmos(b"valid-%d" % i, b"cosmos message %d" % i)
    for i in range(4):
        ether(b"valid-eth-%d" % i, b"eth message %d" % i)

    # tampered signature byte
    cosmos(b"t-sig", tamper=lambda k, p, m, s: (p, m, s[:40] + bytes([s[40] ^ 1]) + s[41:]))
    # tampered message
    cosmos(b"t-msg", tamper=lambda k, p, m, s: (p, m + b"!", s))
    # high-s (raw-equation-valid, low-s-invalid)
    def _high_s(k, p, m, s):
        r = int.from_bytes(s[:32], "big")
        sv = int.from_bytes(s[32:], "big")
        return p, m, r.to_bytes(32, "big") + (host.N - sv).to_bytes(32, "big")
    cosmos(b"t-hs", tamper=_high_s)
    # r = 0 / s = 0 / r,s >= n
    cosmos(b"t-r0", tamper=lambda k, p, m, s: (p, m, b"\x00" * 32 + s[32:]))
    cosmos(b"t-s0", tamper=lambda k, p, m, s: (p, m, s[:32] + b"\x00" * 32))
    cosmos(b"t-rn", tamper=lambda k, p, m, s: (p, m, host.N.to_bytes(32, "big") + s[32:]))
    cosmos(b"t-sn", tamper=lambda k, p, m, s: (p, m, s[:32] + (host.N + 1).to_bytes(32, "big")))
    # wrong key
    def _wrong_key(k, p, m, s):
        return host.PrivKey.from_seed(b"other").pub_key().data, m, s
    cosmos(b"t-wk", tamper=_wrong_key)
    # invalid pubkey encodings: bad prefix, x >= p, x off-curve
    cosmos(b"t-pfx", tamper=lambda k, p, m, s: (b"\x05" + p[1:], m, s))
    cosmos(b"t-xp", tamper=lambda k, p, m, s: (bytes([2]) + host.P.to_bytes(32, "big"), m, s))
    x = 5
    while True:
        y2 = (pow(x, 3, host.P) + host.B) % host.P
        if pow(y2, (host.P + 1) // 4, host.P) ** 2 % host.P != y2:
            break
        x += 1
    cosmos(b"t-oc", tamper=lambda k, p, m, s, x=x: (bytes([2]) + x.to_bytes(32, "big"), m, s))
    # cross-shape: cosmos key with an eth-length signature
    cosmos(b"t-xs", tamper=lambda k, p, m, s: (p, m, s + b"\x01"))

    # eth edges: wrong v, v out of range, tampered r, off-curve pubkey
    ether(b"e-v", tamper=lambda k, p, m, s: (p, m, s[:64] + bytes([s[64] ^ 1])))
    ether(b"e-v2", tamper=lambda k, p, m, s: (p, m, s[:64] + bytes([2])))
    ether(b"e-r", tamper=lambda k, p, m, s: (p, m, bytes([s[0] ^ 1]) + s[1:]))
    def _eth_badpub(k, p, m, s):
        bad = bytearray(p)
        bad[64] ^= 1
        return bytes(bad), m, s
    ether(b"e-pub", tamper=_eth_badpub)
    # eth key with a cosmos-length signature
    ether(b"e-xs", tamper=lambda k, p, m, s: (p, m, s[:64]))

    # the x(R') mod n wraparound branch never fires for honest
    # signatures (r + n < p needs x >= n, a ~2^-128 event) but the
    # compare must still agree: exercised implicitly by every row
    return items


def test_device_bit_identical_to_host_adversarial_corpus():
    """The acceptance pin: batched device verdicts == pure-host lane,
    row for row, over valid + tampered + invalid-encoding rows, both
    wire shapes, in one dispatch."""
    items = _corpus()
    expect = [mv._host_verify_one(p, m, s) for (p, m, s) in items]
    # sanity on the corpus itself: both verdicts present
    assert True in expect and False in expect
    ok, res = mv._verify_items(items, use_device=True)
    assert res == expect
    assert ok == (all(expect) and bool(expect))
    # and the pure-host verifier path returns the same thing
    ok_h, res_h = mv._verify_items(items, use_device=False)
    assert res_h == expect and ok_h == ok


def test_malformed_row_cannot_poison_batch_inverses():
    """The PR-11 lesson, re-proven for this lane: attacker-chosen rows
    whose s = 0 (a zero in the shared s^-1 Montgomery batch-inversion
    product) or whose pubkey is malformed (an all-zero limb row) ride
    in the same dispatch as valid rows — the valid rows' inverses, and
    therefore verdicts, must be unaffected."""
    sk = host.PrivKey.from_seed(b"poison-victim")
    msg = b"victim tx"
    sig = sk.sign(msg)
    # 11 victims + 6 poison rows -> the same 32-wide bucket as the
    # corpus test: the fast tier compiles exactly one program shape
    victims = [(sk.pub_key().data, msg, sig)] * 11

    attacker = host.PrivKey.from_seed(b"poison-attacker")
    a_sig = attacker.sign(msg)
    poison = [
        # s = 0: would zero the shared prefix product if unsanitized
        (attacker.pub_key().data, msg, a_sig[:32] + b"\x00" * 32),
        # malformed pubkey: all-zero limbs enter the point pipeline
        (b"\x05" + attacker.pub_key().data[1:], msg, a_sig),
        # r = 0 for good measure
        (attacker.pub_key().data, msg, b"\x00" * 32 + a_sig[32:]),
    ]
    # poison rows FIRST, so their prefix products precede the victims'
    items = poison + victims + poison
    ok, res = mv._verify_items(items, use_device=True)
    assert res == [False] * 3 + [True] * 11 + [False] * 3
    assert not ok


def test_verdict_independent_of_batch_composition():
    """A row's verdict must not depend on its neighbors (independent
    rows, per-row blame): the same row verifies identically solo-ish
    and embedded in a hostile batch."""
    sk = host.PrivKey.from_seed(b"compo")
    msg = b"compo tx"
    good = (sk.pub_key().data, msg, sk.sign(msg))
    bad = (sk.pub_key().data, msg, b"\x00" * 64)
    base = [good] * 20  # same 32-wide bucket as the other fast tests
    _, res_base = mv._verify_items(base, use_device=True)
    mixed = [bad, good] * 10
    _, res_mixed = mv._verify_items(mixed, use_device=True)
    assert res_base == [True] * 20
    assert res_mixed == [False, True] * 10


@pytest.mark.slow
def test_randomized_sweep_multiple_buckets():
    """Wider randomized differential across bucket shapes (each new
    bucket is a fresh XLA compile on the CPU backend — slow tier)."""
    for n in (11, 21):
        items = []
        for i in range(n):
            kind = int(rng.integers(0, 4))
            seed = rng.bytes(16)
            msg = bytes(rng.bytes(int(rng.integers(1, 64))))
            if kind == 0:
                sk = host.PrivKey.from_seed(seed)
                items.append((sk.pub_key().data, msg, sk.sign(msg)))
            elif kind == 1:
                sk = heth.PrivKey.from_seed(seed)
                items.append((sk.pub_key().data, msg, sk.sign(msg)))
            elif kind == 2:
                sk = host.PrivKey.from_seed(seed)
                sig = bytearray(sk.sign(msg))
                sig[int(rng.integers(0, 64))] ^= 1 << int(rng.integers(0, 8))
                items.append((sk.pub_key().data, msg, bytes(sig)))
            else:
                sk = heth.PrivKey.from_seed(seed)
                sig = bytearray(sk.sign(msg))
                sig[int(rng.integers(0, 65))] ^= 1 << int(rng.integers(0, 8))
                items.append((sk.pub_key().data, msg, bytes(sig)))
        expect = [mv._host_verify_one(p, m, s) for (p, m, s) in items]
        _, res = mv._verify_items(items, use_device=True)
        assert res == expect, n


@pytest.mark.slow
def test_field_and_inverse_differential():
    """Field-level differentials of the generalized Montgomery limb
    arithmetic (both moduli) and the batch inversion."""
    import jax
    import jax.numpy as jnp

    from cometbft_tpu.ops import secp256k1 as dev

    n = 32
    for mod in (dev.FP, dev.FN):
        a = [int.from_bytes(rng.bytes(32), "big") % mod.m for _ in range(n)]
        b = [int.from_bytes(rng.bytes(32), "big") % mod.m for _ in range(n)]
        am = [mod.to_mont(x) for x in a]
        bm = [mod.to_mont(x) for x in b]
        la = jnp.asarray(dev.ints_to_limbs_np(am))
        lb = jnp.asarray(dev.ints_to_limbs_np(bm))
        got = dev.from_limbs(np.asarray(jax.jit(
            lambda x, y, mod=mod: dev.mul(x, y, mod)
        )(la, lb)))
        for i in range(n):
            assert mod.from_mont(int(got[i])) == a[i] * b[i] % mod.m, i
        # batch inversion: every row's modular inverse in one pass
        inv = dev.from_limbs(np.asarray(jax.jit(
            lambda x, mod=mod: dev.batch_inverse(x, mod)
        )(la)))
        for i in range(n):
            assert mod.from_mont(int(inv[i])) == pow(a[i], mod.m - 2, mod.m), i


def _edge_values(m: int, top: int) -> list[int]:
    """Operands below ``top`` that make long carry chains: 0, 1, the
    values next to m and to powers of two (runs of 4095 limbs), and
    random ones."""
    vals = [0, 1, 2, m - 1, m - 2, (m - 1) // 2, top - 1, 4095, 4096]
    vals += [(1 << k) - d for k in (12, 13, 132, 255, 256, 263)
             for d in (0, 1)]
    vals += [int.from_bytes(rng.bytes(33), "big") % top for _ in range(48)]
    return [v % top for v in vals]


_R = 1 << 264  # the Montgomery radix

# op: (the device op on limb batches, the exact result in integers)
_FIELD_OPS = {
    "add": (lambda d, mod, x, y: d.add(x, y, mod),
            lambda m, x, y: (x + y) % m),
    "sub": (lambda d, mod, x, y: d.sub(x, y, mod),
            lambda m, x, y: (x - y) % m),
    "mul": (lambda d, mod, x, y: d.mul(x, y, mod),
            lambda m, x, y: x * y * pow(_R, -1, m) % m),
    "add_n": (lambda d, mod, x, y: d._add_const(x, d.FN.limbs),
              lambda m, x, y: x + host.N),
    # x^(m-2) of the element whose Montgomery form is x
    "invert": (lambda d, mod, x, y: d._mont_pow_inv(x, mod),
               lambda m, x, y: pow(x * pow(_R, -1, m), m - 2, m) * _R % m),
}


@pytest.mark.parametrize("op", ["add", "sub", "mul", "mul_raw", "add_n", "invert"])
@pytest.mark.parametrize("modulus", ["p", "n"])
def test_field_ops_match_big_integers(modulus, op):
    """Each field op against Python integers on operands that make long
    carry chains: the parallel carries and the whole-width reduction
    give the canonical digits of the exact result.  ``mul_raw`` is the
    to-Montgomery case, the second operand any value below 2^264."""
    import jax

    from cometbft_tpu.ops import secp256k1 as dev

    mod = dev.FP if modulus == "p" else dev.FN
    m = mod.m
    a = _edge_values(m, m)
    b = _edge_values(m, _R if op == "mul_raw" else m)[::-1]
    device_op, exact = _FIELD_OPS["mul" if op == "mul_raw" else op]
    limbs = np.asarray(jax.jit(lambda x, y: device_op(dev, mod, x, y))(
        dev.ints_to_limbs_np(a), dev.ints_to_limbs_np(b)))
    assert limbs.min() >= 0 and limbs.max() < 4096  # canonical digits
    got = [int(g) for g in dev.from_limbs(limbs)]
    for i, (x, y) in enumerate(zip(a, b)):
        if op == "invert" and x == 0:
            continue  # zero has no inverse: callers sanitise it first
        assert got[i] == exact(m, x, y), (i, x, y)


def test_host_packer_roundtrip():
    from cometbft_tpu.ops import secp256k1 as dev

    vals = [0, 1, dev.P - 1, dev.N - 1, (1 << 256) - 1] + [
        int.from_bytes(rng.bytes(32), "big") for _ in range(8)
    ]
    limbs = dev.ints_to_limbs_np(vals)
    back = dev.from_limbs(limbs)
    assert [int(x) for x in back] == vals
