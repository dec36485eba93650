"""PR-11 coverage: host-precomputed comb A-tables.

Fast tier: bit-identity of ops/comb.build_a_tables_host against the
device build over a randomized corpus including invalid/edge pubkey
encodings (eager device execution — no XLA program compile in the fast
tier), the COMB_HOST_BUILD_MAX routing seam in models/comb_verifier,
the lock-guarded jit publish (the PR-11 bugfix), the kernel
compile-cost budget gate, and the checked-in goldens carrying the
table path under its budget (the deleted grandfather clause).

Slow tier: the same bit-identity against the genuinely JITTED build
(one XLA compile of the scan-rolled kernel).
"""

import threading
import time

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.ops import comb



def _corpus(rng, n_valid):
    """n_valid real pubkeys plus adversarial rows: a guaranteed-invalid
    encoding (no square root: ~half of random y values are off-curve,
    so search), the non-canonical all-ones encoding, y = 0 with sign
    bit 1, and all-zero."""
    keys = [host.PrivKey.from_seed(rng.bytes(32)) for _ in range(n_valid)]
    pubs = [k.pub_key().data for k in keys]
    while True:
        garbage = rng.bytes(32)
        if not comb._host_decompress_zip215(garbage)[1]:
            break
    pubs += [garbage, b"\xff" * 32, bytes(31) + b"\x80", bytes(32)]
    return np.frombuffer(b"".join(pubs), np.uint8).reshape(-1, 32)


def test_host_build_bit_identical_to_device_build():
    """Tables AND valid flags agree bit for bit with the device build —
    including invalid rows, which both paths sanitize to identity
    chains (the shared-batch-inversion poisoning fix).  Eager device
    execution: integer ops are exact, and the jitted variant (identical
    program, one XLA compile) is the slow test below."""
    import jax.numpy as jnp

    rng = np.random.default_rng(20260804)
    a = _corpus(rng, 4)
    th, vh = comb.build_a_tables_host(a)
    td, vd = comb.build_a_tables(jnp.asarray(a))
    assert th.shape == (comb.NPOS_A, comb.NENT_A, 3, 22, a.shape[0])
    assert np.array_equal(vh, np.asarray(vd))
    assert np.array_equal(th, np.asarray(td))
    # invalid rows really are identity rows: niels (1, 1, 0) everywhere
    bad = np.flatnonzero(~vh)
    assert bad.size >= 1  # the garbage row
    for b in bad:
        row = th[..., b]  # (pos, ent, 3, 22)
        assert (row[:, :, 0, 0] == 1).all() and (row[:, :, 0, 1:] == 0).all()
        assert (row[:, :, 1, 0] == 1).all() and (row[:, :, 1, 1:] == 0).all()
        assert (row[:, :, 2] == 0).all()


@pytest.mark.slow
def test_host_build_bit_identical_to_jitted_build():
    """The satellite's letter: host precompute vs the JITTED
    build_a_tables output, randomized corpus.  One XLA compile of the
    scan-rolled kernel (compile-cached across runs)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    a = _corpus(rng, 6)
    th, vh = comb.build_a_tables_host(a)
    td, vd = comb.build_a_tables_jit(jnp.asarray(a))
    assert np.array_equal(vh, np.asarray(vd))
    assert np.array_equal(th, np.asarray(td))


def test_build_routing_honors_host_build_max(monkeypatch):
    """models/comb_verifier._build_tables: host precompute at/below the
    knob, the jitted kernel above it, device-only at 0.  The host
    builder sees each distinct key once (pad lanes repeat one) and the
    lanes come back in the caller's order."""
    from cometbft_tpu.models import comb_verifier as cv

    import types

    def keys(*firsts):
        return np.repeat(np.asarray(firsts, np.uint8)[:, None], 32, axis=1)

    calls = []
    dev_t = types.SimpleNamespace(block_until_ready=lambda: None)
    monkeypatch.setattr(
        comb, "build_a_tables_host",
        lambda a: (
            calls.append(("host", int(a.shape[0]))),
            (a[None, :, 0].astype(np.int32), a[:, 0] != 0),
        )[1],
    )
    monkeypatch.setattr(
        comb, "build_a_tables_jit",
        lambda a: (calls.append(("device", int(a.shape[0]))), (dev_t, "V"))[1],
    )
    monkeypatch.setenv("COMETBFT_TPU_COMB_HOST_BUILD_MAX", "8")
    cv._build_tables(keys(*range(4)))
    cv._build_tables(keys(*range(8)))  # boundary: host
    cv._build_tables(keys(*range(16)))
    tables, valid = cv._build_tables(keys(7, 0, 9, 7, 7))  # 7 built once
    assert tables[0].tolist() == [7, 0, 9, 7, 7]
    assert valid.tolist() == [True, False, True, True, True]
    monkeypatch.setenv("COMETBFT_TPU_COMB_HOST_BUILD_MAX", "0")
    cv._build_tables(keys(*range(4)))
    assert calls == [
        ("host", 4), ("host", 8), ("device", 16), ("host", 3), ("device", 4),
    ]


def test_entry_built_from_host_tables_verifies_via_host_route(monkeypatch):
    """End-to-end sanity on the default (host-build) path: a cache
    entry built without any XLA program still serves a correct verify
    (host-routed small batch keeps the fast tier compile-free)."""
    from cometbft_tpu.models import comb_verifier as cv

    n = 4
    keys = [host.PrivKey.from_seed(bytes([60 + i]) * 32) for i in range(n)]
    pubs = [k.pub_key().data for k in keys]
    built = []
    real = cv._build_tables
    monkeypatch.setattr(
        cv, "_build_tables", lambda a: (built.append(a.shape[0]), real(a))[1]
    )
    entry = cv.ValsetCombCache().ensure(pubs)
    assert built == [entry.vpad] and (entry.size, entry.vpad) == (n, 128)
    bv = cv.CombBatchVerifier(entry)
    for i, sk in enumerate(keys):
        msg = b"hostbuild-%d" % i
        bv.add(pubs[i], msg + (b"!" if i == 2 else b""), sk.sign(msg))
    ok, per = bv.verify()
    assert not ok and per == [i != 2 for i in range(n)]


def test_build_a_tables_jit_publishes_under_lock(monkeypatch):
    """The PR-11 bugfix: two threads racing the first build share ONE
    jit wrapper — the unlocked publish let each install its own,
    guaranteeing two traces of the (pre-rework: 2-minute) build."""
    created = []
    barrier = threading.Barrier(2)

    def fake_jit(fn):
        # widen the race window with a busy spin (time.sleep under the
        # publish lock would trip the lockwitness blocking check)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.03:
            pass
        created.append(fn)
        return lambda a: ("compiled", a)

    monkeypatch.setattr(comb.jax, "jit", fake_jit)
    monkeypatch.setattr(comb, "_BUILD_A_JIT", None)

    results = []

    def run():
        barrier.wait()
        results.append(comb.build_a_tables_jit("arg"))

    threads = [
        threading.Thread(target=run, name=f"hb-race-{i}") for i in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(created) == 1, "racing threads traced the build twice"
    assert results == [("compiled", "arg")] * 2


def test_kernel_eqn_budget_enforced():
    """kernelcheck's compile-cost budget: a kernel past its max_eqns is
    a contract finding, and NO production kernel rides unbudgeted (the
    deleted grandfather clause)."""
    from cometbft_tpu.analysis import kernel_manifest as manifest
    from cometbft_tpu.analysis import kernelcheck

    k = manifest.Kernel(
        name="hb_budget", fn="cometbft_tpu.ops.sha2:sha256_blocks",
        args=(manifest.u8(8, 2, 64), manifest.i32(8)),
        out=(manifest.u8(8, 32),),
        max_eqns=10,
    )
    t = kernelcheck.trace_kernel(k)
    assert t.eqns > 10
    msgs = " | ".join(f.message for f in t.findings)
    assert "compile-cost budget" in msgs and "exceeds the budget of 10" in msgs
    # unbudgeted fixture kernels skip the gate (max_eqns=0)...
    k0 = manifest.Kernel(
        name="hb_nobudget", fn="cometbft_tpu.ops.sha2:sha256_blocks",
        args=(manifest.u8(8, 2, 64), manifest.i32(8)),
        out=(manifest.u8(8, 32),),
    )
    assert kernelcheck.trace_kernel(k0).findings == []
    # ...but the real manifest may not contain one
    assert all(kk.max_eqns > 0 for kk in manifest.KERNELS)
    assert kernelcheck._manifest_findings() == []


def test_table_build_fits_its_budget_in_the_goldens():
    """The acceptance surface on a backend-less host: the checked-in
    golden's eqn count for the table path sits under its manifest
    budget — far below the ~84k-equation build whose XLA compile ran
    2m34s (on XLA:CPU).  The slow full-fingerprint gate proves the
    goldens match a fresh trace."""
    from cometbft_tpu.analysis import kernel_manifest as manifest
    from cometbft_tpu.analysis import kernelcheck

    golden = kernelcheck.load_fingerprints()
    row = manifest.by_name()["comb_build_a_tables"]
    eqns = golden["comb_build_a_tables"]["costs"]["eqns"]
    assert 0 < eqns <= row.max_eqns
    assert eqns < 40_000  # the grandfathered build was ~84k
