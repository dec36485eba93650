"""Sharded verification over the virtual 8-device mesh: the multi-chip
code path (shard_map + psum/all_gather) must agree with the single-device
kernel and the host reference."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """Mesh-sharded executables intermittently crash XLA's persistent-
    cache READ path (SIGSEGV/SIGABRT in get_executable_and_time) when
    the pytest process carries the full slow tier's state — always
    compile fresh in this module (see __graft_entry__.dryrun_multichip,
    which does the same for the driver's multichip validation).

    The cache object LATCHES on first use (is_cache_used memoizes), so
    merely changing the dir config mid-process is a no-op: the enable
    flag must flip AND reset_cache() must drop the latch, both ways."""
    import jax

    try:
        from jax._src import compilation_cache as cc

        old_enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
    except Exception:  # noqa: BLE001 — private API; fail open like
        cc = None      # __graft_entry__._disable_compile_cache
    yield
    if cc is not None:
        jax.config.update("jax_enable_compilation_cache", old_enabled)
        cc.reset_cache()

pytestmark = [
    pytest.mark.slow,  # kernel compiles take minutes on the CPU backend
    pytest.mark.usefixtures("tiny_device_batches"),
]

from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.crypto import merkle as hostM
from cometbft_tpu.ops import merkle as M
from cometbft_tpu.ops import sha2
from cometbft_tpu.parallel import (
    make_mesh,
    sharded_verify_batch,
    sharded_merkle_root,
)


def _batch(n, corrupt=()):
    a = np.zeros((n, 32), dtype=np.uint8)
    r = np.zeros((n, 32), dtype=np.uint8)
    s = np.zeros((n, 32), dtype=np.uint8)
    hashed = []
    for i in range(n):
        sk = host.PrivKey.from_seed(bytes([i + 1]) * 32)
        pub = sk.pub_key().data
        msg = b"sharded-%d" % i
        sig = sk.sign(msg)
        if i in corrupt:
            sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        a[i] = np.frombuffer(pub, dtype=np.uint8)
        r[i] = np.frombuffer(sig[:32], dtype=np.uint8)
        s[i] = np.frombuffer(sig[32:], dtype=np.uint8)
        hashed.append(sig[:32] + pub + msg)
    blocks, active = sha2.pad_messages_sha512(hashed)
    return (
        jnp.asarray(a),
        jnp.asarray(r),
        jnp.asarray(s),
        jnp.asarray(blocks),
        jnp.asarray(active),
    )


def test_sharded_verify_all_valid():
    mesh = make_mesh(8)
    ok, valid = sharded_verify_batch(mesh, *_batch(16))
    assert bool(ok)
    assert np.asarray(valid).all()


def test_sharded_verify_blame():
    mesh = make_mesh(8)
    ok, valid = sharded_verify_batch(mesh, *_batch(16, corrupt={3, 11}))
    valid = np.asarray(valid)
    assert not bool(ok)
    assert not valid[3] and not valid[11]
    assert valid.sum() == 14


def test_sharded_merkle_matches_host():
    mesh = make_mesh(8)
    leaves = [b"tx-%d" % i for i in range(32)]  # 4 per device (pow2)
    lb, la = M.pad_leaves(leaves)
    root = sharded_merkle_root(mesh, jnp.asarray(lb), jnp.asarray(la))
    assert bytes(np.asarray(root)) == hostM.hash_from_byte_slices(
        leaves, device=False
    )


def test_sharded_proofs_match_host():
    """Batched proof generation with the query axis sharded 8 ways:
    root, selected leaf hashes, and every gathered aunt must equal the
    host oracle (crypto/merkle.proofs_from_byte_slices) byte for byte —
    the kernel uses zero collectives, so any disagreement is a sharding
    spec bug, not a reduction bug."""
    from cometbft_tpu.parallel.verify import sharded_merkle_proofs

    mesh = make_mesh(8)
    leaves = [b"proof-leaf-%d" % i for i in range(24)]  # non-pow2 tree
    idxs = [0, 23, 7, 11, 3, 16, 22, 1, 5, 9, 13, 2, 19, 8, 21, 4]  # K=16
    depth, sib = hostM.proof_plan(24, idxs)
    lb, la = M.pad_leaves(leaves)
    root, leaf_sel, aunts = sharded_merkle_proofs(
        mesh,
        jnp.asarray(lb),
        jnp.asarray(la),
        jnp.asarray(np.asarray(idxs, dtype=np.int32)),
        jnp.asarray(np.asarray(sib, dtype=np.int32)),
    )
    want_root, all_proofs = hostM.proofs_from_byte_slices(leaves)
    want = [all_proofs[i] for i in idxs]
    assert bytes(np.asarray(root)) == want_root
    leaf_np, aunt_np = np.asarray(leaf_sel), np.asarray(aunts)
    for k, w in enumerate(want):
        assert bytes(leaf_np[k]) == w.leaf_hash
        got_aunts = [
            bytes(aunt_np[k, l]) for l in range(depth) if sib[k][l] >= 0
        ]
        assert got_aunts == list(w.aunts)


def _fresh_interpreter(argv: list) -> None:
    """Run code in a clean python process, CPU-meshed like the driver.

    XLA's CPU compiler intermittently SEGFAULTS compiling the
    mesh-sharded comb programs inside a pytest process laden with the
    full slow tier's state (leaked p2p threads, cygrpc, dozens of live
    backends) — the same compile always succeeds in a fresh process,
    which is also exactly how the driver invokes these entry points.
    """
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # a child must never contend for a chip
    env["COMETBFT_TPU_DEVICE_BATCH_MIN"] = "1"
    # don't rely on conftest's env mutation leaking through: the child
    # needs the 8-device flag before its first backend init
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + ":" + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable] + argv,
        cwd=repo,
        env=env,
        capture_output=True,
        text=True,
        timeout=1800,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_graft_entry_dryrun():
    _fresh_interpreter(
        [
            "-c",
            "import __graft_entry__ as g\n"
            "import jax, numpy as np\n"
            "fn, args = g.entry()\n"
            "assert np.asarray(jax.jit(fn)(*args)).all()\n"
            "g.dryrun_multichip(8)\n",
        ]
    )


def test_sharded_comb_path_matches_host():
    """The engine's production verifier (comb-cached) over the 8-device
    mesh: tables sharded on the validator lane axis, blame + all-ok via
    all_gather/psum (parallel/verify.sharded_verify_cached).  Runs in a
    fresh interpreter (see _fresh_interpreter) with the body in
    tests/sharded_comb_check.py."""
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    _fresh_interpreter([os.path.join(here, "sharded_comb_check.py")])
