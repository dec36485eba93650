"""Profile the flagship VerifyCommit path: host assembly vs device time."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
from cometbft_tpu.utils import compilecache

compilecache.enable()
import jax.numpy as jnp

from cometbft_tpu.crypto import ed25519 as host
from cometbft_tpu.ops import sha2, ed25519 as E

N = 10_000
rng = np.random.default_rng(7)
keys = [host.PrivKey.from_seed(rng.bytes(32)) for _ in range(N)]
items = []
for i, sk in enumerate(keys):
    msg = b"\x08\x02\x10\x01\x18\x05" + i.to_bytes(8, "big") + b"|chain-bench"
    items.append((sk.pub_key().data, msg, sk.sign(msg)))

# --- host assembly timing (current loop) ---
def assemble(bucket):
    a = np.zeros((bucket, 32), dtype=np.uint8)
    r = np.zeros((bucket, 32), dtype=np.uint8)
    s = np.zeros((bucket, 32), dtype=np.uint8)
    hashed = []
    for i, (pub, msg, sig) in enumerate(items):
        a[i] = np.frombuffer(pub, dtype=np.uint8)
        r[i] = np.frombuffer(sig[:32], dtype=np.uint8)
        s[i] = np.frombuffer(sig[32:], dtype=np.uint8)
        hashed.append(sig[:32] + pub + msg)
    for i in range(N, bucket):
        a[i], r[i], s[i] = a[0], r[0], s[0]
        hashed.append(hashed[0])
    blocks, active = sha2.pad_messages_sha512(hashed)
    return a, r, s, blocks, active

t0 = time.perf_counter()
a, r, s, blocks, active = assemble(16384)
t1 = time.perf_counter()
print(f"host assembly (16384 bucket): {(t1-t0)*1e3:.1f} ms", flush=True)

# --- host sha512 timing via hashlib ---
import hashlib
t0 = time.perf_counter()
digests = [hashlib.sha512(sig[:32] + pub + msg).digest() for (pub, msg, sig) in items]
t1 = time.perf_counter()
print(f"host hashlib sha512 x10k: {(t1-t0)*1e3:.1f} ms", flush=True)

fn = jax.jit(E.verify_batch)
aj, rj, sj, bj, actj = jnp.asarray(a), jnp.asarray(r), jnp.asarray(s), jnp.asarray(blocks), jnp.asarray(active)

t0 = time.perf_counter()
ok = np.asarray(fn(aj, rj, sj, bj, actj))
t1 = time.perf_counter()
print(f"first call (compile+run): {(t1-t0):.1f} s; ok={ok[:N].all()}", flush=True)

# steady state with device-resident inputs
for _ in range(2):
    fn(aj, rj, sj, bj, actj).block_until_ready()
t0 = time.perf_counter()
for _ in range(5):
    fn(aj, rj, sj, bj, actj).block_until_ready()
t1 = time.perf_counter()
print(f"device-resident kernel: {(t1-t0)/5*1e3:.1f} ms", flush=True)

# with H2D each time
t0 = time.perf_counter()
for _ in range(5):
    fn(jnp.asarray(a), jnp.asarray(r), jnp.asarray(s), jnp.asarray(blocks), jnp.asarray(active)).block_until_ready()
t1 = time.perf_counter()
print(f"H2D + kernel: {(t1-t0)/5*1e3:.1f} ms", flush=True)
print(f"input bytes: a/r/s {3*16384*32}, blocks {blocks.nbytes}, active {active.nbytes}", flush=True)

# sub-kernel split: sha512 on device vs scalar-mul
sha_fn = jax.jit(sha2.sha512_blocks)
dg = sha_fn(bj, actj); dg.block_until_ready()
t0 = time.perf_counter()
for _ in range(5):
    sha_fn(bj, actj).block_until_ready()
t1 = time.perf_counter()
print(f"device sha512 subkernel: {(t1-t0)/5*1e3:.1f} ms", flush=True)

from cometbft_tpu.ops import scalar


def scalarmul_only(a_enc, r_enc, s_bytes, k_digest):
    k_limbs = scalar.reduce_mod_l(scalar.bytes_to_limbs(k_digest, scalar.NL_X))
    k_windows = scalar.limbs_to_windows(k_limbs)
    s_windows = scalar.bytes_to_windows(s_bytes)
    s_ok = scalar.s_lt_l(s_bytes)
    return E.verify_prepared(a_enc, r_enc, s_windows, k_windows, s_ok)

sm_fn = jax.jit(scalarmul_only)
out = sm_fn(aj, rj, sj, dg); out.block_until_ready()
t0 = time.perf_counter()
for _ in range(5):
    sm_fn(aj, rj, sj, dg).block_until_ready()
t1 = time.perf_counter()
print(f"scalar-mul subkernel (incl decompress+table): {(t1-t0)/5*1e3:.1f} ms", flush=True)
