"""Phase profiler for the comb-cached VerifyCommit path: host assembly,
H2D+dispatch, kernel (parallel-chains AND sequential accumulation), result
fetch, table build, scalar reduce, R decompression, A/B comb loops,
single field ops — run on the real chip to direct optimization (numbers
recorded in BASELINE.md).

The headline lines:
  assembly_ms   — host staging-slab fill (models/comb_verifier), the
                  phase the round-5 capture measured at ~22 ms
  kernel tree/seq — verify_cached with K parallel add_niels chains and
                  a short fold (K from the lane count) vs the 87-step
                  sequential chain
  fetch_ms      — the one packed device->host result readback

Layout note: field elements are limbs-first (..., 22, V) since round 4
(see ops/field.py); the comb tables are (64, 9, 3, 22, V)."""
import sys, os, time, hashlib
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax, jax.numpy as jnp
from jax import lax
from cometbft_tpu.ops import comb, ed25519 as E, field as F, scalar, sha2
from cometbft_tpu.crypto import ed25519 as host

V = int(os.environ.get("COMBPROF_V", "10000"))
TDIR = "/tmp/combprof"
rng = np.random.default_rng(7)
keys = [host.PrivKey.from_seed(rng.bytes(32)) for _ in range(V)]
pubs = [k.pub_key().data for k in keys]

# ---- table_build phase: the cold-start cost (PR-11), attributable per
# sub-phase.  COMBPROF_TABLE_BUILD=host|device|both|skip (default: host
# at small V, device at large V — the models/comb_verifier routing).
# host  = build_a_tables_host (bigint precompute) + device_put H2D
# device = build_a_tables_jit (compile + arithmetic; the compile half
#          vanishes with a warm compile cache)
_tb_mode = os.environ.get("COMBPROF_TABLE_BUILD", "")
if not _tb_mode:
    _tb_mode = "host" if V <= 2048 else "device"
a = np.frombuffer(b"".join(pubs), dtype=np.uint8).reshape(-1, 32)
tables = valid = None
if _tb_mode in ("host", "both"):
    t0 = time.time()
    th, vh = comb.build_a_tables_host(a)
    t1 = time.time()
    tables = jax.device_put(th); valid = jax.device_put(vh)
    tables.block_until_ready(); valid.block_until_ready()
    t2 = time.time()
    print(
        f"table_build (host): precompute {t1-t0:.1f} s + device_put H2D "
        f"{t2-t1:.1f} s = {t2-t0:.1f} s  ({(t2-t0)/max(V,1)*1e3:.1f} ms/validator)",
        flush=True,
    )
if _tb_mode in ("device", "both"):
    t0 = time.time()
    tables, valid = comb.build_a_tables_jit(jnp.asarray(a))
    tables.block_until_ready()
    print(
        f"table_build (device, compile+run): {time.time()-t0:.1f} s "
        "(a warm compile cache removes the compile half)",
        flush=True,
    )
tp, vp = os.path.join(TDIR, f"tablesT{V}.npy"), os.path.join(TDIR, f"validT{V}.npy")
if tables is None and os.path.exists(tp) and os.path.exists(vp):
    t0=time.time()
    tables = jnp.asarray(np.load(tp, mmap_mode="r"))
    valid = jnp.asarray(np.load(vp))
    tables.block_until_ready()
    print("tables loaded from disk", round(time.time()-t0,1), "s", flush=True)
elif tables is None:
    t0=time.time()
    tables, valid = comb.build_a_tables_jit(jnp.asarray(a))
    tables.block_until_ready()
    print("tables built", round(time.time()-t0,1), "s", flush=True)
    if os.environ.get("COMBPROF_SAVE") == "1":
        # 2.7 GB device->host fetch, so opt-in
        os.makedirs(TDIR, exist_ok=True)
        np.save(tp, np.asarray(tables))
        np.save(vp, np.asarray(valid))

r_all=np.zeros((V,32),np.uint8); s_all=np.zeros((V,32),np.uint8); dig_all=np.zeros((V,64),np.uint8)
for i,sk in enumerate(keys):
    msg=b"m%d"%i; sig=sk.sign(msg)
    r_all[i]=np.frombuffer(sig[:32],np.uint8); s_all[i]=np.frombuffer(sig[32:],np.uint8)
    dig_all[i]=np.frombuffer(hashlib.sha512(sig[:32]+pubs[i]+msg).digest(),np.uint8)
ra,sa,da = jnp.asarray(r_all), jnp.asarray(s_all), jnp.asarray(dig_all)
bt = comb.get_b_tables()

def timeit(name, f, *args):
    t0=time.perf_counter()
    o = f(*args); jax.tree_util.tree_map(lambda x: x.block_until_ready(), o)
    compile_s = time.perf_counter()-t0
    ts=[]
    for _ in range(5):
        t0=time.perf_counter(); o=f(*args); jax.tree_util.tree_map(lambda x: x.block_until_ready(), o); ts.append(time.perf_counter()-t0)
    print(f"{name}: {1e3*min(ts):.1f} ms   (first {compile_s:.1f}s)", flush=True)

print(
    f"accumulation: tree={comb.tree_enabled()} "
    f"chains={comb.fold_chains(V)} "
    f"dependent_depth={comb.accumulation_depth(V)} "
    f"(sequential chain would be {comb.NPOS_A + comb.NPOS_B + 1})",
    flush=True,
)
timeit(
    "full verify_cached (tree)",
    jax.jit(lambda *x: comb.verify_cached(*x, tree=True)),
    tables, valid, ra, sa, da, bt,
)
timeit(
    "full verify_cached (seq)",
    jax.jit(lambda *x: comb.verify_cached(*x, tree=False)),
    tables, valid, ra, sa, da, bt,
)

# ---- host assembly phase: the staging-slab fill the engine's submit()
# runs (models/comb_verifier._fill_payload) on a commit-shaped batch —
# all V validators signing ~100-byte sign-bytes in row order.  First
# call allocates + writes every column; steady-state calls (same row
# layout) rewrite only R | s | msg.  The ~22 ms round-5 capture is the
# number this phase replaces.
from cometbft_tpu.models import comb_verifier as _cv

items = []
for i, sk in enumerate(keys):
    msg = b"\x08\x02\x10\x01\x18\x05" + i.to_bytes(8, "big") + b"|prof-comb"
    sig = sk.sign(msg)
    items.append((pubs[i], msg, sig))
rows = np.arange(V, dtype=np.int64)
slab = _cv._PayloadSlab(V, _cv._payload_width(items))
t0 = time.perf_counter(); _cv._fill_payload(slab, items, rows)
cold = (time.perf_counter() - t0) * 1e3
ts = []
for _ in range(5):
    t0 = time.perf_counter(); payload_host = _cv._fill_payload(slab, items, rows)
    ts.append((time.perf_counter() - t0) * 1e3)
print(f"assembly_ms (slab fill): {min(ts):.2f} ms   (cold {cold:.2f} ms)", flush=True)

# H2D + dispatch and the single packed result fetch, measured around the
# jitted engine program on the same payload
pl_dev = jnp.asarray(payload_host); pl_dev.block_until_ready()
t0 = time.perf_counter(); pl_dev = jnp.asarray(payload_host); pl_dev.block_until_ready()
print(f"h2d_ms (payload transfer): {(time.perf_counter()-t0)*1e3:.2f} ms", flush=True)
_vc = jax.jit(
    lambda *x: jnp.concatenate(
        [jnp.packbits(comb.verify_cached(*x)), jnp.ones((1,), jnp.uint8)]
    )
)  # the engine's packed [bitmap | all_ok] single-fetch contract
out = _vc(tables, valid, ra, sa, da, bt); out.block_until_ready()
t0 = time.perf_counter(); _ = np.asarray(out)
print(f"fetch_ms (packed result readback): {(time.perf_counter()-t0)*1e3:.2f} ms", flush=True)

# device SHA-512 digest phase (the engine path hashes on device now)
msgs = [b"m%d" % i for i in range(V)]
blocks, active = sha2.pad_messages_sha512([s_all[i].tobytes() for i in range(V)])
timeit("sha512 digests", jax.jit(sha2.sha512_blocks), jnp.asarray(blocks), jnp.asarray(active))

timeit("scalar+nibbles", jax.jit(lambda d: scalar.nibbles_lsb(scalar.reduce_mod_l(scalar.bytes_to_limbs(d, scalar.NL_X)), comb.NPOS_A)), da)
timeit("decompress R", jax.jit(lambda r: E.decompress(r)[0].x), ra)

@jax.jit
def a_loop(tables, dig):
    k_dig = scalar.signed_digits_radix16(scalar.reduce_mod_l(scalar.bytes_to_limbs(dig, scalar.NL_X)), comb.NPOS_A)
    ents = jnp.arange(comb.NENT_A, dtype=jnp.int32)[:, None]
    def a_body(i, acc):
        slab = lax.dynamic_index_in_dim(tables, i, axis=0, keepdims=False)
        d = lax.dynamic_index_in_dim(k_dig, i, axis=0, keepdims=False)
        neg = d < 0
        onehot=(ents == jnp.abs(d)[None,:]).astype(jnp.int32)
        sel=jnp.sum(slab*onehot[:,None,None,:],axis=0)
        return E.add_niels(acc, E.Niels(F.select(neg, sel[1], sel[0]), F.select(neg, sel[0], sel[1]), F.select(neg, -sel[2], sel[2])))
    return lax.fori_loop(0, comb.NPOS_A, a_body, E.identity((dig.shape[0],))).x
timeit("A loop", a_loop, tables, da)

@jax.jit
def b_loop(bt, s):
    s_dig = scalar.bytes_to_limbs(s, comb.NPOS_B)
    ents = jnp.arange(comb.NENT_B, dtype=jnp.int32)[:, None]
    def b_body(i, acc):
        slab = lax.dynamic_index_in_dim(bt, i, axis=0, keepdims=False)
        d = lax.dynamic_index_in_dim(s_dig, i, axis=0, keepdims=False)
        onehot=(ents == d[None,:]).astype(jnp.float32)
        sel=jnp.matmul(slab,onehot,precision=lax.Precision.HIGHEST).astype(jnp.int32)
        return E.add_niels(acc, E.Niels(sel[0:22],sel[22:44],sel[44:66]))
    return lax.fori_loop(0, comb.NPOS_B, b_body, E.identity((s.shape[0],))).x
timeit("B loop", b_loop, bt, sa)

x = jnp.ones((F.NLIMBS, V), jnp.int32)
timeit("1 field mul", jax.jit(F.mul), x, x)
timeit("100 field muls", jax.jit(lambda a,b: lax.fori_loop(0,100,lambda _,v: F.mul(v,b), a)), x, x)
nl = E.Niels(x, x, x)
timeit("1 add_niels", jax.jit(lambda p, a,b,c: E.add_niels(p, E.Niels(a,b,c)).x), E.identity((V,)), x,x,x)
