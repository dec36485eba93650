"""Slope-based micro-benchmarks: vary inner iteration count and diff, so
fixed dispatch overhead cancels out."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from functools import partial

from cometbft_tpu.utils import compilecache

compilecache.enable()

from cometbft_tpu.ops import field as F

N = 16384


def timeit(fn, *args, iters=3):
    out = fn(*args)
    _ = np.asarray(out.ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        _ = np.asarray(out.ravel()[0])
    return (time.perf_counter() - t0) / iters


@jax.jit
def noop(x):
    return x[:1, :1]

x32 = jnp.asarray(np.random.randint(1, 1000, size=(N, 128), dtype=np.int32))
print(f"noop round-trip: {timeit(noop, x32)*1e3:.2f} ms", flush=True)


@partial(jax.jit, static_argnums=1)
def chain_i32(x, n):
    return lax.fori_loop(0, n, lambda _, a: (a * a) & 0xFFFF | 1, x)

t1 = timeit(chain_i32, x32, 256)
t2 = timeit(chain_i32, x32, 4096)
rate = (4096 - 256) * N * 128 / (t2 - t1)
print(f"int32 mul: lo={t1*1e3:.1f} hi={t2*1e3:.1f} ms -> {rate/1e9:.1f} G/s", flush=True)

xf = jnp.asarray(np.random.uniform(1.0, 1.001, size=(N, 128)).astype(np.float32))

@partial(jax.jit, static_argnums=1)
def chain_f32(x, n):
    return lax.fori_loop(0, n, lambda _, a: a * a + 0.25, x)

t1 = timeit(chain_f32, xf, 256)
t2 = timeit(chain_f32, xf, 4096)
rate = (4096 - 256) * N * 128 / (t2 - t1)
print(f"f32 fma: lo={t1*1e3:.1f} hi={t2*1e3:.1f} ms -> {rate/1e9:.1f} G/s", flush=True)

ab = jnp.asarray(np.random.randn(2048, 2048)).astype(jnp.bfloat16)

@partial(jax.jit, static_argnums=1)
def mmb(a, n):
    def body(_, b):
        return (b @ a).astype(jnp.bfloat16) * jnp.bfloat16(1e-3)
    return lax.fori_loop(0, n, body, a)

t1 = timeit(mmb, ab, 4)
t2 = timeit(mmb, ab, 64)
rate = (64 - 4) * 2 * 2048**3 / (t2 - t1)
print(f"bf16 mm 2048: lo={t1*1e3:.1f} hi={t2*1e3:.1f} ms -> {rate/1e12:.1f} TF/s", flush=True)

fx = jnp.asarray(np.random.randint(0, 2000, size=(N, 22), dtype=np.int32))

@partial(jax.jit, static_argnums=1)
def chain_fmul(x, n):
    return lax.fori_loop(0, n, lambda _, a: F.mul(a, a), x)

t1 = timeit(chain_fmul, fx, 64)
t2 = timeit(chain_fmul, fx, 1024)
per = (t2 - t1) / (1024 - 64) / N
print(f"field mul: lo={t1*1e3:.1f} hi={t2*1e3:.1f} ms -> {per*1e9:.2f} ns/row-mul", flush=True)

# Straus window-step cost estimate: 3700 muls/sig target check
print(f"  => 10k sigs x 3700 muls ~= {3700*10000*per*1e3:.0f} ms", flush=True)

# point double and add-niels chain for direct cost
from cometbft_tpu.ops import ed25519 as E

pt = E.identity((N,))

@partial(jax.jit, static_argnums=1)
def chain_dbl(p, n):
    return lax.fori_loop(0, n, lambda _, q: E.double(q), p)

t1 = timeit(lambda p, n: chain_dbl(p, n).x, pt, 32)
t2 = timeit(lambda p, n: chain_dbl(p, n).x, pt, 256)
per = (t2 - t1) / (256 - 32) / N
print(f"point double: lo={t1*1e3:.1f} hi={t2*1e3:.1f} ms -> {per*1e9:.1f} ns/row-double", flush=True)
print(f"  => 256 doubles x 16384 = {256*16384*per*1e3:.0f} ms", flush=True)
