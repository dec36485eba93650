"""Phase profiler for the MODE_SECP CheckTx ingest lane: where does a
batched secp256k1/ECDSA dispatch actually spend its wall time?

Phases (models/secp_verifier.LAST_PHASES, filled per device dispatch):
  hash_ms     — HOST side of hashing: the SHA-256/Keccak-256 digest
                loop on the host-hash path; just the block padding on
                the fused path (digests then ride inside kernel_ms)
  decode_ms   — pubkey decode (field sqrt per compressed key; cached —
                iteration 1 pays the sqrt, steady state hits the cache
                like repeat-sender ingest does)
  assembly_ms — the rest of the host staging loop + limb scatter
  h2d_ms      — jnp.asarray transfers of the packed arrays
  kernel_ms   — jitted program dispatch to blocked result
  fetch_ms    — the one device->host verdict readback

Configs sweep the two static axes of the kernel (the before/after
story of the GLV + hashing-residency PR):
  noglv+host — the PR-15 baseline: Shamir double-scalar walk, digests
               on host
  glv+host   — GLV endomorphism quad-scalar walk, digests on host
  glv+fused  — GLV + on-device hashing (the default production shape)

Each config compiles its own program variant (~minutes cold on the CPU
backend; a warm compile cache removes it), so the default
sweep is opt-down via SECPPROF_CONFIGS.

Env: SECPPROF_N (rows, default 512), SECPPROF_ITERS (timed reps, 5),
SECPPROF_CONFIGS (comma list from the three above), SECPPROF_JSON
(path: also dump the table as JSON).
"""
import json
import os
import statistics
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np  # noqa: E402

N = int(os.environ.get("SECPPROF_N", "512"))
ITERS = int(os.environ.get("SECPPROF_ITERS", "5"))
CONFIGS = [
    c.strip()
    for c in os.environ.get(
        "SECPPROF_CONFIGS", "noglv+host,glv+host,glv+fused"
    ).split(",")
    if c.strip()
]

from cometbft_tpu.crypto import secp256k1 as cosmos  # noqa: E402
from cometbft_tpu.crypto import secp256k1eth as eth  # noqa: E402
from cometbft_tpu.models import secp_verifier as sv  # noqa: E402

# a CheckTx-shaped mixed corpus: all three wire formats interleaved,
# repeat senders (8 keys per type) so the decode cache behaves like
# real ingest
rng = np.random.default_rng(16)
ck = [cosmos.PrivKey.from_seed(rng.bytes(32)) for _ in range(8)]
ek = [eth.PrivKey.from_seed(rng.bytes(32)) for _ in range(8)]
rk = [eth.RecoverPrivKey.from_seed(rng.bytes(32)) for _ in range(8)]
items = []
for i in range(N):
    msg = b"profile tx %d" % i + rng.bytes(24)
    sk = (ck, ek, rk)[i % 3][i // 3 % 8]
    items.append((sk.pub_key().bytes(), msg, sk.sign(msg)))

_KNOBS = {
    "noglv+host": {"COMETBFT_TPU_SECP_GLV": "0",
                   "COMETBFT_TPU_SECP_HASH_DEVICE_MIN": "0"},
    "glv+host": {"COMETBFT_TPU_SECP_GLV": "1",
                 "COMETBFT_TPU_SECP_HASH_DEVICE_MIN": "0"},
    "glv+fused": {"COMETBFT_TPU_SECP_GLV": "1",
                  "COMETBFT_TPU_SECP_HASH_DEVICE_MIN": "1"},
}
PHASE_KEYS = ("hash_ms", "decode_ms", "assembly_ms",
              "h2d_ms", "kernel_ms", "fetch_ms")

report = {"rows": N, "iters": ITERS, "configs": {}}
for cfg in CONFIGS:
    if cfg not in _KNOBS:
        print(f"unknown config {cfg!r}; pick from {sorted(_KNOBS)}")
        raise SystemExit(2)
    os.environ.update(_KNOBS[cfg])
    sv.reset_caches()
    t0 = time.perf_counter()
    _, first = sv._verify_items(items, use_device=True)
    warm_s = time.perf_counter() - t0
    assert all(first), "profiler corpus must verify clean"
    cold = dict(sv.LAST_PHASES)
    samples = {k: [] for k in PHASE_KEYS}
    walls = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        sv._verify_items(items, use_device=True)
        walls.append((time.perf_counter() - t0) * 1e3)
        for k in PHASE_KEYS:
            samples[k].append(sv.LAST_PHASES.get(k, 0.0))
    wall = statistics.median(walls)
    row = {"wall_ms": round(wall, 3), "first_call_s": round(warm_s, 1)}
    print(f"\n{cfg}  ({N} rows, wall p50 {wall:.1f} ms, "
          f"first call {warm_s:.1f} s incl. compile)")
    for k in PHASE_KEYS:
        p50 = statistics.median(samples[k])
        row[k] = {
            "p50_ms": round(p50, 3),
            "share_of_wall": round(p50 / wall, 3) if wall else 0.0,
        }
        print(f"  {k:12s} {p50:10.3f} ms  "
              f"({row[k]['share_of_wall']:.1%} of wall)")
    print(f"  decode_ms cold (cache-miss sqrt): "
          f"{cold.get('decode_ms', 0.0):.3f} ms")
    row["decode_ms_cold"] = round(cold.get("decode_ms", 0.0), 3)
    report["configs"][cfg] = row

if os.environ.get("SECPPROF_JSON"):
    with open(os.environ["SECPPROF_JSON"], "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"\nwrote {os.environ['SECPPROF_JSON']}")
