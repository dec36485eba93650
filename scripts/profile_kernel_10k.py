"""Pure device-side comb kernel time at the flagship 10k shape.

Times _device_verify on DEVICE-RESIDENT inputs (block_until_ready, no
host->device transfer or result fetch inside the timed region) — i.e.
the compute itself, apart from the transfer and fetch terms.  Writes
one JSON line per stage.
"""
import json
import os
import sys
import threading
import time

# Hard self-timeout: an accelerator that hangs blocks PJRT calls in C++
# where Python signal handlers never run; a daemon timer + os._exit is
# the only reliable bail.  Exiting is safe — a hung run is lost either
# way, and a zombie profiler would hold the chip in front of whatever
# runs next.
_DEADLINE_S = int(os.environ.get("KERNEL_PROF_TIMEOUT", "1800"))
_watchdog = threading.Timer(
    _DEADLINE_S,
    lambda: (print(f"TIMEOUT after {_DEADLINE_S}s", flush=True), os._exit(3)),
)
_watchdog.daemon = True
_watchdog.start()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

OUT = os.environ.get("KERNEL_PROF_OUT", "/tmp/kernel_10k.jsonl")


def emit(**kw):
    rec = {"ts": time.time(), **kw}
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def main():
    import jax

    emit(stage="backend", platform=jax.devices()[0].platform)
    from __graft_entry__ import _enable_compile_cache

    _enable_compile_cache()
    import jax.numpy as jnp

    from cometbft_tpu.crypto import ed25519 as host
    from cometbft_tpu.models import comb_verifier as cv

    V = int(os.environ.get("KERNEL_PROF_V", "10000"))
    rng = np.random.default_rng(7)
    keys = [host.PrivKey.from_seed(rng.bytes(32)) for _ in range(V)]
    pubs = [k.pub_key().data for k in keys]
    t0 = time.perf_counter()
    entry = cv.global_cache().ensure(pubs)
    emit(stage="table_build", v=V, s=round(time.perf_counter() - t0, 1))

    bv = cv.CombBatchVerifier(entry)
    for i, sk in enumerate(keys):
        msg = b"\x08\x02\x10\x01\x18\x05" + i.to_bytes(8, "big") + b"|kp"
        bv.add(pubs[i], msg, sk.sign(msg))
    # reuse submit()'s own assembly, then re-run the jitted program on the
    # SAME device arrays to time compute alone
    ticket = bv.submit()
    all_ok, per = bv.collect(ticket)
    assert all_ok and len(per) == V

    # rebuild the device args exactly as submit() does, staged once
    payload = cv.assemble_payload(
        bv._items, np.asarray(bv._rows, np.int64), entry.vpad
    )
    dev_payload = jnp.asarray(payload)
    dev_payload.block_until_ready()

    fn = bv._program(payload.shape[1])
    out = fn(entry.tables, entry.valid, entry.pubs, dev_payload)
    out.block_until_ready()
    ts = []
    for _ in range(10):
        t0 = time.perf_counter()
        out = fn(entry.tables, entry.valid, entry.pubs, dev_payload)
        out.block_until_ready()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    emit(
        stage="kernel_device_resident",
        v=V,
        p50_ms=round(1e3 * ts[len(ts) // 2], 2),
        min_ms=round(1e3 * ts[0], 2),
        max_ms=round(1e3 * ts[-1], 2),
    )
    # the residual end-to-end call on the same process for comparison
    t0 = time.perf_counter()
    ok2, _ = bv.collect(bv.submit())
    emit(
        stage="full_call_same_process",
        ok=bool(ok2),
        ms=round(1e3 * (time.perf_counter() - t0), 2),
    )
    emit(stage="done")


if __name__ == "__main__":
    main()
