"""Batch signature verifiers: the TPU data plane behind the crypto seam.

Implements the BatchVerifier contract of the reference
(crypto/crypto.go:47-55): add(pubkey, msg, sig) accumulates work, verify()
returns (all_valid, per_signature_validity) — per-signature blame is what
lets commit verification tally honest voting power even when some
signatures are bad (types/validation.go:384-399).

The TPU provider assembles the batch on host (numpy), pads to a
power-of-two bucket so XLA compiles a handful of shapes, and runs the
fully fused kernel from ops/ed25519.verify_batch.  A CPU provider with
identical semantics backs tests and TPU-less hosts.

These classes are the DATA PLANE only: production consumers never
submit to them directly — all scheduling, batching, and dispatch goes
through the unified verify service (verifysvc/service.py), whose
scheduler constructs these verifiers per dispatched batch
(docs/verify_service.md).
"""

from __future__ import annotations

import threading
from typing import Protocol

import numpy as np

from ..crypto import ed25519 as host_ed25519
from ..utils import tracing
from ..utils.metrics import hub as _metrics_hub

# compiled uncached programs, one per input-shape set (bucket x SHA blocks)
_VERIFY_PROGRAMS: dict[tuple, object] = {}
_VERIFY_PROGRAMS_MTX = threading.Lock()
# compiled single-device comb programs, one per (lanes, payload width,
# tree flag): tables, validity mask and keys are arguments, so every
# cache entry of that shape runs the same executable
# (models/comb_verifier.CombBatchVerifier._program)
_COMB_PROGRAMS: dict[tuple, object] = {}
_COMB_PROGRAMS_MTX = threading.Lock()


def program_for(cache: dict, mtx, key, lower, on_compile=None):
    """``cache[key]``: a program compiled ahead of its first call, so
    that the compile is a step of its own and not part of a dispatch.

    A first-shape compile takes minutes on a cold cache (trace, lower,
    XLA).  It is legitimate work, not a hung device: whoever runs a clock
    on the batch (verifysvc/service) hands in ``on_compile`` and is told
    when the compile starts (True) and ends (False), and stops its clock
    for exactly that long.  Assembly, transfers and the dispatch itself
    stay on the clock.  ``lower`` returns the jax ``Lowered`` to compile;
    ``mtx`` makes two first users share one compile."""
    prog = cache.get(key)
    if prog is None:
        with mtx:
            prog = cache.get(key)
            if prog is None:
                if on_compile is not None:
                    on_compile(True)
                try:
                    prog = cache[key] = lower().compile()
                finally:
                    if on_compile is not None:
                        on_compile(False)
    return prog


class BatchVerifier(Protocol):
    def add(self, pub_key: bytes, msg: bytes, sig: bytes) -> None: ...

    def verify(self) -> tuple[bool, list[bool]]: ...


class CpuEd25519BatchVerifier:
    """Sequential ZIP-215 verification (host fallback)."""

    def __init__(self) -> None:
        self._items: list[tuple[bytes, bytes, bytes]] = []

    def __len__(self) -> int:
        return len(self._items)

    def add(self, pub_key: bytes, msg: bytes, sig: bytes) -> None:
        if len(pub_key) != 32 or len(sig) != 64:
            raise ValueError("malformed ed25519 pubkey or signature")
        self._items.append((pub_key, msg, sig))

    def verify(self) -> tuple[bool, list[bool]]:
        res = [
            host_ed25519.verify_signature(p, m, s) for (p, m, s) in self._items
        ]
        return all(res) and bool(res), res


def _next_bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


def _device_batch_min() -> int:
    """Batch width below which the dispatch overhead (and, on first use
    of a bucket shape, its compile) dwarfs the arithmetic and the host
    verifies instead (COMETBFT_TPU_DEVICE_BATCH_MIN, default 32)."""
    from ..utils import envknobs

    return envknobs.get_int(envknobs.DEVICE_BATCH_MIN)


def host_route(items, lane: str) -> tuple[bool, list[bool]]:
    """A batch under DEVICE_BATCH_MIN, verified on the host by a device
    verifier (``lane``: "uncached" or "comb").  Counted and spanned: a
    healthy chip's cells must read zero here, and /metrics says so
    without the span ring."""
    _metrics_hub().verify_host_route.inc(lane=lane, reason="below_batch_min")
    cpu = CpuEd25519BatchVerifier()
    cpu._items = items
    with tracing.span("verify.host_route"):
        return cpu.verify()


class TpuEd25519BatchVerifier:
    """Batched ZIP-215 verification on the default JAX device.

    One compiled program per (bucket, nblocks) shape; buckets are powers
    of two so a 10k-validator commit and a 150-validator light-client
    check each compile once and are then cache hits (the TPU analogue of
    the reference's expanded-key LRU, ed25519.go:43,68).
    """

    # told when this batch's program starts and stops compiling
    # (program_for); the verify service sets it per dispatched batch
    on_compile = None

    def __init__(self) -> None:
        self._items: list[tuple[bytes, bytes, bytes]] = []

    def __len__(self) -> int:
        return len(self._items)

    def add(self, pub_key: bytes, msg: bytes, sig: bytes) -> None:
        if len(pub_key) != 32 or len(sig) != 64:
            raise ValueError("malformed ed25519 pubkey or signature")
        self._items.append((pub_key, msg, sig))

    def _program(self, arrays):
        """The program for these input shapes, compiled at first use
        (the power-of-two bucketing keeps the shape set small).  The jit
        site is registered in kernel_manifest.JIT_SITES (manifest kernel
        ``ed25519_verify_batch``)."""
        import jax

        def lower():
            from ..ops import ed25519 as E

            return jax.jit(E.verify_batch).lower(
                *(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in arrays)
            )

        return program_for(
            _VERIFY_PROGRAMS, _VERIFY_PROGRAMS_MTX,
            tuple((x.shape, x.dtype.str) for x in arrays),
            lower, self.on_compile,
        )

    def verify(self) -> tuple[bool, list[bool]]:
        return self.collect(self.submit())

    def submit(self):
        """Dispatch without waiting — the same async seam the comb-cached
        verifier exposes (models/comb_verifier.CombBatchVerifier.submit),
        so the blocksync verify-ahead pipeline can overlap host work with
        device execution even while comb tables are still warming (the
        async-build window) or for foreign-key sets.  Returns an opaque
        ticket for collect()."""
        n = len(self._items)
        if n == 0:
            return ("sync", (False, []))
        _metrics_hub().verify_batch_width.observe(float(n))
        # The hot configs (150-val light blocks, 10k-val commits) always
        # take the device path.
        if n < _device_batch_min():
            return ("sync", host_route(self._items, "uncached"))
        return ("dev", (self._submit_device(n), n))

    def collect(self, ticket) -> tuple[bool, list[bool]]:
        kind, payload = ticket
        if kind == "sync":
            return payload
        out, n = payload
        with tracing.phase("verify.device_wait", "device_wait"):
            ok = np.asarray(out)[:n]  # blocks until the device result lands
        res = [bool(x) for x in ok]
        return all(res), res

    def _submit_device(self, n: int):
        import jax.numpy as jnp
        from ..ops import sha2

        with tracing.phase("verify.uncached_assemble", "assembly"):
            bucket = _next_bucket(n)
            a = np.zeros((bucket, 32), dtype=np.uint8)
            r = np.zeros((bucket, 32), dtype=np.uint8)
            s = np.zeros((bucket, 32), dtype=np.uint8)
            hashed = []
            for i, (pub, msg, sig) in enumerate(self._items):
                a[i] = np.frombuffer(pub, dtype=np.uint8)
                r[i] = np.frombuffer(sig[:32], dtype=np.uint8)
                s[i] = np.frombuffer(sig[32:], dtype=np.uint8)
                hashed.append(sig[:32] + pub + msg)
            # Pad rows repeat row 0 so padded lanes do real-but-ignored work.
            for i in range(n, bucket):
                a[i], r[i], s[i] = a[0], r[0], s[0]
                hashed.append(hashed[0])
            blocks, active = sha2.pad_messages_sha512(hashed)
        arrays = (a, r, s, blocks, active)
        fn = self._program(arrays)  # a new bucket shape compiles here
        # device dispatch is asynchronous: the returned array is a future
        with tracing.phase("verify.h2d_dispatch", "h2d_dispatch"):
            out = fn(*(jnp.asarray(x) for x in arrays))
        return out
