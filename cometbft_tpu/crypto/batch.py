"""Batch-verifier factory — the plugin seam of the framework.

Reference: crypto/batch/batch.go:10-27 (CreateBatchVerifier /
SupportsBatchVerifier).  This is the exact point the north star names: the
TPU provider registers here, and types.ValidatorSet.VerifyCommit routes
through it whenever the validator set's key type supports batching.

Backend selection:
  COMETBFT_TPU_CRYPTO_BACKEND = "tpu" | "cpu" | "auto" (default "auto")
"auto" uses the accelerator kernel whenever JAX is importable; "cpu"
forces the sequential host path (the kernel still runs under jit on the
CPU backend in tests).
"""

from __future__ import annotations

from ..models.verifier import BatchVerifier, CpuEd25519BatchVerifier
from ..utils import envknobs
from . import ed25519
from .encoding import BLS_KEY_TYPE

_BATCH_MIN = 2  # below this, single verification is cheaper (validation.go:15)


def backend() -> str:
    return envknobs.get_str(envknobs.CRYPTO_BACKEND)


def supports_batch_verifier(key_type: str) -> bool:
    """ed25519 batches through the comb/plain kernels; bls12_381
    through the aggregate lane (models/bls_verifier — one pairing per
    batch); secp256k1 / secp256k1eth through the batched ECDSA lane
    (models/secp_verifier — Shamir double-scalar kernels + Montgomery
    batch inversion).  The key type comes from the validator set's
    genesis pubkey encoding, constrained by
    ConsensusParams.validator.pub_key_types — that is the whole
    backend-selection story (docs/verify_service.md)."""
    return key_type in (
        ed25519.KEY_TYPE, BLS_KEY_TYPE,
        "secp256k1", "secp256k1eth", "ecrecover",
    )


def comb_min() -> int:
    """Minimum size of a validator set the caller names for the
    device-resident comb-table path (COMETBFT_TPU_COMB_MIN).  Its default
    is COMETBFT_TPU_DEVICE_BATCH_MIN's, 32: below that both device
    verifiers answer from the host anyway, and from there up every set
    binds, the 100-200 validators real chains run included.  A set
    costs 152 KB of device memory a lane (lanes in buckets of 128: 39 MB
    at 175 validators) and a host table build at first sight (about
    10 ms a key); the compiled program is shared by every set of one
    lane bucket (models/comb_verifier).  What a test suite compiles is
    no reason for a production threshold: a test that wants the uncached
    program names no set."""
    return envknobs.get_int(envknobs.COMB_MIN)


def comb_async_min() -> int:
    """Set size above which a missing comb table builds in the
    BACKGROUND while verification proceeds through the uncached kernel —
    a large build must never stall consensus (the reference's
    expanded-key LRU likewise fills lazily, ed25519.go:43,68).  Smaller
    sets build synchronously: their build is fast and callers (and
    tests) get the comb verifier deterministically on first use."""
    return envknobs.get_int(envknobs.COMB_ASYNC_MIN)


def device_capable() -> bool:
    """Whether the accelerator data plane is selectable at all: the
    backend knob allows it AND (in `auto`) JAX is importable.  The
    verify-service clients (verifysvc/) use this to decide between the
    scheduled device path and an inline host check."""
    be = backend()
    if be == "cpu":
        return False
    if be != "tpu":  # "auto": accelerator only when JAX is importable
        try:
            import jax  # noqa: F401
        except ImportError:
            return False
    return True


def create_batch_verifier(
    key_type: str, pubkeys: list[bytes] | None = None, klass=None,
    tenant: str | None = None,
) -> BatchVerifier:
    """(crypto/batch/batch.go:10)  Device-capable backends return a
    verify-service client (verifysvc.ServiceBatchVerifier) bound to the
    caller's priority class (default: consensus) and tenant (default:
    this process's COMETBFT_TPU_VERIFYSVC_TENANT — single-chain callers
    never pass one) — the service owns all batching, scheduling, and
    device dispatch.  When the caller knows the validator set (pubkeys,
    in set order), a set of comb_min() keys or more binds to the
    comb-cached program here, in the caller's thread: tables stay
    device-resident across calls, keyed by the set (the reference's
    expanded-key LRU, ed25519.go:43,68, writ large), and a first-sight
    table build never runs on the shared scheduler thread."""
    if not supports_batch_verifier(key_type):
        raise ValueError(f"no batch verifier for key type {key_type!r}")
    from ..verifysvc.service import remote_plane_configured

    if not device_capable() and not remote_plane_configured():
        if key_type == BLS_KEY_TYPE:
            from ..models.bls_verifier import CpuBlsBatchVerifier

            return CpuBlsBatchVerifier()
        if key_type in ("secp256k1", "secp256k1eth", "ecrecover"):
            from ..models.secp_verifier import CpuSecpBatchVerifier

            return CpuSecpBatchVerifier()
        return CpuEd25519BatchVerifier()
    from ..verifysvc.client import ServiceBatchVerifier, resolve_mode
    from ..verifysvc.service import Klass

    return ServiceBatchVerifier(
        Klass.CONSENSUS if klass is None else klass,
        resolve_mode(pubkeys, key_type=key_type),
        tenant=tenant,
    )
