"""The kernel manifest: every jitted entry point of the TPU verify
plane, declared once.

This file is the single source of truth three consumers share:

* ``analysis/kernelcheck.py`` abstract-interprets each declared kernel
  (``jax.make_jaxpr`` under ``JAX_PLATFORMS=cpu``) and enforces the
  numeric contract — dtype closure, jaxpr purity, primitive-budget /
  fingerprint drift (``analysis/kernel_fingerprints.json``).
* the ``untracked-jit`` AST check fails any ``jax.jit`` site in the
  kernel plane that is not registered in :data:`JIT_SITES` — a new jit
  entry point cannot land without a manifest row (and therefore without
  a traced fingerprint).
* the ``weak-type-literal`` / ``jax-purity`` checks seed their traced
  closures from :func:`traced_roots` — functions jitted from *another*
  module (``ops/sha2.sha512_blocks`` is jitted via ``models/``) are
  invisible to a per-module jit-root scan, but not to the manifest.

Deliberately stdlib-only (no jax, no numpy): the AST linter half must
run anywhere the stdlib does.  Shapes here are the CANONICAL trace
shapes — small enough to trace in milliseconds, shaped exactly like
production (batch lane minor, limbs on sublanes) so the traced program
is the production program at a smaller lane count.
"""

from __future__ import annotations

from dataclasses import dataclass

# Canonical trace sizes.  V = validator lanes for the comb path, N =
# signature batch for the uncached path.  Small on purpose: jaxpr shape
# and primitive mix do not depend on the lane count, only trace time
# does.
V = 4  # comb-path validator lanes
N = 8  # uncached-path signature lanes
MAXM = 32  # payload message bucket (models/comb_verifier._bucket_mlen floor)
PAYLOAD_W = 68 + MAXM  # R(32) | s(32) | mlen(3) | live(1) | msg


@dataclass(frozen=True)
class Arg:
    """One traced input/output leaf: shape + dtype name."""

    shape: tuple[int, ...]
    dtype: str


def u8(*shape: int) -> Arg:
    return Arg(shape, "uint8")


def i32(*shape: int) -> Arg:
    return Arg(shape, "int32")


def f32(*shape: int) -> Arg:
    return Arg(shape, "float32")


def boolean(*shape: int) -> Arg:
    return Arg(shape, "bool")


@dataclass(frozen=True)
class Kernel:
    """One jitted entry point: where it lives, how to trace it, what it
    must produce.

    fn            : "package.module:function".  With needs_mesh, the
                    function is a FACTORY taking the mesh
                    and returning the jitted callable (the
                    parallel/verify.py pattern).
    args          : canonical input leaves, in call order.
    out           : expected output leaves (flattened pytree order) —
                    checked against the traced out_avals, so an output
                    shape/dtype drift fails before any fingerprint
                    comparison.
    static_kwargs : Python-level keyword arguments bound before tracing
                    (trace-time constants: the secp glv flag, churn V);
                    with needs_mesh they are bound onto the factory call.
    needs_mesh    : build a 1-device CPU mesh and call fn as a factory.
    max_eqns      : compile-cost budget — hard ceiling on the traced
                    jaxpr's total equation count (nested bodies
                    included).  EVERY production kernel must declare a
                    positive budget (kernelcheck fails the manifest
                    otherwise): the old ``comb_build_a_tables`` rode
                    unbudgeted past the PR-6 gate straight into a 2m34s
                    XLA compile (on XLA:CPU); that grandfather clause
                    is gone.  Budgets are measured counts plus ~30%
                    headroom — an unrolled-loop blowup fails in
                    milliseconds, an innocuous +1 eqn does not.
    arg_ranges    : declared input value ranges for the range abstract
                    interpreter (analysis/rangecheck.py), one entry per
                    arg: ``(lo, hi)`` inclusive, or None for the full
                    dtype range.  These are the ASSUMPTIONS the range
                    certificates are proved under — callers owe them
                    (canonical limb digits [0, 2^12), flags {0, 1},
                    active block counts).  None for the whole tuple =
                    every arg at its dtype range.
    out_ranges    : declared output ranges, same shape as ``out`` —
                    the checker PROVES these hold (canonical digits out
                    means limb-equality-is-value-equality downstream).
                    None entries are unchecked.
    loop_invariants : assume-guarantee bounds for scan carries where
                    widening is too coarse: ``(scan_ordinal,
                    carry_ordinal, lo, hi)`` tuples, ordinals in
                    interpretation (pre-order) encounter order.  The
                    checker verifies each declared bound covers the
                    initial carry and is inductive before using it.
    """

    name: str
    fn: str
    args: tuple[Arg, ...]
    out: tuple[Arg, ...]
    static_kwargs: tuple[tuple[str, object], ...] = ()
    needs_mesh: bool = False
    max_eqns: int = 0  # fixture rows may omit; production rows may not
    arg_ranges: tuple | None = None
    out_ranges: tuple | None = None
    loop_invariants: tuple = ()


_TABLES = i32(64, 9, 3, 22, V)  # ops/comb.py layout: validator axis minor
_B_TABLES = f32(22, 66, 4096)  # shared radix-4096 base-point comb

# Declared value ranges (analysis/rangecheck.py input specs).
DIGITS = (0, 4095)  # canonical 12-bit limb digit, ops/field.py freeze()
FLAG = (0, 1)  # bit-packed / boolean-as-int payload field


# ops/field.py's bound contract, one (lo, hi) per limb row: limb 0 takes
# the top carry's fold and is bounded wider than limbs 1..21.  MULIN is
# what mul/square accept.  CARRIED is what the interval proof certifies
# of their output: a carried limb in [-2048, 2047] plus its carry-in,
# and above limb 0 the fold's (19q mod 8) * 2^9 <= 3584
MULIN = ((-14336, 14336),) + ((-8204, 8204),) * 21
CARRIED = ((-2048, 5631),) + ((-2051, 2051),) * 21


KERNELS: tuple[Kernel, ...] = (
    # ---- ops/comb.py — the validator-set fast path
    Kernel(
        # scan-rolled since PR 11 (measured 25,359 eqns; the unrolled
        # pre-rework build was ~84k and compiled for 2m34s) — this budget
        # is the deleted grandfather clause
        name="comb_build_a_tables",
        fn="cometbft_tpu.ops.comb:build_a_tables",
        args=(u8(V, 32),),
        out=(_TABLES, boolean(V)),
        max_eqns=32_000,
        out_ranges=(DIGITS, None),
    ),
    Kernel(
        name="comb_verify_cached",
        fn="cometbft_tpu.ops.comb:verify_cached",
        args=(_TABLES, boolean(V), u8(V, 32), u8(V, 32), u8(V, 64), _B_TABLES),
        out=(boolean(V),),
        # the accumulation: K parallel add_niels chains in one rolled
        # scan (K = 8 at the 4-lane trace)
        max_eqns=39_000,  # measured 29,892
        arg_ranges=(DIGITS, None, None, None, None, DIGITS),
    ),
    # ---- ops/field.py — the exponentiation chains in row form: the body
    # of the on-chip kernel that F.pow_p58 / F.invert run as on a TPU
    # (ops/field._on_chip), traced here as the plain JAX it is.  No
    # program of its own: it rides inside every program that
    # decompresses a point or normalizes a table.  Trace shape: rows of
    # (1, V) lanes; the chip's are (S, 128).
    Kernel(
        name="field_pow_p58_rows",
        fn="cometbft_tpu.ops.field:pow_p58_rows",
        args=(i32(22, 1, V),),
        out=(i32(22, 1, V),),
        max_eqns=4_000,  # measured 2,957: one squaring and one multiplication
        # body (every limb row its own equation), jitted and called 20 times
        arg_ranges=(MULIN,),
        out_ranges=(CARRIED,),
    ),
    Kernel(
        name="field_invert_rows",
        fn="cometbft_tpu.ops.field:invert_rows",
        args=(i32(22, 1, V),),
        out=(i32(22, 1, V),),
        max_eqns=4_000,  # measured 2,958
        arg_ranges=(MULIN,),
        out_ranges=(CARRIED,),
    ),
    # ---- ops/ed25519.py — the uncached Straus kernel
    Kernel(
        name="ed25519_verify_batch",
        fn="cometbft_tpu.ops.ed25519:verify_batch",
        args=(u8(N, 32), u8(N, 32), u8(N, 32), u8(N, 2, 128), i32(N)),
        out=(boolean(N),),
        max_eqns=100_000,  # measured 76,880
        arg_ranges=(None, None, None, None, (0, 2)),
    ),
    # ---- ops/sha2.py — challenge hashing + device payload assembly
    Kernel(
        name="sha256_blocks",
        fn="cometbft_tpu.ops.sha2:sha256_blocks",
        args=(u8(N, 2, 64), i32(N)),
        out=(u8(N, 32),),
        max_eqns=1_000,  # measured 153
        arg_ranges=(None, (0, 2)),
    ),
    Kernel(
        name="sha512_blocks",
        fn="cometbft_tpu.ops.sha2:sha512_blocks",
        args=(u8(N, 2, 128), i32(N)),
        out=(u8(N, 64),),
        max_eqns=1_000,  # measured 376
        arg_ranges=(None, (0, 2)),
    ),
    Kernel(
        name="sha2_parse_verify_payload",
        fn="cometbft_tpu.ops.sha2:parse_verify_payload",
        args=(u8(N, PAYLOAD_W), u8(N, 32)),
        out=(u8(N, 32), u8(N, 32), u8(N, 1, 128), i32(N), boolean(N)),
        max_eqns=500,  # measured 79
    ),
    # ---- ops/merkle.py — the block-hash pass
    Kernel(
        name="merkle_root_from_leaves",
        fn="cometbft_tpu.ops.merkle:root_from_leaves",
        args=(u8(N, 1, 64), i32(N)),
        out=(u8(32),),
        max_eqns=2_000,  # measured 628
        arg_ranges=(None, (0, 1)),
    ),
    # the proof-serving plane: ONE dispatch retains every interior level
    # and one-hot-gathers K audit paths.  Sibling positions are computed
    # on HOST (crypto/merkle.proof_plan) so the traced program carries no
    # data-dependent control flow and no xor/shift index arithmetic —
    # the gathers are MXU matmuls over {0,1} masks (exact in f32).
    # Trace shape: n=8 leaves (depth 3), K=4 queries.
    Kernel(
        name="merkle_proofs_from_leaves",
        fn="cometbft_tpu.ops.merkle:proofs_from_leaves",
        args=(u8(N, 1, 64), i32(N), i32(4), i32(4, 3)),
        out=(u8(32), u8(4, 32), u8(4, 3, 32)),
        max_eqns=1_500,  # measured 990
        # indices are valid leaf positions; sib_pos carries -1 as the
        # "no aunt at this level" sentinel (promoted odd trailing node)
        arg_ranges=(None, (0, 1), (0, N - 1), (-1, N - 1)),
    ),
    # the multiproof shape: M deduplicated nodes gathered from the flat
    # level concatenation (n + ceil(n/2) + ... + 1 = 15 nodes at n=8);
    # shared aunts appear once however many queries need them.
    Kernel(
        name="merkle_multiproof_from_leaves",
        fn="cometbft_tpu.ops.merkle:multiproof_from_leaves",
        args=(u8(N, 1, 64), i32(N), i32(6)),
        out=(u8(32), u8(6, 32)),
        max_eqns=1_500,  # measured 951
        arg_ranges=(None, (0, 1), (0, 14)),
    ),
    # ---- ops/bls381.py — the FastAggregateVerify data plane: batched
    # KeyValidate (on-curve + subgroup) and the tree-reduced G1 pubkey
    # sum; Miller loop + final exponentiation stay on host
    # (crypto/bls12381), exactly as the reference keeps them in blst
    Kernel(
        name="bls381_aggregate_g1",
        fn="cometbft_tpu.ops.bls381:aggregate_g1",
        args=(i32(N, 32), i32(N, 32), i32(N, 32)),
        out=(i32(32), i32(32), i32(32)),
        max_eqns=18_000,  # measured 12,966
        arg_ranges=(DIGITS, DIGITS, DIGITS),
        out_ranges=(DIGITS, DIGITS, DIGITS),
    ),
    Kernel(
        # subgroup check = [r]P via lax.scan over the 255 order bits: the
        # jaxpr is O(1) in the bit count (one double+add body), so the
        # budget is small despite the 255-step runtime chain
        name="bls381_validate_g1",
        fn="cometbft_tpu.ops.bls381:validate_g1",
        args=(i32(N, 32), i32(N, 32), boolean(N)),
        out=(boolean(N),),
        max_eqns=8_500,  # measured 6,474
        arg_ranges=(DIGITS, DIGITS, None),
    ),
    Kernel(
        # validation + tree-reduced aggregation fused into ONE dispatch —
        # the aggregate-commit hot path (one device call per commit)
        name="bls381_validate_aggregate_g1",
        fn="cometbft_tpu.ops.bls381:validate_aggregate_g1",
        args=(i32(N, 32), i32(N, 32), boolean(N)),
        out=(boolean(N), i32(32), i32(32), i32(32)),
        max_eqns=26_000,  # measured 19,445
        arg_ranges=(DIGITS, DIGITS, None),
        out_ranges=(None, DIGITS, DIGITS, DIGITS),
    ),
    # ---- ops/secp256k1.py — the batched ECDSA lane (MODE_SECP):
    # range/low-s validation, Montgomery batch inversion (s^-1 mod n and
    # the affine z^-1 mod p, one Fermat chain each), the scalar walk
    # u1*G + u2*Q, and the cosmos/eth/ecrecover verdicts — ONE fused
    # program.  The G window table is host-precomputed and
    # device_put-resident (PR-11 pattern: never a table-build compile),
    # passed as the last tensor argument.  TWO static axes, each the
    # default-and-witness pattern: ``glv`` selects the GLV endomorphism
    # quad-scalar walk over 33 windows (True, the default) vs the plain
    # 66-window Shamir chain (False, the bit-exactness witness —
    # COMETBFT_TPU_SECP_GLV=0), and ``recover`` adds the ecrecover
    # R-lift (sqrt chain) + recovered-address Keccak, traced only when
    # a batch actually carries ecrecover rows.  All four combinations
    # are declared so none can drift unfingerprinted.
    Kernel(
        name="secp256k1_verify_batch",
        fn="cometbft_tpu.ops.secp256k1:verify_batch",
        args=(
            i32(N, 22), i32(N, 22), boolean(N),  # pubkey x, y, decode-ok
            i32(N, 22), i32(N, 22), i32(N, 22),  # e, r, s (raw 256-bit)
            boolean(N), i32(N),  # eth-row flag, recovery id
            boolean(N), u8(N, 20),  # ecrecover-row flag, sender address
            i32(16, 66),  # resident G window table (flat Jacobian rows)
        ),
        out=(boolean(N),),
        static_kwargs=(("glv", True), ("recover", False)),
        max_eqns=28_000,  # measured 21,248
        arg_ranges=(DIGITS, DIGITS, None, DIGITS, DIGITS, DIGITS, None, FLAG, None, None, DIGITS),
    ),
    Kernel(
        name="secp256k1_verify_batch_recover",
        fn="cometbft_tpu.ops.secp256k1:verify_batch",
        args=(
            i32(N, 22), i32(N, 22), boolean(N),
            i32(N, 22), i32(N, 22), i32(N, 22),
            boolean(N), i32(N), boolean(N), u8(N, 20),
            i32(16, 66),
        ),
        out=(boolean(N),),
        static_kwargs=(("glv", True), ("recover", True)),
        max_eqns=29_500,  # measured 22,694
        arg_ranges=(DIGITS, DIGITS, None, DIGITS, DIGITS, DIGITS, None, FLAG, None, None, DIGITS),
    ),
    Kernel(
        name="secp256k1_verify_batch_noglv",
        fn="cometbft_tpu.ops.secp256k1:verify_batch",
        args=(
            i32(N, 22), i32(N, 22), boolean(N),
            i32(N, 22), i32(N, 22), i32(N, 22),
            boolean(N), i32(N), boolean(N), u8(N, 20),
            i32(16, 66),
        ),
        out=(boolean(N),),
        static_kwargs=(("glv", False), ("recover", False)),
        max_eqns=18_000,  # measured 13,688 (the pre-GLV program, unchanged)
        arg_ranges=(DIGITS, DIGITS, None, DIGITS, DIGITS, DIGITS, None, FLAG, None, None, DIGITS),
    ),
    Kernel(
        name="secp256k1_verify_batch_noglv_recover",
        fn="cometbft_tpu.ops.secp256k1:verify_batch",
        args=(
            i32(N, 22), i32(N, 22), boolean(N),
            i32(N, 22), i32(N, 22), i32(N, 22),
            boolean(N), i32(N), boolean(N), u8(N, 20),
            i32(16, 66),
        ),
        out=(boolean(N),),
        static_kwargs=(("glv", False), ("recover", True)),
        max_eqns=20_000,  # measured 15,134
        arg_ranges=(DIGITS, DIGITS, None, DIGITS, DIGITS, DIGITS, None, FLAG, None, None, DIGITS),
    ),
    # the fused hash->verify program: padded message bytes in, verdicts
    # out — SHA-256 (cosmos) and Keccak-256 (eth/ecrecover) digests
    # computed on device and multiplexed per row, then the verify_batch
    # body.  Trace shape = the CheckTx envelope bucket
    # (COMETBFT_TPU_SECP_HASH_MAX_LEN=119: 2 SHA blocks, 1 Keccak block).
    Kernel(
        name="secp256k1_hash_verify",
        fn="cometbft_tpu.ops.secp256k1:hash_verify_batch",
        args=(
            u8(N, 2, 64), i32(N),  # SHA-256-padded blocks + active
            u8(N, 1, 136), i32(N),  # Keccak-padded blocks + active
            i32(N, 22), i32(N, 22), boolean(N),  # pubkey x, y, decode-ok
            i32(N, 22), i32(N, 22),  # r, s
            boolean(N), i32(N), boolean(N), u8(N, 20),
            i32(16, 66),
        ),
        out=(boolean(N),),
        static_kwargs=(("glv", True), ("recover", False)),
        max_eqns=29_000,  # measured 22,111
        arg_ranges=(None, (0, 2), None, FLAG, DIGITS, DIGITS, None, DIGITS, DIGITS, None, FLAG, None, None, DIGITS),
    ),
    Kernel(
        name="secp256k1_hash_verify_recover",
        fn="cometbft_tpu.ops.secp256k1:hash_verify_batch",
        args=(
            u8(N, 2, 64), i32(N),
            u8(N, 1, 136), i32(N),
            i32(N, 22), i32(N, 22), boolean(N),
            i32(N, 22), i32(N, 22),
            boolean(N), i32(N), boolean(N), u8(N, 20),
            i32(16, 66),
        ),
        out=(boolean(N),),
        static_kwargs=(("glv", True), ("recover", True)),
        max_eqns=30_500,  # measured 23,557
        arg_ranges=(None, (0, 2), None, FLAG, DIGITS, DIGITS, None, DIGITS, DIGITS, None, FLAG, None, None, DIGITS),
    ),
    # ---- ops/keccak.py — batched Keccak-256 (the Ethereum 0x01-padded
    # variant): (hi, lo) uint32 lane halves, 24 rounds as ONE fori_loop
    # body, rho/pi statically unrolled — the hashing half the fused secp
    # program inlines, also dispatched standalone via keccak256_device.
    Kernel(
        name="keccak256_blocks",
        fn="cometbft_tpu.ops.keccak:keccak256_blocks",
        args=(u8(N, 1, 136), i32(N)),
        out=(u8(N, 32),),
        max_eqns=700,  # measured 577 (fori-rolled: O(1) in round count)
        arg_ranges=(None, (0, 1)),
    ),
    # ---- models/comb_verifier.py — cache assembly + the device program
    Kernel(
        name="comb_assemble_churn",
        fn="cometbft_tpu.models.comb_verifier:_assemble_churn",
        args=(
            _TABLES, boolean(V),
            i32(64, 9, 3, 22, 2), boolean(2),  # freshly built bucket (2 keys)
            i32(2), i32(2), i32(2),  # new_rows, base_rows, fresh_rows
        ),
        out=(_TABLES, boolean(V)),
        static_kwargs=(("V", V),),
        max_eqns=500,  # measured 32
        arg_ranges=(DIGITS, None, DIGITS, None, (0, V - 1), (0, V - 1),
                    (0, V - 1)),
        out_ranges=(DIGITS, None),
    ),
    Kernel(
        name="comb_device_verify",
        fn="cometbft_tpu.models.comb_verifier:_device_verify",
        args=(_TABLES, boolean(V), u8(V, 32), u8(V, PAYLOAD_W)),
        out=(u8(2),),  # packbits(V=4 lanes) -> 1 byte, + the all-ok byte
        max_eqns=39_500,  # measured 30,334
        arg_ranges=(DIGITS, None, None, None),
    ),
    # ---- parallel/verify.py — the mesh-sharded programs (1-device CPU
    # mesh for the trace; the collective mix is what the fingerprint pins)
    Kernel(
        name="sharded_verify_batch",
        fn="cometbft_tpu.parallel.verify:_verify_fn",
        args=(u8(N, 32), u8(N, 32), u8(N, 32), u8(N, 2, 128), i32(N)),
        out=(boolean(), boolean(N)),
        needs_mesh=True,
        max_eqns=100_000,  # measured 76,888
        arg_ranges=(None, None, None, None, (0, 2)),
    ),
    Kernel(
        name="sharded_verify_cached",
        fn="cometbft_tpu.parallel.verify:_comb_verify_fn",
        args=(_TABLES, boolean(V), u8(V, 32), u8(V, PAYLOAD_W)),
        out=(u8(2),),
        needs_mesh=True,
        max_eqns=39_500,  # measured 30,341
        arg_ranges=(DIGITS, None, None, None),
    ),
    Kernel(
        name="sharded_merkle_root",
        fn="cometbft_tpu.parallel.verify:_merkle_fn",
        args=(u8(N, 1, 64), i32(N)),
        out=(u8(32),),
        needs_mesh=True,
        max_eqns=2_000,  # measured 633
        arg_ranges=(None, (0, 1)),
    ),
    Kernel(
        # query axis sharded, tree replicated: every device holds the
        # whole (small) tree and answers its own K/devices queries with
        # ZERO collectives — the proof fan-out scaling shape
        name="sharded_merkle_proofs",
        fn="cometbft_tpu.parallel.verify:_merkle_proofs_fn",
        args=(u8(N, 1, 64), i32(N), i32(4), i32(4, 3)),
        out=(u8(32), u8(4, 32), u8(4, 3, 32)),
        needs_mesh=True,
        max_eqns=1_500,  # measured 995
        arg_ranges=(None, (0, 1), (0, N - 1), (-1, N - 1)),
    ),
)


# --------------------------------------------------------------- jit sites
#
# Every ``jax.jit`` call/decorator site in the kernel plane (ops/,
# parallel/, models/, crypto/), keyed "path::target" where target is the
# jitted function's name (or the enclosing factory for composed sites
# like ``jax.jit(shard_map(local))``).  The value names the manifest
# kernel whose trace covers the site.  The ``untracked-jit`` check fails
# any site missing here; kernelcheck fails any value naming no kernel.

JIT_SITES: dict[str, str] = {
    # the row form's two bodies, jitted so that a chain traces each once
    # (calls inside the on-chip kernel's body, not programs of their own)
    "cometbft_tpu/ops/field.py::_rows_square": "field_pow_p58_rows",
    "cometbft_tpu/ops/field.py::_rows_mul": "field_pow_p58_rows",
    "cometbft_tpu/ops/comb.py::build_a_tables": "comb_build_a_tables",
    "cometbft_tpu/ops/bls381.py::aggregate_g1": "bls381_aggregate_g1",
    "cometbft_tpu/ops/bls381.py::validate_g1": "bls381_validate_g1",
    "cometbft_tpu/ops/bls381.py::validate_aggregate_g1": (
        "bls381_validate_aggregate_g1"
    ),
    "cometbft_tpu/ops/secp256k1.py::verify_batch": "secp256k1_verify_batch",
    "cometbft_tpu/ops/secp256k1.py::hash_verify_batch": "secp256k1_hash_verify",
    "cometbft_tpu/ops/keccak.py::keccak256_blocks": "keccak256_blocks",
    # models/verifier.py jits ops/ed25519.verify_batch (the uncached path)
    "cometbft_tpu/models/verifier.py::verify_batch": "ed25519_verify_batch",
    "cometbft_tpu/models/comb_verifier.py::_assemble_churn": "comb_assemble_churn",
    "cometbft_tpu/models/comb_verifier.py::_device_verify": "comb_device_verify",
    # parallel factories: jax.jit(shard_map(local)) — registered under the
    # enclosing factory name, traced through a 1-device mesh
    "cometbft_tpu/parallel/verify.py::_verify_fn": "sharded_verify_batch",
    "cometbft_tpu/parallel/verify.py::_comb_verify_fn": "sharded_verify_cached",
    "cometbft_tpu/parallel/verify.py::_merkle_fn": "sharded_merkle_root",
    "cometbft_tpu/parallel/verify.py::_merkle_proofs_fn": "sharded_merkle_proofs",
    # crypto/merkle.py jits ops/merkle.root_from_leaves for host callers
    "cometbft_tpu/crypto/merkle.py::root_from_leaves": "merkle_root_from_leaves",
    # crypto/merkle.py jits the proof kernels for the proof-serving plane
    "cometbft_tpu/crypto/merkle.py::proofs_from_leaves": (
        "merkle_proofs_from_leaves"
    ),
    "cometbft_tpu/crypto/merkle.py::multiproof_from_leaves": (
        "merkle_multiproof_from_leaves"
    ),
}


# ------------------------------------------------------ collect boundaries
#
# Functions in ops//parallel/ that are DECLARED host<->device collect
# points: the documented places where a device value is fetched to host
# (np.asarray on a device array, the one blocking sync of a pipeline).
# The ``host-sync-in-hot-path`` check exempts these; anywhere else in
# the hot path a sync is a finding.

COLLECT_BOUNDARIES: dict[str, str] = {
    "cometbft_tpu/ops/comb.py::build_a_tables_host": (
        "the host-precomputed A-table build: pure host bigint/numpy by "
        "design (the compile-free cold-start path); its np.asarray "
        "normalizes the caller's host pubkey array, never a device fetch"
    ),
    "cometbft_tpu/ops/bls381.py::aggregate_pubkeys_device": (
        "the BLS host bridge: one blocking fetch of the aggregated point"
    ),
    "cometbft_tpu/ops/bls381.py::validate_pubkeys_device": (
        "the BLS validation bridge: one blocking fetch of the per-row "
        "validity bits"
    ),
    "cometbft_tpu/ops/bls381.py::validate_aggregate_device": (
        "the fused FastAggregateVerify bridge: one blocking fetch of "
        "(validity bits, aggregate point)"
    ),
    "cometbft_tpu/ops/bls381.py::_jac_to_affine_host": (
        "host-side Jacobian->affine converter for an already-computed "
        "device aggregate; its np.asarray is THE one result fetch"
    ),
    "cometbft_tpu/ops/bls381.py::from_limbs": (
        "host-side limb decoder; receives the already-fetched aggregate"
    ),
    "cometbft_tpu/ops/field.py::from_limbs": (
        "host-side limb decoder used by tests and host bridges"
    ),
    "cometbft_tpu/ops/secp256k1.py::verify_batch_device": (
        "the secp ECDSA bridge: one blocking fetch of the per-row "
        "verdict bits"
    ),
    "cometbft_tpu/ops/secp256k1.py::hash_verify_batch_device": (
        "the fused hash->verify bridge: one blocking fetch of the "
        "per-row verdict bits"
    ),
    "cometbft_tpu/ops/keccak.py::keccak256_device": (
        "the batched Keccak-256 bridge: one blocking fetch of the "
        "digests"
    ),
    "cometbft_tpu/ops/secp256k1.py::from_limbs": (
        "host-side limb decoder (tests); receives already-fetched "
        "results"
    ),
}
# NOT boundaries: the parallel/mesh.py factories' np.array calls wrap
# the host device list — the host-sync check recognizes devices()
# dataflow itself, so the fetch-boundary registry stays exactly the
# set of real host<->device collect points.


def collect_boundary(path: str, target: str) -> bool:
    """True when ``path::target`` is a declared host boundary (suffix
    match on a '/' boundary, same rule as :func:`site_registered`)."""
    for site in COLLECT_BOUNDARIES:
        rpath, _, rtarget = site.partition("::")
        if target != rtarget:
            continue
        if path == rpath or path.endswith("/" + rpath):
            return True
    return False


# ------------------------------------------------------- dtype conversions
#
# Every ``convert_element_type`` a manifest kernel is allowed to contain,
# as (src, dst) dtype-name pairs.  Anything outside this set fails the
# dtype-closure gate: an unlisted conversion is exactly how silent
# promotion creep lands.  Keep each pair justified.

ALLOWED_CONVERSIONS: frozenset[tuple[str, str]] = frozenset(
    {
        # byte <-> word unpacking at kernel edges
        ("uint8", "int32"),  # payload/scalar bytes -> limb arithmetic
        ("uint8", "uint32"),  # SHA message bytes -> 32-bit words
        ("uint32", "uint8"),  # digest words -> output bytes
        ("int32", "uint8"),  # packed flags / byte stores
        # the one-hot MXU matmul round trip (ops/comb.py b-part lookup:
        # 12-bit Niels limbs are exact in f32; HIGHEST precision)
        ("int32", "float32"),
        ("float32", "int32"),
        # masks and validity plumbing
        ("bool", "uint32"),  # SHA-512 (hi, lo) pair addition: the carry
        #   of each 32-bit lane add is (lo < al).astype(uint32)
        #   (ops/sha2._add64) — 64-bit words don't exist on TPU
        ("bool", "int32"),  # invalid-count psum accumulators
        ("bool", "uint8"),  # the all-ok byte of the packed result
        ("bool", "float32"),  # one-hot select masks on the MXU path
        ("int32", "bool"),  # borrow-chain compare results
        ("uint8", "bool"),  # live-row flags decoded from the payload
    }
)

# Jaxpr-level dtypes that must NEVER appear in a kernel: 64-bit creep
# either silently doubles HBM traffic or (under the default x64-disabled
# config) silently truncates — both are contract violations.
FORBIDDEN_DTYPES: frozenset[str] = frozenset(
    {"int64", "uint64", "float64", "complex64", "complex128"}
)


# ------------------------------------------------------- sharded kernels
#
# The sharding extension of the manifest: every mesh-parameterized
# kernel (the parallel/verify.py factories) declares, next to its trace
# shapes, the SHARDED-PLANE contract ``analysis/shardcheck.py`` enforces
# under a real 8-way CPU mesh (subprocess with
# ``XLA_FLAGS=--xla_force_host_platform_device_count=8``):
#
# * ``in_specs``/``out_specs`` — the intended PartitionSpec per
#   argument/output, spelled stdlib-only as one tuple per array with an
#   axis name (or None) per dimension; ``()`` = fully replicated.  The
#   checker compares them against the traced shard_map's in/out names,
#   so a silent respec (a stage suddenly receiving replicated rows it
#   expected sharded) fails statically.
# * ``donate_argnums`` — arguments the lowered program must actually
#   donate (and nothing else): the staging-slab HBM-reuse discipline of
#   ROADMAP item 1, checked on the pjit's ``donated_invars``.
#   ``entry_donated_params`` names the same arguments as (param-name,
#   positional-index) of the PUBLIC wrapper, for the
#   ``donated-read-after-dispatch`` AST check.
# * ``collectives`` — the declared collective census.  Any collective
#   primitive (psum / all_gather / all_to_all / ppermute /
#   sharding_constraint resharding copies, ...) the traced program
#   contains beyond this census is a finding: silent reshard-per-stage
#   is exactly how a pipelined handoff degrades to gather+scatter.
# * ``max_eqns`` / ``max_loop_depth`` / ``max_device_bytes`` — the
#   compile-cost budget: total jaxpr equation count (an unrolled table
#   build lands thousands of flat equations — the static face of the
#   2m34s ``jit_build_a_tables`` XLA compile), deepest nested
#   control-flow loop, and a per-device peak-bytes estimate from the
#   shard_map body's (already per-device) avals.
#
# ``name`` must match a ``needs_mesh`` Kernel row above (same fn ref) so
# the two declarations cannot drift apart; ``args``/``out`` here are the
# 8-way trace shapes (every sharded axis divisible by the mesh).

SHARD_MESH_DEVICES = 8  # the CI mesh: forced host devices in the child
SHARD_AXIS = "sig"

V8 = 8  # validator lanes under the 8-way mesh (1 per device)
_TABLES8 = i32(64, 9, 3, 22, V8)


@dataclass(frozen=True)
class ShardedKernel:
    """One mesh-parameterized kernel's sharded-plane contract."""

    name: str  # the needs_mesh Kernel row this extends
    entrypoint: str  # public wrapper in parallel/verify.py
    args: tuple[Arg, ...]  # 8-way trace shapes
    out: tuple[Arg, ...]
    in_specs: tuple[tuple, ...]  # per arg: axis-or-None per dim
    out_specs: tuple[tuple, ...]
    collectives: tuple[tuple[str, int], ...]  # declared census
    max_eqns: int  # compile-cost budget: total equation count
    max_loop_depth: int  # deepest nested scan/while body
    max_device_bytes: int  # per-device peak-bytes estimate ceiling
    donate_argnums: tuple[int, ...] = ()
    # (wrapper param name, wrapper positional index) per donated arg
    entry_donated_params: tuple[tuple[str, int], ...] = ()


SHARDED_KERNELS: tuple[ShardedKernel, ...] = (
    ShardedKernel(
        name="sharded_verify_batch",
        entrypoint="sharded_verify_batch",
        args=(u8(N, 32), u8(N, 32), u8(N, 32), u8(N, 2, 128), i32(N)),
        out=(boolean(), boolean(N)),
        in_specs=(
            (SHARD_AXIS,),
            (SHARD_AXIS,),
            (SHARD_AXIS,),
            (SHARD_AXIS, None, None),
            (SHARD_AXIS,),
        ),
        out_specs=((), ()),
        # one psum folds the per-device bad counts, one all_gather
        # replicates the blame vector; anything else is a reshard
        collectives=(("all_gather", 1), ("psum", 1)),
        # measured 76,888 eqns / loop depth 1 / ~11 KB per device at the
        # 8-lane trace; budgets leave headroom for kernel evolution but
        # fail an unrolled-table-build-class blowup immediately
        max_eqns=100_000,
        max_loop_depth=4,
        max_device_bytes=8 << 20,
        # every argument is a per-call staging transfer, dead after
        # dispatch — all five donated (PR-11: "finish the set")
        donate_argnums=(0, 1, 2, 3, 4),
        entry_donated_params=(
            ("a_enc", 1), ("r_enc", 2), ("s_bytes", 3),
            ("msg_blocks", 4), ("msg_active", 5),
        ),
    ),
    ShardedKernel(
        name="sharded_verify_cached",
        entrypoint="sharded_verify_cached",
        args=(_TABLES8, boolean(V8), u8(V8, 32), u8(V8, PAYLOAD_W)),
        out=(u8(2),),
        in_specs=(
            (None, None, None, None, SHARD_AXIS),  # tables: lanes minor
            (SHARD_AXIS,),
            (SHARD_AXIS, None),  # pubs
            (SHARD_AXIS, None),  # payload rows
        ),
        out_specs=((),),
        collectives=(("all_gather", 1), ("psum", 1)),
        # measured 30,341 eqns / loop depth 1 / ~24.9 MB per device at
        # the 8-lane trace (the replicated radix-4096 basepoint comb is
        # ~23.8 MB on EVERY device — the estimate is dominated by it)
        max_eqns=39_500,
        max_loop_depth=4,
        max_device_bytes=48 << 20,
        # the per-call staging payload is consumed by the dispatch;
        # tables/valid/pubs persist in the cache entry — never donated
        donate_argnums=(3,),
        entry_donated_params=(("payload", 4),),  # wrapper: (mesh, t, v, p, payload)
    ),
    ShardedKernel(
        name="sharded_merkle_root",
        entrypoint="sharded_merkle_root",
        args=(u8(N, 1, 64), i32(N)),
        out=(u8(32),),
        in_specs=((SHARD_AXIS, None, None), (SHARD_AXIS,)),
        out_specs=((),),
        collectives=(("all_gather", 1),),
        # measured 633 eqns / loop depth 1 / ~4 KB per device
        max_eqns=2_000,
        max_loop_depth=4,
        max_device_bytes=1 << 20,
        # per-call leaf staging transfers, dead after dispatch
        donate_argnums=(0, 1),
        entry_donated_params=(("leaf_blocks", 1), ("leaf_active", 2)),
    ),
    ShardedKernel(
        name="sharded_merkle_proofs",
        entrypoint="sharded_merkle_proofs",
        # 8-way trace: n=8 leaves replicated, K=8 queries (1 per device)
        args=(u8(N, 1, 64), i32(N), i32(V8), i32(V8, 3)),
        out=(u8(32), u8(V8, 32), u8(V8, 3, 32)),
        in_specs=(
            (),  # leaf blocks: replicated (every device holds the tree)
            (),  # active counts: replicated
            (SHARD_AXIS,),  # query indices: sharded
            (SHARD_AXIS, None),  # per-level sibling positions: sharded
        ),
        out_specs=((), (SHARD_AXIS, None), (SHARD_AXIS, None, None)),
        # ZERO collectives: the tree is replicated, each device answers
        # its own query slice locally — any collective here is a reshard
        collectives=(),
        # measured 995 eqns / loop depth 0 / ~4 KB per device
        max_eqns=1_500,
        max_loop_depth=4,
        max_device_bytes=1 << 20,
        # the per-call query plan is dead after dispatch; the leaf
        # blocks are NOT donated — callers reuse a registered tree
        # across dispatches
        donate_argnums=(2, 3),
        entry_donated_params=(("indices", 3), ("sib_pos", 4)),
    ),
)


def sharded_by_name() -> dict[str, ShardedKernel]:
    return {s.name: s for s in SHARDED_KERNELS}


def donated_entrypoints() -> dict[str, tuple[tuple[str, int], ...]]:
    """Wrapper-function name -> ((param name, positional index), ...)
    for every sharded kernel with declared donations — the
    ``donated-read-after-dispatch`` AST check's worklist."""
    out: dict[str, tuple[tuple[str, int], ...]] = {}
    for s in SHARDED_KERNELS:
        if s.entry_donated_params:
            out[s.entrypoint] = s.entry_donated_params
    return out


# ----------------------------------------------------------------- helpers


def by_name() -> dict[str, Kernel]:
    return {k.name: k for k in KERNELS}


def module_path(k: Kernel) -> str:
    """'package.module:fn' -> 'package/module.py' (repo-relative)."""
    mod = k.fn.split(":", 1)[0]
    return mod.replace(".", "/") + ".py"


def fn_name(k: Kernel) -> str:
    return k.fn.split(":", 1)[1]


def traced_roots(path: str) -> set[str]:
    """Manifest-declared traced entry points living in ``path`` (a
    repo-relative or absolute module path) — the extra closure roots the
    AST checks seed beyond per-module ``jax.jit`` discovery."""
    roots: set[str] = set()
    for k in KERNELS:
        mp = module_path(k)
        if path == mp or path.endswith("/" + mp):
            roots.add(fn_name(k))
    return roots


def site_registered(path: str, target: str) -> bool:
    """True when ``path::target`` matches a JIT_SITES entry (suffix match
    on a '/' boundary, same rule as the allowlist)."""
    for site in JIT_SITES:
        rpath, _, rtarget = site.partition("::")
        if target != rtarget:
            continue
        if path == rpath or path.endswith("/" + rpath):
            return True
    return False
