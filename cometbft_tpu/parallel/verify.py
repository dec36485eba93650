"""Sharded verification kernels (shard_map over a device Mesh).

One Commit = N independent signature checks plus a Merkle pass over the
block — embarrassingly parallel across chips.  Shardings:

  - signatures: batch axis sharded over "sig"; each device runs the fused
    Ed25519 kernel on its shard; a psum over invalid counts yields the
    global all-valid bit while the per-signature validity vector stays
    sharded (gathered once at the end for blame, validation.go:384-399).
  - Merkle leaves: leaf axis sharded over "sig" too (leaf counts per
    device stay static); each device reduces its subtree, then the D
    subtree roots are all_gathered and folded level-by-level, replicated.

Everything is jit-compiled once per (shape, mesh) and reused; the commit
verification step is the framework's flagship compiled program.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, *, mesh, in_specs, out_specs):
    # Disable the varying-manual-axes checker: the SHA-2 fori_loop carries
    # mix varying/unvarying per-device types; the collectives below
    # establish replication explicitly, so the static check adds nothing.
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )

from ..ops import ed25519 as E
from ..ops import merkle as M
from ..utils import tracing
from .mesh import mesh_cache_key

# Compiled sharded programs, keyed on STABLE mesh identity
# (mesh_cache_key: device ids + topology + axis names) plus any
# trace-time knob flag — never on Mesh object identity.  Two equivalent
# meshes built by separate make_mesh calls hand out the SAME program
# object, so nothing re-traces or re-compiles per mesh entry
# (tests/test_shardcheck.py pins one-program-per-equivalent-mesh).
_PROGRAMS: dict[tuple, object] = {}
_PROGRAMS_MTX = threading.Lock()


def _cached_program(key: tuple):
    with _PROGRAMS_MTX:
        return _PROGRAMS.get(key)


def _publish_program(key: tuple, fn):
    """First publisher wins; a racing builder adopts the winner so every
    caller shares one traced/compiled program per key."""
    with _PROGRAMS_MTX:
        return _PROGRAMS.setdefault(key, fn)


def _verify_fn(mesh: Mesh):
    """jit-wrapped sharded verifier, cached per equivalent mesh — without
    the jit every call re-traces the whole kernel and nothing reaches the
    persistent compile cache (this made the un-jitted path effectively
    un-runnable on the CPU backend).

    The jit carries EXPLICIT ``in_shardings``/``out_shardings`` matching
    the shard_map specs: a host batch lands directly in its sharded
    layout (one scatter-free transfer per device), an already-sharded
    device buffer is consumed in place, and a mislaid input can never
    silently reshard at the pjit boundary — the stage-handoff contract
    of docs/sharding_contracts.md.  Every argument is a per-call staging
    transfer, dead after dispatch, so ALL FIVE are donated (the device
    may reuse their HBM for outputs); callers must pass fresh arrays and
    never read them after the call (``donated-read-after-dispatch``
    enforces this statically at declared entrypoints).

    Manifest kernel ``sharded_verify_batch``: the contract checker calls
    this factory with a 1-device CPU mesh and pins the traced program
    (the collective mix — psum/all_gather — is part of the fingerprint);
    analysis/shardcheck.py re-traces it under a real 8-way CPU mesh and
    holds it to the declared shardings/collective census/budgets,
    including the donation vector.
    """
    key = ("verify_batch", mesh_cache_key(mesh))
    cached = _cached_program(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]

    def local(a, r, s, blocks, active):
        ok = E.verify_batch(a, r, s, blocks, active)
        bad = jnp.sum((~ok).astype(jnp.int32))
        total_bad = jax.lax.psum(bad, axis)
        all_ok = jax.lax.all_gather(ok, axis, tiled=True)
        return total_bad == 0, all_ok

    row = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(), P()),
        ),
        in_shardings=(row, row, row, row, row),
        out_shardings=(repl, repl),
        donate_argnums=(0, 1, 2, 3, 4),
    )
    return _publish_program(key, fn)


def sharded_verify_batch(mesh: Mesh, a_enc, r_enc, s_bytes, msg_blocks, msg_active):
    """Batch Ed25519 verify with the batch axis sharded over mesh axis "sig".

    Returns (all_valid: bool scalar, valid: (N,) bool fully replicated).
    N must be divisible by the mesh size (callers pad to bucket sizes).

    ALL FIVE arrays are DONATED to the device program (each is a fresh
    per-call staging transfer): pass fresh arrays and never read them
    after this returns — the ``donated-read-after-dispatch`` check
    enforces it statically at call sites of this entrypoint.
    """
    with tracing.span(
        "verify.shard_dispatch",
        {"devices": int(mesh.devices.size)} if tracing.enabled() else None,
    ):
        return _verify_fn(mesh)(a_enc, r_enc, s_bytes, msg_blocks, msg_active)


def _comb_verify_fn(mesh: Mesh):
    """Sharded comb-cached commit verification — the engine's production
    path (models/comb_verifier.py) over a device mesh.

    Shardings: the comb tables' VALIDATOR axis (their minor lane axis,
    ops/comb.py layout (64, 9, 3, 22, V)) and every per-call row array
    shard over "sig"; the 24 MB base-point table is replicated.  A psum
    over bad counts yields the global all-ok bit; the per-validator
    bitmap is all_gathered and packed on every device (replicated).
    A 10k-validator set's 1.5 GB of tables become ~190 MB per chip on an
    8-chip mesh — the component that most needs sharding.

    The per-call payload rows are DONATED (donate_argnums=(3,)): the
    staging buffer's device copy is consumed by the dispatch and its HBM
    is reusable for the outputs — host code must never touch the device
    payload after submit (models/comb_verifier stages a fresh
    ``jnp.asarray`` per call and recycles only the HOST slab; the
    ``donated-read-after-dispatch`` lint check and shardcheck's donation
    contract keep it that way).  Tables/valid/pubs persist across calls
    in the cache entry and are never donated.

    Manifest kernel ``sharded_verify_cached``.
    """
    key = ("verify_cached", mesh_cache_key(mesh))
    cached = _cached_program(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]
    import jax.numpy as jnp

    from ..ops import comb, sha2

    bt = comb.get_b_tables()

    def local(tables, valid, pubs, payload):
        r, s, blocks, active, live = sha2.parse_verify_payload(payload, pubs)
        dig = sha2.sha512_blocks(blocks, active)
        ok = comb.verify_cached(tables, valid, r, s, dig, bt)
        bad = jnp.sum((~(ok | ~live)).astype(jnp.int32))
        total_bad = jax.lax.psum(bad, axis)
        ok_all = jax.lax.all_gather(ok & live, axis, tiled=True)
        # one replicated [bitmap | all_ok] array — a single host fetch
        return jnp.concatenate(
            [jnp.packbits(ok_all), (total_bad == 0).astype(jnp.uint8)[None]]
        )

    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(None, None, None, None, axis),  # tables: validator lanes
                P(axis),
                P(axis, None),  # pubs
                P(axis, None),  # payload rows
            ),
            out_specs=P(),
        ),
        # explicit shardings = the stage-handoff contract: the cache
        # entry's device-resident tables/valid/pubs (placed by
        # _finish_entry with these exact NamedShardings) are consumed in
        # place — no resharding copy at the pjit boundary — and the
        # host-staged payload transfers straight into its row layout
        in_shardings=(
            NamedSharding(mesh, P(None, None, None, None, axis)),
            NamedSharding(mesh, P(axis)),
            NamedSharding(mesh, P(axis, None)),
            NamedSharding(mesh, P(axis, None)),
        ),
        out_shardings=NamedSharding(mesh, P()),
        # the payload is a per-call staging transfer, dead after dispatch
        donate_argnums=(3,),
    )
    return _publish_program(key, fn)


def sharded_verify_cached(mesh: Mesh, tables, valid, pubs, payload):
    """Comb-cached VerifyCommit with validators sharded over the mesh.

    payload: (V, 68 + maxm) uint8 tight rows (R | s | mlen 3B LE | live |
    msg) — SHA blocks are assembled on device (ops/sha2) so only
    irreducible bytes cross the host->device link.  V must be divisible
    by the mesh size (the comb cache pads entries to lane buckets).
    Returns one uint8 array [packbits(ok & live) | all_ok byte] — the
    same single-fetch contract as models/comb_verifier._device_verify.

    ``payload`` is DONATED to the device program: pass a fresh per-call
    array and never read it again after this returns.  The
    donated-read-after-dispatch check flags violations statically at
    direct and same-scope partial-bound call sites; for handles that
    cross a function boundary (models/comb_verifier stores the partial
    on its cache entry), stage the donated value inline in the call
    expression — never bind it — as stage() does.
    """
    with tracing.span(
        "verify.shard_dispatch",
        {"devices": int(mesh.devices.size)} if tracing.enabled() else None,
    ):
        return _comb_verify_fn(mesh)(tables, valid, pubs, payload)


def _merkle_fn(mesh: Mesh):
    # Manifest kernel ``sharded_merkle_root``.  Explicit shardings +
    # donation like the verify stages: the leaf blocks are a per-call
    # staging transfer, dead after dispatch.
    key = ("merkle_root", mesh_cache_key(mesh))
    cached = _cached_program(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]

    def local(blocks, active):
        sub = M.root_from_leaves(blocks, active)  # (32,)
        roots = jax.lax.all_gather(sub, axis)  # (D, 32)
        return M.root_from_leaf_hashes(roots)

    row = NamedSharding(mesh, P(axis))
    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=P(),
        ),
        in_shardings=(row, row),
        out_shardings=NamedSharding(mesh, P()),
        donate_argnums=(0, 1),
    )
    return _publish_program(key, fn)


def sharded_merkle_root(mesh: Mesh, leaf_blocks, leaf_active):
    """Merkle root with leaves sharded over the mesh's first axis.

    Each device leaf-hashes and reduces its (n/D)-leaf subtree, then the D
    subtree roots are all_gathered and folded on every device (replicated
    result).  Exactly the reference's power-of-two split (tree.go:101)
    when n/D is a power of two — which callers guarantee by padding.

    Both arrays are DONATED (per-call staging transfers): pass fresh
    arrays and never read them after this returns.
    """
    return _merkle_fn(mesh)(leaf_blocks, leaf_active)


def _merkle_proofs_fn(mesh: Mesh):
    """Sharded batched proof generation — the QUERY axis shards, the tree
    replicates.  Each device recomputes every reduction level from the
    replicated leaf blocks (cheap: the tree is one batched SHA-256 pass)
    and one-hot-gathers audit paths for its own query shard, so the
    kernel needs ZERO collectives — the per-query outputs come back
    sharded exactly as the queries went in, and the root is replicated
    by construction.

    Only the query arrays are donated: they are per-call staging
    transfers, while callers may legitimately reuse the (replicated)
    leaf blocks across several proof dispatches against the same tree.

    Manifest kernel ``sharded_merkle_proofs``.
    """
    key = ("merkle_proofs", mesh_cache_key(mesh))
    cached = _cached_program(key)
    if cached is not None:
        return cached
    axis = mesh.axis_names[0]

    def local(blocks, active, indices, sib_pos):
        return M.proofs_from_leaves(blocks, active, indices, sib_pos)

    repl = NamedSharding(mesh, P())
    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), P(axis), P(axis, None)),
            out_specs=(P(), P(axis), P(axis, None, None)),
        ),
        in_shardings=(repl, repl, NamedSharding(mesh, P(axis)),
                      NamedSharding(mesh, P(axis, None))),
        out_shardings=(repl, NamedSharding(mesh, P(axis)),
                       NamedSharding(mesh, P(axis, None, None))),
        donate_argnums=(2, 3),
    )
    return _publish_program(key, fn)


def sharded_merkle_proofs(mesh: Mesh, blocks, active, indices, sib_pos):
    """Batched audit paths with the query axis sharded over the mesh.

    blocks/active: host-padded leaves (ops/merkle.pad_leaves), replicated;
    indices (K,) i32 and sib_pos (K, D) i32 (crypto/merkle.proof_plan)
    shard over the mesh's first axis — K must be divisible by the mesh
    size (callers pad the query list; index-0 padding rows are harmless
    extra gathers the host slices away).  Returns (root (32,) replicated,
    leaf_sel (K, 32), aunts (K, D, 32)) with per-query outputs sharded
    like the queries.

    ``indices`` and ``sib_pos`` are DONATED (per-call staging transfers):
    pass fresh arrays and never read them after this returns.
    """
    with tracing.span(
        "verify.shard_dispatch",
        {"devices": int(mesh.devices.size)} if tracing.enabled() else None,
    ):
        return _merkle_proofs_fn(mesh)(blocks, active, indices, sib_pos)


def commit_verification_step(
    mesh: Mesh, a_enc, r_enc, s_bytes, msg_blocks, msg_active, leaf_blocks, leaf_active
):
    """The flagship step: verify a Commit's signature batch and recompute
    the block's Merkle root, both sharded over the mesh.

    Mirrors what finalizeCommit does per height on the host reference
    (state/validation.go:94 VerifyCommit + types/block.go hashing).
    """
    all_ok, valid = sharded_verify_batch(
        mesh, a_enc, r_enc, s_bytes, msg_blocks, msg_active
    )
    root = sharded_merkle_root(mesh, leaf_blocks, leaf_active)
    return all_ok, valid, root
