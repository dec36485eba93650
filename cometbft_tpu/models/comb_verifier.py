"""Validator-set-keyed comb-table cache + the cached batch verifier.

This is the device-resident fast path for commit verification: the TPU
analogue of the reference's per-pubkey expanded-key LRU
(crypto/ed25519/ed25519.go:43,68), scaled to whole validator sets.  A
validator set's pubkeys are decompressed ONCE into per-validator comb
tables (ops/comb.build_a_tables) and kept on device; every subsequent
VerifyCommit against that set ships only the per-call data — R halves,
s halves, and the SHA-512-padded R || A || M blocks — and runs
ops/comb.verify_cached, which needs no doublings and no decompression of
the pubkeys.  The challenge digests k = SHA-512(R || A || M) are computed
on device (ops/sha2.sha512_blocks) so the host never runs a per-signature
hash loop, and the result comes back as one packed bitmap + one all-ok
scalar instead of a per-row bool array.

Like the uncached verifier, CombBatchVerifier is data plane only:
production consumers reach it through the unified verify service
(verifysvc/ — a request bound to a cache entry via
``mode=("comb", entry)`` dispatches as one solo batch on the scheduler).

Shapes are keyed by the validator-set size V padded to the chip's 128
lanes, not to a power-of-two bucket: 175 validators run 256 lanes and
10,000 run 10,112 (not 16,384), which is what XLA tiles the minor axis
to anyway, and a set that gains a member inside its bucket compiles
nothing.  The compiled program depends on (lanes, payload width) alone
— tables, validity mask and keys are its arguments — so it is cached
once per process, not per set (models/verifier._COMB_PROGRAMS).  Rows
for validators that did not sign, and pad lanes, carry zeros and are
masked out of the result, preserving the per-signature blame contract
of types/validation.go:384-399.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict

import numpy as np

from ..utils import tracing
from ..utils.metrics import hub as _mhub


# lanes of a single-chip entry come in multiples of the chip's lane
# width; 152 KB of tables a lane (64 positions x 9 entries x 3 Niels
# coordinates x 22 int32 limbs)
LANE_BUCKET = 128
TABLE_BYTES_PER_LANE = 64 * 9 * 3 * 22 * 4


class _CacheEntry:
    __slots__ = (
        "tables", "valid", "pubs", "index", "size", "vpad", "mesh",
        "verify_fn", "_slabs", "_slab_mtx",
    )

    def __init__(self, tables, valid, pubs, index: dict[bytes, int], mesh=None):
        self.tables = tables  # device (64, 9, 3, 22, Vpad) int32 — V minor
        self.valid = valid  # device (Vpad,) bool
        self.pubs = pubs  # device (Vpad, 32) uint8 — the raw pubkeys, so
        # the per-call payload never re-ships A (it's in every SHA-512
        # challenge digest R || A || M)
        self.index = index  # pubkey bytes -> row
        self.size = len(index)
        # size padded to the lane bucket (or to the mesh's width)
        self.vpad = int(tables.shape[-1])
        self.mesh = mesh  # jax Mesh when the sharded path is active
        # One callable serving every payload width, when there is one:
        # the sharded program of a mesh entry (bound at first use; it
        # keeps its own per-shape cache and compiles inside its first
        # call, parallel/verify) or a test's stand-in.  A single-device
        # entry leaves it None and runs the process-wide program of its
        # shape (CombBatchVerifier._program).
        self.verify_fn = None
        # reusable host staging buffers, keyed by payload width; two per
        # width = the double-buffer the pipelined submit() path needs
        self._slabs: dict[int, list[_PayloadSlab]] = {}
        self._slab_mtx = threading.Lock()

    def acquire_slab(self, width: int) -> "_PayloadSlab":
        with self._slab_mtx:
            pool = self._slabs.get(width)
            if pool:
                slab = pool.pop()
                _mhub().verify_slab_requests.inc(result="hit")
                return slab
        _mhub().verify_slab_requests.inc(result="miss")
        return _PayloadSlab(self.vpad, width)

    def release_slab(self, slab: "_PayloadSlab") -> None:
        with self._slab_mtx:
            pool = self._slabs.setdefault(slab.buf.shape[1], [])
            if len(pool) < 2:
                pool.append(slab)


class _PayloadSlab:
    """One reusable (vpad, 68 + maxm) host staging buffer for payload
    assembly (the "pinned buffer" of the zero-copy submit path).

    Allocated once per (entry, width bucket) and recycled through the
    entry's two-slab pool, so steady-state assembly never allocates.  A
    full clear between uses is unnecessary: the device masks every byte
    past a row's mlen (ops/sha2.ram_blocks_from_parts) and every row
    whose live flag is 0, so a reuse only needs the PREVIOUS call's live
    flags retired — and when the next call writes the exact same row
    layout (the steady blocksync/consensus case: same signer rows, same
    sign-bytes length), the constant mlen/live columns are already
    correct and are not touched at all; only the R | s | msg columns are
    rewritten."""

    __slots__ = ("buf", "dirty", "layout")

    def __init__(self, vpad: int, width: int):
        self.buf = np.zeros((vpad, width), dtype=np.uint8)
        self.dirty = None  # previous use's live rows (array or slice)
        self.layout = None  # (kind, n, mlen) of the previous use

    def retire(self) -> None:
        """Forget every previous/partial fill: all live flags cleared,
        full header rewrite forced on next use.  The safe state for
        returning a slab to the pool from an ERROR path, where a partial
        fill may have set live flags the dirty bookkeeping doesn't
        cover."""
        self.buf[:, 67] = 0
        self.dirty = None
        self.layout = None


def active_mesh():
    """Device mesh for the sharded comb path.

    COMETBFT_TPU_MESH = N (N > 1) shards comb tables + signature rows
    over the first N devices (parallel/verify.sharded_verify_cached);
    unset/<=1 keeps the single-device program.  Resolved once per
    process — consensus builds one cache per validator set and the mesh
    must be identical across entries.
    """
    global _MESH
    if _MESH is _UNSET:
        from ..utils import envknobs

        n = envknobs.get_int(envknobs.MESH)
        if n <= 1:
            _MESH = None
        else:
            from ..parallel import make_mesh

            _MESH = make_mesh(n)
    return _MESH


_UNSET = object()
_MESH = _UNSET


def set_active_mesh(mesh) -> None:
    """Explicitly bind (or clear, with None) the comb-path mesh —
    overrides the COMETBFT_TPU_MESH env resolution.  Entries built
    before the change keep their placement; callers flush the cache
    when re-binding."""
    global _MESH
    _MESH = mesh


class ValsetCombCache:
    """LRU of device-resident comb tables, keyed by the pubkey list and
    bounded by the bytes of tables it holds.

    An entry is 152 KB of HBM a lane: 39 MB for a 175-validator chain
    (256 lanes), 1.5 GB at 10,000.  The bound is what two 10,000-validator
    entries take: consensus on a set that large needs the current set
    and, briefly, the previous one across a validator-set change, while
    a production-sized chain keeps some eighty sets resident, so a light
    -client or evidence check against an older set never evicts the
    tables consensus is using.  The newest entry is never evicted,
    whatever its size.
    """

    def __init__(self, max_bytes: int = 2 * 10_112 * TABLE_BYTES_PER_LANE):
        self._entries: OrderedDict[bytes, _CacheEntry] = OrderedDict()
        self._max_bytes = max_bytes
        self._mtx = threading.Lock()
        self._building: dict[bytes, threading.Lock] = {}
        self._async_inflight: set[bytes] = set()

    @staticmethod
    def fingerprint(pubkeys: list[bytes]) -> bytes:
        h = hashlib.sha256()
        for pk in pubkeys:
            h.update(pk)
        return h.digest()

    def get(self, fp: bytes) -> _CacheEntry | None:
        with self._mtx:
            e = self._entries.get(fp)
            if e is not None:
                self._entries.move_to_end(fp)
            return e

    def ensure(self, pubkeys: list[bytes], _count: bool = True) -> _CacheEntry:
        """Return the entry for this exact pubkey list, building the
        tables on first sight (one-time per validator set).  Concurrent
        first calls for the same set serialize on a per-fingerprint lock —
        a 10k-validator build must never race a duplicate.  When an entry
        for a *different* pubkey list already exists, its rows are reused
        for the unchanged validators (incremental churn update): only the
        new/changed pubkeys go through the table-build kernel."""
        fp = self.fingerprint(pubkeys)
        e = self.get(fp)
        if e is not None:
            if _count:
                _mhub().comb_table_cache.inc(result="hit")
            return e
        with self._mtx:
            build_lock = self._building.setdefault(fp, threading.Lock())
        with build_lock:
            e = self.get(fp)  # the race loser finds the winner's entry
            if e is not None:
                if _count:
                    # served by a build another caller performed — a
                    # "building" wait, not a second miss: misses must
                    # stay 1:1 with actual table builds
                    _mhub().comb_table_cache.inc(result="building")
                return e
            if _count:
                _mhub().comb_table_cache.inc(result="miss")
            # the span's labels; _build says what the bind came to
            bind: dict = {"thread": "background" if threading.current_thread()
                          .name == "comb-build" else "caller"}
            with tracing.span("verify.table_bind", bind):
                base = self._newest()
                entry = self._build(pubkeys, base, bind)
                with self._mtx:
                    self._entries[fp] = entry
                    held = sum(e.tables.nbytes for e in self._entries.values())
                    while held > self._max_bytes and len(self._entries) > 1:
                        _, oldest = self._entries.popitem(last=False)
                        held -= oldest.tables.nbytes
                        _mhub().comb_table_evictions.inc()
                    self._building.pop(fp, None)
            return entry

    def ensure_async(self, pubkeys: list[bytes]) -> _CacheEntry | None:
        """Non-blocking ensure: the entry if it's ready, else None with a
        background build kicked off (once per fingerprint).  The caller
        verifies through the uncached Straus kernel until the tables are
        warm — the analog of the reference's lazily-filling expanded-key
        LRU (ed25519.go:43,68), where the first verification under a new
        key also pays an expansion the cache then amortizes.  A validator
        -set change therefore never stalls consensus behind a table
        build: the new set's tables (an incremental churn build when the
        previous set's entry exists) land a few blocks later."""
        fp = self.fingerprint(pubkeys)
        e = self.get(fp)
        if e is not None:
            _mhub().comb_table_cache.inc(result="hit")
            return e
        with self._mtx:
            if fp in self._async_inflight:
                _mhub().comb_table_cache.inc(result="building")
                return None  # background build already running
            self._async_inflight.add(fp)
        _mhub().comb_table_cache.inc(result="miss")
        pubkeys = list(pubkeys)

        def build():
            try:
                # ensure() owns the per-fingerprint build lock, so a
                # concurrent synchronous caller can never duplicate the
                # build — whoever wins, the loser finds the entry
                # (_count=False: this lookup was already tallied above)
                self.ensure(pubkeys, _count=False)
            finally:
                with self._mtx:
                    self._async_inflight.discard(fp)

        threading.Thread(target=build, name="comb-build", daemon=True).start()
        return None

    def _newest(self) -> _CacheEntry | None:
        with self._mtx:
            if not self._entries:
                return None
            return next(reversed(self._entries.values()))

    @staticmethod
    def _build(
        pubkeys: list[bytes], base: _CacheEntry | None = None,
        bind: dict | None = None,
    ) -> _CacheEntry:
        """One bind.  ``bind`` takes what it came to for the caller's
        span: ``kind`` (full | incremental), ``fresh`` (keys built) and
        ``lanes``; the hub counts the same."""
        import jax.numpy as jnp

        mesh = active_mesh()
        index = {pk: i for i, pk in enumerate(pubkeys)}
        # pad lanes carry a repeated real key but are never scattered
        # into (valid rows only come from `index`), so they do
        # dead-but-defined work
        pad = lane_count(len(pubkeys), mesh) - len(pubkeys)
        if pad:
            pubkeys = list(pubkeys) + [pubkeys[0]] * pad
        reuse: list[tuple[int, int]] = []  # (new row, base row)
        fresh: list[int] = []
        if base is not None:
            for i, pk in enumerate(pubkeys):
                j = base.index.get(pk)
                if j is None:
                    fresh.append(i)
                else:
                    reuse.append((i, j))
        pub_arr = np.frombuffer(b"".join(pubkeys), dtype=np.uint8).reshape(-1, 32)
        if bind is None:
            bind = {}
        bind["lanes"] = len(pubkeys)
        if base is None or not reuse:
            bind.update(kind="full", fresh=len(index))
            _mhub().comb_table_bind.inc(kind="full")
            _mhub().comb_fresh_keys.inc(len(index))
            tables, valid = _build_tables(pub_arr)
            return _finish_entry(tables, valid, pub_arr, index, mesh)

        # Incremental churn: gather unchanged rows from the previous set's
        # device tables, build only the new keys.  A single-validator swap
        # reuses the other V-1 rows (the expensive part of a table row is
        # its doubling chain, ~64 * 4 point doubles).  Fresh keys are padded
        # to a power-of-two bucket so churn of any size hits a handful of
        # compiled build shapes rather than one compile per distinct count,
        # and the gather/scatter assembly runs as one jitted program so XLA
        # fuses it instead of materializing intermediate full-size copies
        # (an entry is ~1.5 GB at V=10k; transient copies would OOM HBM).
        V = len(pubkeys)
        # pad lanes repeat a key: fresh lanes can outnumber fresh keys
        bind.update(kind="incremental", fresh=len({pubkeys[i] for i in fresh}))
        _mhub().comb_table_bind.inc(kind="incremental")
        _mhub().comb_fresh_keys.inc(bind["fresh"])
        if fresh:
            bucket = 1 << (len(fresh) - 1).bit_length()
            padded = [pubkeys[i] for i in fresh]
            padded += [padded[0]] * (bucket - len(fresh))
            a = np.frombuffer(b"".join(padded), dtype=np.uint8).reshape(-1, 32)
            t_new, v_new = _build_tables(a)
            t_new, v_new = jnp.asarray(t_new), jnp.asarray(v_new)
        else:
            t_new = base.tables[..., :0]
            v_new = base.valid[:0]
        with tracing.span("verify.table_assemble"):
            tables, valid = _assemble_churn_jit(
                base.tables,
                base.valid,
                t_new,
                v_new,
                jnp.asarray(np.asarray([i for i, _ in reuse], np.int32)),
                jnp.asarray(np.asarray([j for _, j in reuse], np.int32)),
                jnp.asarray(np.asarray(fresh, np.int32)),
                V,
            )
            return _finish_entry(tables, valid, pub_arr, index, mesh)


def _build_tables(pub_arr: np.ndarray):
    """One table build, routed: sets up to COMETBFT_TPU_COMB_HOST_BUILD_MAX
    validators (or churn buckets that size) are precomputed on HOST
    (ops/comb.build_a_tables_host — exact bigint, bit-identical, ~10 ms
    per validator, NO XLA program, so a cold pod never pays the
    table-build compile); bigger builds run the scan-rolled jitted
    kernel (ops/comb.build_a_tables_jit), whose compile the persistent
    XLA cache amortizes and whose arithmetic the device wins at scale.
    Returns (tables, valid) — host numpy or device arrays; callers
    device_put with their placement (_finish_entry).

    The default threshold (2048) matches COMETBFT_TPU_COMB_ASYNC_MIN:
    foreground builds stay host/compile-free, while the giant sets that
    would be slow on host already build in the background behind the
    uncached fallback (ensure_async)."""
    from ..ops import comb
    from ..utils import envknobs

    lim = envknobs.get_int(envknobs.COMB_HOST_BUILD_MAX)
    if 0 < pub_arr.shape[0] <= lim:
        with tracing.phase(
            "verify.table_build", "table_build_host",
            labels={"backend": "host"},
        ):
            # a key is built once: pad lanes repeat one
            uniq, lanes = np.unique(pub_arr, axis=0, return_inverse=True)
            lanes = lanes.reshape(-1)
            tables, valid = comb.build_a_tables_host(uniq)
            return tables[..., lanes], valid[lanes]
    import jax.numpy as jnp

    with tracing.phase(
        "verify.table_build", "table_build_device",
        labels={"backend": "device"},
    ):
        out = comb.build_a_tables_jit(jnp.asarray(pub_arr))
        # the jit dispatch is async: wait for the arithmetic so the
        # phase is the COMPLETED build (the host counterpart measures
        # completed work; comparing the two is this split's purpose)
        out[0].block_until_ready()
    return out


def _finish_entry(tables, valid, pub_arr, index, mesh) -> _CacheEntry:
    """Place the built tables: sharded over the mesh's lane axis when the
    multi-chip path is active, resident on the default device otherwise.
    ``tables``/``valid`` may be host numpy (the precomputed path) or
    device arrays (the jitted build) — ``device_put`` with the explicit
    ``NamedSharding`` covers both, landing host tables directly in their
    sharded layout with no resharding copy."""
    import jax

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = mesh.axis_names[0]
        tables = jax.device_put(
            tables, NamedSharding(mesh, P(None, None, None, None, axis))
        )
        valid = jax.device_put(valid, NamedSharding(mesh, P(axis)))
        pubs = jax.device_put(pub_arr, NamedSharding(mesh, P(axis, None)))
    else:
        tables = jax.device_put(tables)
        valid = jax.device_put(valid)
        pubs = jax.device_put(pub_arr)
    tables.block_until_ready()
    return _CacheEntry(tables, valid, pubs, index, mesh)


def _assemble_churn(base_t, base_v, new_t, new_v, new_rows, base_rows, fresh_rows, V):
    """One fused gather/scatter: reused rows from the old tables + freshly
    built rows into a V-lane table.  The validator axis is the tables'
    LAST axis (ops/comb.py layout); new_t may carry bucket padding beyond
    len(fresh_rows) lanes, which the scatter never reads.

    Manifest kernel ``comb_assemble_churn`` (V is the static argument)."""
    import jax.numpy as jnp

    tables = jnp.zeros(tuple(base_t.shape[:-1]) + (V,), base_t.dtype)
    valid = jnp.zeros((V,), bool)
    tables = tables.at[..., new_rows].set(base_t[..., base_rows])
    valid = valid.at[new_rows].set(base_v[base_rows])
    nf = fresh_rows.shape[0]
    if nf:
        tables = tables.at[..., fresh_rows].set(new_t[..., :nf])
        valid = valid.at[fresh_rows].set(new_v[:nf])
    return tables, valid


_ASSEMBLE_CHURN = None


def _assemble_churn_jit(*args):
    global _ASSEMBLE_CHURN
    if _ASSEMBLE_CHURN is None:
        import jax

        _ASSEMBLE_CHURN = jax.jit(_assemble_churn, static_argnums=(7,))
    return _ASSEMBLE_CHURN(*args)


_GLOBAL_CACHE = ValsetCombCache()


def global_cache() -> ValsetCombCache:
    return _GLOBAL_CACHE


_STAGING_POOL = None
_STAGING_POOL_MTX = threading.Lock()


def _staging_executor():
    """One process-wide staging thread for submit(): a single worker
    keeps host->device transfers and kernel dispatches in submission
    order (so pipelined tickets resolve FIFO on the device queue) while
    still unblocking every submitter immediately.  Assembly itself is
    numpy and releases the GIL for the big writes, so the caller's
    Python thread runs concurrently.  Creation is locked: a first-use
    race (blocksync pool thread vs consensus thread) must not spawn two
    workers, which would break the FIFO ordering guarantee."""
    global _STAGING_POOL
    if _STAGING_POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        with _STAGING_POOL_MTX:
            if _STAGING_POOL is None:
                _STAGING_POOL = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="comb-stage"
                )
    return _STAGING_POOL


def _fill_payload(
    slab: _PayloadSlab, items: list[tuple[bytes, bytes, bytes]], rows: np.ndarray
) -> np.ndarray:
    """Fill a staging slab with the tight device payload: row layout
    R(32) | s(32) | mlen(3B LE) | live(1B) | msg.

    items are (pubkey, msg, sig) in add() order; rows maps each item to
    its validator row.  Pure NumPy slice/scatter writes — no per-row
    Python loop on any commit-shaped batch.  Fast paths, in order:

      - same layout as the slab's previous use (row set + message
        length): the constant mlen/live columns survive verbatim; only
        R | s | msg are rewritten.
      - contiguous rows 0..n-1 (every validator signed, commit order):
        plain slice writes instead of fancy-index scatters.
      - all-equal message lengths (canonical vote sign-bytes): one
        reshaped block write for the messages.
    """
    buf = slab.buf
    n = len(items)
    sig_arr = np.frombuffer(
        b"".join(s for _, _, s in items), dtype=np.uint8
    ).reshape(n, 64)
    msgs = [m for _, m, _ in items]
    lens = np.fromiter((len(m) for m in msgs), np.int64, n)
    l0 = int(lens[0]) if n else 0
    same_len = bool((lens == l0).all()) if n else True

    contig = bool(
        n
        and int(rows[0]) == 0
        and int(rows[-1]) == n - 1
        and (rows == np.arange(n, dtype=rows.dtype)).all()
    )
    target = slice(0, n) if contig else rows
    layout = ("contig", n, l0) if (contig and same_len) else None

    if layout is None or slab.layout != layout:
        # retire the previous use's live rows, then write the header
        # columns fresh (stale bytes beyond a live row's mlen are masked
        # on device, so only the live flags need clearing)
        if slab.dirty is not None:
            buf[slab.dirty, 67] = 0
        if same_len:
            buf[target, 64] = l0 & 0xFF
            buf[target, 65] = (l0 >> 8) & 0xFF
            buf[target, 66] = (l0 >> 16) & 0xFF
        else:
            buf[target, 64] = lens & 0xFF
            buf[target, 65] = (lens >> 8) & 0xFF
            buf[target, 66] = (lens >> 16) & 0xFF
        buf[target, 67] = 1  # live-row flag (mlen == 0 is legal)

    buf[target, :64] = sig_arr
    if same_len:
        if l0:
            buf[target, 68 : 68 + l0] = np.frombuffer(
                b"".join(msgs), np.uint8
            ).reshape(n, l0)
    else:
        for row, m in zip(rows, msgs):
            buf[row, 68 : 68 + len(m)] = np.frombuffer(m, np.uint8)
    slab.dirty = target if contig else rows
    slab.layout = layout
    return buf


def _payload_width(items: list[tuple[bytes, bytes, bytes]]) -> int:
    return 68 + _bucket_mlen(max((len(m) for _, m, _ in items), default=0))


def assemble_payload(
    items: list[tuple[bytes, bytes, bytes]], rows: np.ndarray, vpad: int
) -> np.ndarray:
    """One-shot payload assembly into a fresh buffer (profiling/compat
    entry point); the hot path recycles per-entry slabs instead
    (CombBatchVerifier.submit)."""
    slab = _PayloadSlab(vpad, _payload_width(items))
    return _fill_payload(slab, items, np.asarray(rows, dtype=np.int64))


class CombBatchVerifier:
    """BatchVerifier (crypto/crypto.go:47-55) bound to a cached set.

    add() expects pubkeys that are members of the bound validator set; a
    foreign key silently demotes the whole batch to the uncached kernel
    (TpuEd25519BatchVerifier), preserving results and blame order.  add()
    only appends — all assembly, hashing, and transfer happen in one
    vectorized verify() call.
    """

    # told while this batch waits for its program to compile (submit());
    # the verify service sets it per dispatched batch
    on_compile = None

    def __init__(self, entry: _CacheEntry):
        self._entry = entry
        self._rows: list[int] = []
        self._row_set: set[int] = set()
        self._items: list[tuple[bytes, bytes, bytes]] = []
        self._fallback = None
        self.last_timings: dict[str, float] = {}  # ms per phase, set by verify()

    def __len__(self) -> int:
        return len(self._items)

    def add(self, pub_key: bytes, msg: bytes, sig: bytes) -> None:
        if len(pub_key) != 32 or len(sig) != 64:
            raise ValueError("malformed ed25519 pubkey or signature")
        if len(msg) >= 1 << 24:
            # the payload's mlen field is 3 bytes; a silent wrap would
            # verify against a truncated message (vote sign-bytes are
            # ~100 B — anything near 16 MiB is caller error)
            raise ValueError("message too large for batch verification")
        self._items.append((pub_key, msg, sig))
        if self._fallback is not None:
            self._fallback.add(pub_key, msg, sig)
            return
        row = self._entry.index.get(pub_key)
        if row is None or row in self._row_set:
            # key outside the cached set, or a second signature under the
            # same key (the scatter is one row per validator): demote to
            # the uncached kernel, replaying everything added so far
            from .verifier import TpuEd25519BatchVerifier

            self._fallback = TpuEd25519BatchVerifier()
            for p, m, s in self._items:
                self._fallback.add(p, m, s)
            return
        self._row_set.add(row)
        self._rows.append(row)

    def submit(self):
        """Dispatch the batch WITHOUT waiting for the result, and without
        even blocking on host assembly: the slab fill + transfer + kernel
        dispatch run on a dedicated staging thread, so the caller's
        thread is free the moment the ticket exists and call N+1's host
        work (vote decoding, batch building, the next submit) genuinely
        overlaps call N's assembly AND device execution — the double
        buffer the blocksync verify-ahead pipeline (blocksync/reactor.py,
        blocksync/replay.py) is built around.  Returns an opaque ticket
        for collect(); tickets resolve in submission order."""
        if self._fallback is not None:
            self._fallback.on_compile = self.on_compile
            return ("sync", self._fallback.verify())
        n = len(self._rows)
        if n == 0:
            return ("sync", (False, []))
        _mhub().verify_batch_width.observe(float(n))
        # same rule as the uncached kernel: a small batch (few signers
        # of a large cached set) finishes sooner on the host even though
        # the tables are warm
        from .verifier import _COMB_PROGRAMS, _device_batch_min, host_route

        if n < _device_batch_min():
            return ("sync", host_route(self._items, "comb"))

        idx = np.asarray(self._rows, dtype=np.int64)
        # real snapshot for the staging thread: a verifier is one batch
        # (every call site builds a fresh one per commit); copying makes
        # a stray post-submit add() harmless to the in-flight ticket
        items = list(self._items)
        entry = self._entry
        width = _payload_width(items)
        # A batch whose program is not compiled yet waits for it on the
        # staging thread — minutes on a cold cache, whether this batch
        # compiles it or the one queued ahead does.  That wait is work,
        # not a hung device: whoever runs a clock on the batch
        # (``on_compile``, verifysvc/service) is told from here until
        # stage() has the program in hand; the slab fill, the transfer
        # and the dispatch stay on the clock.  (A mesh entry's sharded
        # program still compiles inside its first call, unannounced.)
        on_compile = self.on_compile
        waiting = (
            on_compile is not None
            and entry.verify_fn is None
            and entry.mesh is None
            and _program_key(entry, width) not in _COMB_PROGRAMS
        )
        if waiting:
            on_compile(True)
        m = _mhub()
        m.verify_submit_queue_depth.add(1)

        def stage():
            import jax.numpy as jnp

            timings = {}
            slab = None
            try:
                try:
                    fn = self._program(width)
                finally:
                    if waiting:
                        on_compile(False)
                # One TIGHT (V, 68 + maxm) row: R | s | mlen(3B LE) | live |
                # msg.  The call ships only irreducible bytes in ONE
                # transfer: no SHA padding (rebuilt on device,
                # ops/sha2.ram_blocks_from_parts), no pubkeys (device-resident
                # in the cache entry), no zero blocks.  The slab is recycled
                # host memory — steady state allocates nothing.
                with tracing.phase(
                    "verify.slab_fill", "assembly", timings, "assembly_ms"
                ):
                    slab = entry.acquire_slab(width)
                    payload = _fill_payload(slab, items, idx)
                with tracing.phase(
                    "verify.h2d_dispatch", "h2d_dispatch", timings,
                    "h2d_dispatch_ms",
                ):
                    out = fn(
                        entry.tables, entry.valid, entry.pubs,
                        jnp.asarray(payload),
                    )
                m.verify_staging_busy.inc(
                    (timings["assembly_ms"] + timings["h2d_dispatch_ms"]) / 1e3
                )
                return out, slab, timings
            except BaseException:
                # a failed fill/dispatch must not leak the pooled slab —
                # each loss would put steady state back on fresh
                # allocations
                if slab is not None:
                    slab.retire()
                    entry.release_slab(slab)
                raise
            finally:
                m.verify_submit_queue_depth.add(-1)

        try:
            # under the batch's trace identity (the scheduler installs it
            # around this call), so that the staging thread's spans carry
            # the id the dispatch, the wait and the blame unpack carry
            fut = _staging_executor().submit(tracing.carry_context(stage))
        except BaseException:
            m.verify_submit_queue_depth.add(-1)  # stage() never ran
            if waiting:
                on_compile(False)
            raise
        return ("dev", (fut, idx))

    def collect(self, ticket) -> tuple[bool, list[bool]]:
        """Wait for a submit() ticket and unpack (all_ok, per-signature).

        One device->host fetch: the program returns a single packed array
        [ok bitmap | all_ok byte] — a second fetch would be a second
        device round trip.  The blame bitmap is indexed with the
        row order captured at submit time, so per-signature ordering is
        preserved however deep the pipeline runs."""
        kind, payload = ticket
        if kind == "sync":
            return payload
        fut, idx = payload
        # Two distinct waits, measured separately: fut.result() blocks
        # until the STAGING thread finishes (queue + slab fill + H2D +
        # dispatch — in the submit-then-collect-immediately pattern this
        # covers the whole staging pass, which must not be billed to the
        # device), then np.asarray blocks until the KERNEL's result lands.
        waits: dict[str, float] = {}
        with tracing.phase(
            "verify.staging_wait", "staging_wait", waits, "staging_wait_ms"
        ):
            out, slab, timings = fut.result()
        try:
            with tracing.phase(
                "verify.device_wait", "device_wait", waits, "device_wait_ms"
            ):
                host = np.asarray(out)  # the one blocking device fetch
        except BaseException:
            # async dispatch errors surface at this fetch (a lost
            # device, an HBM OOM): same no-leak invariant as stage()
            slab.retire()
            self._entry.release_slab(slab)
            raise
        # the kernel has consumed the staged payload; recycle the slab
        self._entry.release_slab(slab)
        self.last_timings.update(timings)
        self.last_timings.update(waits)
        with tracing.span("verify.blame_unpack"):
            all_ok = bool(host[-1])
            picked = (
                np.unpackbits(host[:-1], count=self._entry.vpad)
                .astype(bool)[idx]
            )
            return all_ok, picked.tolist()

    def verify(self) -> tuple[bool, list[bool]]:
        self.last_timings = {}
        outer: dict[str, float] = {}
        with tracing.phase("verify.submit", None, outer, "submit_ms"):
            ticket = self.submit()
        # no span of its own: collect()'s waits and the blame unpack are
        # the spans of these lines
        with tracing.phase(None, None, outer, "kernel_ms"):
            result = self.collect(ticket)
        if ticket[0] == "sync":
            # host-routed (small batch / fallback): all work happened
            # inside submit(); labeling it assembly_ms would corrupt the
            # phase breakdowns the measurement scripts record
            self.last_timings = {"host_ms": outer["submit_ms"]}
        else:
            # collect() merged the staging thread's assembly_ms /
            # h2d_dispatch_ms into last_timings already; kernel_ms is the
            # caller-visible wait (device execution minus what overlapped)
            self.last_timings.update(outer)
        return result

    def _program(self, width: int):
        """The verify program for this entry's lane count and payload
        rows ``width`` bytes wide, compiled at the first use by any
        entry of that shape.  Called on the staging thread only."""
        e = self._entry
        if e.verify_fn is None and e.mesh is not None:
            # multi-chip: tables + rows sharded over the mesh's lane
            # axis, psum/all_gather combine (parallel/verify.py)
            import functools

            from ..parallel.verify import sharded_verify_cached

            e.verify_fn = functools.partial(sharded_verify_cached, e.mesh)
        if e.verify_fn is not None:
            return e.verify_fn

        def lower():
            import jax

            from ..ops import comb

            # materialize the process-global B table OUTSIDE any trace:
            # created lazily inside the jit it would be a leaked tracer
            comb.get_b_tables()
            return jax.jit(_device_verify).lower(
                *(
                    jax.ShapeDtypeStruct(x.shape, x.dtype)
                    for x in (e.tables, e.valid, e.pubs)
                ),
                jax.ShapeDtypeStruct((e.vpad, width), np.uint8),
            )

        from .verifier import _COMB_PROGRAMS, _COMB_PROGRAMS_MTX, program_for

        compiled = []  # program_for announces a compile, and only that
        prog = program_for(
            _COMB_PROGRAMS, _COMB_PROGRAMS_MTX, _program_key(e, width),
            lower, compiled.append,
        )
        hub = _mhub()
        hub.comb_program_cache.inc(result="compile" if compiled else "hit")
        if compiled:
            from ..ops import comb, field

            # both are fixed when the program is traced
            hub.comb_fold_chains.set(
                comb.fold_chains(e.vpad), lanes=str(e.vpad)
            )
            hub.comb_pow_form.set(
                1, lanes=str(e.vpad), form=field.pow_form()
            )
        return prog


def _program_key(entry: _CacheEntry, width: int) -> tuple:
    """What the single-device program depends on: lanes and payload
    width."""
    return (entry.vpad, width)


def _device_verify(tables, valid, pubs, payload):
    """The single-device comb verify program on a tight payload.

    payload rows: R(32) | s(32) | mlen(3B LE) | live(1B) | msg(maxm).
    Returns ONE uint8 array [packbits(ok & live) | all_ok] so the caller
    pays a single device->host fetch.

    Manifest kernel ``comb_device_verify``.
    """
    import jax
    import jax.numpy as jnp

    from ..ops import comb, sha2

    bt = comb.get_b_tables()
    with jax.named_scope("payload_parse"):
        r, s, blocks, active, live = sha2.parse_verify_payload(payload, pubs)
    k_digest = sha2.sha512_blocks(blocks, active)
    ok = comb.verify_cached(tables, valid, r, s, k_digest, bt)
    with jax.named_scope("pack_result"):
        bits = jnp.packbits(ok & live)
        all_ok = jnp.all(ok | ~live).astype(jnp.uint8)
        return jnp.concatenate([bits, all_ok[None]])


def lane_count(n_keys: int, mesh) -> int:
    """The lanes a set of ``n_keys`` binds at: padded to the chip's lane
    bucket, or to the mesh's width when sharded."""
    d = LANE_BUCKET if mesh is None else mesh.devices.size
    return n_keys + (-n_keys) % d


def _bucket_mlen(mlen: int) -> int:
    """Round a max message length up to a small set of compiled widths:
    one program per (valset, bucket) rather than one per distinct length
    (vote sign-bytes drift by a byte when heights/timestamps cross varint
    boundaries)."""
    if mlen <= 32:
        return 32
    return -(-mlen // 64) * 64
