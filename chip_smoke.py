#!/usr/bin/env python3
"""chip_smoke.py: the commit-verification path, once, on one TPU chip.

The quickest proof that the system still starts on the chip.  One
process, no children, no probe: it fails at once unless JAX's default
device is a TPU, then drives the path a node drives —
types/validation -> crypto/batch -> verifysvc -> models -> ops, and the
same path as blocksync drives it — under the knobs' defaults, at two
widths no run may cut:

* 10,000 ed25519 validators (BASELINE.json: "VerifyCommit p50 latency @
  10k validators"): a chain of a few blocks built by the BlockExecutor,
  every commit 10,000 signatures over real canonical sign-bytes.  The
  first commits verify through the uncached program (bucket 16,384)
  while the set's comb tables build in the background; once the tables
  are resident, more commits verify through the comb program, and a
  fresh BlocksyncReactor applies the chain.
* 175 ed25519 validators (CometBFT QA v1: 200 nodes / 175 validators,
  the shape production chains have): at COMETBFT_TPU_COMB_MIN's default
  (32) or above, like every set the device serves at all, so the set is
  bound at first sight (a host table build of seconds in the caller's
  thread, 152 KB of device memory a lane) and the comb program serves
  it at 256 lanes: verify_commit, the light check that counts every
  signature (evidence), and the default verify_commit_light a light
  client or blocksync makes, whose +2/3 = 117 signatures are live rows
  of the same program.  The run fails if a bound set compiles more
  than one program per lane count, or is served by the uncached one.

Every verdict is compared with the host oracle
(crypto/ed25519.verify_signature) position by position; a commit with a
few signatures flipped must be refused at the first bad index; the
10,000-leaf validator-set hash must equal the host tree.  And because
every host route gives the same verdicts, the run reads the COUNTERS,
not only the verdicts (route_report / route_failures): it fails unless
the service never left ``tpu`` mode, nothing was host-re-verified,
rejected or timed out, the span ring holds no fallback span, and every
batch the scheduler dispatched was waited for on the device exactly
once.

The exponentiation of point decompression (ops/field.pow_p58) runs on a
TPU as one on-chip kernel, whose Mosaic lowering no CPU test sees: at
both lane counts the run compares it (and F.invert) on the device with
the array form, limb for limb, on adversarial inputs, and fails unless
the gauge cometbft_verify_comb_pow_form names ``kernel`` for each
compiled comb program (check_pow_kernel, PR 35).

Keys, transactions and timestamps come from SEED.  A run in which every
check held writes two lines to stdout and exits 0: the facts it
gathered (versions, widths, batches per program, the counters, set-up
seconds — compile and build times, not performance claims), then, last,
the result line and nothing but it:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it.  Any other run exits non-zero and
writes nothing to stdout.
"""

from __future__ import annotations

import copy
import hashlib
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEED = 20260926
WIDTH_LARGE = 10_000
WIDTH_SMALL = 175
N_BLOCKS = 4  # blocks 2..4 carry the commits for heights 1..3
COMB_COMMITS_MIN = 3
TABLES_RESIDENT_DEADLINE_S = 600.0
FLIPPED = (0.7777, 0.0123, 0.9001)  # tampered rows, as fractions of the width

# spans that mean a batch was answered from the host (models/verifier,
# models/comb_verifier, verifysvc/client, verifysvc/service)
FALLBACK_SPANS = (
    "verify.host_route",
    "verify.svc_fallback",
    "verify.collect_stall_fallback",
    "verify.failover.reverify",
)


class SmokeFailure(Exception):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------ jax events


class JaxEvents:
    """What JAX itself reports (jax.monitoring): seconds spent in each
    program's backend compile, and persistent-cache requests and hits."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE_LOWER = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
    )
    REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
    HITS = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.compile_s: dict[str, list[float]] = {}
        self.trace_lower_s: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self._mtx = threading.Lock()

    def install(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        fun = kw.get("fun_name", "?").removeprefix("jit(").removesuffix(")")
        with self._mtx:
            if event == self.COMPILE:
                self.compile_s.setdefault(fun, []).append(round(secs, 3))
            elif event in self.TRACE_LOWER:
                self.trace_lower_s[fun] = self.trace_lower_s.get(fun, 0) + secs

    def _event(self, event: str, **kw) -> None:
        with self._mtx:
            self.counts[event] = self.counts.get(event, 0) + 1

    def summary(self) -> dict:
        """Per program, every compile (or cache load) of a second or
        more; the small ones only as a total."""
        with self._mtx:
            big = {
                k: v for k, v in self.compile_s.items() if max(v) >= 1.0
            }
            rest = sum(
                sum(v) for k, v in self.compile_s.items() if k not in big
            )
            return {
                "backend_compile_s": big,
                "trace_and_lower_s": {
                    k: round(self.trace_lower_s.get(k, 0.0), 3) for k in big
                },
                "other_programs_total_s": round(rest, 3),
                "cache_requests": self.counts.get(self.REQUESTS, 0),
                "cache_hits": self.counts.get(self.HITS, 0),
            }


# ------------------------------------------------------------ the chain


class Chain:
    def __init__(self, chain_id, state0, blocks, block_ids, states, consumer):
        self.chain_id = chain_id
        self.vals = state0.validators
        self.blocks = blocks  # blocks[h-1] = block at height h
        self.block_ids = block_ids  # block_ids[h-1] = its BlockID
        self.states = states  # states[h] = producer state after block h
        self.consumer = consumer  # (state0, executor, block store, conns)

    def commit_for(self, h: int):
        """The commit for height h, as block h+1 carries it."""
        return self.blocks[h].last_commit


def make_keys(width: int, tag: bytes):
    from cometbft_tpu.crypto import ed25519 as host

    return [
        host.PrivKey.from_seed(
            hashlib.sha256(b"%d|%s|%d" % (SEED, tag, i)).digest()
        )
        for i in range(width)
    ]


def build_chain(keys, n_blocks: int, chain_id: str) -> Chain:
    """A valid chain produced through the real BlockExecutor
    (PrepareProposal / apply) over a kvstore app with a few transactions
    per block, every commit signed by all of ``keys`` — plus a second,
    untouched node to consume it (tests/test_blocksync_replay's recipe)."""
    from cometbft_tpu.abci import KVStoreApplication
    from cometbft_tpu.abci.kvstore import default_lanes
    from cometbft_tpu.mempool import CListMempool, MempoolConfig
    from cometbft_tpu.proxy import local_client_creator, new_app_conns
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import make_genesis_state
    from cometbft_tpu.state.store import StateStore
    from cometbft_tpu.store.block_store import BlockStore
    from cometbft_tpu.store.db import MemDB
    from cometbft_tpu.types.block import (
        BlockID, ExtendedCommit, ExtendedCommitSig,
    )
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.vote import Vote
    from cometbft_tpu.types.vote_set import VoteSet
    from cometbft_tpu.wire import abci_pb as pb
    from cometbft_tpu.wire.canonical import PRECOMMIT_TYPE, Timestamp

    t_genesis = 1_700_000_000 + SEED % 1000
    genesis = GenesisDoc(
        chain_id=chain_id,
        genesis_time=Timestamp(seconds=t_genesis),
        validators=[
            GenesisValidator(
                pub_key_type="ed25519", pub_key_bytes=k.pub_key().data,
                power=10,
            )
            for k in keys
        ],
        app_hash=b"",
    )

    def make_node():
        app = KVStoreApplication(lanes=default_lanes())
        conns = new_app_conns(local_client_creator(app))
        conns.start()
        app.init_chain(pb.InitChainRequest(chain_id=chain_id))
        state_store = StateStore(MemDB())
        state = make_genesis_state(genesis)
        state_store.bootstrap(state)
        block_store = BlockStore(MemDB())
        mem = CListMempool(
            MempoolConfig(), conns.mempool,
            lane_priorities=default_lanes(), default_lane="default",
        )
        ex = BlockExecutor(
            state_store, conns.consensus, mem, block_store=block_store
        )
        return state, ex, block_store, conns, mem

    state, ex, _store, conns, mem = make_node()
    state0 = state
    by_addr = {k.pub_key().address(): k for k in keys}
    blocks, block_ids, states = [], [], {0: state}
    last_ext = None
    try:
        for h in range(1, n_blocks + 1):
            for j in range(3):
                mem.check_tx(b"smoke-%d-%d-%d=%d" % (SEED, h, j, h * 10 + j))
            proposer = state.validators.get_proposer().address
            block, parts = ex.create_proposal_block(h, state, last_ext, proposer)
            bid = BlockID(hash=block.hash(), part_set_header=parts.header)
            vs = VoteSet(chain_id, h, 0, PRECOMMIT_TYPE, state.validators)
            for i, v in enumerate(state.validators.validators):
                vote = Vote(
                    type=PRECOMMIT_TYPE, height=h, round=0, block_id=bid,
                    timestamp=Timestamp(seconds=t_genesis + h),
                    validator_address=v.address, validator_index=i,
                )
                vote.signature = by_addr[v.address].sign(
                    vote.sign_bytes(chain_id)
                )
                vs.add_vote(vote)
            commit = vs.make_commit()
            blocks.append(block)
            block_ids.append(bid)
            state = ex.apply_verified_block(state, bid, block)
            states[h] = state
            last_ext = ExtendedCommit(
                height=commit.height, round=commit.round,
                block_id=commit.block_id,
                extended_signatures=[
                    ExtendedCommitSig(commit_sig=cs) for cs in commit.signatures
                ],
            )
    finally:
        conns.stop()
    return Chain(chain_id, state0, blocks, block_ids, states, make_node()[:4])


def tampered(commit, width: int):
    """A copy of ``commit`` with a few signatures flipped (well under
    1/3 of the power), and the first flipped index."""
    idxs = sorted({int(f * width) for f in FLIPPED})
    bad = copy.deepcopy(commit)
    for i in idxs:
        cs = bad.signatures[i]
        cs.signature = cs.signature[:-1] + bytes([cs.signature[-1] ^ 1])
    return bad, idxs


# ------------------------------------------------------------ the checks


def check_vector(chain: Chain, commit, want_bad: list[int]) -> None:
    """The per-signature vector of the batch verifier the node would
    make for this set, against the host oracle, position by position."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519 as host

    sign_bytes = commit.vote_sign_bytes_fn(chain.chain_id)
    items = [
        (v.pub_key.bytes(), sign_bytes(i), commit.signatures[i].signature)
        for i, v in enumerate(chain.vals.validators)
    ]
    bv = crypto_batch.create_batch_verifier(
        "ed25519", pubkeys=chain.vals.pub_keys_bytes()
    )
    for it in items:
        bv.add(*it)
    ok, vec = bv.verify()
    oracle = [host.verify_signature(*it) for it in items]
    require(len(vec) == len(oracle), "verdict vector has the wrong length")
    diff = [i for i, (a, b) in enumerate(zip(vec, oracle)) if a != b]
    require(
        not diff,
        f"{chain.chain_id}: device verdicts differ from the host oracle at "
        f"rows {diff[:8]} ({len(diff)} in all)",
    )
    require(
        [i for i, b in enumerate(oracle) if not b] == want_bad,
        f"{chain.chain_id}: the oracle itself blames the wrong rows",
    )
    require(ok == (not want_bad), f"{chain.chain_id}: all-ok flag is wrong")


def check_refused(chain: Chain, h: int, bad, first_bad: int) -> None:
    from cometbft_tpu.types.validation import (
        CommitVerificationError, verify_commit,
    )

    try:
        verify_commit(chain.chain_id, chain.vals, chain.block_ids[h - 1], h, bad)
    except CommitVerificationError as e:
        require(
            f"(#{first_bad})" in str(e),
            f"{chain.chain_id}: tampered commit refused at the wrong index: {e}",
        )
        return
    raise SmokeFailure(f"{chain.chain_id}: tampered commit was accepted")


def verify_height(chain: Chain, h: int, light: bool = False, **light_kw) -> None:
    """verify_commit, or verify_commit_light, on the commit for height
    h; raises what they raise."""
    from cometbft_tpu.types.validation import verify_commit, verify_commit_light

    fn = verify_commit_light if light else verify_commit
    fn(chain.chain_id, chain.vals, chain.block_ids[h - 1], h,
       chain.commit_for(h), **light_kw)


def verify_heights(chain: Chain) -> None:
    """Every commit the chain carries, full and light."""
    for h in range(1, len(chain.blocks)):
        verify_height(chain, h)
        verify_height(chain, h, light=True)


def check_valset_hash(chain: Chain) -> None:
    from cometbft_tpu.crypto import merkle

    leaves = [v.bytes() for v in chain.vals.validators]
    host_root = merkle.hash_from_byte_slices(leaves, device=False)
    require(
        merkle.hash_from_byte_slices(leaves, device=True) == host_root,
        f"{chain.chain_id}: device Merkle root differs from the host tree",
    )
    require(
        chain.vals.hash() == host_root,
        f"{chain.chain_id}: ValidatorSet.hash() differs from the host tree",
    )


def blocksync_apply(chain: Chain, timeout_s: float) -> None:
    """A fresh BlocksyncReactor applies the chain (verify-ahead commit
    checks + validate_block's embedded verify_commit) and must end at
    the producer's height and app hash."""
    from cometbft_tpu.blocksync import pool as pool_mod
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor

    state0, ex, store, conns = chain.consumer
    n = len(chain.blocks)
    target = n - 1  # the last block's commit would ride in block n+1
    stop = threading.Event()
    try:
        reactor = BlocksyncReactor(state0, ex, store, block_sync=False)
        reactor.pool.set_peer_range("p1", 1, n)
        for h in range(1, n + 1):
            reactor.pool.requesters[h] = pool_mod._Requester(
                h, peer_id="p1", got_block_from="p1", block=chain.blocks[h - 1]
            )
        reactor.is_running = lambda: not stop.is_set()
        reactor.pool.is_running = lambda: True
        reactor._check_switch_to_consensus = lambda state: False
        th = threading.Thread(
            target=reactor._pool_routine, name="smoke-blocksync", daemon=True
        )
        th.start()
        deadline = time.monotonic() + timeout_s
        while store.height < target and time.monotonic() < deadline:
            time.sleep(0.05)
        stop.set()
        th.join(timeout=60)
        require(not th.is_alive(), "blocksync routine did not stop")
        got = ex.store.load()
        want = chain.states[target]
        require(
            store.height == target and got.last_block_height == target,
            f"{chain.chain_id}: blocksync stalled at {store.height}/{target}",
        )
        require(
            got.app_hash == want.app_hash,
            f"{chain.chain_id}: blocksync app hash differs from the producer's",
        )
        require(
            store.load_block(target).hash() == chain.blocks[target - 1].hash(),
            f"{chain.chain_id}: blocksync stored a different block",
        )
    finally:
        stop.set()
        conns.stop()


# ------------------------------------------------ which route served what


def check_pow_kernel(lanes: int) -> None:
    """ops/field's exponentiations as the device programs run them
    (F.pow_form(): on a TPU one on-chip kernel, the Mosaic lowering no
    CPU test sees) against the array form on the same device, limb for
    limb, and against Python's pow, on adversarial inputs: 0, 1, p - 1,
    non-canonical values up to 2^264, signed limbs at the MULIN bounds,
    random elements for the rest."""
    import jax
    import numpy as np

    from cometbft_tpu.ops import field as F

    rng = np.random.default_rng(SEED + lanes)
    top = np.full(F.NLIMBS, 8204, dtype=np.int64)
    top[0] = 14336
    zigzag = top * np.where(np.arange(F.NLIMBS) % 2, -1, 1)
    values = [0, 1, F.P - 1, F.P, F.P + 3, (1 << 255) - 1, (1 << 256) - 1,
              (1 << 264) - 1, (1 << 264) - 9728]
    cols = [[(v >> (F.BITS * i)) & F.MASK for i in range(F.NLIMBS)]
            for v in values] + [top, -top, zigzag, -zigzag]
    cols += [F.to_limbs(int.from_bytes(rng.bytes(32), "little"))
             for _ in range(lanes - len(cols))]
    x = np.stack(cols, axis=-1).astype(np.int32)  # (22, lanes)
    freeze = jax.jit(F.freeze)
    for name, shipped, array, exponent in (
        ("pow_p58", F.pow_p58, F._pow_p58_chain, (F.P - 5) // 8),
        ("invert", F.invert, F._invert_chain, F.P - 2),
    ):
        got = np.asarray(jax.jit(shipped)(x))
        require(
            np.array_equal(got, np.asarray(jax.jit(array)(x))),
            f"{name} at {lanes} lanes: the {F.pow_form()} form and the "
            "array form differ",
        )
        canon = np.asarray(freeze(got))
        for i in list(range(16)) + [lanes // 2, lanes - 1]:
            require(
                F.from_limbs(canon[:, i])
                == pow(F.from_limbs(x[:, i]), exponent, F.P),
                f"{name} at {lanes} lanes: lane {i} is not x^e mod p",
            )


def _counter_total(counter) -> float:
    return sum(float(line.rsplit(" ", 1)[1]) for line in counter.expose())


def route_report() -> dict:
    """Everything that says which route served: the service's own
    stats, the hub's fallback counters, and the span ring reduced to one
    row per batch the scheduler dispatched (spans of one batch share its
    trace id, verifysvc/client)."""
    from cometbft_tpu.models.verifier import _next_bucket
    from cometbft_tpu.utils import tracing
    from cometbft_tpu.utils.metrics import hub
    from cometbft_tpu.verifysvc.service import global_service

    st = global_service().stats()
    m = hub()
    by_trace: dict[str, list[dict]] = {}
    names: dict[str, int] = {}
    table_builds = []
    for e in tracing.chrome_trace_events():
        if e.get("ph") not in ("X", "i"):
            continue
        names[e["name"]] = names.get(e["name"], 0) + 1
        if e["name"] == "verify.table_build":
            table_builds.append(e)
        tid = (e.get("args") or {}).get("trace_id")
        if tid is not None:
            by_trace.setdefault(tid, []).append(e)
    programs: dict[str, int] = {}
    waits_wrong = 0
    for evs in by_trace.values():
        for d in (e for e in evs if e["name"] == "verify.sched.dispatch"):
            seen = [e["name"] for e in evs]
            if "verify.uncached_assemble" in seen:
                prog = "uncached_b%d" % _next_bucket(int(d["args"]["sigs"]))
            elif "verify.device_wait" in seen:
                prog = "comb"
            else:
                prog = "host"
            programs[prog] = programs.get(prog, 0) + 1
            if seen.count("verify.device_wait") != 1:
                waits_wrong += 1
    return {
        "backend_mode": st["backend_mode"],
        "failover_trips": st["failover"]["trips"],
        "rejected": st["rejected"],
        "dispatched_batches": st["dispatched_batches"],
        "verify_svc_host_reverify": _counter_total(m.verify_svc_host_reverify),
        "verify_svc_collect_timeout": _counter_total(m.verify_svc_collect_timeout),
        "verify_svc_failover": _counter_total(m.verify_svc_failover),
        "comb_table_cache": {
            r: m.comb_table_cache.value(result=r)
            for r in ("hit", "miss", "building")
        },
        "comb_program_cache": {
            r: m.comb_program_cache.value(result=r) for r in ("hit", "compile")
        },
        "commit_assemble_rows": {
            r: m.commit_assemble_rows.value(path=r)
            for r in ("columns", "per_row")
        },
        "fallback_spans": {n: names.get(n, 0) for n in FALLBACK_SPANS},
        "dispatch_spans": names.get("verify.sched.dispatch", 0),
        "device_wait_spans": names.get("verify.device_wait", 0),
        "batches_without_exactly_one_device_wait": waits_wrong,
        "spans_dropped": tracing.dropped_count(),
        "batches_by_program": programs,
        # each A-table build span holds trace + lower + compile + run;
        # its end, on time.perf_counter()'s clock, is when the tables
        # became resident
        "table_build_span_s": [round(e["dur"] / 1e6, 2) for e in table_builds],
        "table_build_end_s": [
            round((e["ts"] + e["dur"]) / 1e6, 2) for e in table_builds
        ],
    }


def route_failures(rep: dict) -> list[str]:
    """Why this run does NOT prove that the device served every batch
    (empty: it does)."""
    out = []
    if rep["backend_mode"] != "tpu":
        out.append(f"backend_mode is {rep['backend_mode']!r}")
    if rep["failover_trips"]:
        out.append(f"{rep['failover_trips']} failover trip(s)")
    if any(rep["rejected"].values()):
        out.append(f"rejected submits: {rep['rejected']}")
    for c in ("verify_svc_host_reverify", "verify_svc_collect_timeout",
              "verify_svc_failover"):
        if rep[c]:
            out.append(f"{c} = {rep[c]:g}")
    for n, k in rep["fallback_spans"].items():
        if k:
            out.append(f"{k} {n} span(s)")
    if rep["spans_dropped"]:
        out.append(f"span ring dropped {rep['spans_dropped']} events")
    if rep["dispatch_spans"] != sum(rep["dispatched_batches"].values()):
        out.append("span ring and service disagree on the batches dispatched")
    if (rep["device_wait_spans"] != rep["dispatch_spans"]
            or rep["batches_without_exactly_one_device_wait"]):
        out.append(
            f"{rep['dispatch_spans']} batches dispatched, "
            f"{rep['device_wait_spans']} device waits, "
            f"{rep['batches_without_exactly_one_device_wait']} batches "
            "without exactly one"
        )
    if rep["batches_by_program"].get("host"):
        out.append(f"{rep['batches_by_program']['host']} batch(es) ran no "
                   "device program")
    return out


# ----------------------------------------------------------------- legs


def run(width_large: int, width_small: int, facts: dict) -> dict:
    """Both legs, every check; raises SmokeFailure on the first that
    does not hold.  ``facts`` is filled as the run goes, so a failure
    leaves behind what was learned up to it."""
    import jax

    from cometbft_tpu.models.comb_verifier import ValsetCombCache, global_cache
    from cometbft_tpu.models.verifier import _next_bucket
    from cometbft_tpu.ops import comb
    from cometbft_tpu.utils import compilecache, tracing
    from cometbft_tpu.utils.metrics import hub
    from cometbft_tpu.verifysvc.service import reset_global_service

    steps = facts.setdefault("setup_seconds", {})

    def timed(name: str, t0: float) -> None:
        steps[name] = round(time.monotonic() - t0, 2)
        print(f"chip_smoke: {name} {steps[name]} s", file=sys.stderr, flush=True)

    cache_dir = compilecache.enable()
    entries_at_start = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    events = JaxEvents()
    events.install()
    tracing.set_enabled(True, ring_capacity=1 << 20)
    tracing.reset()

    def compiles_of(program: str) -> int:
        return len(events.compile_s.get(program, ()))

    comb_compiles_0 = hub().comb_program_cache.value(result="compile")

    # ---- 10,000 validators: chain, validator-set hash, then the
    # uncached program while the comb tables build in the background
    t0 = time.monotonic()
    big = build_chain(make_keys(width_large, b"L"), N_BLOCKS, f"smoke-{width_large}")
    timed("chain_large", t0)
    check_valset_hash(big)
    t0 = time.monotonic()
    comb.get_b_tables()  # timed apart; the comb program would build it
    timed("b_table_build", t0)
    bad_big, flipped_big = tampered(big.commit_for(1), width_large)
    fp = ValsetCombCache.fingerprint(big.vals.pub_keys_bytes())
    t0 = time.monotonic()
    t_bound = time.perf_counter()  # the span ring's clock
    # the tampered vector first: this batch binds the set, so it is the
    # one batch sure to go through the uncached program (it compiles)
    check_vector(big, bad_big, flipped_big)
    timed(f"first_verify_uncached_b{_next_bucket(width_large)}", t0)
    if global_cache().get(fp) is None:  # else the tables are there already
        check_refused(big, 1, bad_big, flipped_big[0])
    h = 0
    while global_cache().get(fp) is None:  # keep verifying meanwhile
        require(
            time.monotonic() - t0 < TABLES_RESIDENT_DEADLINE_S,
            f"comb tables not resident after {TABLES_RESIDENT_DEADLINE_S:g}s",
        )
        h = h % (len(big.blocks) - 1) + 1
        verify_height(big, h)
    for t in threading.enumerate():
        if t.name == "comb-build":  # a process that exits mid-compile aborts
            t.join(timeout=60)

    # ---- the comb program: its first verify apart (it compiles), then
    # the checks, every commit full and light, and blocksync
    n0 = compiles_of("_device_verify")
    t0 = time.monotonic()
    check_vector(big, big.commit_for(1), [])
    timed("first_verify_comb", t0)
    steps["first_verify_comb_compiles_in_step"] = compiles_of("_device_verify") - n0
    check_vector(big, bad_big, flipped_big)
    check_refused(big, 1, bad_big, flipped_big[0])
    verify_heights(big)
    t0 = time.monotonic()
    blocksync_apply(big, timeout_s=300)
    timed("blocksync_apply", t0)

    # ---- 175 validators: bound at first sight in the caller's thread,
    # then the comb program at the set's own lane count; the first
    # verify apart (table build and compile), the first light check
    # apart too (it must compile nothing: fewer live rows, same program)
    t0 = time.monotonic()
    small = build_chain(make_keys(width_small, b"S"), N_BLOCKS, f"smoke-{width_small}")
    timed("chain_small", t0)
    check_valset_hash(small)
    bad_small, flipped_small = tampered(small.commit_for(1), width_small)
    n0 = compiles_of("_device_verify")
    t0 = time.monotonic()
    check_vector(small, small.commit_for(1), [])
    timed("first_verify_comb_small", t0)
    steps["first_verify_comb_small_compiles_in_step"] = (
        compiles_of("_device_verify") - n0
    )
    check_vector(small, bad_small, flipped_small)
    check_refused(small, 1, bad_small, flipped_small[0])
    t0 = time.monotonic()
    verify_height(small, 1, light=True)
    timed("first_light_verify_comb_small", t0)
    verify_heights(small)
    for h in range(1, len(small.blocks)):
        verify_height(small, h, light=True, count_all_signatures=True)

    # ---- which route served: the counters, not only the verdicts
    rep = route_report()
    facts["routes"] = rep
    # resident when the build's span ended, not when the run next looked
    if rep["table_build_end_s"]:
        steps["tables_resident_after_first_bind"] = round(
            rep["table_build_end_s"][0] - t_bound, 2
        )
    compiled = events.summary()
    facts["compile"] = {
        "cache_dir": cache_dir,
        "cache_entries_at_start": entries_at_start,
        "cache_warm": bool(entries_at_start and compiled["cache_hits"]),
        **compiled,
    }
    stats = jax.devices()[0].memory_stats() or {}
    facts["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    problems = route_failures(rep)
    progs = rep["batches_by_program"]
    warming = "uncached_b%d" % _next_bucket(width_large)
    for name, least in ((warming, 1), ("comb", 2 * COMB_COMMITS_MIN)):
        if progs.get(name, 0) < least:
            problems.append(f"program {name} served {progs.get(name, 0)} "
                            f"batch(es), need {least}")
    for name in sorted(set(progs) - {warming, "comb"}):
        problems.append(f"program {name} served {progs[name]} batch(es) "
                        "of a set that was bound")
    if rep["comb_table_cache"]["hit"] < 1:
        problems.append("comb table cache never hit")
    rows = rep["commit_assemble_rows"]
    if rows["per_row"] or not rows["columns"]:
        problems.append(f"commit.assemble encoded its rows {rows}, not all "
                        "by columns")
    lanes = {
        global_cache().get(
            ValsetCombCache.fingerprint(c.vals.pub_keys_bytes())
        ).vpad
        for c in (big, small)
    }
    facts["comb_lanes"] = sorted(lanes)
    facts["comb_fold_chains"] = {
        str(n): hub().comb_fold_chains.value(lanes=str(n)) for n in sorted(lanes)
    }
    print(
        f"chip_smoke: comb_fold_chains {facts['comb_fold_chains']}",
        file=sys.stderr, flush=True,
    )
    if not all(facts["comb_fold_chains"].values()):
        problems.append("a compiled comb program set no fold-chains gauge")
    # the exponentiation of decompress: one on-chip kernel on a TPU
    # (what the gauge has to say, so not asked of ops/field.pow_form)
    form = "kernel" if jax.default_backend() == "tpu" else "array"
    facts["comb_pow_form"] = {
        str(n): [f for f in ("kernel", "array")
                 if hub().comb_pow_form.value(lanes=str(n), form=f)]
        for n in sorted(lanes)
    }
    print(
        f"chip_smoke: comb_pow_form {facts['comb_pow_form']}",
        file=sys.stderr, flush=True,
    )
    if any(got != [form] for got in facts["comb_pow_form"].values()):
        problems.append(f"a compiled comb program's pow form is not {form}")
    t0 = time.monotonic()
    for n in sorted(lanes):
        check_pow_kernel(n)
    timed("pow_kernel_check", t0)
    compiled_comb = rep["comb_program_cache"]["compile"] - comb_compiles_0
    if compiled_comb > len(lanes):
        problems.append(
            f"{compiled_comb:g} comb programs compiled for {len(lanes)} "
            "lane count(s)"
        )
    require(not problems, "; ".join(problems))
    reset_global_service()
    tracing.set_enabled(False)
    return facts


def main() -> int:
    import jax
    import jaxlib

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU: JAX's default device is "
            f"{devs[0].platform!r} ({devs[0].device_kind})",
            file=sys.stderr,
        )
        return 2
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    facts: dict = {
        "device": device,
        "versions": {
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
        },
        "widths": [WIDTH_LARGE, WIDTH_SMALL],
        "seed": SEED,
    }
    t0 = time.monotonic()
    try:
        run(WIDTH_LARGE, WIDTH_SMALL, facts)
    except BaseException:
        # what was learned up to the failure, for whoever debugs it —
        # on stderr: stdout carries a result only when there is one
        print(json.dumps(facts, default=str), file=sys.stderr, flush=True)
        raise
    facts["total_seconds"] = round(time.monotonic() - t0, 1)
    print("chip_smoke facts: " + json.dumps(facts), flush=True)
    # the result line: these keys and no others, and nothing after it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
