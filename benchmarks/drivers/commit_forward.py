"""One caller, closed loop, forward along a chain whose validator set
moves: one request is ``types/validation.verify_commit`` of the next
height's commit against that height's set, and no height is asked for
twice.  The set at every height differs from the last by one key, so
each request meets a set the process has not bound and waits, in the
caller's thread as consensus does, for ``ValsetCombCache.ensure`` to bind
it from the newest entry (one fresh key built on the host, the other
rows gathered on the device) and, the LRU being full, to drop the
oldest.  A validator or full node of a chain whose active set changes
every block lives like this.

traffic: {"driver": "commit_forward", "warm_s": <seconds>}

The chain is ``benchmarks/light_chain.Chain`` (upstream's genMockNode
with ChangeKeys(1)); the sets the requests use are the ones a node
derives, each from the last by ``ValidatorSet.update_with_change_set``,
and set-up holds every one of them to the chain's.  The driver sets no
``COMETBFT_TPU_*`` variable.  What decides ``correct`` is
``benchmarks/reference.py`` through ``benchmarks/checks.py``, on sets
bound incrementally, and the cache's own counters: every request of the
window one miss, none a hit.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

from .. import checks, data, light_chain, reference
from . import commit_serial
from .light_walk import Collections, grew

# the hub's counters that tell a bind, its kind and its price, each with
# the label sets read; a program that lacks one is read without it
COUNTERS = {
    "comb_table_cache": [{"result": r} for r in ("hit", "miss", "building")],
    "comb_program_cache": [{"result": r} for r in ("hit", "compile")],
    "comb_table_bind": [{"kind": k} for k in ("full", "incremental")],
    "comb_fresh_keys": [{}],
    "comb_table_evictions": [{}],
}
KEPT_BACK = 2  # the chain's last heights, for the check after the window


@dataclass
class State:
    chain: light_chain.Chain
    sets: dict  # height -> the ValidatorSet the node derived for it
    commits: dict  # height -> (BlockID, Commit) in the program's types
    next_height: int
    last_height: int  # the last height a request may ask for
    warm_s: float
    log: object
    facts: dict = field(default_factory=dict)


# ------------------------------------------------- the program's objects


def validator_set(vals):
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.validators import Validator, ValidatorSet

    return ValidatorSet([Validator(ed25519.PubKey(v.pub), v.power) for v in vals])


def derived_sets(chain: light_chain.Chain) -> dict:
    """The set of every height as a node comes by it: height 1's from
    genesis, each later one a copy of the last with the block's
    validator updates applied (the dropped key at power 0, the new one
    at its power); each held to the chain's set, key and power, in set
    order.  Nothing else reads a set before its request: its pubkey
    list and its per-set facts are built inside the request."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.validators import Validator

    sets = {1: validator_set(chain.vals(1))}
    for h in range(2, chain.heights + 1):
        old = {v.pub for v in chain.vals(h - 1)}
        new = {v.pub: v.power for v in chain.vals(h)}
        changes = [Validator(ed25519.PubKey(p), 0) for p in old - new.keys()]
        changes += [Validator(ed25519.PubKey(p), new[p]) for p in new.keys() - old]
        vals = sets[h - 1].copy()
        vals.update_with_change_set(changes)
        checks.require(
            [(v.pub_key.bytes(), v.voting_power) for v in vals.validators]
            == [(v.pub, v.power) for v in chain.vals(h)],
            f"height {h}: the derived set differs from the chain's")
        sets[h] = vals
    return sets


def program_commit(block):
    """A reference block's commit as (BlockID, Commit) of the program."""
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, PartSetHeader,
    )
    from cometbft_tpu.wire.canonical import Timestamp

    hash_, total, parts = block.commit_block_id
    block_id = BlockID(hash=hash_, part_set_header=PartSetHeader(total, parts))
    sigs = [
        CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=s.address,
            timestamp=Timestamp(seconds=s.seconds),
            signature=s.signature,
        )
        for s in block.sigs
    ]
    return block_id, Commit(height=block.commit_height, round=0,
                            block_id=block_id, signatures=sigs)


def newcomer_first(chain: light_chain.Chain) -> int:
    """The first height whose new key sorts first in its set.  The lane
    padding repeats a set's first key, so there the pad lanes are fresh
    too and the incremental bind runs its second shape."""
    h = 2
    while chain.vals(h)[0] in chain.vals(h - 1):
        h += 1
    return h


# ------------------------------------------------------------ the checks


def cache_counts() -> dict:
    from cometbft_tpu.utils.metrics import hub

    counts = {}
    for name, series in COUNTERS.items():
        counter = getattr(hub(), name, None)
        if counter is None:
            continue
        for labels in series:
            counts[".".join([name, *labels.values()])] = counter.value(**labels)
    return counts


def _valset(chain, vals) -> data.Valset:
    return data.Valset(chain.chain_id, vals, [], chain.seed, chain.t_genesis)


def _commit_and_rows(chain, h: int):
    """Height h's commit in the program's types, and the reference's own
    sign-bytes for each of its rows."""
    block = chain.block(h)
    return *program_commit(block), [
        block.sign_bytes(i) for i in range(len(block.sigs))]


def check_vector(chain, h: int, vals, tamper: bool) -> None:
    """Height h's commit, or a copy with signatures flipped, through the
    batch verifier a node makes for ``vals`` (which binds the set if it
    is not bound): the verdict vector against the reference's."""
    _, commit, rows = _commit_and_rows(chain, h)
    flipped: list[int] = []
    if tamper:
        commit, flipped = checks.tampered(commit, len(rows))
    checks.check_vector(_valset(chain, vals), commit, rows, flipped)


def check_refused(chain, h: int, vals) -> None:
    """verify_commit refuses height h's flipped copy at the index where
    the reference's verdicts first fail."""
    block_id, commit, rows = _commit_and_rows(chain, h)
    bad, _ = checks.tampered(commit, len(rows))
    oracle = [
        reference.verify(v.pub, rows[i], bad.signatures[i].signature)
        for i, v in enumerate(chain.vals(h))
    ]
    checks.check_refused(_valset(chain, vals), block_id, h, bad,
                         oracle.index(False))


def check_height(chain, h: int, vals) -> None:
    check_vector(chain, h, vals, tamper=False)
    check_vector(chain, h, vals, tamper=True)
    check_refused(chain, h, vals)


# ------------------------------------------------------------ one request


def following(state: State):
    """The next height's arguments, or None at the end of the chain."""
    h = state.next_height
    if h > state.last_height:
        return None
    state.next_height = h + 1
    return (state.chain.chain_id, state.sets[h], state.commits[h][0], h,
            state.commits[h][1])


def verdict(args) -> None:
    from cometbft_tpu.types.validation import verify_commit

    verify_commit(*args)


# ----------------------------------------------------------------- set-up


def setup(cell, seed: int, log) -> State:
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.models.comb_verifier import global_cache

    cfg = cell.config
    t0 = time.monotonic()
    chain = light_chain.Chain(cfg, seed)
    commits = {h: program_commit(chain.block(h))
               for h in range(1, chain.heights + 1)}
    log(f"{chain.heights} heights, {chain.heights * chain.width} signatures, "
        f"made in {time.monotonic() - t0:.1f} s")
    t0 = time.monotonic()
    sets = derived_sets(chain)
    log(f"derived sets equal the chain's, {time.monotonic() - t0:.1f} s")
    state = State(chain, sets, commits, 3, chain.heights - KEPT_BACK,
                  float(cell.traffic["warm_s"]), log)
    before = cache_counts()
    # sets under the program's floor are answered from the host (and the
    # run says so): nothing to bind or to fill there
    binds = chain.width >= crypto_batch.comb_min()

    def bind_in_full(vals):
        if not binds:
            return None
        t0 = time.monotonic()
        entry = global_cache().ensure(vals.pub_keys_bytes())
        log(f"a set bound in full after {time.monotonic() - t0:.1f} s")
        return entry

    # the churn program's second shape, on a chain of other keys so that
    # no set of the chain proper is bound before its request: a set bound
    # in full, then its successor, whose newcomer sorts first
    shapes = light_chain.Chain(
        dict(cfg, name=cfg["name"] + ".shapes"), seed, heights=1 << 30)
    special = newcomer_first(shapes)
    bind_in_full(validator_set(shapes.vals(special - 1)))
    t0 = time.monotonic()
    check_height(shapes, special, validator_set(shapes.vals(special)))
    log(f"first verdict vectors (the newcomer sorts first) after "
        f"{time.monotonic() - t0:.1f} s")
    # the chain proper: set 1 in full, and from height 2 on every set
    # from the one before it
    entry = bind_in_full(sets[1])
    t0 = time.monotonic()
    check_height(chain, 2, sets[2])
    log(f"height 2 (one fresh lane) checked after {time.monotonic() - t0:.1f} s")
    state.facts = {"shapes_height": special, "fill_heights": 0}
    if entry is not None:
        # forward until the LRU has dropped an entry: from then on every
        # bind drops one (every entry of this chain has one size)
        room = global_cache()._max_bytes // entry.tables.nbytes
        t0 = time.monotonic()
        while grew(before, cache_counts()).get("comb_table_cache.miss", 0) <= room:
            verdict(following(state))
        state.facts.update(
            cache_entries=room, fill_heights=state.next_height - 3,
            fill_s=time.monotonic() - t0)
    state.facts["setup_cache"] = grew(before, cache_counts())
    log(f"{state.facts['fill_heights']} more heights to fill the cache; "
        f"set-up's binds: {state.facts['setup_cache']}")
    return state


def warm(state: State) -> None:
    """The window's own loop for ``warm_s`` seconds, none of it sampled."""
    i, end = 0, time.monotonic() + state.warm_s
    while time.monotonic() < end and (args := following(state)):
        verdict(args)
        i += 1
    state.log(f"warm-up: {i} verdicts in {state.warm_s:g} s")


def run(state: State, window) -> None:
    collections = Collections()
    before = cache_counts()
    first = state.next_height
    gc.callbacks.append(collections)
    try:
        while not window.expired():
            args = following(state)
            if args is None:
                state.facts["chain_exhausted"] = True
                break
            window.tick()
            with window.request():
                t0 = time.perf_counter()
                try:
                    verdict(args)
                finally:
                    window.sample("request_s", time.perf_counter() - t0)
    finally:
        gc.callbacks.remove(collections)
    state.facts.update(
        window_first_height=first, window_last_height=state.next_height - 1,
        window_requests=state.next_height - first,
        window_cache=grew(before, cache_counts()),
        window_collections={
            "count": collections.count, "seconds": collections.seconds})


def finish(state: State) -> list[str]:
    """Once the window has closed, through the entry and the programs it
    drove, each on a set the process has not bound: the flipped copy of
    the next height gives the reference's verdict vector, and the one
    after it is refused at the reference's index.  And every request of
    the window was one miss served by one incremental bind."""
    problems = []
    h = state.next_height
    try:
        check_vector(state.chain, h, state.sets[h], tamper=True)
        check_refused(state.chain, h + 1, state.sets[h + 1])
    except checks.CheckFailure as e:
        problems.append(f"after the window: {e}")
    inside = state.facts.get("window_cache")
    if inside is None:
        return problems
    requests = state.facts["window_requests"]
    want = {"comb_table_cache.hit": 0, "comb_table_cache.building": 0,
            "comb_program_cache.compile": 0, "comb_table_cache.miss": requests}
    if "comb_table_bind.full" in cache_counts():
        want.update({"comb_table_bind.full": 0,
                     "comb_table_bind.incremental": requests})
    for k, n in want.items():
        if inside.get(k, 0) != n:
            problems.append(f"inside the window: {k} grew by "
                            f"{inside.get(k, 0):g} in {requests} requests")
    return problems


def end_to_end(state: State, window) -> dict:
    values = commit_serial.end_to_end(state, window)
    if values:
        values["commit_forward"] = state.facts
    return values
