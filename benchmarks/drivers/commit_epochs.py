"""One caller, closed loop, forward through the epochs of a chain that
rotates part of its validator set at every epoch boundary, and never an
epoch twice.  A request is ``types/validation.verify_commit`` with the
clock around that call and nothing else; an epoch is ``epoch_heights``
requests:

1. the rotation's: the epoch's first commit, on the ``ValidatorSet``
   object the node derived for the epoch, which no call has used and
   whose keys ``ValsetCombCache`` has not bound.  From ``comb_async_min``
   validators up the program answers such a miss from the uncached
   program while the thread ``comb-build`` binds the set from the newest
   entry (the warming route, ``verifysvc/client.resolve_mode``);
2. then, OUTSIDE the clock, the driver waits until the epoch's entry is
   resident (``warming_wait_s``).  That stands for the rest of the block
   interval, of which a closed loop has none: a chain's next commit comes
   seconds later.  Without it, how many requests of an epoch go uncached
   would be a race between two threads;
3. the epoch's second commit, ``epoch_heights - 1`` times, each a hit
   over the comb program.

traffic: {"driver": "commit_epochs", "warm_s": <seconds>}

The chain is ``benchmarks/epoch_chain.Chain``; the sets the requests use
are the ones a node derives, each from the last by ``ValidatorSet.copy``
and ``update_with_change_set``, and set-up holds every one of them to
the chain's.  The driver sets no ``COMETBFT_TPU_*`` variable.  What
decides ``correct`` is ``benchmarks/reference.py`` through
``benchmarks/checks.py``, on both routes, and the cache's own counters:
an epoch of the window is one miss, one incremental bind of the rotated
keys, one eviction and ``epoch_heights - 1`` hits.
"""

from __future__ import annotations

import gc
import threading
import time
from dataclasses import dataclass, field

from .. import checks, data, epoch_chain, reference, stats
from . import commit_forward, commit_serial
from .light_walk import Collections, grew

ROTATION, SECOND = 0, 1  # the commits of an epoch that are signed
# epochs kept for the check after the window: each verdict asked for on
# the warming route needs a set the process has not bound
KEPT_BACK = 2
POLL_S = 0.002  # between two looks at the cache while a bind is awaited
WAIT_MAX_S = 600.0  # a first bind may compile its churn program


@dataclass
class State:
    chain: epoch_chain.Chain
    sets: dict  # epoch -> the ValidatorSet the node derived for it
    next_epoch: int
    last_epoch: int  # the last epoch a request may ask for
    warm_s: float
    binds: bool  # the sets are wide enough for the program to bind them
    log: object
    facts: dict = field(default_factory=dict)
    kinds: dict = field(default_factory=lambda: {"rotation": [], "hit": []})
    waits: list = field(default_factory=list)


# ------------------------------------------------------------ the sets


def derived_sets(chain: epoch_chain.Chain) -> dict:
    """The set of every epoch as a node comes by it: epoch 0's from
    genesis, each later one a copy of the last with the epoch's
    validator updates applied (state/execution.update_state: the dropped
    keys at power 0, the new ones at the chain's power); each held to
    the chain's set, key and power, in set order.  Nothing else reads a
    set before its first request."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.validators import Validator

    sets = {0: commit_forward.validator_set(chain.vals(0))}
    for e in range(1, chain.epochs):
        old = {v.pub for v in chain.vals(e - 1)}
        new = {v.pub: v.power for v in chain.vals(e)}
        changes = [Validator(ed25519.PubKey(p), 0) for p in old - new.keys()]
        changes += [Validator(ed25519.PubKey(p), new[p]) for p in new.keys() - old]
        vals = sets[e - 1].copy()
        vals.update_with_change_set(changes)
        checks.require(
            [(v.pub_key.bytes(), v.voting_power) for v in vals.validators]
            == [(v.pub, v.power) for v in chain.vals(e)],
            f"epoch {e}: the derived set differs from the chain's")
        sets[e] = vals
    return sets


def cache_counts() -> dict:
    """``commit_forward.cache_counts`` and, where the program has it,
    the verdicts that took the warming route (over every lane count)."""
    from cometbft_tpu.utils.metrics import hub

    counts = commit_forward.cache_counts()
    warming = getattr(hub(), "comb_warming", None)
    if warming is not None:
        counts["comb_warming"] = checks._counter_total(warming)
    return counts


def wait_resident(state: State, e: int) -> float:
    """Until epoch e's entry is in the table cache; the seconds waited.
    Asked after a request on the epoch's set, which has left the set's
    pubkey list on it."""
    from cometbft_tpu.models.comb_verifier import global_cache

    if not state.binds:
        return 0.0
    cache = global_cache()
    fp = cache.fingerprint(state.sets[e].pub_keys_bytes())
    t0 = time.monotonic()
    while cache.get(fp) is None:
        checks.require(time.monotonic() - t0 < WAIT_MAX_S,
                       f"epoch {e}: its tables were not bound in {WAIT_MAX_S:g} s")
        time.sleep(POLL_S)
    return time.monotonic() - t0


def no_bind_running() -> None:
    """A process that exits while ``comb-build`` is inside a compile
    aborts: wait for every such thread."""
    for t in threading.enumerate():
        if t.name == "comb-build":
            t.join(WAIT_MAX_S)


# ------------------------------------------------------------ the checks


def _valset(state: State, e: int) -> data.Valset:
    chain = state.chain
    return data.Valset(chain.chain_id, state.sets[e], [], chain.seed,
                       chain.t_genesis)


def _routed(state: State, what: str, route: str, check) -> None:
    """``check()``, and that its one look at the table cache was a
    ``route`` (hit | miss).  A miss at ``comb_async_min`` keys or more is
    the warming route: the uncached program answers."""
    before = cache_counts()
    check()
    if not state.binds:
        return  # answered from the host, and the run says so
    seen = grew(before, cache_counts())
    checks.require(
        {k: n for k, n in seen.items() if k.startswith("comb_table_cache.")}
        == {"comb_table_cache." + route: 1},
        f"{what}: not one {route} of the table cache: {seen}")


def check_vector(state: State, e: int, k: int, tamper: bool, route: str) -> None:
    """Epoch e's k-th commit, or a copy with signatures flipped, through
    the batch verifier a node makes for the epoch's derived set: the
    verdict vector against the reference's."""
    sc = state.chain.commit(e, k)
    commit, flipped = sc.commit, []
    if tamper:
        commit, flipped = checks.tampered(commit, state.chain.width)
    _routed(state, f"epoch {e} commit {k} vector", route,
            lambda: checks.check_vector(
                _valset(state, e), commit, sc.sign_bytes, flipped))


def check_refused(state: State, e: int, k: int, route: str) -> None:
    """verify_commit refuses the flipped copy at the index where the
    reference's verdicts first fail."""
    sc = state.chain.commit(e, k)
    bad, _ = checks.tampered(sc.commit, state.chain.width)
    oracle = [
        reference.verify(v.pub, sc.sign_bytes[i], bad.signatures[i].signature)
        for i, v in enumerate(state.chain.vals(e))
    ]
    _routed(state, f"epoch {e} commit {k} refusal", route,
            lambda: checks.check_refused(
                _valset(state, e), sc.block_id, sc.height, bad,
                oracle.index(False)))


# ------------------------------------------------------------- one epoch


def epoch(state: State, ask) -> bool:
    """The next epoch's requests, each through ``ask(kind, args)``, which
    says whether another is wanted; False at the end of the chain too.
    The wait for the epoch's entry is made in any case: no bind is left
    running."""
    e = state.next_epoch
    if e > state.last_epoch:
        state.facts["chain_exhausted"] = True
        return False
    state.next_epoch = e + 1
    chain, vals = state.chain, state.sets[e]
    first, second = chain.commit(e, ROTATION), chain.commit(e, SECOND)
    more = ask("rotation", (chain.chain_id, vals, first.block_id, first.height,
                            first.commit))
    state.waits.append(wait_resident(state, e))
    for _ in range(chain.epoch_heights - 1):
        if not more:
            break
        more = ask("hit", (chain.chain_id, vals, second.block_id,
                           second.height, second.commit))
    return more


def unclocked(kind: str, args) -> bool:
    commit_forward.verdict(args)
    return True


# ----------------------------------------------------------------- set-up


def setup(cell, seed: int, log) -> State:
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.models.comb_verifier import global_cache

    t0 = time.monotonic()
    chain = epoch_chain.Chain(cell.config, seed)
    for e in range(chain.epochs):
        chain.commit(e, ROTATION), chain.commit(e, SECOND)
    log(f"{chain.epochs} epochs, {2 * chain.epochs * chain.width} signatures, "
        f"{len(chain.skipped)} candidate keys skipped, made in "
        f"{time.monotonic() - t0:.1f} s")
    t0 = time.monotonic()
    sets = derived_sets(chain)
    log(f"derived sets equal the chain's, {time.monotonic() - t0:.1f} s")
    # sets under the program's floor are answered from the host (and the
    # run says so): nothing to bind or to wait for there
    binds = chain.width >= crypto_batch.comb_min()
    state = State(chain, sets, 4, chain.epochs - 1 - KEPT_BACK,
                  float(cell.traffic["warm_s"]), binds, log)
    before = cache_counts()
    # epoch 0 bound in full, as a node binds the set it starts with
    if binds:
        t0 = time.monotonic()
        global_cache().ensure(sets[0].pub_keys_bytes())
        log(f"epoch 0 bound in full after {time.monotonic() - t0:.1f} s")
    t0 = time.monotonic()
    check_vector(state, 0, ROTATION, False, "hit")
    log(f"first verdict vector (comb route) after {time.monotonic() - t0:.1f} s")
    check_vector(state, 0, ROTATION, True, "hit")
    check_refused(state, 0, ROTATION, "hit")
    # the warming route, each time on a set the process has not bound:
    # the honest vector (epoch 1), the flipped one (2), the refusal (3);
    # and epoch 1's incrementally bound entry over the comb program
    t0 = time.monotonic()
    check_vector(state, 1, ROTATION, False, "miss")
    log(f"first verdict vector (warming route: the uncached program at "
        f"{chain.width} rows) after {time.monotonic() - t0:.1f} s")
    waits = [wait_resident(state, 1)]
    check_vector(state, 1, SECOND, False, "hit")
    check_vector(state, 1, SECOND, True, "hit")
    check_refused(state, 1, SECOND, "hit")
    check_vector(state, 2, ROTATION, True, "miss")
    waits.append(wait_resident(state, 2))
    check_refused(state, 3, ROTATION, "miss")
    waits.append(wait_resident(state, 3))
    log(f"both routes checked; waited {[round(w, 2) for w in waits]} s for "
        "the three binds")
    # whole epochs until the cache has dropped an entry: from then on
    # every bind drops one (every entry of this chain has one size)
    filled = 0
    while binds and not grew(before, cache_counts()).get("comb_table_evictions"):
        epoch(state, unclocked)
        filled += 1
    state.facts.update(fill_epochs=filled, setup_waits_s=waits,
                       setup_cache=grew(before, cache_counts()),
                       skipped_candidates=len(chain.skipped))
    log(f"set-up's binds: {state.facts['setup_cache']}")
    return state


def warm(state: State) -> None:
    """The window's own loop for ``warm_s`` seconds and on to the end of
    the epoch it is in, none of it sampled: the window opens on a
    rotation."""
    first, t0 = state.next_epoch, time.monotonic()
    while time.monotonic() < t0 + state.warm_s and epoch(state, unclocked):
        pass
    state.log(f"warm-up: {state.next_epoch - first} epochs in "
              f"{time.monotonic() - t0:.1f} s")


def run(state: State, window) -> None:
    def ask(kind: str, args) -> bool:
        window.tick()
        with window.request():
            t0 = time.perf_counter()
            try:
                commit_forward.verdict(args)
            finally:
                s = time.perf_counter() - t0
                window.sample("request_s", s)
                state.kinds[kind].append(s)
        return not window.expired()

    collections = Collections()
    before = cache_counts()
    first = state.next_epoch
    state.waits.clear()  # the window's own from here
    gc.callbacks.append(collections)
    try:
        while not window.expired() and epoch(state, ask):
            pass
    finally:
        gc.callbacks.remove(collections)
    state.facts.update(
        window_first_epoch=first, window_last_epoch=state.next_epoch - 1,
        window_cache=grew(before, cache_counts()),
        window_collections={
            "count": collections.count, "seconds": collections.seconds})


def finish(state: State) -> list[str]:
    """Once the window has closed, through the entries and the programs
    it drove: the next epoch's flipped rotation commit gives the
    reference's verdict vector on the warming route, the one after it is
    refused there at the reference's index, and its second commit's
    flipped copy gives the vector and the refusal over the comb program
    on the entry just bound.  And every epoch of the window was one miss
    answered on the warming route, one incremental bind of the rotated
    keys, one eviction, and a hit for every other request."""
    problems = []
    e = state.next_epoch
    try:
        check_vector(state, e, ROTATION, True, "miss")
        wait_resident(state, e)
        check_refused(state, e + 1, ROTATION, "miss")
        wait_resident(state, e + 1)
        check_vector(state, e + 1, SECOND, True, "hit")
        check_refused(state, e + 1, SECOND, "hit")
    except checks.CheckFailure as err:
        problems.append(f"after the window: {err}")
    no_bind_running()
    inside = state.facts.get("window_cache")
    if inside is None:
        return problems
    rotations, hits = (len(state.kinds[k]) for k in ("rotation", "hit"))
    want = {"comb_table_cache.miss": rotations, "comb_table_cache.hit": hits,
            "comb_table_cache.building": 0, "comb_program_cache.compile": 0}
    counted = cache_counts()
    if "comb_table_bind.full" in counted:
        want.update({
            "comb_table_bind.full": 0, "comb_table_bind.incremental": rotations,
            "comb_fresh_keys": rotations * state.chain.rotated,
            "comb_table_evictions": rotations})
    if "comb_warming" in counted:
        want["comb_warming"] = rotations
    for k, n in want.items():
        if inside.get(k, 0) != n:
            problems.append(
                f"inside the window: {k} grew by {inside.get(k, 0):g} in "
                f"{rotations} rotations and {hits} other requests")
    return problems


def _ms(seconds: list) -> dict:
    ms = [1e3 * s for s in seconds]
    if not ms:
        return {"n": 0}
    return {"n": len(ms), "min": min(ms), "p50": stats.percentile(ms, 50),
            "p90": stats.percentile(ms, 90), "max": max(ms)}


def end_to_end(state: State, window) -> dict:
    values = commit_serial.end_to_end(state, window)
    if values:
        state.facts.update(
            rotation_ms=_ms(state.kinds["rotation"]),
            hit_ms=_ms(state.kinds["hit"]),
            warming_wait_s=sum(state.waits),
            warming_wait_ms=_ms(state.waits))
        values["commit_epochs"] = state.facts
    return values
