"""One caller, closed loop: ``types/validation.verify_commit`` over a
pool of distinct commits of consecutive heights, cycled.  Consensus is
one caller that waits for its verdict, so the next commit is asked for
only when the last one is answered.

traffic: {"driver": "commit_serial", "pool": <commits>, "warm_s": <seconds>}

The warm-up is a stated time of the cell's own traffic, not a count: a
count shrinks with the verdict, and the first two seconds of steady
traffic read higher than the rest (PERF.md section 6, PR 28), inside
the window if the warm-up is shorter.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from .. import checks, data, stats


@dataclass
class State:
    valset: data.Valset
    pool: list
    warm_s: float
    log: object


def verdict(valset, sc) -> None:
    from cometbft_tpu.types.validation import verify_commit

    verify_commit(valset.chain_id, valset.vals, sc.block_id, sc.height, sc.commit)


def setup(cell, seed: int, log) -> State:
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.models.comb_verifier import global_cache

    valset = data.make_valset(cell.config, seed)
    pool = data.make_commits(valset, cell.traffic["pool"])
    width = valset.vals.size()
    if width >= crypto_batch.comb_min():
        # a set this large is served by the comb program once its tables
        # are on the device.  Build them here, with the cache's own
        # synchronous ensure, so that no batch of this run waits for
        # them in the uncached program (whose bucket-16,384 compile is
        # minutes): the first verify_commit already takes the comb path
        t0 = time.monotonic()
        global_cache().ensure(valset.vals.pub_keys_bytes())
        log(f"comb tables resident after {time.monotonic() - t0:.1f} s")
    first = pool[0]
    t0 = time.monotonic()
    checks.check_vector(valset, first.commit, first.sign_bytes, [])
    log(f"first verdict vector after {time.monotonic() - t0:.1f} s")
    bad, flipped = checks.tampered(first.commit, width)
    checks.check_vector(valset, bad, first.sign_bytes, flipped)
    checks.check_refused(valset, first.block_id, first.height, bad, flipped[0])
    return State(valset, pool, float(cell.traffic["warm_s"]), log)


def warm(state: State) -> None:
    """The window's own loop for ``warm_s`` seconds, none of it sampled."""
    i, end = 0, time.monotonic() + state.warm_s
    while time.monotonic() < end:
        verdict(state.valset, state.pool[i % len(state.pool)])
        i += 1
    state.log(f"warm-up: {i} verdicts in {state.warm_s:g} s")


def run(state: State, window) -> None:
    i = 0
    while not window.expired():
        window.tick()
        sc = state.pool[i % len(state.pool)]
        i += 1
        with window.request():
            t0 = time.perf_counter()
            try:
                verdict(state.valset, sc)
            finally:
                window.sample("request_s", time.perf_counter() - t0)


def finish(state: State) -> list[str]:
    """Once the window has closed, through the entry and the programs it
    drove: a commit of the pool drawn from the seed, with signatures
    flipped, must give the reference's verdict vector and be refused at
    its first flipped index (set-up showed both on the pool's first
    commit; this shows that the window left them so)."""
    sc = state.pool[state.valset.seed % len(state.pool)]
    bad, flipped = checks.tampered(sc.commit, state.valset.vals.size())
    try:
        checks.check_vector(state.valset, bad, sc.sign_bytes, flipped)
        checks.check_refused(
            state.valset, sc.block_id, sc.height, bad, flipped[0])
    except checks.CheckFailure as e:
        return [f"after the window: {e}"]
    return []


def end_to_end(state: State, window) -> dict:
    ms = [1e3 * s for s in window.samples.get("request_s", [])]
    state.log(
        f"{len(ms)} verdict samples, {stats.samples_beyond(len(ms), 90)} "
        "beyond the 90th percentile"
    )
    if not ms:
        return {}
    return {
        "verdict_p50_ms": stats.percentile(ms, 50),
        "verdict_p90_ms": stats.percentile(ms, 90),
        # for the facts line only: no metric of BENCHMARK.json has the name
        "verdict_ms_thirds": stats.thirds(ms),
    }
