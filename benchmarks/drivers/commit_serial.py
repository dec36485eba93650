"""One caller, closed loop: ``types/validation.verify_commit`` over a
pool of distinct commits of consecutive heights, cycled.  Consensus is
one caller that waits for its verdict, so the next commit is asked for
only when the last one is answered.

traffic: {"driver": "commit_serial", "pool": <commits>, "warm_verdicts": <n>}
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from .. import checks, data, stats


@dataclass
class State:
    valset: data.Valset
    pool: list
    warm_verdicts: int
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
    return State(valset, pool, cell.traffic["warm_verdicts"], log)


def warm(state: State) -> None:
    for i in range(state.warm_verdicts):
        verdict(state.valset, state.pool[i % len(state.pool)])


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
    }
