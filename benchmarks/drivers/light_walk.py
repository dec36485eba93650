"""One caller, closed loop: a fresh light client a request.  Each
request constructs ``cometbft_tpu.light.Client`` (its trust
initialisation verifies the trusted header's own commit) and asks it for
the chain's last height, which it reaches by skipping verification; the
next request starts when the last returns.  A relayer or light-proxy
host brings up light clients of a production chain like this, and has
met the chain's validator sets before: set-up's first walk binds them.

traffic: {"driver": "light_walk", "warm_s": <seconds>}

The driver enters at ``light.Client`` and nowhere below it, sets no
``COMETBFT_TPU_*`` variable and binds no set itself.  What decides
``correct`` is ``benchmarks/reference_light.py``: the blocks the client
fetches, in order, and the heights it ends up trusting equal the
reference's walk (the bisection is deterministic, so equal fetches and
an equal store are equal hops with equal results), in set-up and in
every request of the window; where the program records ``light.hop``
spans, set-up also compares them hop by hop.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from fractions import Fraction

from .. import checks, light_chain, stats
from .. import reference_light as ref

# the hub's counters that tell a bind and a compile, and their results
CACHE_RESULTS = {
    "comb_table_cache": ("hit", "miss", "building"),
    "comb_program_cache": ("hit", "compile"),
}


@dataclass
class State:
    chain: light_chain.Chain
    trusted_height: int
    target: int
    now_ns: int
    period_ns: int
    level: Fraction
    want: ref.WalkResult
    tampered: dict  # name -> (provider arguments, the reference's walk)
    warm_s: float
    log: object
    facts: dict = field(default_factory=dict)


class WalkDiffers(Exception):
    pass


# ------------------------------------------------------------ one request


def walk(state: State, provider: light_chain.Provider):
    """One request: a fresh client over ``provider``, trusted at the
    trusted height by hash, asked for the target height.  Returns the
    heights it trusts afterwards."""
    from cometbft_tpu.light import Client, LightStore, TrustOptions
    from cometbft_tpu.store.db import MemDB

    root = state.chain.block(state.trusted_height)
    db = MemDB()
    client = Client(
        state.chain.chain_id,
        TrustOptions(state.period_ns, state.trusted_height, root.header.hash()),
        provider, [provider], LightStore(db),
        trust_level=state.level,
    )
    client.verify_light_block_at_height(state.target, state.now_ns)
    return trusted_heights(db)


def trusted_heights(db) -> list[int]:
    """What the client's store holds, read off the driver's own MemDB:
    light/store keeps a block under a prefix and its height as eight
    big-endian bytes (decoding every stored block would cost more than
    a hop)."""
    return [int.from_bytes(k[-8:], "big") for k, _ in db.iterator(b"", b"\xff")]


def request(state: State) -> None:
    """A walk over the honest chain; raises unless it went as the
    reference's did."""
    provider = light_chain.Provider(state.chain)
    got = walk(state, provider)
    if provider.fetched != state.want.fetched:
        raise WalkDiffers(
            f"fetched {provider.fetched}, the reference {state.want.fetched}")
    if got != state.want.trusted:
        raise WalkDiffers(
            f"trusts {got}, the reference {state.want.trusted}")


# ----------------------------------------------------------------- set-up


def cache_counts() -> dict:
    from cometbft_tpu.utils.metrics import hub

    return {
        f"{name}.{result}": getattr(hub(), name).value(result=result)
        for name, results in CACHE_RESULTS.items() for result in results
    }


def table_build_s() -> float:
    """Seconds the program spent building comb tables, host and device
    (verify_phase_seconds, phases table_build_*)."""
    from cometbft_tpu.utils.metrics import hub

    return sum(
        float(line.rsplit(" ", 1)[1])
        for line in hub().verify_phase_seconds.expose()
        if "_sum{" in line and 'phase="table_build' in line
    )


def grew(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0.0) for k in after
            if after[k] != before.get(k, 0.0)}


def hop_spans() -> list[tuple[int, int, str]]:
    """(from, to, result) of the ``light.hop`` spans in the ring, in
    order; empty where the program records none."""
    from cometbft_tpu.utils import tracing

    return [
        (e["args"]["from"], e["args"]["to"], e["args"]["result"])
        for e in tracing.chrome_trace_events()
        if e.get("ph") == "X" and e["name"] == "light.hop"
    ]


def flipped_rows(rows) -> set[int]:
    """Three commit indices among the counted ``rows``, at the fractions
    the other cells flip at."""
    return {rows[int(f * len(rows))][0] for f in checks.FLIPPED}


def check_refused(state: State, name: str) -> None:
    """A walk over a chain with one tampered block: the client must give
    up where the reference does, and for a bad signature name the index
    the reference names."""
    kwargs, want = state.tampered[name]
    provider = light_chain.Provider(state.chain, **kwargs)
    try:
        walk(state, provider)
    except Exception as e:  # noqa: BLE001 - any refusal; which one is compared
        said = f"{type(e).__name__}: {e}"
    else:
        raise checks.CheckFailure(f"{name}: the tampered chain was accepted")
    checks.require(
        provider.fetched == want.fetched,
        f"{name}: fetched {provider.fetched}, the reference {want.fetched}")
    last = want.hops[-1][2]
    checks.require(
        want.ended == ref.REFUSED and last.kind == ref.REFUSED,
        f"{name}: the reference itself does not refuse ({want.ended})")
    if last.index is not None:
        checks.require(
            f"(#{last.index})" in said,
            f"{name}: refused, but not at signature {last.index}: {said}")
    else:
        checks.require(
            "validators hash" in said.lower() or "validators_hash" in said,
            f"{name}: refused, but not for the set's hash: {said}")


def check_vectors(state: State, trusted: ref.Block, new: ref.Block) -> None:
    """Both passes of one hop through the batch verifier the path makes
    (bound to the trusted set, then to the new one; background class),
    the per-signature vector against the reference's, honest and with
    three of the counted signatures flipped."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.verifysvc.service import Klass

    t_rows, enough = ref.trusting_rows(trusted, new, state.level)
    c_rows, enough2 = ref.commit_rows(new)
    checks.require(enough and enough2, "the reference cannot make the hop")
    flipped = sorted(flipped_rows(c_rows) | {t_rows[len(t_rows) // 2][0]})
    bad = state.chain.flipped(new.height, flipped)
    for block, want_bad in ((new, []), (bad, flipped)):
        for name, bound, rows in (("trusting", trusted, t_rows),
                                  ("commit", new, c_rows)):
            bv = crypto_batch.create_batch_verifier(
                "ed25519", pubkeys=[v.pub for v in bound.vals],
                klass=Klass.BACKGROUND)
            for idx, pub in rows:
                bv.add(pub, block.sign_bytes(idx), block.sigs[idx].signature)
            ok, vec = bv.verify()
            oracle = [block.sig_ok(idx, pub) for idx, pub in rows]
            checks.require(
                list(vec) == oracle,
                f"{name} pass of hop {trusted.height}->{new.height}: "
                "verdicts differ from the reference's")
            blamed = [idx for (idx, _), good in zip(rows, oracle) if not good]
            checks.require(
                blamed == [i for i in want_bad if i in {r[0] for r in rows}],
                f"{name} pass: the reference itself blames the wrong rows")
            checks.require(ok == (not blamed), f"{name} pass: all-ok flag is wrong")


def setup(cell, seed: int, log) -> State:
    cfg = cell.config
    chain = light_chain.Chain(cfg, seed)
    trust = cfg["client"]
    trusted_height, target = cfg["trusted_height"], chain.heights
    level = Fraction(*trust["trust_level"])
    period_ns = trust["trusting_period_s"] * ref.NS
    now_ns = (chain.seconds(target) + trust["now_after_last_block_s"]) * ref.NS
    t0 = time.monotonic()
    want = ref.walk(chain.block, trusted_height, target, now_ns, period_ns, level)
    checks.require(want.ended == ref.OK, f"the reference's walk ends {want.ended}")
    kinds = [r.kind for _, _, r in want.hops]
    log(f"reference walk: {kinds.count(ref.OK)} accepted and "
        f"{kinds.count(ref.CANT_BE_TRUSTED)} refused hops over "
        f"{len(set(want.fetched))} blocks, {time.monotonic() - t0:.1f} s")
    # the first accepted hop that is not adjacent: its target is tampered
    a, b = next((a, b) for a, b, r in want.hops if r.kind == ref.OK and b > a + 1)
    c_rows, _ = ref.commit_rows(chain.block(b))
    flipped = sorted(flipped_rows(c_rows))
    tampered = {}
    for name, block in (("flipped signatures", chain.flipped(b, flipped)),
                        ("wrong validators_hash", chain.wrong_set_hash(b))):
        kwargs = {"replaced": {b: block}}
        tampered[name] = (kwargs, ref.walk(
            light_chain.Provider(chain, **kwargs).block_at, trusted_height,
            target, now_ns, period_ns, level))
    state = State(chain, trusted_height, target, now_ns, period_ns, level,
                  want, tampered, float(cell.traffic["warm_s"]), log)

    # the first walk: it binds every set the walk meets and compiles
    # every shape of the incremental table build, in the caller's thread
    before, built = cache_counts(), table_build_s()
    t0 = time.monotonic()
    try:
        request(state)
    except WalkDiffers as e:
        raise checks.CheckFailure(f"first walk: {e}") from e
    first_walk_s = time.monotonic() - t0
    after = cache_counts()
    spans = hop_spans()
    if spans:
        hops = [(a_, b_, r.kind) for a_, b_, r in want.hops]
        checks.require(
            spans == hops,
            f"first walk: the client's hops {spans} differ from the "
            f"reference's {hops}")
    else:
        log("the program records no light.hop span: hops compared through "
            "the fetches and the store alone")
    state.facts = {
        "first_walk_s": first_walk_s,
        "first_walk_cache": grew(before, after),
        "first_walk_table_build_s": table_build_s() - built,
        "hops": {"accepted": kinds.count(ref.OK),
                 "refused": kinds.count(ref.CANT_BE_TRUSTED)},
        "blocks_fetched": len(set(want.fetched)),
    }
    log(f"first walk {first_walk_s:.1f} s, "
        f"{state.facts['first_walk_table_build_s']:.1f} s of it table builds: "
        f"{state.facts['first_walk_cache']}")
    t0 = time.monotonic()
    request(state)
    log(f"second walk {time.monotonic() - t0:.3f} s")
    check_vectors(state, chain.block(a), chain.block(b))
    for name in tampered:
        check_refused(state, name)
    state.facts["before_window_cache"] = cache_counts()
    return state


def warm(state: State) -> None:
    """The window's own loop for ``warm_s`` seconds, none of it sampled."""
    i, end = 0, time.monotonic() + state.warm_s
    while time.monotonic() < end:
        request(state)
        i += 1
    state.log(f"warm-up: {i} walks in {state.warm_s:g} s")
    state.facts["before_window_cache"] = cache_counts()


class Collections:
    """The interpreter's garbage collections while installed, by
    generation: how many and how long.  A full one costs tens of
    milliseconds of a request, so the share of requests that meet one
    sits in the tail that verdict_p90_ms reads."""

    def __init__(self):
        self.count, self.seconds, self._t0 = [0, 0, 0], [0.0, 0.0, 0.0], 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count[info["generation"]] += 1
            self.seconds[info["generation"]] += time.perf_counter() - self._t0


def run(state: State, window) -> None:
    collections = Collections()
    gc.callbacks.append(collections)
    try:
        while not window.expired():
            window.tick()
            with window.request():
                t0 = time.perf_counter()
                try:
                    request(state)
                finally:
                    window.sample("request_s", time.perf_counter() - t0)
    finally:
        gc.callbacks.remove(collections)
    state.facts["window_cache"] = grew(
        state.facts["before_window_cache"], cache_counts())
    state.facts["window_collections"] = {
        "count": collections.count, "seconds": collections.seconds}


def finish(state: State) -> list[str]:
    """Once the window has closed: both tampered chains again, through
    the entry and the programs the window drove; and no set was bound
    and no program compiled inside the window."""
    problems = []
    for name in state.tampered:
        try:
            check_refused(state, name)
        except checks.CheckFailure as e:
            problems.append(f"after the window: {e}")
    inside = state.facts.get("window_cache", {})
    for k in ("comb_table_cache.miss", "comb_table_cache.building",
              "comb_program_cache.compile"):
        if inside.get(k):
            problems.append(f"inside the window: {k} grew by {inside[k]:g}")
    return problems


def end_to_end(state: State, window) -> dict:
    ms = [1e3 * s for s in window.samples.get("request_s", [])]
    state.log(
        f"{len(ms)} walk samples, {stats.samples_beyond(len(ms), 90)} "
        f"beyond the 90th percentile; {state.facts}"
    )
    if not ms:
        return {}
    return {
        "verdict_p50_ms": stats.percentile(ms, 50),
        "verdict_p90_ms": stats.percentile(ms, 90),
        # for the facts line only: no metric of BENCHMARK.json has the name
        "verdict_ms_thirds": stats.thirds(ms),
        "light_walk": state.facts,
    }
