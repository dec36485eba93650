"""commit_serial's traffic over a secp256k1 validator set: one caller,
closed loop, ``types/validation.verify_commit`` over a pool of distinct
commits of consecutive heights, cycled.  The loop, the warm-up and the
end-to-end values are commit_serial's; what differs is the data (keys
and signatures from benchmarks/reference_secp.py), the checks (the
verdict vector against that reference, on a commit tampered as
``tampered`` says) and set-up, which compiles the secp program through
the program's announced warm-up before anything verifies.

traffic: {"driver": "commit_serial_secp", "pool": <commits>, "warm_s": <seconds>}

Besides the harness's checks, ``correct`` holds the program's own
counters of the lane to the window: every row the lane verified in it
was verified on the device (``cometbft_verify_secp_rows_total``), one
batch of the set's width a request, and no batch compiled its program
(``cometbft_verify_secp_program_cache_total{result=compile}``).
"""

from __future__ import annotations

import copy
import time
from dataclasses import dataclass, field

from .. import checks, data, data_secp, reference_secp
from . import commit_serial

KEY_TYPE = "secp256k1"

# rows a checked commit is tampered at besides checks.FLIPPED, as
# fractions of the width: a high-S twin of the row's own signature, and
# a signature of the row's sign-bytes by a key of another set
HIGH_S = 0.3
FOREIGN = 0.5

warm = commit_serial.warm
end_to_end = commit_serial.end_to_end


@dataclass
class State(commit_serial.State):
    # the lane's counters when the window opened and when it closed,
    # and the requests the window made
    before: dict = field(default_factory=dict)
    after: dict = field(default_factory=dict)
    requests: int = 0


def setup(cell, seed: int, log) -> State:
    from cometbft_tpu.models import secp_verifier

    warm_program = getattr(secp_verifier, "warm", None)
    if warm_program is None:
        raise checks.CheckFailure(
            "the program has no announced warm-up of its secp256k1 program "
            "(models/secp_verifier.warm): its first commit would compile "
            "under the verify service's collect and failover clocks"
        )
    valset = data_secp.make_valset(cell.config, seed)
    pool = data.make_commits(valset, cell.traffic["pool"])
    width = valset.vals.size()
    first = pool[0]
    t0 = time.monotonic()
    route = warm_program(width, max(map(len, first.sign_bytes)))
    log(f"secp program {route} (bucket, hash) compiled and run after "
        f"{time.monotonic() - t0:.1f} s")
    t0 = time.monotonic()
    check_vector(valset, first.commit, first.sign_bytes, [])
    log(f"first verdict vector after {time.monotonic() - t0:.1f} s")
    check_tampered(valset, first)
    return State(valset, pool, float(cell.traffic["warm_s"]), log)


def tampered(valset, sc):
    """checks.tampered's copy of the pooled commit ``sc`` with two more
    bad rows, and every bad index.  At HIGH_S the row's signature with s
    replaced by n - s: a valid ECDSA signature that only the lower-S
    rule refuses.  At FOREIGN the row's sign-bytes signed by a key of
    another set."""
    width = valset.vals.size()
    bad, flipped = checks.tampered(sc.commit, width)
    high_s, foreign = int(HIGH_S * width), int(FOREIGN * width)
    checks.require(
        len({high_s, foreign, *flipped}) == len(flipped) + 2,
        f"{width} validators: too few rows to tamper apart")
    bad.signatures[high_s].signature = high_s_twin(
        bad.signatures[high_s].signature)
    other = reference_secp.SigningKey(valset.seed, b"another set", foreign)
    bad.signatures[foreign].signature = other.sign(sc.sign_bytes[foreign])
    return bad, sorted({high_s, foreign, *flipped})


def high_s_twin(sig: bytes) -> bytes:
    """r || n - s: the same signature with s in the upper half."""
    s = int.from_bytes(sig[32:], "big")
    return sig[:32] + (reference_secp.N - s).to_bytes(32, "big")


def check_tampered(valset, sc) -> None:
    """Through the entry and the programs it drives: ``tampered``'s
    commit gives the reference's verdict vector and is refused at its
    first bad index, and a commit whose one bad row is a high-S twin is
    refused at that row."""
    bad, idxs = tampered(valset, sc)
    check_vector(valset, bad, sc.sign_bytes, idxs)
    checks.check_refused(valset, sc.block_id, sc.height, bad, idxs[0])
    high_s = int(HIGH_S * valset.vals.size())
    only = copy.deepcopy(sc.commit)
    only.signatures[high_s].signature = high_s_twin(
        only.signatures[high_s].signature)
    checks.check_refused(valset, sc.block_id, sc.height, only, high_s)


def check_vector(valset, commit, sign_bytes, want_bad: list[int]) -> None:
    """benchmarks/checks.check_vector for a secp256k1 set: the batch
    verifier the node makes for it, against reference_secp, position by
    position."""
    from cometbft_tpu.crypto import batch as crypto_batch

    program_bytes = commit.vote_sign_bytes_fn(valset.chain_id)
    vals = valset.vals.validators
    bv = crypto_batch.create_batch_verifier(
        KEY_TYPE, pubkeys=valset.vals.pub_keys_bytes()
    )
    for i, v in enumerate(vals):
        bv.add(v.pub_key.bytes(), program_bytes(i), commit.signatures[i].signature)
    ok, vec = bv.verify()
    oracle = [
        reference_secp.verify(v.pub_key.bytes(), sign_bytes[i],
                              commit.signatures[i].signature)
        for i, v in enumerate(vals)
    ]
    checks.require(len(vec) == len(oracle), "verdict vector has the wrong length")
    diff = [i for i, (a, b) in enumerate(zip(vec, oracle)) if a != b]
    checks.require(
        not diff,
        f"{valset.chain_id}: verdicts differ from the reference at rows "
        f"{diff[:8]} ({len(diff)} in all)",
    )
    checks.require(
        [i for i, b in enumerate(oracle) if not b] == want_bad,
        f"{valset.chain_id}: the reference itself blames the wrong rows",
    )
    checks.require(ok == (not want_bad), f"{valset.chain_id}: all-ok flag is wrong")


def lane_counts() -> dict:
    """The secp lane's counters: rows by where they were verified and
    where hashed, batches by how their program was found."""
    from cometbft_tpu.utils.metrics import hub

    m = hub()
    return {
        **{f"rows_{w}_{h}": m.verify_secp_rows.value(where=w, hash=h)
           for w in ("device", "host") for h in ("device", "host")},
        **{f"program_{r}": m.verify_secp_program_cache.value(result=r)
           for r in ("hit", "compile")},
    }


def run(state: State, window) -> None:
    state.before = lane_counts()
    try:
        commit_serial.run(state, window)
    finally:
        state.after = lane_counts()
        state.requests = window.attempted


def window_failures(state: State) -> list[str]:
    """What the lane's counters say did not hold in the window: a row
    verified on the host, a program compiled, or batches that are not
    one device batch of the set's width a request."""
    d = {k: state.after[k] - state.before[k] for k in state.after}
    requests = state.requests
    width = state.valset.vals.size()
    out = []
    host = d["rows_host_device"] + d["rows_host_host"]
    if host:
        out.append(f"{host:g} rows verified on the host in the window")
    if d["program_compile"]:
        out.append(f"{d['program_compile']:g} secp batches compiled their "
                   "program in the window")
    device = d["rows_device_device"] + d["rows_device_host"]
    if device != width * requests or d["program_hit"] != requests:
        out.append(
            f"{requests} requests, {d['program_hit']:g} device batches of "
            f"{device:g} rows in the window: not one batch of {width} a request"
        )
    return out


def finish(state: State) -> list[str]:
    """After the window: the lane's counters over it, then
    :func:`check_tampered` on a commit of the pool drawn from the
    seed."""
    out = window_failures(state) if state.after else []
    sc = state.pool[state.valset.seed % len(state.pool)]
    try:
        check_tampered(state.valset, sc)
    except checks.CheckFailure as e:
        out.append(f"after the window: {e}")
    return out
