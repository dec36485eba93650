"""Commit verification — the framework's hot path (reference:
types/validation.go, 529 LoC; "the heart of the north star" per SURVEY.md).

verify_commit* assemble a batch of (pubkey, sign-bytes, signature) triples
and hand it to the BatchVerifier seam (crypto/batch.create_batch_verifier),
which routes device-capable backends through the unified verify service
(verifysvc/: priority-scheduled batching; the `klass` parameter below is
the caller's priority class — consensus by default, blocksync for the
catch-up path, background for light/evidence); on batch failure the
per-signature validity vector assigns blame exactly like the reference
(validation.go:384-399), and a sequential fallback covers heterogeneous
key sets (shouldBatchVerify, validation.go:17-21).

The seam routes by the validator set's KEY TYPE (the genesis pubkey
encoding, constrained by ConsensusParams.validator.pub_key_types):
ed25519 sets batch through the comb/plain kernels; bls12_381 sets take
the aggregate lane (models/bls_verifier — a commit whose rows share one
message and one aggregate signature verifies as ONE pairing-product
check; see docs/verify_service.md "Backend selection").  Blame inside a
BLS aggregate unit is unit-granular by nature, so the first-invalid
report below points at the first row of the failing unit.
"""

from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction

import numpy as np

from ..crypto import batch as crypto_batch
from ..utils import metrics, tracing
from .block import (
    BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig,
)
from .validators import ValidatorSet

BATCH_VERIFY_THRESHOLD = 2  # validation.go:15

# optional latency observer (seconds) installed by the node's metrics
# wiring; covers the device batch-verify call specifically
VERIFY_LATENCY_OBSERVER = None


class CommitVerificationError(Exception):
    pass


class NotEnoughVotingPowerError(CommitVerificationError):
    def __init__(self, got: int, needed: int):
        super().__init__(f"invalid commit -- insufficient voting power: got {got}, needed more than {needed}")
        self.got = got
        self.needed = needed


@dataclass
class SignatureCacheValue:
    validator_address: bytes
    vote_sign_bytes: bytes


class SignatureCache:
    """Cross-call dedup of verified signatures (validation.go SignatureCache);
    shared between the 1/3-trusting and 2/3 passes of light verification."""

    def __init__(self, max_size: int = 1 << 16):
        self._d: dict[bytes, SignatureCacheValue] = {}
        self._max = max_size

    def get(self, sig: bytes) -> SignatureCacheValue | None:
        return self._d.get(sig)

    def add(self, sig: bytes, value: SignatureCacheValue) -> None:
        if len(self._d) >= self._max:
            self._d.pop(next(iter(self._d)))
        self._d[sig] = value

    def __len__(self):
        return len(self._d)


def should_batch_verify(vals: ValidatorSet, commit: Commit) -> bool:
    """(validation.go:17) >= 2 sigs, key type batchable, homogeneous set."""
    proposer = vals.get_proposer()
    return (
        len(commit.signatures) >= BATCH_VERIFY_THRESHOLD
        and proposer is not None
        and crypto_batch.supports_batch_verifier(proposer.pub_key.type)
        and vals.all_keys_have_same_type()
    )


def verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    klass=None,
) -> None:
    """+2/3 of the set signed this commit; checks ALL signatures (the ABCI
    app's incentive logic depends on every flag being right)
    (validation.go:30)."""
    with tracing.span("commit.prepare"):
        _verify_basic_vals_and_commit(vals, commit, height, block_id)
        voting_power_needed = vals.total_voting_power() * 2 // 3
        bv = _batch_verifier(vals, commit, klass)
    if bv is not None:
        _verify_commit_batch(
            bv, chain_id, vals, commit, voting_power_needed,
            commit_only=False, count_all_signatures=True,
            lookup_by_index=True, cache=None,
        )
    else:
        _verify_commit_single(
            chain_id, vals, commit, voting_power_needed, commit_only=False,
            count_all_signatures=True, lookup_by_index=True, cache=None,
        )


def verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    count_all_signatures: bool = False,
    cache: SignatureCache | None = None,
    klass=None,
) -> None:
    """+2/3 check that may exit early — the light-client / blocksync path
    (validation.go:65-147)."""
    with tracing.span("commit.prepare"):
        _verify_basic_vals_and_commit(vals, commit, height, block_id)
        voting_power_needed = vals.total_voting_power() * 2 // 3
        bv = _batch_verifier(vals, commit, klass)
    if bv is not None:
        _verify_commit_batch(
            bv, chain_id, vals, commit, voting_power_needed, commit_only=True,
            count_all_signatures=count_all_signatures, lookup_by_index=True,
            cache=cache,
        )
    else:
        _verify_commit_single(
            chain_id, vals, commit, voting_power_needed, commit_only=True,
            count_all_signatures=count_all_signatures, lookup_by_index=True,
            cache=cache,
        )


def verify_commit_light_trusting(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level: Fraction = Fraction(1, 3),
    count_all_signatures: bool = False,
    cache: SignatureCache | None = None,
    klass=None,
) -> None:
    """trustLevel of a *trusted* set signed this commit; validators are
    looked up by address since the sets differ (validation.go:150-253)."""
    with tracing.span("commit.prepare"):
        if vals is None:
            raise CommitVerificationError("nil validator set")
        if commit is None:
            raise CommitVerificationError("nil commit")
        if trust_level.denominator == 0:
            raise CommitVerificationError("trustLevel has zero Denominator")
        total = vals.total_voting_power()
        voting_power_needed = (
            total * trust_level.numerator // trust_level.denominator
        )
        bv = _batch_verifier(vals, commit, klass)
    if bv is not None:
        _verify_commit_batch(
            bv, chain_id, vals, commit, voting_power_needed, commit_only=True,
            count_all_signatures=count_all_signatures, lookup_by_index=False,
            cache=cache,
        )
    else:
        _verify_commit_single(
            chain_id, vals, commit, voting_power_needed, commit_only=True,
            count_all_signatures=count_all_signatures, lookup_by_index=False,
            cache=cache,
        )


# ------------------------------------------------------------------ internal


def _batch_verifier(vals: ValidatorSet, commit: Commit, klass):
    """The batch verifier of a commit that takes the batch path (None
    where it does not), bound to the set's keys: resolve_mode binds the
    comb tables here, in the caller's thread."""
    if not should_batch_verify(vals, commit):
        return None
    return crypto_batch.create_batch_verifier(
        vals.get_proposer().pub_key.type, pubkeys=vals.pub_keys_bytes(),
        klass=klass,
    )


def _verify_basic_vals_and_commit(vals, commit, height, block_id):
    """(validation.go:507)."""
    if vals is None:
        raise CommitVerificationError("nil validator set")
    if commit is None:
        raise CommitVerificationError("nil commit")
    if vals.size() != len(commit.signatures):
        raise CommitVerificationError(
            f"invalid commit -- wrong set size: {vals.size()} vs {len(commit.signatures)}"
        )
    if height != commit.height:
        raise CommitVerificationError(
            f"invalid commit -- wrong height: {height} vs {commit.height}"
        )
    if block_id != commit.block_id:
        raise CommitVerificationError(
            f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
        )


def _select_rows(
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    commit_only: bool,
    count_all_signatures: bool,
    lookup_by_index: bool,
):
    """Which rows of the commit the loop of validation.go:265 would have
    reached and kept, from columns and with nothing encoded: returns
    (rows, val_idxs, tallied, fault).  ``rows`` are the commit's row
    indices in order, ``val_idxs`` each row's validator in ``vals``,
    ``tallied`` the power counted over them, and ``fault`` the double
    vote that ends the scan (the rows before it stand), or None.

    commit_only: a row is kept if it is for the block and every kept
    row counts (the light checks); else a row is kept unless absent and
    counts if it is for the block (verify_commit)."""
    sigs = commit.signatures
    # no dtype asked for: a flag off the wire may be any varint, and
    # numpy then compares it as the Python int it is
    flags = np.array([cs.block_id_flag for cs in sigs])
    for_block = flags == BLOCK_ID_FLAG_COMMIT
    rows = np.flatnonzero(
        for_block if commit_only else flags != BLOCK_ID_FLAG_ABSENT
    )
    fault = None
    if lookup_by_index:
        val_idxs = rows
    else:
        index = vals.address_index()
        found = np.array(
            [index.get(sigs[i].validator_address, -1) for i in rows.tolist()],
            dtype=np.int64,
        )
        rows, val_idxs = rows[found >= 0], found[found >= 0]
        firsts = np.unique(val_idxs, return_index=True)[1]
        if len(firsts) != len(val_idxs):
            # a validator signs twice: the scan ends at the first row
            # whose validator an earlier row named
            again = np.ones(len(val_idxs), dtype=bool)
            again[firsts] = False
            at = int(np.flatnonzero(again)[0])
            first = int(np.flatnonzero(val_idxs == val_idxs[at])[0])
            fault = CommitVerificationError(
                f"double vote from {vals.validators[val_idxs[at]]} "
                f"({rows[first]} and {rows[at]})"
            )
            rows, val_idxs = rows[:at], val_idxs[:at]
    counted = vals.voting_powers()[val_idxs]
    if not commit_only:
        counted = np.where(for_block[rows], counted, 0)
    if count_all_signatures:
        tallied = int(counted.sum())
    else:
        # the scan stops behind the first row that carries the tally
        # over the bar; a double vote behind that row is never seen
        running = np.cumsum(counted)
        over = np.flatnonzero(running > voting_power_needed)
        if len(over):
            stop = int(over[0]) + 1
            rows, val_idxs, running, fault = (
                rows[:stop], val_idxs[:stop], running[:stop], None
            )
        tallied = int(running[-1]) if len(running) else 0
    return rows.tolist(), val_idxs.tolist(), tallied, fault


def _assemble_commit_batch(
    bv,
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    commit_only: bool,
    count_all_signatures: bool,
    lookup_by_index: bool,
    cache: SignatureCache | None,
):
    """(validation.go:265, assembly half) — fill the batch verifier and
    tally power; raises on insufficient power / double votes.  Returns
    (batch_sig_idxs, msgs) for the judging half: the rows handed to the
    verifier and their sign-bytes, in add() order.

    By columns, and equal to the reference's loop row for row (kept as
    the oracle in tests/test_commit_assemble_columns.py): the rows are
    selected first and nothing is encoded for a row that is not in; the
    selected rows' sign-bytes come from one pass (Commit.
    vote_sign_bytes_rows); the cache drops the rows it knows; the rest
    go to the verifier in one add_many.  What the loop raised first
    still comes first: a row the verifier refuses as malformed, then a
    double vote, then the tally."""
    labels = {} if tracing.enabled() else None
    with tracing.span("commit.assemble", labels):
        rows, val_idxs, tallied, fault = _select_rows(
            vals, commit, voting_power_needed, commit_only,
            count_all_signatures, lookup_by_index,
        )
        msgs, path = commit.vote_sign_bytes_rows(chain_id, rows)
        metrics.hub().commit_assemble_rows.inc(len(rows), path=path)
        if labels is not None:
            labels.update(rows=len(rows), path=path)
        sigs = [commit.signatures[i].signature for i in rows]
        if cache is not None:
            validators = vals.validators
            fresh = []
            for j, (sig, msg) in enumerate(zip(sigs, msgs)):
                cv = cache.get(sig)
                if (
                    cv is None
                    or cv.validator_address != validators[val_idxs[j]].address
                    or cv.vote_sign_bytes != msg
                ):
                    fresh.append(j)
            if len(fresh) != len(rows):
                rows, val_idxs, msgs, sigs = (
                    [col[j] for j in fresh]
                    for col in (rows, val_idxs, msgs, sigs)
                )
        all_pubs = vals.pub_keys_bytes()
        pubs = [all_pubs[i] for i in val_idxs]
        add_many = getattr(bv, "add_many", None)
        if add_many is not None:
            add_many(pubs, msgs, sigs)
        else:
            for row in zip(pubs, msgs, sigs):
                bv.add(*row)
        if fault is not None:
            raise fault
        if tallied <= voting_power_needed:
            raise NotEnoughVotingPowerError(
                got=tallied, needed=voting_power_needed
            )
    return rows, msgs


def _judge_batch_result(
    ok: bool,
    valid_sigs: list[bool],
    commit: Commit,
    batch_sig_idxs: list[int],
    msgs: list[bytes],
    cache: SignatureCache | None,
) -> None:
    """(validation.go:384-399, judging half) — blame order + cache fill.
    ``msgs`` are the batch's sign-bytes as assembled, in the order of
    ``batch_sig_idxs``: nothing is encoded a second time."""
    if ok:
        if cache is not None:
            for idx, msg in zip(batch_sig_idxs, msgs):
                cs = commit.signatures[idx]
                cache.add(
                    cs.signature,
                    SignatureCacheValue(
                        validator_address=cs.validator_address,
                        vote_sign_bytes=msg,
                    ),
                )
        return

    # per-signature blame: report the first invalid one (validation.go:384)
    for i, sig_ok in enumerate(valid_sigs):
        idx = batch_sig_idxs[i]
        cs = commit.signatures[idx]
        if not sig_ok:
            raise CommitVerificationError(
                f"wrong signature (#{idx}): {cs.signature.hex()}"
            )
        if cache is not None:
            cache.add(
                cs.signature,
                SignatureCacheValue(
                    validator_address=cs.validator_address,
                    vote_sign_bytes=msgs[i],
                ),
            )
    raise CommitVerificationError(
        "BUG: batch verification failed with no invalid signatures"
    )


def _verify_commit_batch(
    bv,
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    commit_only: bool,
    count_all_signatures: bool,
    lookup_by_index: bool,
    cache: SignatureCache | None,
) -> None:
    """(validation.go:265) — batch assembly, power tally, verify-service
    dispatch (TPU), blame, through ``bv`` (``_batch_verifier``)."""
    batch_sig_idxs, msgs = _assemble_commit_batch(
        bv, chain_id, vals, commit, voting_power_needed, commit_only,
        count_all_signatures, lookup_by_index, cache,
    )
    if not batch_sig_idxs:
        return  # everything came from the cache

    with tracing.span("commit.verify", wait=True):
        if VERIFY_LATENCY_OBSERVER is not None:
            import time as _time

            _t0 = _time.perf_counter()
            ok, valid_sigs = bv.verify()
            VERIFY_LATENCY_OBSERVER(_time.perf_counter() - _t0)
        else:
            ok, valid_sigs = bv.verify()
    with tracing.span("commit.judge"):
        _judge_batch_result(ok, valid_sigs, commit, batch_sig_idxs, msgs, cache)


class PendingCommitVerification:
    """An in-flight verify_commit_light: the device kernel was dispatched
    by submit_verify_commit_light and is running while the caller does
    other host work (the blocksync verify-ahead pipeline).  collect()
    waits for the result and raises exactly what verify_commit_light
    would have."""

    __slots__ = ("_bv", "_ticket", "_commit", "_idxs", "_msgs", "_cache")

    def __init__(self, bv, ticket, commit, idxs, msgs, cache):
        self._bv = bv
        self._ticket = ticket
        self._commit = commit
        self._idxs = idxs
        self._msgs = msgs  # the batch's sign-bytes, in the order of idxs
        self._cache = cache

    def collect(self) -> None:
        if self._bv is None:
            return  # everything came from the signature cache
        with tracing.span("commit.verify", wait=True):
            ok, valid_sigs = self._bv.collect(self._ticket)
        with tracing.span("commit.judge"):
            _judge_batch_result(
                ok, valid_sigs, self._commit, self._idxs,
                self._msgs, self._cache,
            )


def submit_verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    count_all_signatures: bool = False,
    cache: SignatureCache | None = None,
    klass=None,
) -> PendingCommitVerification | None:
    """Asynchronous verify_commit_light (reactor.go:547's hot path,
    pipelined): run every host-side phase that can raise immediately —
    basic checks, batch assembly, power tally — and dispatch the device
    work WITHOUT waiting for its verdict.  Both device verifiers expose
    the submit()/collect() seam (the comb-cached CombBatchVerifier, whose
    submit also offloads payload staging to a background thread, and the
    uncached TpuEd25519BatchVerifier that covers the table-warming
    window), so a pipelined caller overlaps the next block's host work
    with this one's assembly AND kernel.  Returns None when the commit
    doesn't take a device batch path at all (small set, heterogeneous
    keys, cpu backend): the caller must then run verify_commit_light
    synchronously."""
    with tracing.span("commit.prepare"):
        _verify_basic_vals_and_commit(vals, commit, height, block_id)
        bv = _batch_verifier(vals, commit, klass)
    if bv is None:
        return None
    if not hasattr(bv, "submit"):
        return None  # host verifier: no async seam, caller runs sync
    voting_power_needed = vals.total_voting_power() * 2 // 3
    batch_sig_idxs, msgs = _assemble_commit_batch(
        bv, chain_id, vals, commit, voting_power_needed,
        commit_only=True,
        count_all_signatures=count_all_signatures,
        lookup_by_index=True,
        cache=cache,
    )
    if not batch_sig_idxs:
        return PendingCommitVerification(None, None, commit, [], [], cache)
    with tracing.span("commit.verify", wait=True):
        ticket = bv.submit()
    return PendingCommitVerification(
        bv, ticket, commit, batch_sig_idxs, msgs, cache
    )


def _verify_commit_single(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    commit_only: bool,
    count_all_signatures: bool,
    lookup_by_index: bool,
    cache: SignatureCache | None,
) -> None:
    """(validation.go:413) — the sequential fallback."""
    seen_vals: dict[int, int] = {}
    tallied = 0
    sign_bytes_at = commit.vote_sign_bytes_fn(chain_id)
    for idx, cs in enumerate(commit.signatures):
        if (not cs.for_block()) if commit_only else cs.absent_flag():
            continue
        try:
            cs.validate_basic()
        except ValueError as e:
            raise CommitVerificationError(
                f"invalid signature at index {idx}: {e}"
            ) from e
        if lookup_by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(cs.validator_address)
            if val is None:
                continue
            if val_idx in seen_vals:
                raise CommitVerificationError(
                    f"double vote from {val} ({seen_vals[val_idx]} and {idx})"
                )
            seen_vals[val_idx] = idx

        if val.pub_key is None:
            raise CommitVerificationError(f"validator {val} has a nil PubKey at index {idx}")

        sign_bytes = sign_bytes_at(idx)

        cache_hit = False
        if cache is not None:
            cv = cache.get(cs.signature)
            cache_hit = (
                cv is not None
                and cv.validator_address == val.pub_key.address()
                and cv.vote_sign_bytes == sign_bytes
            )
        if not cache_hit:
            if not val.pub_key.verify_signature(sign_bytes, cs.signature):
                raise CommitVerificationError(
                    f"wrong signature (#{idx}): {cs.signature.hex()}"
                )
            if cache is not None:
                cache.add(
                    cs.signature,
                    SignatureCacheValue(
                        validator_address=val.pub_key.address(),
                        vote_sign_bytes=sign_bytes,
                    ),
                )

        if commit_only or cs.for_block():
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            return

    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(got=tallied, needed=voting_power_needed)
