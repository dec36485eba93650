"""A configuration's data, made from the seed by the plain reference:
the validator set and commits over it, built directly (chip_smoke's
build_chain pays 45 s for 4 blocks at 10,000 validators because
VoteSet.add_vote verifies every vote; a benchmark run cannot).

Keys, block hashes and timestamps come from the seed; every length is
the configuration's, so no seed changes a program's shape.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

from . import reference


@dataclass
class Valset:
    """A validator set in the program's types, with the reference's
    private keys in set order."""

    chain_id: str
    vals: object  # cometbft_tpu.types.validators.ValidatorSet
    keys: list  # keys[i] signs for vals.validators[i]
    seed: int
    t_genesis: int


@dataclass
class SignedCommit:
    height: int
    block_id: object  # cometbft_tpu.types.block.BlockID
    commit: object  # cometbft_tpu.types.block.Commit
    sign_bytes: list[bytes]  # the reference's encoding, per validator


def make_valset(config: dict, seed: int) -> Valset:
    from cometbft_tpu.crypto import ed25519 as program_ed25519
    from cometbft_tpu.types.validators import Validator, ValidatorSet

    if config["key_type"] != "ed25519":
        raise ValueError(f"no reference for key type {config['key_type']!r}")
    keys = [
        reference.private_key(seed, config["name"].encode(), i)
        for i in range(config["validators"])
    ]
    by_pub = {reference.public_bytes(k): k for k in keys}
    vals = ValidatorSet([
        Validator(program_ed25519.PubKey(pub), config["assumed"]["voting_power"])
        for pub in by_pub
    ])
    return Valset(
        chain_id=config["assumed"]["chain_id"],
        vals=vals,
        keys=[by_pub[v.pub_key.bytes()] for v in vals.validators],
        seed=seed,
        t_genesis=1_700_000_000 + seed % 1000,
    )


def sign_commit(valset: Valset, height: int, block_id) -> SignedCommit:
    """Every validator's precommit for ``block_id``, signed over the
    reference's canonical sign-bytes."""
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, Commit, CommitSig,
    )
    from cometbft_tpu.wire.canonical import Timestamp

    seconds = valset.t_genesis + height
    msg = reference.precommit_sign_bytes(
        valset.chain_id, height, 0, block_id.hash,
        block_id.part_set_header.total, block_id.part_set_header.hash, seconds,
    )
    sigs = [
        CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=v.address,
            timestamp=Timestamp(seconds=seconds),
            signature=k.sign(msg),
        )
        for v, k in zip(valset.vals.validators, valset.keys)
    ]
    commit = Commit(height=height, round=0, block_id=block_id, signatures=sigs)
    return SignedCommit(height, block_id, commit, [msg] * len(sigs))


def make_commits(valset: Valset, n: int) -> list[SignedCommit]:
    """Commits for heights 1..n over block ids drawn from the seed."""
    from cometbft_tpu.types.block import BlockID, PartSetHeader

    def digest(what: bytes, h: int) -> bytes:
        return hashlib.sha256(b"%d|%s|%d" % (valset.seed, what, h)).digest()

    return [
        sign_commit(
            valset, h,
            BlockID(hash=digest(b"block", h),
                    part_set_header=PartSetHeader(1, digest(b"parts", h))),
        )
        for h in range(1, n + 1)
    ]
