"""A chain whose validator set changes at epoch boundaries only (Cosmos
SDK ADR-039: set changes are buffered and land at the end of an epoch),
made from the seed by the plain reference: the set of epoch e+1 is the
set of epoch e with its ``rotated`` oldest keys dropped and as many new
ones added (upstream light/helpers_test.go ChangeKeys(delta)), every key
at the chain's power, and every validator of an epoch's set signs each
of its commits.

The keys are the seed's stream ``reference.private_key(seed, tag, i)``,
i = 0, 1, 2, ...; the key list is that stream less the candidates that
would sort FIRST in the set they join (``skipped``: one epoch in a
hundred has one at 10,000 validators and 100 keys an epoch), and the set
of epoch e is keys ``rotated * e .. rotated * e + validators - 1`` of the
key list in set order (power descending, then address ascending).  Why a
newcomer never sorts first: the lane padding of a bound set repeats its
first key, so such a set has more fresh lanes than fresh keys and its
incremental bind runs a second program shape (the configuration's
``assumed`` says so).

A commit is what ``benchmarks/data.sign_commit`` makes, over the
reference's sign-bytes, without a ``ValidatorSet`` of the program (one
of 10,000 costs a second to construct); sets and commits are made when
first asked for and kept.
"""

from __future__ import annotations

import hashlib

from . import data, reference
from . import reference_light as ref


class Chain:
    def __init__(self, config: dict, seed: int):
        assumed = config["assumed"]
        self.chain_id = assumed["chain_id"]
        self.power = assumed["voting_power"]
        self.width = config["validators"]
        self.rotated = config["rotated_per_epoch"]
        self.epoch_heights = config["epoch_heights"]
        self.epochs = config["epochs"]
        self.seed = seed
        self.t_genesis = 1_700_000_000 + seed % 1000
        self.skipped: list[int] = []  # candidates that would have sorted first
        self._tag = config["name"].encode()
        self._candidate = 0  # the next index of the seed's key stream
        self._keys: list[tuple[bytes, bytes, object]] = []  # (address, pub, key)
        self._sets: dict[int, list] = {}  # epoch -> its keys in set order
        self._commits: dict[tuple[int, int], data.SignedCommit] = {}

    # ------------------------------------------------------------ the sets

    def _draw(self, floor: bytes | None):
        """The stream's next key whose address sorts after ``floor``."""
        while True:
            i, self._candidate = self._candidate, self._candidate + 1
            key = reference.private_key(self.seed, self._tag, i)
            pub = reference.public_bytes(key)
            address = ref.address(pub)
            if floor is None or address > floor:
                return address, pub, key
            self.skipped.append(i)

    def _keys_of(self, e: int) -> list:
        """The key list's entries of epoch e, the list grown epoch by
        epoch as far as e needs."""
        while len(self._keys) < self.width:
            self._keys.append(self._draw(None))
        lo = self.rotated * e
        while len(self._keys) < lo + self.width:
            # the epoch being filled, and the keys that stay into it
            at = (len(self._keys) - self.width) // self.rotated + 1
            staying = self._keys[self.rotated * at:]
            floor = min(a for a, _, _ in staying)
            while len(self._keys) < self.rotated * at + self.width:
                self._keys.append(self._draw(floor))
        return self._keys[lo:lo + self.width]

    def _in_order(self, e: int) -> list:
        """Epoch e's (address, pub, key) in set order: every key has the
        chain's power, so by address."""
        if e not in self._sets:
            self._sets[e] = sorted(self._keys_of(e), key=lambda k: k[0])
        return self._sets[e]

    def vals(self, e: int) -> list[ref.Val]:
        return [ref.Val(pub, self.power) for _, pub, _ in self._in_order(e)]

    # --------------------------------------------------------- the commits

    def height(self, e: int, k: int) -> int:
        """The height of epoch e's k-th commit (k from 0)."""
        return e * self.epoch_heights + 1 + k

    def _digest(self, what: bytes, h: int) -> bytes:
        return hashlib.sha256(b"%d|%s|%d" % (self.seed, what, h)).digest()

    def commit(self, e: int, k: int) -> data.SignedCommit:
        """Epoch e's k-th commit, as ``data.sign_commit`` makes one:
        every validator of the epoch's set signs, over the reference's
        sign-bytes, for a block id drawn from the seed."""
        if not (0 <= e < self.epochs and 0 <= k < self.epoch_heights):
            raise KeyError((e, k))
        if (e, k) not in self._commits:
            from cometbft_tpu.types.block import (
                BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, PartSetHeader,
            )
            from cometbft_tpu.wire.canonical import Timestamp

            h = self.height(e, k)
            seconds = self.t_genesis + h
            block_id = BlockID(
                hash=self._digest(b"block", h),
                part_set_header=PartSetHeader(1, self._digest(b"parts", h)))
            msg = reference.precommit_sign_bytes(
                self.chain_id, h, 0, block_id.hash, 1,
                block_id.part_set_header.hash, seconds)
            sigs = [
                CommitSig(
                    block_id_flag=BLOCK_ID_FLAG_COMMIT,
                    validator_address=address,
                    timestamp=Timestamp(seconds=seconds),
                    signature=key.sign(msg),
                )
                for address, _, key in self._in_order(e)
            ]
            self._commits[e, k] = data.SignedCommit(
                h, block_id,
                Commit(height=h, round=0, block_id=block_id, signatures=sigs),
                [msg] * len(sigs))
        return self._commits[e, k]
