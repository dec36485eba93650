"""A chain of signed headers over validator sets that move, made from
the seed by the plain reference (upstream light/helpers_test.go
genMockNode with ChangeKeys(1)): the set at height h+1 is the set at h
with the oldest key dropped and one new key added, every validator of a
height's set signs its commit, and every hash in a header is the real
one.  A block is made when it is first asked for and kept: a walk
touches some tens of the chain's heights, and a height's block is the
same whenever it is made.

The blocks are the reference's own (``reference_light.Block``);
``light_block`` gives one as the program's ``LightBlock`` and
``Provider`` is the node that hands them to a light client.
"""

from __future__ import annotations

import copy
import hashlib

from . import reference
from . import reference_light as ref


class Chain:
    def __init__(self, config: dict, seed: int, heights: int | None = None,
                 validators: int | None = None):
        assumed = config["assumed"]
        self.chain_id = assumed["chain_id"]
        self.heights = heights or config["heights"]
        self.width = validators or config["validators"]
        self.power = assumed["voting_power"]
        self.block_seconds = assumed["block_seconds"]
        self.seed = seed
        self.t_genesis = 1_700_000_000 + seed % 1000
        self._tag = config["name"].encode()
        self._keys: dict[int, object] = {}  # key number -> private key
        self._by_pub: dict[bytes, object] = {}
        self._sets: dict[int, list[ref.Val]] = {}
        self._blocks: dict[int, ref.Block] = {}
        self._light: dict[int, object] = {}

    # ------------------------------------------------------------ the sets

    def _key(self, i: int):
        if i not in self._keys:
            k = reference.private_key(self.seed, self._tag, i)
            self._keys[i] = k
            self._by_pub[reference.public_bytes(k)] = k
        return self._keys[i]

    def vals(self, h: int) -> list[ref.Val]:
        """Keys h-1 .. h-2+width of the key list: one dropped from the
        front and one added at the end with every height."""
        if h not in self._sets:
            self._sets[h] = ref.sorted_set([
                ref.Val(reference.public_bytes(self._key(i)), self.power)
                for i in range(h - 1, h - 1 + self.width)
            ])
        return self._sets[h]

    def seconds(self, h: int) -> int:
        return self.t_genesis + self.block_seconds * h

    def _digest(self, what: bytes, h: int) -> bytes:
        return hashlib.sha256(b"%d|%s|%d" % (self.seed, what, h)).digest()

    # ---------------------------------------------------------- the blocks

    def header(self, h: int) -> ref.Header:
        vals = self.vals(h)
        return ref.Header(
            chain_id=self.chain_id,
            height=h,
            seconds=self.seconds(h),
            last_block_id=(self._digest(b"last", h), 1, self._digest(b"lastparts", h)),
            validators_hash=ref.valset_hash(vals),
            next_validators_hash=ref.valset_hash(self.vals(h + 1)),
            proposer_address=vals[0].address,
            last_commit_hash=self._digest(b"lastcommit", h),
            data_hash=self._digest(b"data", h),
            consensus_hash=self._digest(b"params", 0),
            app_hash=self._digest(b"app", h),
            last_results_hash=self._digest(b"results", h),
            evidence_hash=ref.sha256(b""),
        )

    def signed(self, header: ref.Header, vals: list[ref.Val]) -> ref.Block:
        """The commit for ``header``: a precommit of every validator of
        ``vals`` over the reference's sign-bytes."""
        block_id = (header.hash(), 1, self._digest(b"parts", header.height))
        msg = reference.precommit_sign_bytes(
            header.chain_id, header.height, 0, *block_id, header.seconds)
        sigs = [
            ref.Sig(v.address, header.seconds, self._by_pub[v.pub].sign(msg))
            for v in vals
        ]
        return ref.Block(header, vals, header.height, block_id, sigs)

    def block(self, h: int) -> ref.Block:
        if not 1 <= h <= self.heights:
            raise KeyError(h)
        if h not in self._blocks:
            self._blocks[h] = self.signed(self.header(h), self.vals(h))
        return self._blocks[h]

    def light_block(self, h: int):
        if h not in self._light:
            self._light[h] = light_block(self.block(h))
        return self._light[h]

    # ------------------------------------------------- two tampered blocks

    def flipped(self, h: int, idxs: list[int]) -> ref.Block:
        """Block h with the signatures at ``idxs`` flipped in one bit."""
        bad = copy.copy(self.block(h))
        bad.sigs = [copy.copy(s) for s in bad.sigs]
        bad._verdicts = {}
        for i in idxs:
            s = bad.sigs[i].signature
            bad.sigs[i].signature = s[:-1] + bytes([s[-1] ^ 1])
        return bad

    def wrong_set_hash(self, h: int) -> ref.Block:
        """Block h whose header names the NEXT height's set, with a
        commit that the block's own set signed over that header: well
        formed, and not the set that is supplied with it."""
        header = self.header(h)
        header.validators_hash = header.next_validators_hash
        return self.signed(header, self.vals(h))


def encode(block: ref.Block) -> bytes:
    """The block as the program's LightBlock, in wire form."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, Header,
        PartSetHeader,
    )
    from cometbft_tpu.types.light_block import LightBlock, SignedHeader
    from cometbft_tpu.types.validators import Validator, ValidatorSet
    from cometbft_tpu.wire.canonical import Timestamp

    def block_id(t) -> BlockID:
        return BlockID(hash=t[0], part_set_header=PartSetHeader(t[1], t[2]))

    h = block.header
    header = Header(
        chain_id=h.chain_id, height=h.height,
        time=Timestamp(seconds=h.seconds),
        last_block_id=block_id(h.last_block_id),
        last_commit_hash=h.last_commit_hash, data_hash=h.data_hash,
        validators_hash=h.validators_hash,
        next_validators_hash=h.next_validators_hash,
        consensus_hash=h.consensus_hash, app_hash=h.app_hash,
        last_results_hash=h.last_results_hash, evidence_hash=h.evidence_hash,
        proposer_address=h.proposer_address,
    )
    commit = Commit(
        height=block.commit_height, round=0,
        block_id=block_id(block.commit_block_id),
        signatures=[
            CommitSig(
                block_id_flag=BLOCK_ID_FLAG_COMMIT,
                validator_address=s.address,
                timestamp=Timestamp(seconds=s.seconds),
                signature=s.signature,
            )
            for s in block.sigs
        ],
    )
    vals = ValidatorSet(
        [Validator(ed25519.PubKey(v.pub), v.power) for v in block.vals])
    return LightBlock(SignedHeader(header, commit), vals).to_proto().encode()


def light_block(block: ref.Block):
    """The block as a node holds it: the program's LightBlock, decoded
    from the wire form once."""
    from cometbft_tpu.types.light_block import LightBlock
    from cometbft_tpu.wire import types_pb as pb

    return LightBlock.from_proto(pb.LightBlockProto.decode(encode(block)))


class Provider:
    """The full node a light client asks (upstream's mock provider):
    light blocks of one chain by height, 0 for the latest, handed out as
    it stores them, each fetch a new LightBlock around the stored
    header, commit and set (upstream hands out its stored pointers); it
    notes what was asked of it.  ``replaced`` holds blocks served in
    place of the chain's own, ``missing`` heights it does not have.
    ``block_at`` is the same node as the reference's walk asks it."""

    def __init__(self, chain: Chain, replaced: dict[int, ref.Block] | None = None,
                 missing=()):
        self.chain = chain
        self.replaced = replaced or {}
        self._light = {h: light_block(b) for h, b in self.replaced.items()}
        self.missing = set(missing)
        self.fetched: list[int] = []
        self.evidence: list = []

    def chain_id(self) -> str:
        return self.chain.chain_id

    def block_at(self, height: int) -> ref.Block:
        if height in self.missing:
            raise KeyError(height)
        return self.replaced.get(height) or self.chain.block(height)

    def light_block(self, height: int):
        from cometbft_tpu.light.provider import (
            ErrHeightTooHigh, ErrLightBlockNotFound,
        )
        from cometbft_tpu.types.light_block import LightBlock, SignedHeader

        height = height or self.chain.heights
        self.fetched.append(height)
        if height > self.chain.heights:
            raise ErrHeightTooHigh(f"height {height} > {self.chain.heights}")
        if height < 1 or height in self.missing:
            raise ErrLightBlockNotFound(f"no light block at height {height}")
        stored = self._light.get(height) or self.chain.light_block(height)
        held = stored.signed_header
        return LightBlock(SignedHeader(held.header, held.commit),
                          stored.validator_set)

    def report_evidence(self, ev) -> None:
        self.evidence.append(ev)
