"""Validator and ValidatorSet (reference: types/validator.go,
types/validator_set.go — 1,110 LoC).

Sorted validator list (voting power desc, address asc), total-power
accounting capped at MaxInt64/8, proposer selection by priority increment
(validator_set.go:131 IncrementProposerPriority), and the RFC-6962 hash
over SimpleValidator encodings (validator_set.go:386).
"""

from __future__ import annotations

import numpy as np

from ..crypto import encoding as keyenc
from ..crypto import merkle
from ..wire import types_pb as pb

MAX_INT64 = (1 << 63) - 1
MIN_INT64 = -(1 << 63)
MAX_TOTAL_VOTING_POWER = MAX_INT64 // 8
PRIORITY_WINDOW_SIZE_FACTOR = 2


def _clip(v: int) -> int:
    """Saturating int64 (safeAddClip/safeSubClip in the reference)."""
    return max(MIN_INT64, min(MAX_INT64, v))


class Validator:
    __slots__ = ("address", "pub_key", "voting_power", "proposer_priority")

    def __init__(self, pub_key, voting_power: int, proposer_priority: int = 0):
        self.pub_key = pub_key
        self.address: bytes = pub_key.address()
        self.voting_power = int(voting_power)
        self.proposer_priority = int(proposer_priority)

    def copy(self) -> "Validator":
        return Validator(self.pub_key, self.voting_power, self.proposer_priority)

    def bytes(self) -> bytes:
        """SimpleValidator proto encoding — the hashing form
        (types/validator.go Validator.Bytes)."""
        sv = pb.SimpleValidator(
            pub_key=keyenc.pubkey_to_proto(self.pub_key),
            voting_power=self.voting_power,
        )
        return sv.encode()

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """Higher priority wins; ties broken by smaller address
        (validator.go CompareProposerPriority)."""
        if other is None:
            return self
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise ValueError("cannot compare identical validators")

    def validate_basic(self) -> None:
        if self.pub_key is None:
            raise ValueError("validator does not have a public key")
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != 20:
            raise ValueError("validator address is the wrong size")

    def to_proto(self) -> pb.Validator:
        return pb.Validator(
            address=self.address,
            pub_key_bytes=self.pub_key.bytes(),
            pub_key_type=self.pub_key.type,
            voting_power=self.voting_power,
            proposer_priority=self.proposer_priority,
        )

    @classmethod
    def from_proto(cls, msg: pb.Validator) -> "Validator":
        if msg.pub_key_bytes:
            key = keyenc.pubkey_from_type_and_bytes(msg.pub_key_type, msg.pub_key_bytes)
        elif msg.pub_key is not None:
            key = keyenc.pubkey_from_proto(msg.pub_key)
        else:
            raise ValueError("validator proto missing public key")
        return cls(key, msg.voting_power, msg.proposer_priority)

    def __eq__(self, other):
        return (
            isinstance(other, Validator)
            and self.address == other.address
            and self.voting_power == other.voting_power
            and self.proposer_priority == other.proposer_priority
        )

    def __repr__(self):
        return (
            f"Validator(addr={self.address.hex()[:12]}, "
            f"power={self.voting_power}, prio={self.proposer_priority})"
        )


def _val_sort_key(v: Validator):
    """Primary: voting power descending; secondary: address ascending
    (validator_set.go ValidatorsByVotingPower)."""
    return (-v.voting_power, v.address)


class ValidatorSet:
    """Sorted validator set with proposer rotation (validator_set.go:43).

    ``validators`` changes through update_with_change_set only, which
    drops everything cached about the membership (hash, pubkey list,
    _facts); code that edits the list in place would read stale caches."""

    def __init__(self, validators: list[Validator]):
        vals = sorted((v.copy() for v in validators), key=_val_sort_key)
        self.validators: list[Validator] = vals
        self._total_voting_power: int | None = None
        self.proposer: Validator | None = None
        if vals:
            self._update_total_voting_power()
            self.proposer = self._find_proposer()

    # ------------------------------------------------------------- basics

    def is_nil_or_empty(self) -> bool:
        return not self.validators

    def size(self) -> int:
        return len(self.validators)

    def __len__(self):
        return len(self.validators)

    def copy(self) -> "ValidatorSet":
        new = ValidatorSet.__new__(ValidatorSet)
        new.validators = [v.copy() for v in self.validators]
        new._total_voting_power = self._total_voting_power
        # same membership in the same order: the hash and the per-set
        # facts hold for the copy (a fact is never edited in place;
        # update_with_change_set drops them on the set it changes)
        new._set_hash = getattr(self, "_set_hash", None)
        new._set_facts = dict(self._facts())
        new.proposer = None
        if self.proposer is not None:
            for v in new.validators:
                if v.address == self.proposer.address:
                    new.proposer = v
                    break
            else:
                new.proposer = self.proposer.copy()
        return new

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total += v.voting_power
            if total > MAX_TOTAL_VOTING_POWER:
                raise ValueError(
                    f"total voting power exceeds max {MAX_TOTAL_VOTING_POWER}"
                )
        self._total_voting_power = total

    def total_voting_power(self) -> int:
        if self._total_voting_power is None:
            self._update_total_voting_power()
        return self._total_voting_power

    def _facts(self) -> dict:
        """What is kept about the membership beside _pub_keys_bytes,
        each fact built at its first use: dropped by
        update_with_change_set and, the guard pub_keys_bytes has, when
        the list is no longer as long as it was."""
        facts = getattr(self, "_set_facts", None)
        if facts is None or facts["size"] != len(self.validators):
            facts = self._set_facts = {"size": len(self.validators)}
        return facts

    def address_index(self) -> dict[bytes, int]:
        """address -> index in set order.  Of two validators with one
        address the first wins, as in a scan from the front."""
        facts = self._facts()
        index = facts.get("address_index")
        if index is None:
            index = facts["address_index"] = {}
            for i, v in enumerate(self.validators):
                index.setdefault(v.address, i)
        return index

    def voting_powers(self) -> np.ndarray:
        """The voting powers in set order (int64; a set's total is
        capped at MaxInt64/8)."""
        facts = self._facts()
        powers = facts.get("voting_powers")
        if powers is None:
            powers = facts["voting_powers"] = np.array(
                [v.voting_power for v in self.validators], dtype=np.int64
            )
        return powers

    def get_by_address(self, address: bytes) -> tuple[int, Validator | None]:
        i = self.address_index().get(address)
        if i is None:
            return -1, None
        return i, self.validators[i]

    def validator_blocks_the_chain(self, address: bytes) -> bool:
        """True if this validator alone holds > 1/3 power, i.e. the chain
        cannot progress without it (validator_set.go:374) — a blocksyncing
        node with such a key must switch to consensus immediately."""
        _, val = self.get_by_address(address)
        if val is None:
            return False
        return val.voting_power > (self.total_voting_power() - 1) // 3

    def get_by_index(self, index: int) -> tuple[bytes, Validator | None]:
        if index < 0 or index >= len(self.validators):
            return b"", None
        v = self.validators[index]
        return v.address, v

    def has_address(self, address: bytes) -> bool:
        return self.get_by_address(address)[1] is not None

    def get_proposer(self) -> Validator | None:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer

    def _find_proposer(self) -> Validator:
        res = None
        for v in self.validators:
            res = v.compare_proposer_priority(res) if res is not None else v
        return res

    def all_keys_have_same_type(self) -> bool:
        """Batch-verification precondition (validator_set.go AllKeysHaveSameType)."""
        facts = self._facts()
        same = facts.get("same_key_type")
        if same is None:
            same = facts["same_key_type"] = (
                len({v.pub_key.type for v in self.validators}) <= 1
            )
        return same

    def pub_keys_bytes(self) -> list[bytes]:
        """Raw pubkeys in set order, cached — the key for the device-side
        comb-table cache (models/comb_verifier.ValsetCombCache); the TPU
        analogue of the reference's expanded-key LRU (ed25519.go:43)."""
        pks = getattr(self, "_pub_keys_bytes", None)
        if pks is None or len(pks) != len(self.validators):
            pks = [v.pub_key.bytes() for v in self.validators]
            self._pub_keys_bytes = pks
        return pks

    # ------------------------------------------------------------ hashing

    def hash(self) -> bytes:
        """Merkle root over SimpleValidator encodings (validator_set.go:386).
        Memoized: blocksync's verify-ahead pipeline compares it per block,
        and the set only changes through update_with_change_set (which
        drops the cache).  Proposer-priority churn doesn't affect it —
        SimpleValidator excludes priorities."""
        h = getattr(self, "_set_hash", None)
        if h is None:
            h = merkle.hash_from_byte_slices([v.bytes() for v in self.validators])
            self._set_hash = h
        return h

    # ------------------------------------------- proposer priority cycle

    def increment_proposer_priority(self, times: int) -> None:
        """Advance the proposer rotation `times` rounds
        (validator_set.go:131)."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("cannot call increment_proposer_priority with non-positive times")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority + v.voting_power)
        mostest = self._find_proposer()
        mostest.proposer_priority = _clip(
            mostest.proposer_priority - self.total_voting_power()
        )
        return mostest

    def rescale_priorities(self, diff_max: int) -> None:
        """Keep max-min priority distance under diff_max (validator_set.go:158)."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if diff_max <= 0:
            return
        diff = self._max_min_priority_diff()
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                # Go integer division truncates toward zero.
                q = abs(v.proposer_priority) // ratio
                v.proposer_priority = q if v.proposer_priority >= 0 else -q

    def _max_min_priority_diff(self) -> int:
        prios = [v.proposer_priority for v in self.validators]
        return max(prios) - min(prios)

    def _compute_avg_proposer_priority(self) -> int:
        n = len(self.validators)
        total = sum(v.proposer_priority for v in self.validators)
        # Go big.Int Div floors toward negative infinity; Python // matches.
        return total // n

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority - avg)

    # ------------------------------------------------------------ updates

    def update_with_change_set(self, changes: list[Validator]) -> None:
        """Apply validator updates (power 0 = removal), recompute priorities
        (validator_set.go UpdateWithChangeSet + computeNewPriorities:534)."""
        if not changes:
            return
        # no duplicates allowed
        seen = set()
        for c in changes:
            if c.address in seen:
                raise ValueError(f"duplicate address in changes: {c.address.hex()}")
            seen.add(c.address)
            if c.voting_power < 0:
                raise ValueError("voting power cannot be negative")

        removals = {c.address for c in changes if c.voting_power == 0}
        updates = [c.copy() for c in changes if c.voting_power > 0]

        for addr in removals:
            if not self.has_address(addr):
                raise ValueError(
                    f"failed to find validator {addr.hex()} to remove"
                )

        by_addr = {v.address: v for v in self.validators}
        # compute what the new total will be, for new-validator priorities
        new_total = 0
        merged = dict(by_addr)
        for u in updates:
            merged[u.address] = u
        for addr in removals:
            merged.pop(addr, None)
        if not merged:
            raise ValueError("applying the validator changes would result in empty set")
        for v in merged.values():
            new_total += v.voting_power
        if new_total > MAX_TOTAL_VOTING_POWER:
            raise ValueError("total voting power of resulting valset exceeds max")

        for u in updates:
            existing = by_addr.get(u.address)
            if existing is None:
                # new validator starts at -1.125 * new total power
                # (validator_set.go:547)
                u.proposer_priority = -(new_total + (new_total >> 3))
            else:
                u.proposer_priority = existing.proposer_priority
            merged[u.address] = u

        self.validators = sorted(merged.values(), key=_val_sort_key)
        self._total_voting_power = None
        # membership changed: drop the pubkey cache and the per-set facts
        self._pub_keys_bytes = None
        self._set_facts = None
        self._set_hash = None
        self._update_total_voting_power()
        if self.proposer is not None and self.proposer.address not in merged:
            self.proposer = None
        self._shift_by_avg_proposer_priority()

    # ------------------------------------------------------------- misc

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is nil or empty")
        for v in self.validators:
            v.validate_basic()
        p = self.get_proposer()
        if p is None:
            raise ValueError("proposer failed validate basic")
        p.validate_basic()
        if not self.has_address(p.address):
            raise ValueError("proposer not in validator set")

    def to_proto(self) -> pb.ValidatorSet:
        return pb.ValidatorSet(
            validators=[v.to_proto() for v in self.validators],
            proposer=self.proposer.to_proto() if self.proposer else None,
            total_voting_power=self.total_voting_power(),
        )

    @classmethod
    def from_proto(cls, msg: pb.ValidatorSet) -> "ValidatorSet":
        decoded = [Validator.from_proto(v) for v in msg.validators]
        vs = cls(decoded)
        # restore exact priorities (sorting in __init__ copies; map back)
        prio = {v.address: v.proposer_priority for v in decoded}
        for v in vs.validators:
            v.proposer_priority = prio[v.address]
        if msg.proposer is not None:
            _, p = vs.get_by_address(Validator.from_proto(msg.proposer).address)
            vs.proposer = p
        return vs

    def __eq__(self, other):
        return (
            isinstance(other, ValidatorSet)
            and self.validators == other.validators
        )

    def __repr__(self):
        return f"ValidatorSet({len(self.validators)} validators, power={self.total_voting_power()})"
