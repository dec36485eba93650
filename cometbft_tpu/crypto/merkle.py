"""RFC-6962 Merkle tree: hashing, inclusion proofs, proof operators.

Host API mirroring the reference's crypto/merkle package:
  - hash_from_byte_slices   (tree.go:11-27; split rule tree.go:101)
  - proofs_from_byte_slices (proof.go ProofsFromByteSlices)
  - Proof.verify            (proof.go Proof.Verify)
  - ProofOp chaining        (proof_op.go ProofOperators.Verify)

Small trees hash on host (hashlib — a handful of SHA-256 calls); large
trees route through the TPU kernel (ops/merkle.py) where every level is
one batched SHA-256.  Both produce identical roots; tests assert the
equivalence against reference vectors (crypto/merkle/rfc6962_test.go).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field

_LEAF_PREFIX = b"\x00"
_INNER_PREFIX = b"\x01"

# Below this leaf count host hashing wins (device dispatch overhead
# dominates); above it the batched kernel takes over.
_DEVICE_THRESHOLD = 512

_JIT_ROOT = None


def _sha256(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def empty_hash() -> bytes:
    """Root of the empty tree: SHA-256 of the empty string (hash.go:14)."""
    return _sha256(b"")


def leaf_hash(leaf: bytes) -> bytes:
    return _sha256(_LEAF_PREFIX + leaf)


def inner_hash(left: bytes, right: bytes) -> bytes:
    return _sha256(_INNER_PREFIX + left + right)


def get_split_point(length: int) -> int:
    """Largest power of two strictly less than length (tree.go:101)."""
    if length < 1:
        raise ValueError("trying to split tree with length < 1")
    return 1 << (length - 1).bit_length() - 1 if length > 1 else 0


def _root_from_leaf_hashes_host(hashes: list[bytes]) -> bytes:
    nodes = hashes
    while len(nodes) > 1:
        nxt = [
            inner_hash(nodes[i], nodes[i + 1]) for i in range(0, len(nodes) - 1, 2)
        ]
        if len(nodes) % 2:
            nxt.append(nodes[-1])
        nodes = nxt
    return nodes[0]


def _root_device(items: list[bytes]) -> bytes:
    # jit site registered in kernel_manifest.JIT_SITES (manifest kernel
    # ``merkle_root_from_leaves``)
    global _JIT_ROOT
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..ops import merkle as M

    blocks, active = M.pad_leaves(items)
    if _JIT_ROOT is None:
        _JIT_ROOT = jax.jit(M.root_from_leaves)
    return bytes(np.asarray(_JIT_ROOT(jnp.asarray(blocks), jnp.asarray(active))))


def hash_from_byte_slices(items: list[bytes], device: bool | None = None) -> bytes:
    """RFC-6962 root of a list of raw leaves."""
    n = len(items)
    if n == 0:
        return empty_hash()
    if device is None:
        device = n >= _DEVICE_THRESHOLD
    if device:
        return _root_device(items)
    return _root_from_leaf_hashes_host([leaf_hash(i) for i in items])


@dataclass
class Proof:
    """Inclusion proof for item `index` of `total` (proof.go Proof)."""

    total: int
    index: int
    leaf_hash: bytes
    aunts: list[bytes] = field(default_factory=list)

    def compute_root_hash(self) -> bytes | None:
        return _compute_hash_from_aunts(
            self.index, self.total, self.leaf_hash, self.aunts
        )

    def verify(self, root_hash: bytes, leaf: bytes) -> None:
        if self.total < 0:
            raise ValueError("proof total must be positive")
        if self.index < 0:
            raise ValueError("proof index cannot be negative")
        if leaf_hash(leaf) != self.leaf_hash:
            raise ValueError("invalid leaf hash")
        computed = self.compute_root_hash()
        if computed != root_hash:
            raise ValueError(
                f"invalid root hash: wanted {root_hash.hex()} got "
                f"{computed.hex() if computed else None}"
            )


def _compute_hash_from_aunts(
    index: int, total: int, leaf: bytes, aunts: list[bytes]
) -> bytes | None:
    """Recursive root recomputation (proof.go computeHashFromAunts)."""
    if index >= total or index < 0 or total <= 0:
        return None
    if total == 1:
        if aunts:
            return None
        return leaf
    if not aunts:
        return None
    split = get_split_point(total)
    if index < split:
        left = _compute_hash_from_aunts(index, split, leaf, aunts[:-1])
        if left is None:
            return None
        return inner_hash(left, aunts[-1])
    right = _compute_hash_from_aunts(index - split, total - split, leaf, aunts[:-1])
    if right is None:
        return None
    return inner_hash(aunts[-1], right)


class _Node:
    __slots__ = ("hash", "parent", "left", "right")

    def __init__(self, h: bytes):
        self.hash = h
        self.parent = None
        self.left = None
        self.right = None

    def flatten_aunts(self) -> list[bytes]:
        out = []
        node = self
        while node is not None:
            parent = node.parent
            if parent is not None:
                sibling = parent.right if parent.left is node else parent.left
                if sibling is not None:
                    out.append(sibling.hash)
            node = parent
        return out


def _trails_from_leaf_hashes(hashes: list[bytes]):
    if not hashes:
        return [], None
    if len(hashes) == 1:
        node = _Node(hashes[0])
        return [node], node
    split = get_split_point(len(hashes))
    lefts, left_root = _trails_from_leaf_hashes(hashes[:split])
    rights, right_root = _trails_from_leaf_hashes(hashes[split:])
    root = _Node(inner_hash(left_root.hash, right_root.hash))
    root.left, root.right = left_root, right_root
    left_root.parent = right_root.parent = root
    return lefts + rights, root


def proofs_from_byte_slices(items: list[bytes]) -> tuple[bytes, list[Proof]]:
    """Root + one inclusion proof per item (proof.go ProofsFromByteSlices)."""
    hashes = [leaf_hash(i) for i in items]
    trails, root = _trails_from_leaf_hashes(hashes)
    root_hash = root.hash if root else empty_hash()
    proofs = [
        Proof(total=len(items), index=i, leaf_hash=t.hash, aunts=t.flatten_aunts())
        for i, t in enumerate(trails)
    ]
    return root_hash, proofs


# ------------------------------------------------- batched device proofs
#
# The split-point recursion above is equivalent to a level-by-level
# reduction with the odd trailing node promoted unchanged (same argument
# as ops/merkle.hash_level).  Under that view the aunt of a query at
# level l is its pair sibling (position ^ 1) — unless the sibling index
# falls off the level (the query's ancestor IS the promoted node), in
# which case the level contributes no aunt, exactly matching
# _Node.flatten_aunts.  proof_plan computes those positions on host so
# the device kernel is pure one-hot gathers.

_JIT_PROOFS = None
_JIT_MULTI = None


def _level_sizes(total: int) -> list[int]:
    """Sizes of the reduction levels below the root: [n, ceil(n/2), ..., 2]."""
    sizes = []
    n = total
    while n > 1:
        sizes.append(n)
        n = (n + 1) // 2
    return sizes


def proof_plan(total: int, indices: list[int]) -> tuple[int, list[list[int]]]:
    """Per-level sibling positions for each queried index.

    Returns (depth, sib) where sib[k][l] is the position, within level l,
    of query k's aunt node — or -1 when that level's odd trailing node was
    promoted through (no aunt emitted, matching _Node.flatten_aunts).
    Aunt order is leaf-to-root, the order Proof.aunts stores."""
    if total < 1:
        raise ValueError("proof plan needs a non-empty tree")
    sizes = _level_sizes(total)
    sib = []
    for idx in indices:
        idx = int(idx)
        if idx < 0 or idx >= total:
            raise ValueError(f"proof index {idx} out of range for total {total}")
        row = []
        pos = idx
        for sz in sizes:
            s = pos ^ 1
            row.append(s if s < sz else -1)
            pos >>= 1
        sib.append(row)
    return len(sizes), sib


def device_proofs_from_byte_slices(
    items: list[bytes], indices: list[int]
) -> tuple[bytes, list[Proof]]:
    """Batched device proofs for the queried indices: one dispatch gathers
    every audit path via one-hot sibling selection (ops/merkle
    ``merkle_proofs_from_leaves``).  Bit-identical to
    proofs_from_byte_slices by construction — tests assert it over
    randomized corpora."""
    global _JIT_PROOFS
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..ops import merkle as M

    total = len(items)
    depth, sib = proof_plan(total, indices)
    blocks, active = M.pad_leaves(items)
    if _JIT_PROOFS is None:
        # jit site registered in kernel_manifest.JIT_SITES (manifest
        # kernel ``merkle_proofs_from_leaves``)
        _JIT_PROOFS = jax.jit(M.proofs_from_leaves)
    idx_arr = jnp.asarray(np.asarray(indices, dtype=np.int32))
    sib_arr = jnp.asarray(
        np.asarray(sib, dtype=np.int32).reshape(len(indices), depth)
    )
    root, leaf_sel, aunts = _JIT_PROOFS(
        jnp.asarray(blocks), jnp.asarray(active), idx_arr, sib_arr
    )
    leaf_np = np.asarray(leaf_sel)
    aunt_np = np.asarray(aunts)
    proofs = [
        Proof(
            total=total,
            index=int(idx),
            leaf_hash=bytes(leaf_np[k]),
            aunts=[bytes(aunt_np[k, l]) for l in range(depth) if sib[k][l] >= 0],
        )
        for k, idx in enumerate(indices)
    ]
    return bytes(np.asarray(root)), proofs


def multiproof_plan(
    total: int, indices: list[int]
) -> tuple[int, list[list[int]], list[int], int]:
    """Dedup plan for a multiproof: many indices against one tree.

    Returns (depth, sib, coords, naive_slots): coords is the sorted,
    deduplicated list of flat node coordinates (level-size prefix-sum
    offsets, level 0 first) covering every queried leaf hash and every
    aunt; naive_slots is what K independent proofs would have gathered
    (the dedup factor's numerator)."""
    depth, sib = proof_plan(total, indices)
    sizes = _level_sizes(total)
    offsets = [0]
    for sz in sizes:
        offsets.append(offsets[-1] + sz)
    need = set()
    naive = 0
    for k, idx in enumerate(indices):
        need.add(int(idx))  # level-0 leaf hash
        naive += 1
        for l in range(depth):
            if sib[k][l] >= 0:
                need.add(offsets[l] + sib[k][l])
                naive += 1
    return depth, sib, sorted(need), naive


def device_multiproof(
    items: list[bytes], indices: list[int]
) -> tuple[bytes, list[Proof], float]:
    """Multiproof: answer many indices against one tree with shared nodes
    gathered once (ops/merkle ``merkle_multiproof_from_leaves``).  The
    per-query Proofs are reassembled on host from the deduplicated node
    set, so they are byte-for-byte the same objects device_proofs_from_
    byte_slices (and the host oracle) would produce.  Returns
    (root, proofs, dedup_factor = naive gather slots / unique nodes)."""
    global _JIT_MULTI
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..ops import merkle as M

    total = len(items)
    depth, sib, coords, naive = multiproof_plan(total, indices)
    sizes = _level_sizes(total)
    offsets = [0]
    for sz in sizes:
        offsets.append(offsets[-1] + sz)
    blocks, active = M.pad_leaves(items)
    if _JIT_MULTI is None:
        # jit site registered in kernel_manifest.JIT_SITES (manifest
        # kernel ``merkle_multiproof_from_leaves``)
        _JIT_MULTI = jax.jit(M.multiproof_from_leaves)
    coord_arr = jnp.asarray(np.asarray(coords, dtype=np.int32))
    root, nodes = _JIT_MULTI(jnp.asarray(blocks), jnp.asarray(active), coord_arr)
    node_np = np.asarray(nodes)
    by_coord = {c: bytes(node_np[i]) for i, c in enumerate(coords)}
    proofs = [
        Proof(
            total=total,
            index=int(idx),
            leaf_hash=by_coord[int(idx)],
            aunts=[
                by_coord[offsets[l] + sib[k][l]]
                for l in range(depth)
                if sib[k][l] >= 0
            ],
        )
        for k, idx in enumerate(indices)
    ]
    dedup = float(naive) / float(len(coords)) if coords else 1.0
    return bytes(np.asarray(root)), proofs, dedup


# ------------------------------------------------------- proof operators


class ProofOp:
    """A single step in a multi-store proof chain (proof_op.go)."""

    op_type: str = ""

    def run(self, values: list[bytes]) -> list[bytes]:
        raise NotImplementedError

    def get_key(self) -> bytes:
        raise NotImplementedError


class ValueOp(ProofOp):
    """Leaf op: proves key=value inclusion under a root (proof_value.go)."""

    op_type = "simple:v"

    def __init__(self, key: bytes, proof: Proof):
        self.key = key
        self.proof = proof

    def get_key(self) -> bytes:
        return self.key

    def run(self, values: list[bytes]) -> list[bytes]:
        if len(values) != 1:
            raise ValueError("value op expects one value")
        vhash = _sha256(values[0])
        if leaf_hash(self.key + vhash) != self.proof.leaf_hash:
            raise ValueError("leaf hash mismatch")
        root = self.proof.compute_root_hash()
        if root is None:
            raise ValueError("could not compute root")
        return [root]


class ProofOperators:
    """A chain of ProofOps verified innermost-first (proof_op.go:47)."""

    def __init__(self, ops: list[ProofOp]):
        self.ops = ops

    def verify_value(self, root: bytes, keypath: str, value: bytes) -> None:
        self.verify(root, keypath, [value])

    def verify(self, root: bytes, keypath: str, args: list[bytes]) -> None:
        keys = _parse_key_path(keypath)
        for op in self.ops:
            key = op.get_key()
            if key:
                if not keys:
                    raise ValueError(f"key path exhausted before op key {key!r}")
                if keys[-1] != key:
                    raise ValueError(f"key mismatch: {keys[-1]!r} != {key!r}")
                keys = keys[:-1]
            args = op.run(args)
        if args[0] != root:
            raise ValueError("calculated root does not match provided root")
        if keys:
            raise ValueError("keypath not fully consumed")


def key_path_to_string(keys: list[bytes]) -> str:
    """URL-ish key path encoding (proof_key_path.go KeyPath)."""
    out = []
    for k in keys:
        try:
            s = k.decode("utf-8")
            if s.isprintable() and "/" not in s:
                out.append(s)
                continue
        except UnicodeDecodeError:
            pass
        out.append("x:" + k.hex())
    return "/" + "/".join(out)


def _parse_key_path(path: str) -> list[bytes]:
    if not path.startswith("/"):
        raise ValueError("key path must start with /")
    keys = []
    for part in path.split("/")[1:]:
        if not part:
            continue
        if part.startswith("x:"):
            keys.append(bytes.fromhex(part[2:]))
        else:
            keys.append(part.encode("utf-8"))
    return keys
