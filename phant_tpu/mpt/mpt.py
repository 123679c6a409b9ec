"""Merkle Patricia Trie: construction, root computation, node enumeration.

Equivalent surface to the reference (reference: src/mpt/mpt.zig:13-314):
`keyval` pairs -> trie -> keccak root, with hex-prefix nibble encoding and
the <32-byte node-embedding rule. Goes beyond the reference by also keeping
the built node structure around for proof generation (phant_tpu/mpt/proof.py)
and for the TPU level-order hashing pipeline (phant_tpu/ops/mpt_jax.py):
the reference computes roots only (reference: src/mpt/mpt.zig:38-45).

Yellow-paper appendix D. Node kinds: leaf, extension, branch, empty.
A node's reference inside its parent is its RLP structure itself when the
encoding is shorter than 32 bytes, else keccak256 of the encoding.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

from phant_tpu import rlp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.utils.native import load_engine_ext
from phant_tpu.utils.trace import metrics

EMPTY_TRIE_ROOT = keccak256(rlp.encode(b""))


def bytes_to_nibbles(key: bytes) -> Tuple[int, ...]:
    out = []
    for b in key:
        out.append(b >> 4)
        out.append(b & 0x0F)
    return tuple(out)


def encode_hex_prefix(nibbles: Sequence[int], is_leaf: bool) -> bytes:
    """Hex-prefix encoding (yellow paper appendix C; reference:
    src/mpt/mpt.zig:285-314)."""
    flag = 0x20 if is_leaf else 0x00
    if len(nibbles) % 2:  # odd
        first = flag | 0x10 | nibbles[0]
        rest = nibbles[1:]
    else:
        first = flag
        rest = nibbles
    out = bytearray([first])
    for i in range(0, len(rest), 2):
        out.append((rest[i] << 4) | rest[i + 1])
    return bytes(out)


def decode_hex_prefix(data: bytes) -> Tuple[Tuple[int, ...], bool]:
    if not data:
        raise ValueError("empty hex-prefix encoding")
    flag = data[0]
    is_leaf = bool(flag & 0x20)
    nibbles: List[int] = []
    if flag & 0x10:  # odd
        nibbles.append(flag & 0x0F)
    for b in data[1:]:
        nibbles.append(b >> 4)
        nibbles.append(b & 0x0F)
    return tuple(nibbles), is_leaf


# --- trie nodes -----------------------------------------------------------


@dataclass
class LeafNode:
    path: Tuple[int, ...]
    value: bytes


@dataclass
class ExtensionNode:
    path: Tuple[int, ...]
    child: "Node"


@dataclass
class BranchNode:
    children: List[Optional["Node"]] = field(default_factory=lambda: [None] * 16)
    value: Optional[bytes] = None


Node = Union[LeafNode, ExtensionNode, BranchNode]

#: what the extension's walk tells apart by exact type; a node of any
#: other type enters its parent as its `.digest`
_NODE_KINDS = (LeafNode, ExtensionNode, BranchNode)

#: an entry of a trie's memo: a node's structure, its encoding, and the
#: reference its parent holds of it
_Entry = Tuple[rlp.RLPItem, bytes, rlp.RLPItem]


def _common_prefix_len(a: Sequence[int], b: Sequence[int]) -> int:
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


def _noop_evict(node: Node) -> None:
    pass


def _insert(
    node: Optional[Node], path: Tuple[int, ...], value: bytes, evict=_noop_evict
) -> Node:
    """Insert (path, value); mirrors the reference's recursive insertNode
    (reference: src/mpt/mpt.zig:47-119) but returns fresh subtree roots.

    `evict(node)` is called for every node whose cached encoding becomes
    stale — both MUTATED nodes (their encoding changes) and DISCARDED nodes
    (their id may be reused by a new object, so a live cache entry would be
    a use-after-free-style stale hit). The entry it drops holds the node's
    encoding AND the reference its parent held of it, so neither outlives
    the other. Untouched subtrees keep their entries, making repeated root
    computation O(dirty-paths), not O(trie): a dirty branch reads its clean
    children's references and hashes none of them again.
    """
    if node is None:
        return LeafNode(path, value)

    if isinstance(node, LeafNode):
        if node.path == path:
            evict(node)  # mutated
            node.value = value
            return node
        common = _common_prefix_len(node.path, path)
        branch = BranchNode()
        old_rest, new_rest = node.path[common:], path[common:]
        evict(node)  # discarded (replaced by the split structure)
        if not old_rest:
            branch.value = node.value
        else:
            branch.children[old_rest[0]] = LeafNode(old_rest[1:], node.value)
        if not new_rest:
            branch.value = value
        else:
            branch.children[new_rest[0]] = LeafNode(new_rest[1:], value)
        if common:
            return ExtensionNode(node.path[:common], branch)
        return branch

    if isinstance(node, ExtensionNode):
        common = _common_prefix_len(node.path, path)
        if common == len(node.path):
            evict(node)  # child set below: encoding changes
            node.child = _insert(node.child, path[common:], value, evict)
            return node
        # split the extension
        evict(node)  # discarded
        branch = BranchNode()
        ext_rest = node.path[common:]
        # the shortened old subtree hangs under ext_rest[0]
        if len(ext_rest) == 1:
            branch.children[ext_rest[0]] = node.child
        else:
            branch.children[ext_rest[0]] = ExtensionNode(ext_rest[1:], node.child)
        new_rest = path[common:]
        if not new_rest:
            branch.value = value
        else:
            branch.children[new_rest[0]] = LeafNode(new_rest[1:], value)
        if common:
            return ExtensionNode(path[:common], branch)
        return branch

    # BranchNode
    evict(node)  # value or child slot changes either way
    if not path:
        node.value = value
        return node
    node.children[path[0]] = _insert(node.children[path[0]], path[1:], value, evict)
    return node


# --- deletion (yellow-paper node collapse) ---------------------------------
#
# The reference is insert-only (reference: src/mpt/mpt.zig:47-119 has no
# delete); deletion is required here because the stateless product path
# must handle EIP-158 account cleanup, selfdestruct, and storage-zeroing —
# all of which REMOVE keys and collapse branch/extension structure.


class _Unresolved(Exception):
    """Raised when a collapse needs the structure of an opaque child (only
    possible on PartialTrie, where unwitnessed subtrees are HashNodes)."""


def _merge_into(nibble_prefix: Tuple[int, ...], child: Node, evict=_noop_evict) -> Node:
    """Prepend `nibble_prefix` to a child that lost its parent branch/ext."""
    if isinstance(child, LeafNode):
        evict(child)  # discarded: replaced by the merged leaf
        return LeafNode(nibble_prefix + child.path, child.value)
    if isinstance(child, ExtensionNode):
        evict(child)  # discarded: replaced by the merged extension
        return ExtensionNode(nibble_prefix + child.path, child.child)
    if isinstance(child, BranchNode):
        if not nibble_prefix:
            return child
        return ExtensionNode(nibble_prefix, child)
    # HashNode (PartialTrie): its kind is unknown, so the merged node's
    # encoding cannot be computed — the witness is insufficient
    raise _Unresolved()


def _collapse_branch(node: BranchNode, evict=_noop_evict) -> Optional[Node]:
    """Re-normalize a branch after a child was deleted."""
    live = [(i, c) for i, c in enumerate(node.children) if c is not None]
    if node.value is not None:
        if not live:
            evict(node)  # discarded
            return LeafNode((), node.value)
        return node
    if not live:
        return None
    if len(live) == 1:
        i, child = live[0]
        evict(node)  # discarded (folded into the merged child)
        return _merge_into((i,), child, evict)
    return node


def _delete(
    node: Optional[Node], path: Tuple[int, ...], evict=_noop_evict
) -> Optional[Node]:
    """Remove `path`; returns the re-normalized subtree (None = empty).
    Missing keys are a no-op (matching geth's trie delete semantics).
    `evict` receives every node whose cached encoding goes stale (mutated
    ancestors and discarded/collapsed nodes) — see _insert."""
    if node is None:
        return None

    if isinstance(node, LeafNode):
        if node.path == tuple(path):
            evict(node)  # discarded
            return None
        return node

    if not isinstance(node, (ExtensionNode, BranchNode)):
        # opaque HashNode (PartialTrie): the delete path crosses an
        # unwitnessed subtree
        raise _Unresolved()

    if isinstance(node, ExtensionNode):
        n = len(node.path)
        if tuple(path[:n]) != node.path:
            return node  # key absent
        # anything below may mutate in place; this encoding goes stale
        # either way (eviction on a no-op absent-key delete is harmless)
        evict(node)
        new_child = _delete(node.child, tuple(path[n:]), evict)
        if new_child is node.child:
            return node  # absent below or mutated in place
        if new_child is None:
            evict(node)  # discarded
            return None
        return _merge_into(node.path, new_child, evict)

    # BranchNode
    if not path:
        if node.value is None:
            return node  # key absent
        evict(node)
        node.value = None
        return _collapse_branch(node, evict)
    i = path[0]
    old_child = node.children[i]
    if old_child is None:
        return node  # key absent
    evict(node)  # see extension case: stale either way
    new_child = _delete(old_child, tuple(path[1:]), evict)
    if new_child is old_child:
        return node  # absent below or mutated in place
    node.children[i] = new_child
    if new_child is not None:
        return node
    return _collapse_branch(node, evict)


class Trie:
    """A build-once/query MPT over byte keys.

    The STRUCTURAL algorithms (insert / delete / branch collapse /
    extension merge) are radix-generic: nothing in them assumes 16-way
    branching beyond `children[digit]` indexing. Commitment-scheme
    plugins (phant_tpu/commitment/) subclass with a different digit
    alphabet and node codec — `_digits` maps a key to its path digits
    (nibbles here; bits for the binary scheme), `_path_enc` encodes a
    leaf/extension path (hex-prefix here; bit-prefix for binary) and
    `_embed_below` is the embedded-node rule (a child whose encoding is
    shorter enters its parent as its structure; 0 for a scheme that
    always references by digest). The hooks default to the hexary-MPT
    behavior, byte-identical to the pre-plugin code.

    Encoding runs in the extension where the program has it
    (native/pyext.cc `encode_subtree`: one call walks everything below a
    node that the memo does not hold) and in `_node_encoding_python`
    where it does not. Either reads nothing of a scheme but the three
    hooks: a subclass varies those, never `_ref`. Either hashes a node
    once, where it builds the node's entry of the memo."""

    #: key -> path digits (hexary: nibbles; binary scheme: bits)
    _digits = staticmethod(bytes_to_nibbles)
    #: leaf/extension path encoding (hexary: yellow-paper hex-prefix)
    _path_enc = staticmethod(encode_hex_prefix)
    #: a child encoding shorter than this is embedded, not hashed
    _embed_below = 32

    def __init__(self):
        self.root: Optional[Node] = None
        # upper bound on leaf count (overwrites double-count); used only as
        # the device-dispatch size heuristic in trie_root_hash
        self.approx_size = 0
        # node-id -> (structure, encoding, reference) memo with PER-PATH
        # invalidation: put/delete evict exactly the mutated/discarded
        # nodes (and any freed object is evicted before its id can be
        # reused), so repeated roots after K updates re-encode only the K
        # dirty paths. The reference is what the node's parent holds of it
        # (the structure where the encoding is shorter than `_embed_below`,
        # else keccak256 of the encoding): ONE entry, so that whatever
        # drops an encoding drops its reference in the same act.
        self._enc_cache: Dict[int, _Entry] = {}
        # digests the walks of this trie have computed; `root_hash` books
        # the growth as `mpt.ref_hashes`
        self._ref_hashes = 0
        # mutation epoch: bumped on every put/delete; the device HashPlan
        # cache (phant_tpu/ops/mpt_jax.py trie_root_device) is keyed on it
        self._epoch = 0

    def _evict(self, node: Node) -> None:
        self._enc_cache.pop(id(node), None)

    def put(self, key: bytes, value: bytes) -> None:
        if not value:  # empty value = delete (geth trie semantics)
            self.delete(key)
            return
        self._epoch += 1
        self.approx_size += 1
        # per-path cache eviction: untouched subtrees keep their encodings,
        # so a root after K updates re-encodes O(K * depth) nodes only
        self.root = _insert(self.root, self._digits(key), value, self._evict)

    def delete(self, key: bytes) -> None:
        """Remove `key` with full branch-collapse/extension-merge
        re-normalization (no-op when absent)."""
        self._epoch += 1
        self.approx_size = max(self.approx_size - 1, 0)
        self.root = _delete(self.root, self._digits(key), self._evict)

    def get(self, key: bytes) -> Optional[bytes]:
        node, path = self.root, self._digits(key)
        while node is not None:
            if isinstance(node, LeafNode):
                return node.value if node.path == tuple(path) else None
            if isinstance(node, ExtensionNode):
                n = len(node.path)
                if tuple(path[:n]) != node.path:
                    return None
                node, path = node.child, path[n:]
                continue
            if not path:
                return node.value
            node, path = node.children[path[0]], path[1:]
        return None

    # --- encoding ---------------------------------------------------------

    def node_encoding(self, node: Node) -> _Entry:
        """(structure, rlp_encoding, reference) of a node, memoized per
        build epoch — proof generation and root hashing share subtree
        encodings instead of re-walking them, and a parent reads its
        child's reference instead of hashing the child again."""
        cached = self._enc_cache.get(id(node))
        if cached is not None:
            return cached
        ext = load_engine_ext()
        if ext is None:
            return self._node_encoding_python(node)
        # the walk's own hex-prefix where the scheme's is the yellow paper's
        path_enc = None if self._path_enc is encode_hex_prefix else self._path_enc
        entry, hashed = ext.encode_subtree(
            node, self._enc_cache, path_enc, self._embed_below, _NODE_KINDS
        )
        self._ref_hashes += hashed
        return entry

    def _node_encoding_python(self, node: Node) -> _Entry:
        """`node_encoding` without the extension, and its oracle."""
        if isinstance(node, LeafNode):
            structure: rlp.RLPItem = [self._path_enc(node.path, True), node.value]
        elif isinstance(node, ExtensionNode):
            structure = [self._path_enc(node.path, False), self._ref(node.child)]
        else:
            slots: List[rlp.RLPItem] = []
            for child in node.children:
                slots.append(b"" if child is None else self._ref(child))
            slots.append(node.value if node.value is not None else b"")
            structure = slots
        encoded = rlp.encode_python(structure)
        if len(encoded) < self._embed_below:
            ref: rlp.RLPItem = structure
        else:
            ref = keccak256(encoded)
            self._ref_hashes += 1
        entry = (structure, encoded, ref)
        self._enc_cache[id(node)] = entry
        return entry

    def node_structure(self, node: Node) -> rlp.RLPItem:
        """The node's RLP structure (list), before the embed-or-hash rule."""
        return self.node_encoding(node)[0]

    def _ref(self, node: Node) -> rlp.RLPItem:
        """Reference to a child: embedded structure if rlp < 32B, else hash
        (reference: src/mpt/mpt.zig:132-281 node encode paths). Read from
        the child's entry: hashed when the child was encoded, not again."""
        return self.node_encoding(node)[2]

    def root_hash(self) -> bytes:
        if self.root is None:
            return EMPTY_TRIE_ROOT
        held, hashed = len(self._enc_cache), self._ref_hashes
        _structure, encoded, ref = self.node_encoding(self.root)
        fresh = len(self._enc_cache) - held
        if fresh:
            count_node_encodings(fresh, self._ref_hashes - hashed)
        # a root is never embedded: one that encodes short is hashed here
        return ref if len(encoded) >= self._embed_below else keccak256(encoded)


def count_node_encodings(nodes: int, ref_hashes: int = 0) -> None:
    """`mpt.node_encodings{impl=}`: once a root computation (a walk's
    `root_hash`, a plan's `finish`), the nodes it encoded, under the
    encoder that serves this process now; and beside it
    `mpt.ref_hashes{impl=}`, the digests a host walk computed for them (a
    plan's are the device's: none)."""
    impl = "python" if load_engine_ext() is None else "native"
    metrics.count("mpt.node_encodings", nodes, impl=impl)
    if ref_hashes:
        metrics.count("mpt.ref_hashes", ref_hashes, impl=impl)


# --- public API mirroring the reference ----------------------------------


def trie_root_hash(trie: Trie) -> bytes:
    """Root of a built trie through the selected crypto backend: device
    level-order hashing on `--crypto_backend=tpu` (phant_tpu/ops/mpt_jax.py,
    with automatic host fallback for embedded-node tries), host recursion
    otherwise. This is the root used by the block path
    (phant_tpu/blockchain/chain.py) and the state root (phant_tpu/state/root.py).

    Tiny tries (a handful of txs/receipts) stay on the host even on the tpu
    backend: per-level dispatch latency would dwarf the hashing. The
    threshold is leaf-count based (PHANT_TPU_MIN_TRIE, default 192) on top
    of THE offload-gate story (ops/root_engine.py module docstring — the
    single source of truth for when plan bytes beat the native hasher)."""
    from phant_tpu.backend import crypto_backend, jax_device_ok

    if (
        crypto_backend() == "tpu"
        and trie.approx_size >= _min_device_trie()
        and jax_device_ok()
        and _device_root_pays(trie)
    ):
        from phant_tpu.ops.mpt_jax import trie_root_device

        return trie_root_device(trie)
    return trie.root_hash()


def _min_device_trie() -> int:
    import os

    return int(os.environ.get("PHANT_TPU_MIN_TRIE", "192"))


def _device_root_pays(trie: Trie) -> bool:
    """Link-aware offload gate for device trie roots (THE offload-gate
    story lives in ops/root_engine.py; this applies it with a ~600B/leaf
    payload estimate — leaf + amortized branch encodings — through the
    shared cost model, phant_tpu/backend.py device_offload_pays)."""
    import os

    if os.environ.get("PHANT_TPU_FORCE_TRIE", "0") not in ("", "0"):
        return True
    from phant_tpu.backend import device_offload_pays

    return device_offload_pays(trie.approx_size * 600)


def trie_root(pairs: Iterable[Tuple[bytes, bytes]]) -> bytes:
    """Root of the trie mapping key bytes -> value bytes (values already RLP).

    Equivalent of the reference's `mptize` over KeyVals
    (reference: src/mpt/mpt.zig:38-45)."""
    trie = Trie()
    for key, value in pairs:
        trie.put(key, value)
    return trie_root_hash(trie)


def ordered_trie_root(values: Sequence[bytes]) -> bytes:
    """Root of the index-keyed trie used for tx/receipt/withdrawal roots:
    key i = rlp(i) (reference: src/engine_api/execution_payload.zig:128-139,
    src/blockchain/blockchain.zig:209-235)."""
    return trie_root((rlp.encode(rlp.encode_uint(i)), v) for i, v in enumerate(values))


def secure_trie_root(pairs: Iterable[Tuple[bytes, bytes]]) -> bytes:
    """Root with keccak-hashed keys — the account/storage trie form. The
    reference never builds this (state-root check is TODO-disabled,
    reference: src/blockchain/blockchain.zig:83-85); the north star needs it."""
    return trie_root((keccak256(k), v) for k, v in pairs)
