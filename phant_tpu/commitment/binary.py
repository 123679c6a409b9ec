"""Binary Merkle commitment: fixed-shape 2-ary keccak nodes, bit paths.

The scheme three of the five PAPERS.md papers point at (2504.14069:
binary Merkle dominates hexary MPT on witness bytes; 2606.11736 MHOT:
height-optimized layouts with path compression beat the canonical trie
on proof depth): a Patricia tree over the 256 BITS of the keccak'd key,
with MHOT-style path compression (extension levels carry skipped bit
runs, leaves carry their remaining bit suffix) and every child
referenced by its 32-byte keccak digest — no <32 B embedding, so every
node is a fixed-shape hashing unit.

Node encodings (THE REF-TRANSPARENCY CONTRACT, phant_tpu/commitment/
__init__.py): each node is a single RLP list whose child refs sit
exactly where the shared ref scanners already look, so binary witnesses
flow through all three witness-engine cores, the fused device kernel
and the device-resident table with zero scanner changes:

  * internal (2-ary branch): a 17-item list `[left, right, "" x 15]`
    with both children as 32-byte digests — semantically strictly
    2-ary (slots 2..15 and the value slot are ALWAYS empty; the codec
    rejects anything else), framed so the scanners' branch rule
    extracts both child refs. 83 bytes fixed — one keccak rate chunk,
    vs up to 563 B for a dense hexary branch; the ~19-byte framing tax
    over a raw 64-byte `left||right` payload buys the entire existing
    verification stack unmodified (documented in README);
  * extension: `[bit_prefix(path, leaf=0), child_digest]` — the pair
    rule (0x20 bit clear) extracts the child ref;
  * leaf: `[bit_prefix(path, leaf=1), value]` — account-shaped values
    expose their storage root through the scanners' account-leaf rule,
    exactly like the hexary account leaf (the account VALUE encoding is
    scheme-independent, see CommitmentScheme).

Bit-prefix path encoding (the hex-prefix analogue for bit strings):
2 header bytes + ceil(nbits/8) big-endian bit bytes. Header byte 0 =
0x20*is_leaf | high bit of the 9-bit count (0..256), byte 1 = count's
low 8 bits; trailing pad bits must be zero (canonical encodings only).
The 0x20 flag deliberately lands on the same bit the hex-prefix leaf
flag uses — that is what the shared pair-node scanner rule keys on.

Hash-plan lowering: `BinaryPlanBuilder` is the stock PlanBuilder with
the bit-prefix path encoder and the embedded-node rule disabled (binary
always refs by digest, so every subtree is plannable) — HashPlan,
merge_plans, RootEngine and the scheduler's root lane are template-
agnostic and run binary plans unchanged.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from phant_tpu import rlp
from phant_tpu.commitment import CommitmentScheme, register_scheme
from phant_tpu.mpt.mpt import (
    EMPTY_TRIE_ROOT,
    BranchNode,
    ExtensionNode,
    LeafNode,
    Trie,
)

# module-level on purpose (no cycle: stateless.py reaches commitment/ only
# lazily at call time, never at import time) and jax-free — the binary
# scheme must stay importable on the pure-CPU serving path; the one
# jax-adjacent piece (the plan builder over ops/mpt_jax) is lazy below
from phant_tpu.stateless import HashNode, PartialTrie, StatelessError

#: per-byte bit tuples (MSB first) — key digitization is on the state
#: materialization path, so it's a table lookup, not per-bit arithmetic
_BIT_TABLE = tuple(
    tuple((b >> i) & 1 for i in range(7, -1, -1)) for b in range(256)
)


def bytes_to_bits(key: bytes) -> Tuple[int, ...]:
    """MSB-first bit digits of `key` (bit i of byte b is digit 8*b+7-i)."""
    return tuple(bit for byte in key for bit in _BIT_TABLE[byte])


def encode_bit_prefix(bits, is_leaf: bool) -> bytes:
    """Bit-string path encoding: [flags|count_hi, count_lo, bit bytes...].
    The 0x20 leaf flag intentionally matches hex-prefix so the shared
    pair-node ref-scanner rule (leaf vs extension) applies unchanged."""
    n = len(bits)
    if n > 256:
        raise ValueError(f"bit path of {n} digits exceeds the 256-bit key")
    out = bytearray(2 + (n + 7) // 8)
    out[0] = (0x20 if is_leaf else 0x00) | ((n >> 8) & 0x01)
    out[1] = n & 0xFF
    for i, bit in enumerate(bits):
        if bit:
            out[2 + (i >> 3)] |= 0x80 >> (i & 7)
    return bytes(out)


def decode_bit_prefix(data: bytes) -> Tuple[Tuple[int, ...], bool]:
    """Strict inverse of `encode_bit_prefix`: unknown flag bits, length
    mismatches and nonzero pad bits are all rejected (non-canonical path
    encodings must not alias distinct committed trees)."""
    if len(data) < 2:
        raise ValueError("bit-prefix path too short")
    flag = data[0]
    if flag & ~0x21:
        raise ValueError("bad bit-prefix flag byte")
    is_leaf = bool(flag & 0x20)
    n = ((flag & 0x01) << 8) | data[1]
    if n > 256:
        raise ValueError(f"bit path of {n} digits exceeds the 256-bit key")
    nbytes = (n + 7) // 8
    if len(data) != 2 + nbytes:
        raise ValueError("bit-prefix length mismatch")
    if n & 7:
        pad_mask = (1 << (8 - (n & 7))) - 1
        if data[-1] & pad_mask:
            raise ValueError("nonzero bit-prefix pad bits")
    bits = tuple(
        (data[2 + (i >> 3)] >> (7 - (i & 7))) & 1 for i in range(n)
    )
    return bits, is_leaf


# ---------------------------------------------------------------------------
# tries
# ---------------------------------------------------------------------------


class BinaryTrie(Trie):
    """A build-once/query binary Patricia tree over byte keys.

    Reuses mpt.py's radix-generic structure algorithms wholesale: the
    digit alphabet is {0, 1} (so only `children[0]`/`children[1]` of the
    stock 16-slot BranchNode are ever populated), paths encode with the
    bit-prefix codec, and a child is ALWAYS hashed — the fixed-shape
    rule that makes every node a digest-referenced unit."""

    _digits = staticmethod(bytes_to_bits)
    _path_enc = staticmethod(encode_bit_prefix)
    # no embedding: children are referenced by digest regardless of
    # encoding size (fixed-shape 2-ary rule)
    _embed_below = 0


def _resolve_binary(digest: bytes, db: Dict[bytes, bytes]):
    enc = db.get(digest)
    if enc is None:
        return HashNode(digest)
    return decode_binary_node(rlp.decode(enc), db)


def decode_binary_node(item: rlp.RLPItem, db: Dict[bytes, bytes]):
    """Decoded binary witness structure -> node graph (HashNode at the
    witness edges). STRICTLY 2-ary: a 17-item frame with anything in
    slots 2..16, a missing branch child, an embedded (list-valued) child
    or a non-canonical bit prefix is rejected — the frame is for ref-
    scanner transparency, not for smuggling hexary structure."""
    if not isinstance(item, list):
        raise StatelessError("binary trie node is not an RLP list")
    if len(item) == 17:
        branch = BranchNode()
        for i in (0, 1):
            child = item[i]
            if isinstance(child, list) or len(child) != 32:
                raise StatelessError(
                    "binary branch child must be a 32-byte digest"
                )
            branch.children[i] = _resolve_binary(bytes(child), db)
        for i in range(2, 16):
            if isinstance(item[i], list) or len(item[i]) != 0:
                raise StatelessError("binary branch with >2 children")
        if isinstance(item[16], list) or len(item[16]) != 0:
            raise StatelessError("binary branch must not carry a value")
        return branch
    if len(item) == 2:
        if isinstance(item[0], list):
            raise StatelessError("bad binary path item")
        try:
            path, is_leaf = decode_bit_prefix(bytes(item[0]))
        except ValueError as e:
            raise StatelessError(f"bad bit-prefix path: {e}") from None
        if is_leaf:
            if isinstance(item[1], list) or len(item[1]) == 0:
                raise StatelessError("bad binary leaf value")
            return LeafNode(path, bytes(item[1]))
        if not path:
            raise StatelessError("binary extension with empty path")
        child = item[1]
        if isinstance(child, list) or len(child) != 32:
            raise StatelessError(
                "binary extension child must be a 32-byte digest"
            )
        return ExtensionNode(path, _resolve_binary(bytes(child), db))
    raise StatelessError(f"binary trie node with {len(item)} items")


class PartialBinaryTrie(PartialTrie, BinaryTrie):
    """A witness-backed binary partial tree (the PartialTrie analogue).

    Pure hook composition, no method bodies: PartialTrie supplies the
    witness semantics (HashNode edges and their `_ref` digest
    passthrough, insufficient-witness errors, deletion poisoning) — all
    radix-generic — BinaryTrie supplies the codec (`_digits`,
    `_path_enc`, the always-hash `_embed_below` via the MRO), and the one
    scheme-specific piece is the witness decoder hook."""

    _resolve_witness = staticmethod(_resolve_binary)


# ---------------------------------------------------------------------------
# hash-plan lowering (the batched root lane)
# ---------------------------------------------------------------------------


import functools as _functools


@_functools.lru_cache(maxsize=1)
def _binary_plan_builder_cls():
    """The BinaryPlanBuilder class, built ONCE on first use — the import
    of ops/mpt_jax (which pulls in jax) is what must stay lazy, not the
    class statement: plan_builder() runs per request on the serving
    post-root path."""
    from phant_tpu.ops.mpt_jax import PlanBuilder

    class BinaryPlanBuilder(PlanBuilder):
        _path_enc = staticmethod(encode_bit_prefix)
        _min_template = 0

    return BinaryPlanBuilder


def binary_plan_builder():
    """The stock level-template planner with the binary codec: bit-prefix
    paths, and `_min_template = 0` because binary NEVER embeds — every
    subtree is plannable, so the only host-walk fallback left is the
    oversized-node guard. HashPlan / merge_plans / RootEngine and the
    scheduler's root lane consume the result unchanged (templates with
    32-byte holes are scheme-agnostic)."""
    return _binary_plan_builder_cls()()


# ---------------------------------------------------------------------------
# the scheme
# ---------------------------------------------------------------------------


class BinaryScheme(CommitmentScheme):
    name = "binary"
    #: keccak(rlp(b"")) — the empty-tree root is shared with the hexary
    #: scheme by design: `verify_witness_nodes`' empty-pre-state contract
    #: and the storage-root sentinels stay scheme-independent
    empty_root = EMPTY_TRIE_ROOT

    def fresh_trie(self) -> BinaryTrie:
        return BinaryTrie()

    def partial_trie(self, root_digest: bytes, db: Dict[bytes, bytes]):
        return PartialBinaryTrie(root_digest, db)

    def plan_builder(self):
        return binary_plan_builder()

    # -- witnesses -----------------------------------------------------------

    def collect_nodes(self, trie: Trie, nodes: Dict[bytes, None]) -> None:
        """The binary witness pack loop: EVERY node encoding ships (all
        children are digest-referenced, so all nodes are witness units).
        Serving-hot (witness generation for the differential spans) —
        phantlint HOSTSYNC watches it."""
        if trie.root is None:
            return
        stack = [trie.root]
        while stack:
            node = stack.pop()
            nodes[trie.node_encoding(node)[1]] = None
            if isinstance(node, ExtensionNode):
                stack.append(node.child)
            elif isinstance(node, BranchNode):
                for child in node.children:
                    if child is not None:
                        stack.append(child)

    def proof_nodes(self, trie: Trie, key: bytes) -> List[bytes]:
        """Node encodings along `key`'s lookup path (presence or
        witnessed absence) — sibling digests ride inside the 2-ary
        parents, so the path nodes alone are the proof."""
        out: List[bytes] = []
        node, path = trie.root, list(bytes_to_bits(key))
        while node is not None:
            out.append(trie.node_encoding(node)[1])
            if isinstance(node, LeafNode):
                break
            if isinstance(node, ExtensionNode):
                n = len(node.path)
                if tuple(path[:n]) != node.path:
                    break
                node, path = node.child, path[n:]
                continue
            if not path:
                break
            node, path = node.children[path[0]], path[1:]
        return out


register_scheme(BinaryScheme())
