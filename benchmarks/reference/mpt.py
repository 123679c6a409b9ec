"""The hexary Merkle Patricia trie, plainly: nodes are RLP in a dict keyed by
their keccak (a node shorter than 32 bytes is embedded in its parent, as the
yellow paper's appendix D says). Every update re-encodes the path it walks.
A path is a string of hex digits, one per nibble."""

from __future__ import annotations

from . import rlp
from .keccak import keccak256, keccak256_many

EMPTY_ROOT = bytes.fromhex(
    "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421"
)


def hex_prefix(path: str, leaf: bool) -> bytes:
    """Appendix C: the flag nibble (2 for a leaf, +1 for an odd length),
    a zero nibble where the length is even, then the path."""
    if len(path) % 2:
        return bytes.fromhex(("3" if leaf else "1") + path)
    return bytes.fromhex(("20" if leaf else "00") + path)


def _unprefix(enc: bytes):
    """(path, is leaf) of a hex-prefix encoding."""
    h = enc.hex()
    flag = int(h[0], 16)
    return (h[1:] if flag & 1 else h[2:]), bool(flag & 2)


def _common(a: str, b: str) -> int:
    n = 0
    while n < len(a) and n < len(b) and a[n] == b[n]:
        n += 1
    return n


#: decoded nodes by hash, so that the upper levels are not decoded again for
#: every key (a node is never changed in place: a hash names its content)
_DECODED: dict = {}


class Trie:
    """One trie over a node store that several tries may share."""

    def __init__(self, db: dict, root: bytes = EMPTY_ROOT):
        self.db = db
        self.root = root

    # a reference to a node, as its parent holds it: the 32-byte hash, or
    # the decoded node itself where its RLP is shorter than 32 bytes
    def _ref(self, node):
        enc = rlp.encode(node)
        if len(enc) < 32:
            return node
        h = keccak256(enc)
        self.db[h] = enc
        return h

    def _node(self, ref):
        if isinstance(ref, list):
            return ref
        node = _DECODED.get(ref)
        if node is None:
            if len(_DECODED) > 200_000:
                _DECODED.clear()
            node = _DECODED[ref] = rlp.decode(self.db[ref])
        return node

    def _set_root(self, node) -> None:
        enc = rlp.encode(node)
        self.root = keccak256(enc)
        self.db[self.root] = enc

    def get(self, key: bytes):
        path = key.hex()
        ref = None if self.root == EMPTY_ROOT else self.root
        while ref is not None and ref != b"":
            node = self._node(ref)
            if len(node) == 17:
                if not path:
                    return node[16] or None
                ref, path = node[int(path[0], 16)], path[1:]
                continue
            sub, leaf = _unprefix(node[0])
            if leaf:
                return node[1] if sub == path else None
            if not path.startswith(sub):
                return None
            ref, path = node[1], path[len(sub) :]
        return None

    def prove(self, key: bytes) -> list:
        """The encoded nodes on the way to `key`, root first, as far as the
        trie goes (so an absent key yields its proof of exclusion)."""
        out, path = [], key.hex()
        ref = None if self.root == EMPTY_ROOT else self.root
        while ref is not None and ref != b"":
            if isinstance(ref, list):
                node = ref  # embedded: part of its parent's encoding
            else:
                out.append(self.db[ref])
                node = self._node(ref)
            if len(node) == 17:
                if not path:
                    break
                ref, path = node[int(path[0], 16)], path[1:]
                continue
            sub, leaf = _unprefix(node[0])
            if leaf or not path.startswith(sub):
                break
            ref, path = node[1], path[len(sub) :]
        return out

    def update(self, key: bytes, value: bytes) -> None:
        """Set `key` to a non-empty value."""
        if not value:
            raise ValueError("this trie does not delete")
        root = None if self.root == EMPTY_ROOT else self._node(self.root)
        self._set_root(self._insert(root, key.hex(), value))

    def _insert(self, node, path: str, value: bytes):
        if node is None:
            return [hex_prefix(path, True), value]
        if len(node) == 17:
            node = list(node)
            if not path:
                node[16] = value
                return node
            at = int(path[0], 16)
            sub = None if node[at] == b"" else self._node(node[at])
            node[at] = self._ref(self._insert(sub, path[1:], value))
            return node
        sub, leaf = _unprefix(node[0])
        n = _common(sub, path)
        if leaf and n == len(sub) == len(path):
            return [node[0], value]
        if not leaf and n == len(sub):
            below = self._insert(self._node(node[1]), path[n:], value)
            return [node[0], self._ref(below)]
        # the paths part inside this node: a branch at the point of parting
        branch = [b""] * 17
        if leaf:
            if n < len(sub):
                branch[int(sub[n], 16)] = self._ref([hex_prefix(sub[n + 1 :], True), node[1]])
            else:
                branch[16] = node[1]
        elif n + 1 == len(sub):
            branch[int(sub[n], 16)] = node[1]
        else:
            branch[int(sub[n], 16)] = self._ref([hex_prefix(sub[n + 1 :], False), node[1]])
        if n == len(path):
            branch[16] = value
        else:
            branch[int(path[n], 16)] = self._ref([hex_prefix(path[n + 1 :], True), value])
        if n == 0:
            return branch
        return [hex_prefix(path[:n], False), self._ref(branch)]


def build_sorted(db: dict, keys: list, values: list) -> bytes:
    """The root of the trie of `keys` (32-byte, sorted, distinct) and their
    `values`, all of whose nodes go into `db`: what `update` would build key
    by key, made in one pass from the leaves up. Leaves are hashed in one
    call; a leaf here is never shorter than 32 bytes."""
    n = len(keys)
    if n == 0:
        return EMPTY_ROOT
    paths = [k.hex() for k in keys]
    shared = [_common(paths[i], paths[i + 1]) for i in range(n - 1)]
    depth = [
        max(shared[i - 1] if i else -1, shared[i] if i < n - 1 else -1) + 1
        for i in range(n)
    ]
    leaves = [
        rlp.encode([hex_prefix(paths[i][depth[i] :], True), values[i]])
        for i in range(n)
    ]
    if min(map(len, leaves)) < 32:
        raise ValueError("a leaf short enough to embed")
    digests = keccak256_many(leaves)
    db.update(zip(digests, leaves))

    def put(node) -> bytes:
        enc = rlp.encode(node)
        h = keccak256(enc)
        db[h] = enc
        return h

    def build(lo: int, hi: int, at: int) -> bytes:
        """The reference to the node over keys lo..hi-1, which share their
        first `at` nibbles."""
        if hi - lo == 1:
            return digests[lo]
        upto = min(shared[lo : hi - 1])
        if upto > at:
            below = build(lo, hi, upto)
            return put([hex_prefix(paths[lo][at:upto], False), below])
        branch = [b""] * 17
        start = lo
        for i in range(lo, hi):
            if i + 1 == hi or shared[i] == at:
                branch[int(paths[start][at], 16)] = build(start, i + 1, at + 1)
                start = i + 1
        return put(branch)

    return build(0, n, 0)
