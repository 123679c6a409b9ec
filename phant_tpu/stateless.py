"""Stateless block execution from a witness.

The capability behind `engine_executeStatelessPayloadV1`: execute a block
against ONLY a multiproof witness (RLP trie nodes + contract codes), with no
resident world state, and recompute the post-state root over the witnessed
subtree. The reference client has the Engine API method in its supported
list but no implementation (reference: src/main.zig:24-54 lists it,
main.zig:58-70 implements only newPayloadV2) and skips state roots entirely
(reference: src/blockchain/blockchain.zig:83-85); this module is the north
star's actual product path — witness verification is the TPU-batched hot
loop (phant_tpu/ops/witness_jax.py), and execution runs over a lazily
materialized witness-backed StateDB.

Pieces:
- `PartialTrie`: an MPT reconstructed from witness nodes where unwitnessed
  subtrees are opaque `HashNode`s contributing their digest directly. Reads
  and writes that stay inside the witnessed region work; touching an
  unwitnessed subtree raises StatelessError (the witness is insufficient).
- `WitnessStateDB`: a StateDB that materializes accounts/storage on first
  access by walking the partial trie (account key = keccak(address), slot
  key = keccak(slot_be32)), and whose `state_root()` recomputes the post
  root by writing every dirty account back into the partial trie.

Deletion is fully supported: EIP-158 cleanup of touched-empty accounts,
selfdestruct, and storage-zeroing delete keys from the partial trie with
full branch-collapse/extension-merge re-normalization (phant_tpu/mpt/mpt.py
_delete). The one witness-shaped limit is inherent to stateless execution:
collapsing a branch down to a single unwitnessed (HashNode) sibling needs
that sibling's encoding, so such a witness raises StatelessError — witness
formats must include deletion siblings, as real stateless protocols do.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from phant_tpu import rlp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import (
    BranchNode,
    EMPTY_TRIE_ROOT,
    ExtensionNode,
    LeafNode,
    Trie,
    decode_hex_prefix,
)
from phant_tpu.state.statedb import StateDB
from phant_tpu.types.account import Account, EMPTY_CODE_HASH


class StatelessError(ValueError):
    """The witness is insufficient or unsupported for this execution."""


@dataclass
class HashNode:
    """An unwitnessed subtree: only its digest is known."""

    digest: bytes


def _decode_node(item: rlp.RLPItem, db: Dict[bytes, bytes]):
    """Decoded witness structure -> node graph (HashNode at witness edges)."""
    if not isinstance(item, list):
        raise StatelessError("trie node is not an RLP list")
    if len(item) == 17:
        branch = BranchNode()
        for i in range(16):
            child = item[i]
            if isinstance(child, list):
                branch.children[i] = _decode_node(child, db)
            elif len(child) == 0:
                branch.children[i] = None
            elif len(child) == 32:
                branch.children[i] = _resolve(bytes(child), db)
            else:
                raise StatelessError("bad branch child reference")
        value = bytes(item[16])
        branch.value = value if value else None
        return branch
    if len(item) == 2:
        path, is_leaf = decode_hex_prefix(bytes(item[0]))
        if is_leaf:
            return LeafNode(path, bytes(item[1]))
        child = item[1]
        if isinstance(child, list):
            return ExtensionNode(path, _decode_node(child, db))
        if len(child) == 32:
            return ExtensionNode(path, _resolve(bytes(child), db))
        raise StatelessError("bad extension child reference")
    raise StatelessError(f"trie node with {len(item)} items")


def _resolve(digest: bytes, db: Dict[bytes, bytes]):
    enc = db.get(digest)
    if enc is None:
        return HashNode(digest)
    return _decode_node(rlp.decode(enc), db)


class PartialTrie(Trie):
    """A trie over witness nodes; unwitnessed subtrees are HashNodes.

    Hashing: `root_hash()` is the host walk. Whether a partial trie's
    post-root re-hash runs here or as part of a batched device plan is
    decided by THE offload-gate story in ops/root_engine.py (single
    source of truth) — one witness subtree alone is below the
    device-dispatch break-even, but the serving path coalesces many
    requests' plans into one dispatch, which is where the device wins
    (WitnessStateDB.post_root_plan / compute_post_root)."""

    #: digest -> decoded node graph (scheme hook: the hexary witness
    #: decoder here; the binary scheme swaps in its strict 2-ary decoder,
    #: phant_tpu/commitment/binary.py)
    _resolve_witness = staticmethod(_resolve)

    def __init__(self, root_digest: bytes, db: Dict[bytes, bytes]):
        Trie.__init__(self)
        if root_digest != EMPTY_TRIE_ROOT:
            node = self._resolve_witness(root_digest, db)
            if isinstance(node, HashNode):
                raise StatelessError("witness is missing the root node")
            self.root = node
            self.approx_size = len(db)

    # --- reads ------------------------------------------------------------

    def get(self, key: bytes) -> Optional[bytes]:
        node, path = self.root, self._digits(key)
        while node is not None:
            if isinstance(node, HashNode):
                raise StatelessError(
                    f"witness does not cover key {key.hex()}"
                )
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

    # --- writes -----------------------------------------------------------

    def put(self, key: bytes, value: bytes) -> None:
        if not value:  # empty value = delete (geth trie semantics)
            self.delete(key)
            return
        self._enc_cache.clear()
        self.root = _insert_partial(self.root, self._digits(key), value)

    def delete(self, key: bytes) -> None:
        """Remove `key` with full node collapse. Raises StatelessError when
        the collapse needs the structure of an unwitnessed sibling (a branch
        left with one HashNode child must merge the nibble into it, which
        requires its encoding) — the witness is insufficient, exactly the
        case stateless witness formats require sibling nodes for."""
        from phant_tpu.mpt.mpt import _delete, _Unresolved

        self._enc_cache.clear()
        try:
            self.root = _delete(self.root, self._digits(key))
        except _Unresolved:
            # _delete mutates in place on the way down, so the trie is now
            # half-deleted (key gone, collapse pending) — poison it so no
            # caller can hash the non-canonical structure
            self._broken = True
            raise StatelessError(
                "deletion collapse crosses an unwitnessed subtree "
                f"(key {key.hex()}); the witness must include sibling nodes"
            ) from None

    # --- hashing ----------------------------------------------------------

    def _ref(self, node):
        if isinstance(node, HashNode):
            return node.digest
        return super()._ref(node)

    def node_encoding(self, node):
        if isinstance(node, HashNode):
            raise StatelessError("cannot encode an unwitnessed subtree")
        return super().node_encoding(node)

    _broken = False  # set by a failed delete(); the structure is no longer
    # canonical and must never be hashed

    def root_hash(self) -> bytes:
        if self._broken:
            raise StatelessError(
                "partial trie is poisoned by a failed deletion collapse"
            )
        if isinstance(self.root, HashNode):
            return self.root.digest
        return super().root_hash()


def _insert_partial(node, path, value: bytes):
    """mpt._insert with HashNode awareness: descending INTO an unwitnessed
    subtree is an error; splitting an edge NEXT TO one is fine (the HashNode
    keeps contributing its digest from its new position)."""
    from phant_tpu.mpt.mpt import _common_prefix_len

    if node is None:
        return LeafNode(tuple(path), value)
    if isinstance(node, HashNode):
        raise StatelessError("write path crosses an unwitnessed subtree")

    if isinstance(node, LeafNode):
        if node.path == tuple(path):
            node.value = value
            return node
        common = _common_prefix_len(node.path, path)
        branch = BranchNode()
        old_rest, new_rest = node.path[common:], tuple(path[common:])
        if not old_rest:
            branch.value = node.value
        else:
            branch.children[old_rest[0]] = LeafNode(old_rest[1:], node.value)
        if not new_rest:
            branch.value = value
        else:
            branch.children[new_rest[0]] = LeafNode(new_rest[1:], value)
        if common:
            return ExtensionNode(tuple(path[:common]), branch)
        return branch

    if isinstance(node, ExtensionNode):
        common = _common_prefix_len(node.path, path)
        if common == len(node.path):
            node.child = _insert_partial(node.child, path[common:], value)
            return node
        branch = BranchNode()
        ext_rest = node.path[common:]
        if len(ext_rest) == 1:
            branch.children[ext_rest[0]] = node.child
        else:
            branch.children[ext_rest[0]] = ExtensionNode(ext_rest[1:], node.child)
        new_rest = tuple(path[common:])
        if not new_rest:
            branch.value = value
        else:
            branch.children[new_rest[0]] = LeafNode(new_rest[1:], value)
        if common:
            return ExtensionNode(tuple(path[:common]), branch)
        return branch

    # BranchNode
    if not path:
        node.value = value
        return node
    node.children[path[0]] = _insert_partial(node.children[path[0]], path[1:], value)
    return node


# ---------------------------------------------------------------------------
# witness-backed state
# ---------------------------------------------------------------------------


def witness_node_db(nodes: List[bytes]) -> Dict[bytes, bytes]:
    """The digest -> node-bytes map of one witness, built with ONE batched
    C keccak call instead of a per-node scalar loop — the request path's
    one and only witness decode (`stateless.witness_nodes_decoded` counts
    it, so a reintroduced second decode shows up as a doubled counter in
    the phase metrics). The witness-VERIFICATION decode lives elsewhere
    and is amortized: the serving prefetch stage pre-scans batches
    against the engine's intern tables (ops/witness_engine.py
    prefetch_batch), where the steady-state marginal cost per block is
    ~zero (cross-block reuse, PAPERS.md 2408.14217)."""
    from phant_tpu.crypto.keccak import keccak256_batch_cpu
    from phant_tpu.utils.trace import metrics

    metrics.count("stateless.witness_nodes_decoded", len(nodes))
    return dict(zip(keccak256_batch_cpu(nodes), nodes))


#: `_applied_accounts` sentinels: the account's leaf was deleted from the
#: trie / the address was never written under the current generation
_DELETED = object()
_UNSET = object()


class _RootPatch:
    """One account leaf awaiting its plan-computed storage root: the leaf
    was put with a zeroed 32-byte placeholder, and `apply_post_root`
    patches the real digest in once the plan resolves."""

    __slots__ = ("addr", "leaf", "prefix", "suffix", "gi", "fields")

    def __init__(self, addr, leaf, prefix, suffix, gi, fields):
        self.addr = addr
        self.leaf = leaf  # the LeafNode object inside the account trie
        self.prefix = prefix  # account-RLP bytes before the storage root
        self.suffix = suffix  # account-RLP bytes after it
        self.gi = gi  # the storage root's entry in the plan builder
        self.fields = fields  # (nonce, balance, code_hash)


class PostRootPlan:
    """A request's fused account+storage hash plan plus the host-side
    patch list (`WitnessStateDB.post_root_plan` -> serving root lane ->
    `apply_post_root`). `plan.out_rows` reads back one storage root per
    patch (same order) and the account root LAST."""

    __slots__ = ("plan", "patches")

    def __init__(self, plan, patches):
        self.plan = plan  # ops/mpt_jax.HashPlan
        self.patches = patches  # List[_RootPatch]

    @property
    def levels(self) -> int:
        return len(self.plan.levels)


class WitnessStateDB(StateDB):
    """StateDB over a witness: accounts and storage slots materialize on
    first access by walking the partial state trie; `state_root()` writes
    every dirty account back into the partial trie and recomputes the root.
    Touching anything outside the witness raises StatelessError.

    Write-backs are MEMOIZED (`_applied_*`): what was already written
    into the partial tries is remembered, so a repeated `state_root()`
    call with nothing changed in between re-applies nothing and hashes
    zero nodes (the post-root memo) — the pre-r11 behavior rebuilt
    `changed` from scratch and re-put every changed slot per call.

    `node_db` hands in the witness's digest -> node map decoded earlier
    on the request path (witness_node_db) so each witness is decoded
    exactly once; None decodes here (offline/test callers).

    `scheme` selects the commitment scheme (phant_tpu/commitment/) the
    witness commits state under — the partial tries, the node codec and
    the post-root hash-plan lowering all resolve through it. None means
    the process-wide active scheme (PHANT_COMMITMENT / `--commitment`,
    default the hexary `mpt` scheme, byte-identical to the pre-plugin
    path)."""

    def __init__(
        self,
        state_root: bytes,
        nodes: List[bytes],
        codes: List[bytes],
        node_db: Optional[Dict[bytes, bytes]] = None,
        scheme=None,
    ):
        super().__init__()
        if scheme is None:
            from phant_tpu.commitment import active_scheme

            scheme = active_scheme()
        from phant_tpu.utils.trace import metrics

        self._scheme = scheme
        metrics.count("commitment.state_views", scheme=scheme.name)
        self._db = node_db if node_db is not None else witness_node_db(nodes)
        self._codes = {keccak256(c): c for c in codes}
        self._trie = scheme.partial_trie(state_root, self._db)
        self._seen: set = set()
        self._storage_roots: Dict[bytes, bytes] = {}
        self._storage_ptries: Dict[bytes, PartialTrie] = {}
        self._slots_seen: Dict[bytes, set] = {}  # addr -> slots
        # materialized pre-values, for write-back dirtiness checks: only
        # slots/accounts that actually changed touch the trie at root time
        self._pre_slots: Dict[Tuple[bytes, int], int] = {}
        self._pre_accounts: Dict[bytes, Tuple[int, int, bytes]] = {}
        # the materialized Account object per address: identity change means
        # delete+recreate within the block (journal rollback restores the
        # original object, so identity is a reliable generation marker) —
        # a recreated account starts from an EMPTY storage trie
        self._mat_objs: Dict[bytes, object] = {}
        # post-root write-back memoization (PR 11): what has ALREADY been
        # applied to the partial tries, so repeated state_root() calls
        # are idempotent-cheap and the batched plan path shares one
        # dirtiness scan with the host walk
        self._applied_slots: Dict[Tuple[bytes, int], int] = {}
        self._applied_accounts: Dict[bytes, object] = {}  # tuple | _DELETED
        self._applied_gen: Dict[bytes, object] = {}  # acct identity applied
        self._storage_root_memo: Dict[bytes, bytes] = {}
        self._sroot_dirty: set = set()  # applied writes, root not yet known
        self._post_root_memo: Optional[bytes] = None

    # --- materialization ---------------------------------------------------

    def _materialize(self, addr: bytes) -> None:
        if addr in self._seen:
            return
        self._seen.add(addr)
        leaf = self._trie.get(keccak256(addr))
        if leaf is None:
            return  # witnessed absence
        fields = rlp.decode(leaf)
        if not isinstance(fields, list) or len(fields) != 4:
            raise StatelessError("malformed account leaf in witness")
        nonce = rlp.decode_uint(bytes(fields[0]))
        balance = rlp.decode_uint(bytes(fields[1]))
        storage_root = bytes(fields[2])
        code_hash = bytes(fields[3])
        if code_hash == EMPTY_CODE_HASH:
            code = b""
        else:
            code = self._codes.get(code_hash)
            if code is None:
                raise StatelessError(
                    f"witness is missing code {code_hash.hex()}"
                )
        # pre-state materialization is not journaled: a block rollback must
        # not forget what the witness proved
        acct = Account(nonce=nonce, balance=balance, code=code)
        self.accounts[addr] = acct
        self._storage_roots[addr] = storage_root
        self._pre_accounts[addr] = (nonce, balance, code_hash)
        self._mat_objs[addr] = acct

    def _materialize_slot(self, addr: bytes, slot: int) -> None:
        key = (addr, slot)
        seen = self._slots_seen.setdefault(addr, set())
        if slot in seen:
            return
        seen.add(slot)
        self._materialize(addr)
        acct = self.accounts.get(addr)
        if acct is None:
            return
        if self._mat_objs.get(addr) is not acct:
            return  # recreated after deletion: storage starts empty, the
            # witnessed pre-state slot must NOT leak into the new generation
        sroot = self._storage_roots.get(addr, EMPTY_TRIE_ROOT)
        if sroot == EMPTY_TRIE_ROOT:
            return
        strie = self._storage_ptries.get(addr)
        if strie is None:
            strie = self._scheme.partial_trie(sroot, self._db)
            self._storage_ptries[addr] = strie
        raw = strie.get(keccak256(slot.to_bytes(32, "big")))
        if raw is not None:
            value = rlp.decode_uint(bytes(rlp.decode(raw)))
            acct.storage[slot] = value
            self._pre_slots[key] = value

    # --- overridden accessors ---------------------------------------------

    def account_exists(self, addr):
        self._materialize(addr)
        return super().account_exists(addr)

    def get_account(self, addr):
        self._materialize(addr)
        return super().get_account(addr)

    def _get_or_create(self, addr):
        self._materialize(addr)
        return super()._get_or_create(addr)

    def get_balance(self, addr):
        self._materialize(addr)
        return super().get_balance(addr)

    def get_nonce(self, addr):
        self._materialize(addr)
        return super().get_nonce(addr)

    def get_code(self, addr):
        self._materialize(addr)
        return super().get_code(addr)

    def is_empty(self, addr):
        self._materialize(addr)
        return super().is_empty(addr)

    def touch(self, addr):
        # EIP-158 cleanup (destroy_touched_empty) inspects accounts directly;
        # a touched pre-existing empty account must be materialized or its
        # leaf would silently survive deletion
        self._materialize(addr)
        super().touch(addr)

    def get_storage(self, addr, slot):
        self._materialize_slot(addr, slot)
        return super().get_storage(addr, slot)

    def set_storage(self, addr, slot, value):
        self._materialize_slot(addr, slot)
        return super().set_storage(addr, slot, value)

    # --- post root ----------------------------------------------------------

    def state_root(self) -> bytes:
        """Post-state root over the witnessed subtree — the HOST walk, and
        the oracle the batched device path (post_root_plan / ops/
        root_engine.py) is differential-tested against: write every account
        this execution changed back into the partial trie (untouched
        subtrees contribute their witnessed digests; unchanged materialized
        accounts are skipped — dirtiness check), recomputing storage roots
        for accounts whose slots changed. Deleted accounts (EIP-158 cleanup,
        selfdestruct) are removed with full node collapse. Idempotent-cheap:
        a repeated call with nothing changed applies nothing and returns
        the memoized root without hashing a single node."""
        changed_any = False
        for addr in sorted(self._seen | set(self.accounts)):
            acct = self.accounts.get(addr)
            key = keccak256(addr)
            if acct is None:
                if self._delete_account_leaf(addr, key):
                    changed_any = True
                continue
            sroot = self._storage_root_of(addr, acct)
            target = (acct.nonce, acct.balance, sroot, acct.code_hash())
            if target == self._account_baseline(addr, acct):
                continue  # account unchanged: leave its leaf alone
            self._post_root_memo = None
            self._trie.put(key, self._account_leaf_value(*target))
            self._applied_accounts[addr] = target
            changed_any = True
        if not changed_any and self._post_root_memo is not None:
            return self._post_root_memo
        root = self._trie.root_hash()
        self._post_root_memo = root
        return root

    @staticmethod
    def _account_leaf_value(
        nonce: int, balance: int, sroot: bytes, code_hash: bytes
    ) -> bytes:
        from phant_tpu.commitment import account_leaf_value

        return account_leaf_value(nonce, balance, sroot, code_hash)

    def _delete_account_leaf(self, addr: bytes, key: bytes) -> bool:
        """Delete the account's leaf if the trie currently holds one
        (pre-existed, or put by an earlier state_root call); idempotent."""
        applied = self._applied_accounts.get(addr, _UNSET)
        if applied is _DELETED:
            return False
        if applied is _UNSET and addr not in self._pre_accounts:
            return False
        self._post_root_memo = None  # trie mutates: memo invalid NOW (an
        # abort path between here and the recompute must not resurrect it)
        self._trie.delete(key)
        self._applied_accounts[addr] = _DELETED
        return True

    def _account_baseline(self, addr: bytes, acct: Account):
        """What the account trie currently holds for `addr`: the last
        applied leaf fields, or the witnessed pre-state when nothing was
        applied and the materialized identity is unchanged. _UNSET (never
        equal to a target tuple) when the address has no leaf under the
        current account generation — a create/recreate must put."""
        applied = self._applied_accounts.get(addr, _UNSET)
        if applied is not _UNSET:
            return applied
        pre = self._pre_accounts.get(addr)
        if pre is not None and self._mat_objs.get(addr) is acct:
            return (
                pre[0],
                pre[1],
                self._storage_roots.get(addr, EMPTY_TRIE_ROOT),
                pre[2],
            )
        return _UNSET

    def _storage_changes(
        self, addr: bytes, acct: Account
    ) -> Tuple[bytes, Dict[int, int], bool]:
        """(pre_root, {slot: value} still to apply, fresh): the pending
        storage-trie writes for one account, diffed against what earlier
        state_root/post_root_plan calls already applied."""
        fresh = self._mat_objs.get(addr) is not acct  # created (or recreated
        # after selfdestruct) this block: storage starts from the empty trie
        if fresh and self._applied_gen.get(addr) is not acct:
            # a recreated account invalidates writes applied under the
            # dead generation — its storage trie restarts from EMPTY
            for k in [k for k in self._applied_slots if k[0] == addr]:
                del self._applied_slots[k]
            self._storage_ptries.pop(addr, None)
            self._storage_root_memo.pop(addr, None)
            self._sroot_dirty.discard(addr)
            self._applied_gen[addr] = acct
        pre_root = (
            EMPTY_TRIE_ROOT
            if fresh
            else self._storage_roots.get(addr, EMPTY_TRIE_ROOT)
        )
        dirty = set(self._slots_seen.get(addr, ()))
        dirty |= set(acct.storage)
        changed: Dict[int, int] = {}
        for s in dirty:
            cur = acct.storage.get(s, 0)
            k = (addr, s)
            if k in self._applied_slots:
                base = self._applied_slots[k]
            elif fresh:
                base = 0
            else:
                base = self._pre_slots.get(k, 0)
            if cur != base:
                changed[s] = cur
        return pre_root, changed, fresh

    def _apply_storage(
        self, addr: bytes, acct: Account, pre_root: bytes, changed: Dict[int, int]
    ) -> PartialTrie:
        """Write one account's pending slot changes into its storage trie
        (host structural work — identical on the host-walk and plan
        paths); the root itself is computed by the caller's path."""
        strie = self._storage_ptries.get(addr)
        if strie is None:
            strie = self._scheme.partial_trie(pre_root, self._db)
            self._storage_ptries[addr] = strie
        self._post_root_memo = None  # the account leaf WILL change; an
        # abort before the recompute must not leave the old memo live
        for slot in sorted(changed):
            value = changed[slot]
            key = keccak256(slot.to_bytes(32, "big"))
            if value == 0:
                strie.delete(key)  # storage-zeroing: delete with collapse
            else:
                strie.put(key, rlp.encode(rlp.encode_uint(value)))
            self._applied_slots[(addr, slot)] = value
        self._applied_gen[addr] = acct
        self._storage_root_memo.pop(addr, None)
        self._sroot_dirty.add(addr)
        return strie

    def _storage_root_of(self, addr: bytes, acct: Account) -> bytes:
        pre_root, changed, _fresh = self._storage_changes(addr, acct)
        if changed:
            self._apply_storage(addr, acct, pre_root, changed)
        if addr in self._sroot_dirty:
            root = self._storage_ptries[addr].root_hash()
            self._storage_root_memo[addr] = root
            self._sroot_dirty.discard(addr)
            return root
        return self._storage_root_memo.get(addr, pre_root)

    # --- batched post root (the serving device path) -------------------------

    def post_root_plan(self) -> Optional[PostRootPlan]:
        """Fused account+storage HashPlan for the BATCHED post-root path
        (ops/root_engine.py): trie mutations are applied on the host
        exactly like state_root() (structure is host work either way),
        but every keccak is left to the plan — HashNode digests enter
        parent templates as constants, dirty nodes become per-level RLP
        templates with 32-byte child holes, and each dirty storage
        trie's root is a hole INSIDE its account leaf, so ONE plan per
        request re-derives every digest up to the post root.

        Returns None when the host walk should run instead: nothing is
        dirty (the memo answers), or the ACCOUNT trie contains embedded
        (<32 B) nodes. A storage trie with embedded nodes falls back
        ALONE — its root is hashed on the host and baked into the leaf
        as a constant, the same per-trie fallback trie_root_device
        applies. Either way the tries are left consistent: a follow-up
        state_root() is always correct (and cheap, via the memos)."""
        builder = self._scheme.plan_builder()
        patches: List[_RootPatch] = []
        pending: list = []  # accounts whose leaf takes a storage-root hole
        changed_any = False
        for addr in sorted(self._seen | set(self.accounts)):
            acct = self.accounts.get(addr)
            key = keccak256(addr)
            if acct is None:
                if self._delete_account_leaf(addr, key):
                    changed_any = True
                continue
            pre_root, changed, _fresh = self._storage_changes(addr, acct)
            hole = None  # (gi, level) of a plan-computed storage root
            if changed:
                strie = self._apply_storage(addr, acct, pre_root, changed)
                sroot: Optional[bytes] = None
                if strie.root is None:
                    sroot = EMPTY_TRIE_ROOT
                else:
                    hole = builder.try_subtree(strie.root)
                    if hole is None:
                        # embedded-node storage trie: host fallback for
                        # THIS trie only (constant root in the leaf)
                        sroot = strie.root_hash()
                if sroot is not None:
                    self._storage_root_memo[addr] = sroot
                    self._sroot_dirty.discard(addr)
            elif addr in self._sroot_dirty:
                sroot = self._storage_ptries[addr].root_hash()
                self._storage_root_memo[addr] = sroot
                self._sroot_dirty.discard(addr)
            else:
                sroot = self._storage_root_memo.get(addr, pre_root)
            fields = (acct.nonce, acct.balance, acct.code_hash())
            if hole is None:
                target = (acct.nonce, acct.balance, sroot, acct.code_hash())
                if target == self._account_baseline(addr, acct):
                    continue
                self._post_root_memo = None
                self._trie.put(key, self._account_leaf_value(*target))
                self._applied_accounts[addr] = target
            else:
                prefix, suffix = self._account_leaf_segments(fields)
                self._post_root_memo = None
                self._trie.put(key, prefix + b"\x00" * 32 + suffix)
                pending.append((addr, key, prefix, suffix, hole, fields))
            changed_any = True
        if not changed_any:
            return None  # state_root() answers from the memo / pre root
        # the leaves are looked up only now: a later account's put or
        # delete may split or collapse the path of an earlier one, and
        # the trie then holds ANOTHER leaf object for it
        for addr, key, prefix, suffix, hole, fields in pending:
            leaf = _find_leaf(self._trie, key)
            if leaf is None:  # cannot happen for 32-byte keccak keys
                self._repair_pending(patches)
                return None
            builder.value_holes[id(leaf)] = (prefix, suffix, hole[0], hole[1])
            patches.append(
                _RootPatch(addr, leaf, prefix, suffix, hole[0], fields)
            )
        root = self._trie.root
        res = builder.try_subtree(root) if root is not None else None
        if res is None:
            self._repair_pending(patches)
            return None
        plan = builder.finish(res[0], [p.gi for p in patches] + [res[0]])
        if plan is None:
            self._repair_pending(patches)
            return None
        self._post_root_memo = None  # stale until apply_post_root
        return PostRootPlan(plan, patches)

    @staticmethod
    def _account_leaf_segments(fields: Tuple[int, int, bytes]) -> Tuple[bytes, bytes]:
        """(prefix, suffix) of the account-leaf RLP value around the
        32-byte storage-root slot, derived structurally (never by byte
        search — code hashes are attacker-influenced content)."""
        nonce, balance, code_hash = fields
        enc_n = rlp.encode(rlp.encode_uint(nonce))
        enc_b = rlp.encode(rlp.encode_uint(balance))
        value0 = WitnessStateDB._account_leaf_value(
            nonce, balance, b"\x00" * 32, code_hash
        )
        payload_len = len(enc_n) + len(enc_b) + 66
        off = (len(value0) - payload_len) + len(enc_n) + len(enc_b) + 1
        return value0[:off], value0[off + 32 :]

    def _repair_pending(self, patches: List[_RootPatch]) -> None:
        """Plan build aborted after placeholder leaves were put: compute
        the pending storage roots on the host and patch the real leaves
        back in, leaving the tries exactly as state_root() would."""
        for p in patches:
            sroot = self._storage_ptries[p.addr].root_hash()
            p.leaf.value = p.prefix + sroot + p.suffix
            self._storage_root_memo[p.addr] = sroot
            self._sroot_dirty.discard(p.addr)
            self._applied_accounts[p.addr] = (
                p.fields[0],
                p.fields[1],
                sroot,
                p.fields[2],
            )
        if patches:
            self._trie._enc_cache.clear()

    def apply_post_root(
        self, prp: PostRootPlan, digests: Sequence[bytes]
    ) -> bytes:
        """Fold a resolved plan's digests back into the host state: patch
        each placeholder account leaf with its plan-computed storage root,
        memoize, and return the post root (the plan's LAST out row). After
        this the host tries are canonical again — a follow-up state_root()
        returns the same root from the memo without hashing."""
        for patch, sroot in zip(prp.patches, digests):
            patch.leaf.value = patch.prefix + sroot + patch.suffix
            self._storage_root_memo[patch.addr] = sroot
            self._sroot_dirty.discard(patch.addr)
            self._applied_accounts[patch.addr] = (
                patch.fields[0],
                patch.fields[1],
                sroot,
                patch.fields[2],
            )
        if prp.patches:
            self._trie._enc_cache.clear()
        root = bytes(digests[-1])
        self._post_root_memo = root
        return root

    def copy(self):  # pragma: no cover — stateless runs are one-shot
        raise StatelessError("WitnessStateDB cannot be copied")


def _find_leaf(trie: PartialTrie, key: bytes) -> Optional[LeafNode]:
    """The LeafNode object holding `key` (secure tries: all keys are
    32-byte digests, so a present key always terminates in a leaf).
    Radix-generic: walks whatever digit alphabet the trie's scheme uses."""
    node, path = trie.root, list(trie._digits(key))
    while node is not None:
        if isinstance(node, LeafNode):
            return node if node.path == tuple(path) else None
        if isinstance(node, ExtensionNode):
            n = len(node.path)
            if tuple(path[:n]) != node.path:
                return None
            node, path = node.child, path[n:]
            continue
        if isinstance(node, BranchNode):
            if not path:
                return None
            node, path = node.children[path[0]], path[1:]
            continue
        return None  # HashNode: the put would have raised already
    return None


def _batched_root_wanted() -> bool:
    """Route post roots through the serving root lane? PHANT_BATCHED_ROOT
    =0 pins the host walk, =1 forces the lane (tests / XLA-CPU proxy);
    auto engages it exactly when the device route exists (tpu backend +
    live device) — on the pure-CPU path the host walk stays untouched and
    nothing jax-adjacent is ever imported. The per-dispatch host-vs-
    device decision stays with ops/root_engine.py (THE offload-gate
    story): this is only the cheap 'could a device ever be involved'
    pre-filter."""
    import os

    env = os.environ.get("PHANT_BATCHED_ROOT", "auto")
    if env in ("0", "off", ""):
        return False
    if env == "1":
        return True
    from phant_tpu.backend import crypto_backend, jax_device_ok

    return crypto_backend() == "tpu" and jax_device_ok()


def _batched_sig_wanted() -> bool:
    """Route sender recovery through the serving sig lane?
    PHANT_BATCHED_SIG=0 pins the in-request fused native batch, =1 forces
    the lane (tests / XLA-CPU proxy); auto engages it exactly when the
    device route exists (tpu backend + live device) — on the pure-CPU
    path the lane would only add scheduler latency around the SAME fused
    native batch the request already runs. The per-dispatch native-vs-
    device decision stays with ops/sig_engine.py (THE offload-gate
    story, the merged PHANT_TPU_MIN_ECRECOVER floor): this is only the
    cheap 'could a device ever be involved' pre-filter."""
    import os

    env = os.environ.get("PHANT_BATCHED_SIG", "auto")
    if env in ("0", "off", ""):
        return False
    if env == "1":
        return True
    from phant_tpu.backend import crypto_backend, jax_device_ok

    return crypto_backend() == "tpu" and jax_device_ok()


import threading as _threading

#: per-chain-id TxSigner memo for the request path: the signer resolves
#: its PHANT_TPU_MIN_ECRECOVER floor ONCE at construction (the r14
#: signer bugfix), so a per-request construction would put the env read
#: right back on the serving hot path. dict get is GIL-atomic; the lock
#: only serializes first construction.
_sig_signers: dict = {}
_sig_signers_lock = _threading.Lock()


def _request_signer(chain_id: int):
    signer = _sig_signers.get(chain_id)
    if signer is None:
        from phant_tpu.signer.signer import TxSigner

        with _sig_signers_lock:
            signer = _sig_signers.setdefault(chain_id, TxSigner(chain_id))
    return signer


def sender_lane_available() -> bool:
    """Cheap 'is the sig lane in play for this thread right now'
    pre-filter: `_batched_sig_wanted()` plus a live installed scheduler
    that accepts sig work. `run_blocks`' window prefetch and the replay
    engine consult this ONCE per import/segment instead of paying a
    dispatch_sender_recovery round-trip per block to find out the lane
    is off."""
    if not _batched_sig_wanted():
        return False
    from phant_tpu.serving import active_scheduler

    sched = active_scheduler()
    return sched is not None and sched.accepts_sig()


def dispatch_sender_recovery(chain_id: int, txs, rows=None):
    """Dispatch one block's sender recovery through the active
    scheduler's sig lane; returns `resolve() -> senders`, or None when
    the lane is not in play (no scheduler, `_batched_sig_wanted()`
    false, empty tx list).

    The request path calls this at DECODE time and joins just before EVM
    execution (`apply_body`'s `senders=` prefetch parameter is the join
    point), so the merged device ecrecover computes while this thread
    admits the witness and builds the node db. The signature rows —
    host keccak over RLP, `TxSigner.signature_rows` — are built on THIS
    handler thread (embarrassingly parallel across requests); invalid
    signatures ride the placeholder lane and surface as None senders,
    which `apply_body` raises with the exact per-index message the
    inline `get_senders_batch` path raises (attribution parity is
    differential-tested). A scheduler rejection — overload shed,
    deadline, executor death, at dispatch OR join — degrades to the
    fused native batch over the rows ALREADY built (no second
    signing-hash pass) instead of failing the block: sender recovery
    has a correct local fallback, so the lane may only ever help.

    The resolve-side block time is exported as `sched.sig_wait` — the
    part of the recovery that did NOT hide under the witness's decode
    and the wait for its verdict (the overlap audit, same reading as
    `sched.prefetch_wait`).

    `rows=` optionally supplies PRE-BUILT signature rows for the same
    txs: the replay engine's prefetch worker builds a whole segment's
    merged rows off the critical path (under `replay.prefetch`) and
    hands them here so the signing-hash pass isn't repeated at dispatch
    time; `run_blocks`' window prefetch passes txs and lets this build
    them (one pass per WINDOW, not per block — the r18 bugfix)."""
    if not txs or not _batched_sig_wanted():
        return None
    from phant_tpu.serving import active_scheduler
    from phant_tpu.serving.scheduler import SchedulerError

    sched = active_scheduler()
    if sched is None or not sched.accepts_sig():
        return None
    from phant_tpu.utils.trace import metrics

    signer = _request_signer(chain_id)
    if rows is None:
        with metrics.phase("stateless.sig_rows"):
            rows = signer.signature_rows(list(txs))

    def degrade():
        # shed/crashed lane: recover from the rows ALREADY built (no
        # second signing-hash pass) on the fused native batch —
        # force_cpu because a -32052 may mean the device itself died
        from phant_tpu.backend import device_fallback

        device_fallback("sig_lane")
        return signer.recover_rows_async(rows, force_cpu=True)()

    try:
        inner = sched.sig_async(rows)
    except SchedulerError:
        return degrade  # shed at admission

    def resolve():
        try:
            with metrics.phase("sched.sig_wait"):
                senders, meta = inner()
        except SchedulerError:
            return degrade()
        if meta is not None:
            from phant_tpu.utils.trace import current_span

            sp = current_span()
            if sp is not None:
                # sig_-prefixed: the open verify_block span already
                # carries the WITNESS batch record under the bare keys
                sp.attrs.update({f"sig_{k}": v for k, v in meta.items()})
        return senders

    return resolve


def compute_post_root(state: WitnessStateDB) -> bytes:
    """The request path's post-state root.

    Serving mode with a device in reach: build the request's fused
    account+storage hash plan on THIS (handler) thread
    (`post_root_plan` — host structural work, parallel across requests)
    and submit it to the active scheduler's root lane, where concurrent
    requests' plans coalesce into ONE device dispatch per level-shape
    bucket (serving/scheduler.py submit_root, ops/root_engine.py). The
    batch record the scheduler attaches folds into the open
    `verify_block` span exactly like the witness path's. Everything
    else — offline callers, pure-CPU serving, un-plannable tries —
    is the host walk (`state_root()`), byte-identical by construction
    and differential-tested."""
    from phant_tpu.serving import active_scheduler

    if _batched_root_wanted():
        sched = active_scheduler()
        if sched is not None and sched.accepts_root():
            import os

            from phant_tpu.utils.trace import metrics

            # lone-request guard (THE offload-gate story, root_engine.py):
            # plan construction itself costs ~a host walk's encoding, so
            # a request with NO root work queued to coalesce with — and a
            # witness payload the link model rejects alone — keeps the
            # host walk WITHOUT building a plan. PHANT_BATCHED_ROOT=1
            # forces the lane (tests/proxy); under concurrency the queue
            # has company and every request plans.
            if os.environ.get("PHANT_BATCHED_ROOT") != "1":
                if sched.root_backlog() == 0:
                    from phant_tpu.backend import device_offload_pays

                    # witness bytes over-estimate the dirty-template
                    # payload, so this only ever errs toward planning
                    est = sum(map(len, state._db.values()))
                    if not device_offload_pays(est):
                        return state.state_root()
            with metrics.phase("stateless.post_root_plan"):
                prp = state.post_root_plan()
            if prp is not None:
                digests, meta = sched.root_traced(prp.plan)
                if meta is not None:
                    from phant_tpu.utils.trace import current_span

                    sp = current_span()
                    if sp is not None:
                        # root_-prefixed, like the sig lane's sig_ keys:
                        # the open verify_block span already carries the
                        # WITNESS batch record under the bare keys, and
                        # un-prefixed root meta used to CLOBBER it
                        # (queue_wait_ms/batch_id/stage/backend) — the
                        # critpath rollup (obs/critpath.py) reads both
                        # families apart by prefix
                        sp.attrs.update(
                            {f"root_{k}": v for k, v in meta.items()}
                        )
                return state.apply_post_root(prp, digests)
    return state.state_root()


# ---------------------------------------------------------------------------
# witness verification entry (the TPU-batched hot loop)
# ---------------------------------------------------------------------------
# (`_threading` is the module-level alias imported above, at the sig-
# signer memo)

_witness_engine = None
_witness_engine_lock = _threading.Lock()


def shared_witness_engine():
    """Process-global memoized witness verifier (ops/witness_engine.py).

    Consecutive blocks' witnesses overlap heavily (only the previous
    block's written paths change), so the Engine API serving path pays
    only for never-seen nodes on each request — the r2 review's "stateless
    serving path doesn't batch" gap, solved by memoization instead of
    request batching. The engine routes its novel-node hashing through the
    selected crypto backend internally (device batches on
    `--crypto_backend=tpu`, native C otherwise)."""
    global _witness_engine
    with _witness_engine_lock:
        if _witness_engine is None:
            import os

            from phant_tpu.ops.witness_engine import WitnessEngine

            _witness_engine = WitnessEngine(
                max_nodes=int(os.environ.get("PHANT_WITNESS_CACHE", 1 << 20)),
                # -1 = adaptive link-aware routing (the engine's cost model);
                # a fixed floor is an explicit operator override
                device_batch_floor=int(
                    os.environ.get("PHANT_TPU_MIN_KECCAK", -1)
                ),
            )
        return _witness_engine


def admit_witness(state_root: bytes, nodes: List[bytes]):
    """The admission half of `verify_witness_nodes`: the verdict itself (a
    bool) where it is decided on this thread — the empty pre-state's
    contract, and the direct shared-engine path when no scheduler is
    installed (offline tools, tests, the spec runner by default) — or,
    in serving mode, the scheduler's `PendingVerdict`
    (serving/scheduler.py witness_async) for `join_witness`. The caller
    that has host work which needs nothing from the verdict does it in
    between (execute_stateless); scheduler rejections at admission raise
    here."""
    if state_root == EMPTY_TRIE_ROOT:
        # the empty pre-state needs (and admits) no witness nodes — same
        # contract as the host BFS (mpt/proof.py verify_witness_linked)
        return not nodes
    if not nodes:
        return False
    from phant_tpu.serving import active_scheduler

    sched = active_scheduler()
    if sched is not None and sched.accepts_witness():
        return sched.witness_async(state_root, nodes)
    return shared_witness_engine().verify(state_root, nodes)


def join_witness(pending) -> bool:
    """The verdict of what `admit_witness` returned. For a scheduler job
    this blocks until its batch resolved and folds the batch record the
    scheduler attached (batch_id, batch_size, bucket_bytes, backend, cache
    hit/miss, queue_wait_ms, the stage timings and their measured
    `stages`) into the caller's open span, so the request's
    `verify_block` trace names the shared dispatch that served it
    (phant_tpu/obs/). Scheduler rejections (deadline, eviction, executor
    down) propagate as SchedulerError for the server to map to JSON-RPC
    errors."""
    if isinstance(pending, bool):
        return pending
    ok, meta = pending.join()
    if meta is not None:
        from phant_tpu.utils.trace import current_span

        sp = current_span()
        if sp is not None:
            sp.attrs.update(meta)
    return ok


def verify_witness_nodes(state_root: bytes, nodes: List[bytes]) -> bool:
    """Linked witness verification — the nodes must form a connected subtree
    rooted at `state_root` — through the shared memoized engine. Semantics
    are identical to the host BFS (mpt/proof.py verify_witness_linked) and
    the device kernel (ops/witness_jax.witness_verify_fused); all three are
    differential-tested against each other.

    This is the synchronous face: admission and join back to back
    (`admit_witness`, `join_witness`). Serving mode: when a
    continuous-batching scheduler is installed (phant_tpu/serving/ — the
    Engine API server installs one), the check routes through it so
    concurrent handler threads coalesce into ONE engine/device dispatch
    instead of paying a batch-of-1 each — and with `pipeline_depth >= 2`
    (the default) that dispatch is PIPELINED: the executor packs batch
    N+1 while batch N computes on the device and batch N-1 resolves
    (ops/witness_engine.py begin_batch/resolve_batch). The request path
    itself (`execute_stateless`) does not wait here: it admits, decodes
    the witness while the verdict is computed, and joins where execution
    first needs it. Without a scheduler the direct shared-engine path is
    unchanged."""
    return join_witness(admit_witness(state_root, nodes))


_WITNESS_REJECTED = "witness rejected: not a subtree of preStateRoot"


def _join_verdict(pending, t_decode: int) -> None:
    """Join the verdict a handler decoded under (`t_decode`: when its
    decode began, on the span clock): the second of its two waits, the
    mechanism's counters — was the verdict there when the handler came to
    join, and how much of the decode ran before it arrived — and the
    rejection of a False verdict. SchedulerError propagates."""
    from phant_tpu.utils.trace import clock_ns, metrics

    t_decoded = clock_ns()
    arrived = pending.done_ns
    with metrics.phase("stateless.witness_verify"):
        witness_ok = join_witness(pending)
    metrics.count(
        "stateless.verdict_joins", state="waited" if arrived is None else "ready"
    )
    hidden_ns = (t_decoded if arrived is None else min(arrived, t_decoded)) - t_decode
    metrics.observe_hist(
        "stateless.decode_hidden_seconds", max(hidden_ns, 0) / 1e9
    )
    if not witness_ok:
        raise StatelessError(_WITNESS_REJECTED)


def execute_stateless(
    chain_id: int,
    parent_header,
    block,
    pre_state_root: bytes,
    nodes: List[bytes],
    codes: List[bytes],
    fork=None,
    fork_factory=None,
    scheme=None,
):
    """Verify the witness, execute the block against it, and verify the post
    state root. Returns the BlockExecutionResult plus the computed post root.
    Raises StatelessError / BlockError on any failure.

    `fork_factory(state) -> Fork` builds the fork AGAINST THE WITNESS-BACKED
    STATE (a PragueFork must write its EIP-2935 history slots into the
    partial trie, where they are part of the post root); a prebuilt `fork`
    instance is accepted for forks that own no state (FrontierFork preloaded
    with authenticated ancestor hashes).

    `scheme` is the commitment scheme the witness and the header's state
    roots commit under (phant_tpu/commitment/); None = the process-wide
    active scheme (`--commitment`). Witness verification itself is
    scheme-blind — the engine checks subtree-connectedness over the
    scheme's own node encodings.

    The order of a run. Without a scheduler: verify the witness, decode
    it, execute, check the post root. With one installed (serving), the
    verdict is JOINED where it is first needed, not awaited where it is
    requested: admit sender recovery, admit the witness, wait for the
    witness batch's launch, decode the witness while the device computes
    the verdict, join the verdict, then execute. `chain.run_block` never
    starts before a True verdict, and an error of the early decode never
    speaks before the verdict: a False verdict (or a SchedulerError) is
    what the caller sees, whatever the decode raised.

    Observability: the whole run is one `span("verify_block", block=n)` —
    its JSON trace line carries the witness_verify / witness_decode /
    execute / post_root phase split (witness_verify twice with a
    scheduler: the wait for the launch and the wait at the join);
    `stateless.verdict_joins{state=ready|waited}` and
    `stateless.decode_hidden_seconds` say how much of the decode the wait
    hid; failures count into `stateless.errors{kind=...}`."""
    from phant_tpu.blockchain.chain import Blockchain, BlockError
    from phant_tpu.utils.trace import clock_ns, metrics, span

    with span(
        "verify_block",
        block=block.header.block_number,
        nodes=len(nodes),
        codes=len(codes),
    ) as sp:
        try:
            # The order of admission: sender recovery FIRST (the sig
            # lane, ops/sig_engine.py), then the witness. The sig lane
            # needs the shorter host preparation, so the chip starts on
            # ecrecover while the witness is still being packed, and the
            # device's one serial chain (ecrecover, then the table's
            # update and verdict) ends earliest this way. Senders join
            # just before EVM execution below — apply_body's `senders=`
            # prefetch parameter is the join point. None = no lane in
            # play: apply_body runs the in-request fused batch.
            resolve_senders = dispatch_sender_recovery(
                chain_id, block.transactions
            )
            # The verdict is JOINED where it is first needed, not awaited
            # where it is requested. With a scheduler the handler waits
            # only for its witness batch to be LAUNCHED (until then the
            # executor, the prefetch worker and this thread would share
            # one interpreter lock, and each hand-over of it can move the
            # whole device chain later; from launch to verdict the lane
            # threads stand in C readbacks with the lock released),
            # decodes the witness meanwhile — host work that needs
            # nothing from the verdict — and joins before run_block.
            # Without one (offline tools, the spec runner, tests) the
            # verdict is decided inline, before the decode, as ever.
            with metrics.phase("stateless.witness_verify"):
                pending = admit_witness(pre_state_root, nodes)
                if pending is False:
                    raise StatelessError(_WITNESS_REJECTED)
                if pending is not True:
                    pending.wait_launched()
            t_decode = clock_ns()
            try:
                with metrics.phase("stateless.witness_decode"):
                    # ONE decode per request: the digest map is built
                    # here by a single batched C keccak and handed
                    # through — the counter-pinned contract (a second
                    # decode would double stateless.witness_nodes_decoded
                    # per payload)
                    state = WitnessStateDB(
                        pre_state_root,
                        nodes,
                        codes,
                        node_db=witness_node_db(nodes),
                        scheme=scheme,
                    )
                    if fork is None and fork_factory is not None:
                        fork = fork_factory(state)
                    # verify_state_root=False: the post-root check moves
                    # to the dedicated phase below so it can ride the
                    # BATCHED root lane (run_block's inline check would
                    # pay the serial host walk first and leave nothing
                    # dirty for the plan path — pre-PR-11 the root was in
                    # fact computed TWICE per request, once here and once
                    # below)
                    chain = Blockchain(
                        chain_id, state, parent_header, fork=fork, verify_state_root=False
                    )
            except Exception:
                # a failure of the early decode never speaks before the
                # verdict: join first (the future is always consumed). A
                # False verdict or a SchedulerError is what the caller
                # sees; only under a True verdict is the decode's own
                # error raised
                if pending is not True:
                    _join_verdict(pending, t_decode)
                raise
            if pending is not True:
                # the verdict gates everything that acts on the witness
                _join_verdict(pending, t_decode)
            with metrics.phase("stateless.execute"):
                # join the sig lane: senders recovered while the phases
                # above ran (None entries = invalid signatures, raised
                # by apply_body with the inline path's exact message)
                senders = (
                    resolve_senders() if resolve_senders is not None else None
                )
                result = chain.run_block(block, senders=senders)
            with metrics.phase("stateless.post_root"):
                # batched through the serving root lane when a device is
                # in reach (ops/root_engine.py); host walk otherwise
                post_root = compute_post_root(state)
                if post_root != block.header.state_root:
                    # the exact check (and error contract) run_block's
                    # verify_state_root path would have applied
                    raise BlockError(
                        f"state root mismatch: {post_root.hex()} != "
                        f"{block.header.state_root.hex()}"
                    )
        except Exception as e:
            # by-kind counter (bounded cardinality: exception class names)
            metrics.count("stateless.errors", kind=type(e).__name__)
            # the span closes on the raise: stamp the failure on it so
            # the sinks see it (the timeline tail-sampler keeps every
            # crashed request — the -32052 postmortem must be in-ring)
            sp.attrs["error"] = type(e).__name__
            # and an error record in the flight ring: a postmortem dump
            # carries the failing block + reason, not just a count
            from phant_tpu.obs.flight import flight

            flight.record(
                "error",
                where="stateless.execute_stateless",
                error_kind=type(e).__name__,
                error=str(e)[:240],
                block=block.header.block_number,
            )
            raise
        metrics.count("stateless.blocks_verified")
        return result, post_root
