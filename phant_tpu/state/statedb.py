"""In-memory world state with journaled snapshots.

Equivalent surface to the reference StateDB (reference:
src/state/statedb.zig:16-194) — accounts/storage CRUD, per-tx original
values for SSTORE gas, EIP-2929 warm sets, touched-address tracking — but
snapshots are O(1) journal marks with undo-log revert instead of the
reference's full deep clone (its own TODO admits the inefficiency,
reference: src/state/statedb.zig:172-173).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

from phant_tpu.types.account import Account
from phant_tpu.types.receipt import Log

Address = bytes  # 20 bytes


class StateDB:
    def __init__(self, accounts: Optional[Dict[Address, Account]] = None):
        self.accounts: Dict[Address, Account] = accounts or {}
        # undo log: list of (tag, payload) entries, newest last
        self._journal: List[Tuple] = []
        # incremental state-root cache: a retained secure trie plus the set
        # of addresses mutated since it was last synced. state_root() then
        # re-leafs only dirty accounts instead of rebuilding the whole trie
        # per block (the reference never computes state roots at all —
        # src/blockchain/blockchain.zig:83-85 — so this has no analog).
        # Journal rollbacks restore values of exactly the addresses the
        # forward mutations already marked dirty, so the set stays a
        # superset of every divergence from the synced trie.
        self._root_trie = None
        self._root_dirty: Set[Address] = set()
        # per-account retained storage tries (same per-path scheme): keyed
        # by the Account OBJECT so delete+recreate (journal-rollback-safe
        # identity) invalidates naturally; dirty slots accumulate in
        # set_storage/revert_to
        self._storage_tries: Dict[Address, Tuple[Account, object]] = {}
        self._storage_dirty: Dict[Address, Set[int]] = {}
        # --- per-transaction scope ---
        self._tx_original: Dict[Tuple[Address, int], int] = {}
        self.accessed_addresses: Set[Address] = set()
        self.accessed_storage_keys: Set[Tuple[Address, int]] = set()
        self.touched: Set[Address] = set()
        self.selfdestructs: Set[Address] = set()
        self.created: Set[Address] = set()
        self.logs: List[Log] = []
        self.refund: int = 0
        # EIP-1153 transient storage (Cancun): per-transaction, journaled
        # for call-scope reverts, discarded wholesale at tx end
        self.transient: Dict[Tuple[Address, int], int] = {}

    # ------------------------------------------------------------------
    # tx lifecycle
    # ------------------------------------------------------------------

    def begin_block(self) -> None:
        """Start a block-scoped undo log: every mutation until commit/rollback
        is journaled, so an invalid block rolls back in O(mutations) instead
        of the O(world-state) deep clone the reference uses per snapshot
        (reference: src/state/statedb.zig:171-182)."""
        self._journal.clear()

    def rollback_block(self) -> None:
        """Undo every mutation since begin_block (invalid blocks must leave
        no trace)."""
        self.revert_to(0)

    def start_tx(self) -> None:
        """Reset per-tx scopes (reference: src/state/statedb.zig:62-69 clones
        the whole db as `original_db`; we record originals lazily instead).
        The journal is NOT cleared — it spans the whole block for
        begin_block/rollback_block."""
        self._tx_original.clear()
        self.accessed_addresses = set()
        self.accessed_storage_keys = set()
        self.touched = set()
        self.selfdestructs = set()
        self.created = set()
        self.logs = []
        self.refund = 0
        self.transient = {}  # EIP-1153: cleared at transaction boundaries

    # ------------------------------------------------------------------
    # snapshots (journal marks)
    # ------------------------------------------------------------------

    def snapshot(self) -> int:
        """O(1) — returns a journal mark (reference deep-clones both maps,
        src/state/statedb.zig:171-182)."""
        return len(self._journal)

    def revert_to(self, mark: int) -> None:
        # every state-restoring branch re-marks the address (and slot) in
        # the incremental-root dirty sets: a rollback AFTER a state_root()
        # call (e.g. a block rejected on state-root mismatch) must not
        # leave the retained trie stuck on the rejected state
        while len(self._journal) > mark:
            tag, *payload = self._journal.pop()
            if tag == "balance":
                addr, old = payload
                self.accounts[addr].balance = old
                self._root_dirty.add(addr)
            elif tag == "nonce":
                addr, old = payload
                self.accounts[addr].nonce = old
                self._root_dirty.add(addr)
            elif tag == "storage":
                addr, slot, old = payload
                acct = self.accounts[addr]
                if old == 0:
                    acct.storage.pop(slot, None)
                else:
                    acct.storage[slot] = old
                self._root_dirty.add(addr)
                self._storage_dirty.setdefault(addr, set()).add(slot)
            elif tag == "code":
                addr, old = payload
                self.accounts[addr].code = old
                self._root_dirty.add(addr)
            elif tag == "create_account":
                (addr,) = payload
                self.accounts.pop(addr, None)
                self._root_dirty.add(addr)
            elif tag == "delete_account":
                addr, acct = payload
                self.accounts[addr] = acct
                self._root_dirty.add(addr)
            elif tag == "warm_addr":
                (addr,) = payload
                self.accessed_addresses.discard(addr)
            elif tag == "warm_slot":
                (key,) = payload
                self.accessed_storage_keys.discard(key)
            elif tag == "touch":
                (addr,) = payload
                self.touched.discard(addr)
            elif tag == "selfdestruct":
                (addr,) = payload
                self.selfdestructs.discard(addr)
            elif tag == "created":
                (addr,) = payload
                self.created.discard(addr)
            elif tag == "log":
                # block-level rollback may replay entries from earlier txs
                # whose per-tx log list start_tx already reset
                if self.logs:
                    self.logs.pop()
            elif tag == "refund":
                (old,) = payload
                self.refund = old
            elif tag == "transient":
                key, old = payload
                if old == 0:
                    self.transient.pop(key, None)
                else:
                    self.transient[key] = old
            else:  # pragma: no cover
                raise AssertionError(f"unknown journal tag {tag}")

    # ------------------------------------------------------------------
    # accounts
    # ------------------------------------------------------------------

    def account_exists(self, addr: Address) -> bool:
        return addr in self.accounts

    def get_account(self, addr: Address) -> Optional[Account]:
        return self.accounts.get(addr)

    def _get_or_create(self, addr: Address) -> Account:
        acct = self.accounts.get(addr)
        if acct is None:
            acct = Account()
            self.accounts[addr] = acct
            self._journal.append(("create_account", addr))
            self._root_dirty.add(addr)
        return acct

    def create_account(self, addr: Address) -> Account:
        return self._get_or_create(addr)

    def mark_created(self, addr: Address) -> None:
        """Track contracts created in this tx (EIP-6780-style bookkeeping and
        EIP-2200 original-value semantics for fresh contracts)."""
        self.created.add(addr)
        self._journal.append(("created", addr))

    def delete_account(self, addr: Address) -> None:
        acct = self.accounts.pop(addr, None)
        if acct is not None:
            self._journal.append(("delete_account", addr, acct))
            self._root_dirty.add(addr)

    def is_empty(self, addr: Address) -> bool:
        acct = self.accounts.get(addr)
        return acct is None or acct.is_empty()

    # ------------------------------------------------------------------
    # balances / nonces / code
    # ------------------------------------------------------------------

    def get_balance(self, addr: Address) -> int:
        acct = self.accounts.get(addr)
        return acct.balance if acct else 0

    def set_balance(self, addr: Address, value: int) -> None:
        acct = self._get_or_create(addr)
        self._journal.append(("balance", addr, acct.balance))
        self._root_dirty.add(addr)
        acct.balance = value

    def add_balance(self, addr: Address, delta: int) -> None:
        self.set_balance(addr, self.get_balance(addr) + delta)

    def sub_balance(self, addr: Address, delta: int) -> None:
        bal = self.get_balance(addr)
        if delta > bal:
            raise ValueError("balance underflow")
        self.set_balance(addr, bal - delta)

    def get_nonce(self, addr: Address) -> int:
        acct = self.accounts.get(addr)
        return acct.nonce if acct else 0

    def set_nonce(self, addr: Address, value: int) -> None:
        acct = self._get_or_create(addr)
        self._journal.append(("nonce", addr, acct.nonce))
        self._root_dirty.add(addr)
        acct.nonce = value

    def increment_nonce(self, addr: Address) -> None:
        self.set_nonce(addr, self.get_nonce(addr) + 1)

    def get_code(self, addr: Address) -> bytes:
        acct = self.accounts.get(addr)
        return acct.code if acct else b""

    def set_code(self, addr: Address, code: bytes) -> None:
        acct = self._get_or_create(addr)
        self._journal.append(("code", addr, acct.code))
        self._root_dirty.add(addr)
        acct.code = code

    # ------------------------------------------------------------------
    # storage
    # ------------------------------------------------------------------

    def get_storage(self, addr: Address, slot: int) -> int:
        acct = self.accounts.get(addr)
        return acct.storage.get(slot, 0) if acct else 0

    def set_storage(self, addr: Address, slot: int, value: int) -> None:
        acct = self._get_or_create(addr)
        current = acct.storage.get(slot, 0)
        key = (addr, slot)
        if key not in self._tx_original:
            self._tx_original[key] = current
        self._journal.append(("storage", addr, slot, current))
        self._root_dirty.add(addr)
        self._storage_dirty.setdefault(addr, set()).add(slot)
        if value == 0:
            acct.storage.pop(slot, None)
        else:
            acct.storage[slot] = value

    def get_original_storage(self, addr: Address, slot: int) -> int:
        """Value at the start of the current tx (EIP-2200; reference keeps a
        whole-map clone for this, src/state/statedb.zig:22-25)."""
        key = (addr, slot)
        if key in self._tx_original:
            return self._tx_original[key]
        return self.get_storage(addr, slot)

    # ------------------------------------------------------------------
    # EIP-1153 transient storage (Cancun; no reference analog — the
    # reference EVM is pinned to Shanghai, src/blockchain/vm.zig:472)
    # ------------------------------------------------------------------

    def get_transient(self, addr: Address, slot: int) -> int:
        return self.transient.get((addr, slot), 0)

    def set_transient(self, addr: Address, slot: int, value: int) -> None:
        key = (addr, slot)
        self._journal.append(("transient", key, self.transient.get(key, 0)))
        if value == 0:
            self.transient.pop(key, None)
        else:
            self.transient[key] = value

    # ------------------------------------------------------------------
    # EIP-2929 warm sets (journaled: reverted scopes re-cool their additions)
    # ------------------------------------------------------------------

    def access_address(self, addr: Address) -> bool:
        """Mark warm; returns True if it was already warm."""
        if addr in self.accessed_addresses:
            return True
        self.accessed_addresses.add(addr)
        self._journal.append(("warm_addr", addr))
        return False

    def access_storage_key(self, addr: Address, slot: int) -> bool:
        key = (addr, slot)
        if key in self.accessed_storage_keys:
            return True
        self.accessed_storage_keys.add(key)
        self._journal.append(("warm_slot", key))
        return False

    # ------------------------------------------------------------------
    # touched / selfdestruct / logs / refunds
    # ------------------------------------------------------------------

    def touch(self, addr: Address) -> None:
        if addr not in self.touched:
            self.touched.add(addr)
            self._journal.append(("touch", addr))

    def mark_selfdestruct(self, addr: Address) -> None:
        if addr not in self.selfdestructs:
            self.selfdestructs.add(addr)
            self._journal.append(("selfdestruct", addr))

    def add_log(self, log: Log) -> None:
        self.logs.append(log)
        self._journal.append(("log",))

    def add_refund(self, delta: int) -> None:
        self._journal.append(("refund", self.refund))
        self.refund += delta
        if self.refund < 0:  # pragma: no cover — guarded by EIP-3529 math
            raise AssertionError("negative refund counter")

    # ------------------------------------------------------------------
    # finalization
    # ------------------------------------------------------------------

    def destroy_touched_empty(self) -> None:
        """EIP-158: remove touched accounts that ended the tx empty
        (reference: src/blockchain/blockchain.zig:334-341)."""
        for addr in list(self.touched):
            acct = self.accounts.get(addr)
            if acct is not None and acct.is_empty():
                self.delete_account(addr)

    def _storage_root_incremental(self, addr: Address, acct: Account) -> bytes:
        """Storage root via a retained per-account trie: only dirty slots
        are re-put/deleted. Account-object identity guards delete+recreate
        (rollback restores the original object, so identity is stable)."""
        from phant_tpu.crypto.keccak import keccak256
        from phant_tpu import rlp
        from phant_tpu.state.root import build_storage_trie

        entry = self._storage_tries.get(addr)
        if entry is None or entry[0] is not acct:
            trie = build_storage_trie(acct.storage)
            self._storage_tries[addr] = (acct, trie)
            self._storage_dirty.pop(addr, None)
            return trie.root_hash()
        trie = entry[1]
        for slot in self._storage_dirty.pop(addr, ()):
            value = acct.storage.get(slot, 0)
            key = keccak256(slot.to_bytes(32, "big"))
            if value == 0:
                trie.delete(key)
            else:
                trie.put(key, rlp.encode(rlp.encode_uint(value)))
        return trie.root_hash()

    def flush_root_trie(self):
        """Apply every dirty account to the retained state trie WITHOUT
        hashing it, and return the trie. `state_root()` is flush + host
        `root_hash()`; the replay engine's deferred-root mode flushes per
        block, builds a HashPlan from the unhashed trie, and hashes K
        consecutive block states on device in ONE vmapped dispatch
        (phant_tpu/replay/lowering.py) — the flush/hash split is what lets
        the hashing leave the per-block critical path."""
        from phant_tpu.crypto.keccak import keccak256
        from phant_tpu.state.root import build_state_trie

        if self._root_trie is None:
            self._root_trie = build_state_trie(self.accounts)
        else:
            for addr in self._root_dirty:
                acct = self.accounts.get(addr)
                key = keccak256(addr)
                if acct is None or (acct.is_empty() and not acct.storage):
                    self._root_trie.delete(key)
                    # drop the retained storage trie too: a deleted account's
                    # (acct, trie) entry would otherwise pin the dead Account
                    # and its whole trie for the StateDB's lifetime
                    self._storage_tries.pop(addr, None)
                    self._storage_dirty.pop(addr, None)
                else:
                    # ONE value-encoding definition across every producer
                    # (phant_tpu/commitment/ account_leaf_value) — the
                    # incremental path must never diverge from the
                    # full-rebuild and stateless write-back paths
                    from phant_tpu.commitment import account_leaf_value

                    leaf = account_leaf_value(
                        acct.nonce,
                        acct.balance,
                        self._storage_root_incremental(addr, acct),
                        acct.code_hash(),
                    )
                    self._root_trie.put(key, leaf)
        self._root_dirty.clear()
        return self._root_trie

    def state_root(self) -> bytes:
        # host recursion on purpose, even on --crypto_backend=tpu: the
        # retained trie re-encodes only dirty paths (the per-path enc cache
        # keeps each clean node's encoding and the reference its parent
        # holds of it, so a dirty branch hashes none of its clean children
        # again), which beats shipping a full plan rebuild to the device
        # every block; the device state-root path serves FULL recomputes
        # (the stateless witness pipeline and the replay engine's deferred
        # segment roots), not incremental resident updates
        return self.flush_root_trie().root_hash()

    def copy(self) -> "StateDB":
        return StateDB({a: acct.copy() for a, acct in self.accounts.items()})
