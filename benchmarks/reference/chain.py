"""The benchmark's chain: generator and plain reference in one pass.

From a seed alone it makes a genesis of funded accounts and a run of blocks,
executing every transaction itself (value transfers and calls of a small
counter contract, under mainnet's Shanghai rules) to fill each header with
the gas, receipts root and post-state root a verifier must reproduce. Those
roots ARE the reference the served answers are held to; nothing here imports
the program. Beside each block it keeps what a consensus client would ship:
the proof paths, against the parent state, of exactly the accounts and
storage slots the block touches.

The parameters (`params`) come from a traffic file's `chain` group."""

from __future__ import annotations

import json
from dataclasses import dataclass, field

import numpy as np

from . import rlp, secp
from .keccak import keccak256, keccak256_many
from .mpt import EMPTY_ROOT, Trie, build_sorted

EMPTY_CODE_HASH = bytes.fromhex(
    "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
)
EMPTY_UNCLE_HASH = bytes.fromhex(
    "1dcc4de8dec75d7aab85b567b6ccd41ad312451b948a7413f0a142fd40d49347"
)
CHAIN_ID = 1
GAS_LIMIT = 30_000_000
GAS_PRICE = 10**9  # constant, and never under the (falling) base fee
GENESIS_TIMESTAMP = 1_700_000_000  # Shanghai on mainnet's schedule
COINBASE = b"\x00" * 20
#: slot = calldata word 0; storage[slot] += 1
#: PUSH1 0 CALLDATALOAD DUP1 SLOAD PUSH1 1 ADD SWAP1 SSTORE STOP
COUNTER_CODE = bytes.fromhex("60003580546001019055") + b"\x00"


def hx(b: bytes) -> str:
    return "0x" + b.hex()


# ---------------------------------------------------------------------------
# headers and transactions
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Header:
    parent_hash: bytes
    state_root: bytes
    transactions_root: bytes
    receipts_root: bytes
    number: int
    gas_used: int
    timestamp: int
    base_fee: int
    logs_bloom: bytes = b"\x00" * 256

    def encode(self) -> bytes:
        return rlp.encode([
            self.parent_hash, EMPTY_UNCLE_HASH, COINBASE, self.state_root,
            self.transactions_root, self.receipts_root, self.logs_bloom,
            b"", rlp.uint(self.number), rlp.uint(GAS_LIMIT),
            rlp.uint(self.gas_used), rlp.uint(self.timestamp), b"",
            b"\x00" * 32, b"\x00" * 8, rlp.uint(self.base_fee), EMPTY_ROOT,
        ])  # fmt: skip

    def hash(self) -> bytes:
        return keccak256(self.encode())


def next_base_fee(parent: Header) -> int:
    """EIP-1559."""
    target = GAS_LIMIT // 2
    if parent.gas_used == target:
        return parent.base_fee
    if parent.gas_used > target:
        delta = parent.base_fee * (parent.gas_used - target) // target // 8
        return parent.base_fee + max(delta, 1)
    delta = parent.base_fee * (target - parent.gas_used) // target // 8
    return parent.base_fee - delta


@dataclass(frozen=True)
class Tx:
    """A legacy transaction under EIP-155."""

    sender: bytes
    nonce: int
    gas_limit: int
    to: bytes
    value: int
    data: bytes
    v: int = 0
    r: int = 0
    s: int = 0

    def _body(self) -> list:
        return [
            rlp.uint(self.nonce), rlp.uint(GAS_PRICE), rlp.uint(self.gas_limit),
            self.to, rlp.uint(self.value), self.data,
        ]  # fmt: skip

    def sighash(self) -> bytes:
        return keccak256(rlp.encode([*self._body(), rlp.uint(CHAIN_ID), b"", b""]))

    def encode(self) -> bytes:
        return rlp.encode(
            [*self._body(), rlp.uint(self.v), rlp.uint(self.r), rlp.uint(self.s)]
        )


def ordered_root(items: list) -> bytes:
    """The root of the trie of rlp(i) -> items[i]."""
    trie = Trie({})
    for i, item in enumerate(items):
        trie.update(rlp.encode(rlp.uint(i)), item)
    return trie.root


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------


def account_rlp(nonce: int, balance: int, storage_root=EMPTY_ROOT, code_hash=EMPTY_CODE_HASH):
    return rlp.encode([rlp.uint(nonce), rlp.uint(balance), storage_root, code_hash])


class State:
    """Accounts live in the state trie and nowhere else, as in a client."""

    def __init__(self, db: dict, root: bytes, codes: dict):
        self.db = db
        self.trie = Trie(db, root)
        self.codes = codes  # code hash -> code

    def account(self, addr: bytes):
        """[nonce, balance, storage root, code hash], or None."""
        raw = self.trie.get(keccak256(addr))
        if raw is None:
            return None
        n, b, sr, ch = rlp.decode(raw)
        return [int.from_bytes(n, "big"), int.from_bytes(b, "big"), sr, ch]

    def put(self, addr: bytes, acct: list) -> None:
        self.trie.update(keccak256(addr), account_rlp(*acct))

    def storage(self, acct: list) -> Trie:
        return Trie(self.db, acct[2])


class OutOfGas(Exception):
    pass


def run_code(code: bytes, data: bytes, gas: int, store: Trie, original: dict):
    """The handful of opcodes the traffic's contracts use, with Berlin's
    access costs and EIP-2200/3529's SSTORE. `original` maps each slot this
    transaction touched to its value at the transaction's start (so a slot
    in it is warm). Returns (gas left, refund)."""
    stack, pc, refund = [], 0, 0

    def charge(n):
        nonlocal gas
        if gas < n:
            raise OutOfGas
        gas -= n

    def load(slot: int) -> int:
        raw = store.get(keccak256(slot.to_bytes(32, "big")))
        return int.from_bytes(rlp.decode(raw), "big") if raw else 0

    while pc < len(code):
        op = code[pc]
        pc += 1
        if op == 0x00:  # STOP
            break
        if op == 0x01:  # ADD
            charge(3)
            stack.append((stack.pop() + stack.pop()) % 2**256)
        elif op == 0x35:  # CALLDATALOAD
            charge(3)
            at = stack.pop()
            stack.append(int.from_bytes(data[at : at + 32].ljust(32, b"\x00"), "big"))
        elif op == 0x54:  # SLOAD
            slot = stack.pop()
            charge(100 if slot in original else 2100)
            value = load(slot)
            original.setdefault(slot, value)
            stack.append(value)
        elif op == 0x55:  # SSTORE
            if gas <= 2300:
                raise OutOfGas
            slot, new = stack.pop(), stack.pop()
            current = load(slot)
            if slot not in original:
                charge(2100)
                original[slot] = current
            orig = original[slot]
            if new == current:
                charge(100)
            elif orig == current:
                charge(20000 if orig == 0 else 2900)
                if orig != 0 and new == 0:
                    refund += 4800
            else:
                charge(100)
                if orig != 0:
                    if current == 0:
                        refund -= 4800
                    elif new == 0:
                        refund += 4800
                if new == orig:
                    refund += 19900 if orig == 0 else 2800
            if new == 0:
                raise NotImplementedError("the traffic clears no slot")
            store.update(
                keccak256(slot.to_bytes(32, "big")), rlp.encode(rlp.uint(new))
            )
        elif op == 0x60:  # PUSH1
            charge(3)
            stack.append(code[pc])
            pc += 1
        elif op == 0x80:  # DUP1
            charge(3)
            stack.append(stack[-1])
        elif op == 0x90:  # SWAP1
            charge(3)
            stack[-1], stack[-2] = stack[-2], stack[-1]
        else:
            raise NotImplementedError(f"opcode {op:#x}")
    return gas, refund


def apply_tx(state: State, tx: Tx, base_fee: int) -> int:
    """Execute one transaction; the gas it used. The traffic is made so that
    none fails, and a failure here is a fault of the generator."""
    sender = state.account(tx.sender)
    if sender is None or sender[0] != tx.nonce:
        raise ValueError("bad nonce")
    if sender[1] < tx.gas_limit * GAS_PRICE + tx.value:
        raise ValueError("sender cannot pay")
    intrinsic = 21000 + sum(4 if b == 0 else 16 for b in tx.data)
    sender[0] += 1
    sender[1] -= tx.gas_limit * GAS_PRICE + tx.value
    state.put(tx.sender, sender)
    to = state.account(tx.to) if tx.to != tx.sender else sender
    to = to or [0, 0, EMPTY_ROOT, EMPTY_CODE_HASH]
    to[1] += tx.value
    gas, refund = tx.gas_limit - intrinsic, 0
    if to[3] != EMPTY_CODE_HASH:
        store = state.storage(to)
        gas, refund = run_code(state.codes[to[3]], tx.data, gas, store, {})
        to[2] = store.root
    if tx.value or to[3] != EMPTY_CODE_HASH:
        state.put(tx.to, to)
    used = tx.gas_limit - gas
    used -= min(max(refund, 0), used // 5)
    sender = state.account(tx.sender)
    sender[1] += (tx.gas_limit - used) * GAS_PRICE
    state.put(tx.sender, sender)
    tip = used * (GAS_PRICE - base_fee)
    if tip:
        coinbase = state.account(COINBASE) or [0, 0, EMPTY_ROOT, EMPTY_CODE_HASH]
        coinbase[1] += tip
        state.put(COINBASE, coinbase)
    return used


# ---------------------------------------------------------------------------
# the chain
# ---------------------------------------------------------------------------


@dataclass
class Block:
    header: Header
    parent: Header
    txs: list
    pre_root: bytes
    witness: list  # encoded trie nodes, against the parent state
    codes: list

    def body(self, rpc_id: int, header=None, txs=None, witness=None) -> bytes:
        """The JSON-RPC request a consensus client would POST."""
        h = header or self.header
        payload = {
            "parentHash": hx(h.parent_hash),
            "feeRecipient": hx(COINBASE),
            "stateRoot": hx(h.state_root),
            "receiptsRoot": hx(h.receipts_root),
            "logsBloom": hx(h.logs_bloom),
            "prevRandao": hx(b"\x00" * 32),
            "blockNumber": hex(h.number),
            "gasLimit": hex(GAS_LIMIT),
            "gasUsed": hex(h.gas_used),
            "timestamp": hex(h.timestamp),
            "extraData": "0x",
            "baseFeePerGas": hex(h.base_fee),
            "blockHash": hx(h.hash()),
            "transactions": [hx(t.encode()) for t in (txs or self.txs)],
            "withdrawals": [],
        }
        wit = {
            "headers": [hx(self.parent.encode())],
            "preStateRoot": hx(self.pre_root),
            "state": [hx(n) for n in (witness or self.witness)],
            "codes": [hx(c) for c in self.codes],
        }
        return json.dumps({
            "jsonrpc": "2.0", "id": rpc_id,
            "method": "engine_executeStatelessPayloadV1",
            "params": [payload, wit],
        }).encode()  # fmt: skip

    def body_altered(self, what: str, rpc_id: int) -> bytes:
        """The body with one thing altered and everything else re-derived
        around it (the block hash always, the transactions root where a
        transaction changed), so that it is wrong in that one way only:

        witness        one byte flipped in the middle of the largest node
        signature      one byte of the first transaction's `r` flipped
        state_root     the lowest bit of the header's post-state root flipped
        receipts_root  the lowest bit of the header's receipts root flipped
        gas_used       the header's gas used, plus one
        """
        from dataclasses import replace

        h = self.header
        if what == "witness":
            nodes = list(self.witness)
            i = max(range(len(nodes)), key=lambda k: len(nodes[k]))
            raw = bytearray(nodes[i])
            raw[len(raw) // 2] ^= 0x01
            nodes[i] = bytes(raw)
            return self.body(rpc_id, witness=nodes)
        if what == "signature":
            t = self.txs[0]
            txs = [replace(t, r=t.r ^ (0xFF << 64)), *self.txs[1:]]
            root = ordered_root([x.encode() for x in txs])
            return self.body(rpc_id, header=replace(h, transactions_root=root), txs=txs)
        flip = lambda b: b[:-1] + bytes([b[-1] ^ 0x01])  # noqa: E731
        if what == "state_root":
            return self.body(rpc_id, header=replace(h, state_root=flip(h.state_root)))
        if what == "receipts_root":
            return self.body(rpc_id, header=replace(h, receipts_root=flip(h.receipts_root)))
        if what == "gas_used":
            return self.body(rpc_id, header=replace(h, gas_used=h.gas_used + 1))
        raise ValueError(f"no alteration {what!r}")


@dataclass
class Chain:
    """Made from `seed` and `params` alone; `extend` appends blocks."""

    seed: int
    params: dict
    blocks: list = field(default_factory=list)

    def __post_init__(self):
        p = self.params
        self.rng = np.random.default_rng([self.seed, 0x70E7])
        n_pool, n_contracts = p["sender_pool"], p["contracts"]
        n_cold = (1 << p["genesis_log2"]) - n_pool - n_contracts
        first = int(self.rng.integers(1, 2**62)) + (self.seed << 64)
        nonce0 = first ^ (1 << 200)
        pubs = secp.consecutive(first, n_pool)
        nonce_points = secp.consecutive(nonce0, n_pool)
        self.signers = [
            secp.Signer(first + i, nonce0 + i, nonce_points[i]) for i in range(n_pool)
        ]
        self.pool = [keccak256(secp.pubkey_bytes(q))[12:] for q in pubs]
        blob = self.rng.bytes(20 * n_cold)
        self.cold = [blob[i : i + 20] for i in range(0, len(blob), 20)]
        self.contracts = [bytes([0xC0, i]) + b"\xc0" * 18 for i in range(n_contracts)]
        code_hash = keccak256(COUNTER_CODE)

        addrs = self.pool + self.cold + self.contracts
        # a cold account's balance, 10^18 + i, is 8 bytes for every i: one
        # encoding serves as the form of all, with the balance spliced in
        form = account_rlp(0, 10**18)
        at = form.index((10**18).to_bytes(8, "big"))
        head, tail = form[:at], form[at + 8 :]
        cold_values = [head + (10**18 + i).to_bytes(8, "big") + tail for i in range(n_cold)]
        if n_cold and cold_values[-1] != account_rlp(0, 10**18 + n_cold - 1):
            raise ValueError("the spliced account encoding is wrong")
        values = (
            [account_rlp(0, 10**24)] * n_pool
            + cold_values
            + [account_rlp(0, 0, EMPTY_ROOT, code_hash)] * n_contracts
        )
        keys = keccak256_many(addrs)
        order = sorted(range(len(keys)), key=keys.__getitem__)
        if any(keys[order[i]] == keys[order[i + 1]] for i in range(len(order) - 1)):
            raise ValueError("two genesis accounts collide")
        self.db = {}
        root = build_sorted(
            self.db, [keys[i] for i in order], [values[i] for i in order]
        )
        self.state = State(self.db, root, {code_hash: COUNTER_CODE})
        self.genesis = Header(
            parent_hash=b"\x00" * 32, state_root=root, transactions_root=EMPTY_ROOT,
            receipts_root=EMPTY_ROOT, number=0, gas_used=0,
            timestamp=GENESIS_TIMESTAMP, base_fee=10**9,
        )  # fmt: skip
        ranks = np.arange(1, n_pool + 1, dtype=np.float64)
        self.pool_weights = ranks ** -p["zipf_s"] / np.sum(ranks ** -p["zipf_s"])
        cr = np.arange(1, n_contracts + 1, dtype=np.float64)
        self.contract_weights = cr ** -p["zipf_s"] / np.sum(cr ** -p["zipf_s"])
        self.nonces = [0] * n_pool

    @property
    def head(self) -> Header:
        return self.blocks[-1].header if self.blocks else self.genesis

    def extend(self, n_blocks: int) -> None:
        for _ in range(n_blocks):
            self.blocks.append(self._next_block())

    def _draw_txs(self) -> list:
        p, rng = self.params, self.rng
        n_tr, n_call = p["transfers_per_block"], p["calls_per_block"]
        senders = rng.choice(
            len(self.pool), size=n_tr + n_call, replace=False, p=self.pool_weights
        )
        warm_to = rng.choice(len(self.pool), size=n_tr, p=self.pool_weights)
        cold_to = rng.integers(0, len(self.cold), size=n_tr)
        is_cold = rng.random(n_tr) < p["cold_recipient_share"]
        contracts = rng.choice(len(self.contracts), size=n_call, p=self.contract_weights)
        slots = rng.integers(0, p["slots_per_contract"], size=n_call)
        txs = []
        for j, k in enumerate(senders):
            k = int(k)
            if j < n_tr:
                to = self.cold[cold_to[j]] if is_cold[j] else self.pool[warm_to[j]]
                tx = Tx(self.pool[k], self.nonces[k], 21_000, to, 1, b"")
            else:
                c = j - n_tr
                data = int(slots[c]).to_bytes(32, "big")
                tx = Tx(self.pool[k], self.nonces[k], 60_000, self.contracts[contracts[c]], 0, data)
            self.nonces[k] += 1
            r, s, recid = self.signers[k].sign(tx.sighash())
            txs.append(Tx(**{**tx.__dict__, "v": 35 + 2 * CHAIN_ID + recid, "r": r, "s": s}))
        order = rng.permutation(len(txs))
        return [txs[i] for i in order]

    def _witness(self, txs: list):
        """Proof paths, against the state as it stands (the parent's), of
        every account and slot the block will touch; and the codes."""
        nodes: dict = {}
        state = self.state
        touched = dict.fromkeys([COINBASE, *(a for t in txs for a in (t.sender, t.to))])
        for addr in touched:
            for enc in state.trie.prove(keccak256(addr)):
                nodes[enc] = None
        codes = {}
        for t in txs:
            if not t.data:
                continue
            acct = state.account(t.to)
            codes[acct[3]] = state.codes[acct[3]]
            for enc in state.storage(acct).prove(keccak256(t.data)):
                nodes[enc] = None
        return list(nodes), sorted(codes.values())

    def _next_block(self) -> Block:
        parent = self.head
        txs = self._draw_txs()
        pre_root = self.state.trie.root
        witness, codes = self._witness(txs)
        base_fee = next_base_fee(parent)
        total, receipts = 0, []
        for tx in txs:
            total += apply_tx(self.state, tx, base_fee)
            receipts.append(
                rlp.encode([b"\x01", rlp.uint(total), b"\x00" * 256, []])
            )
        if total > GAS_LIMIT:
            raise ValueError("block over the gas limit")
        header = Header(
            parent_hash=parent.hash(),
            state_root=self.state.trie.root,
            transactions_root=ordered_root([t.encode() for t in txs]),
            receipts_root=ordered_root(receipts),
            number=parent.number + 1,
            gas_used=total,
            timestamp=parent.timestamp + 12,
            base_fee=base_fee,
        )
        return Block(header, parent, txs, pre_root, witness, codes)
