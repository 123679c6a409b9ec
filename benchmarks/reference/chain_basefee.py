"""The reference's chain for blocks at the gas limit: `reference/chain.py`
with a gas price a transaction.

`chain.py` signs every transaction at a constant 1 gwei, which holds while
its blocks stay under the gas target and the base fee falls. A block FULL of
plain transfers (1,428 x 21,000 = 29,988,000 of the 30,000,000 gas limit:
ethereum/execution-spec-tests, the `benchmark` suite's worst-case blocks,
`test_block_full_of_ether_transfers`, distinct senders to distinct
receivers) uses twice the target, so the base fee RISES an eighth a block
(EIP-1559) and is over 1 gwei at the third: a verifier refuses a
transaction priced under its block's base fee, and the second such block
could not be made. Here every transaction of a block is priced at that
block's base fee plus a tip of 1 gwei: the sender is debited its gas limit
at that price, refunded what it did not use, the coinbase is paid the tip
and the base fee is burnt.

The traffic is the source's and nothing else: transfers of 1 wei, senders
drawn without replacement from the funded pool, recipients drawn without
replacement from the genesis's cold accounts, no calls. Headers, the state,
the trie, the witness and the request body are `chain.py`'s, unchanged;
like it, this file imports nothing of the program, and the roots it fills
its headers with ARE the reference the served answers are held to."""

from __future__ import annotations

from dataclasses import dataclass

from . import chain, rlp
from .chain import (
    CHAIN_ID,
    COINBASE,
    EMPTY_CODE_HASH,
    EMPTY_ROOT,
    GAS_LIMIT,
    Block,
    Header,
    State,
    next_base_fee,
    ordered_root,
)

TIP = 10**9  # what a transaction pays the coinbase over its block's base fee
TRANSFER_GAS = 21_000


@dataclass(frozen=True)
class Tx(chain.Tx):
    """`chain.Tx` at a gas price of its own."""

    gas_price: int = 0

    def _body(self) -> list:
        return [
            rlp.uint(self.nonce), rlp.uint(self.gas_price), rlp.uint(self.gas_limit),
            self.to, rlp.uint(self.value), self.data,
        ]  # fmt: skip


def apply_transfer(state: State, tx: Tx, base_fee: int) -> int:
    """Execute one plain transfer at its own gas price; the gas it used. The
    traffic is made so that none fails, and a failure here is a fault of
    the generator."""
    if tx.data or tx.gas_limit < TRANSFER_GAS:
        raise ValueError("not a plain transfer")
    if tx.gas_price < base_fee:
        raise ValueError("gas price under the base fee")
    sender = state.account(tx.sender)
    if sender is None or sender[0] != tx.nonce:
        raise ValueError("bad nonce")
    if sender[1] < tx.gas_limit * tx.gas_price + tx.value:
        raise ValueError("sender cannot pay")
    sender[0] += 1
    sender[1] -= tx.gas_limit * tx.gas_price + tx.value
    sender[1] += (tx.gas_limit - TRANSFER_GAS) * tx.gas_price  # the refund
    state.put(tx.sender, sender)
    to = state.account(tx.to) or [0, 0, EMPTY_ROOT, EMPTY_CODE_HASH]
    if to[3] != EMPTY_CODE_HASH:
        raise ValueError("the recipient has code")
    to[1] += tx.value
    state.put(tx.to, to)
    tip = TRANSFER_GAS * (tx.gas_price - base_fee)
    if tip:
        coinbase = state.account(COINBASE) or [0, 0, EMPTY_ROOT, EMPTY_CODE_HASH]
        coinbase[1] += tip
        state.put(COINBASE, coinbase)
    return TRANSFER_GAS


class Chain(chain.Chain):
    """`chain.Chain`'s genesis and witness; blocks of transfers between
    distinct accounts, each priced at its block's base fee plus `TIP`."""

    def __post_init__(self):
        p = self.params
        if p["calls_per_block"] or p["cold_recipient_share"] != 1.0:
            raise ValueError("this chain is of plain transfers to cold accounts alone")
        super().__post_init__()

    def _draw_txs(self) -> list:
        n, rng = self.params["transfers_per_block"], self.rng
        price = next_base_fee(self.head) + TIP
        senders = rng.choice(len(self.pool), size=n, replace=False, p=self.pool_weights)
        recipients = rng.choice(len(self.cold), size=n, replace=False)
        txs = []
        for k, to in zip(senders.tolist(), recipients.tolist()):
            tx = Tx(self.pool[k], self.nonces[k], TRANSFER_GAS, self.cold[to], 1, b"", gas_price=price)
            self.nonces[k] += 1
            r, s, recid = self.signers[k].sign(tx.sighash())
            txs.append(Tx(**{**tx.__dict__, "v": 35 + 2 * CHAIN_ID + recid, "r": r, "s": s}))
        return txs

    def _next_block(self) -> Block:
        parent = self.head
        txs = self._draw_txs()
        pre_root = self.state.trie.root
        witness, codes = self._witness(txs)
        base_fee = next_base_fee(parent)
        total, receipts = 0, []
        for tx in txs:
            total += apply_transfer(self.state, tx, base_fee)
            receipts.append(rlp.encode([b"\x01", rlp.uint(total), b"\x00" * 256, []]))
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
