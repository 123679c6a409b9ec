"""From the reference's chain to the program's replay fixture, without
executing anything with the program.

`reference/chain.py` makes a genesis, a run of blocks and each block's
witness, and fills every header with the gas, receipts root and post-state
root it computed itself. Here those are put into the program's own types
(`phant_tpu/replay/fixture.py`: a genesis header, the genesis accounts, the
blocks, a `(pre-state root, nodes)` pair a block), field by field: no block
is run, no root is computed, no trie is built. A replay of such a fixture
compares the program with the reference; one made by
`replay/fixture.build_synthetic_chain`, which executes its blocks on the
program's own `Blockchain`, compares the program with itself.

The key holders (`chainproc_holders.holders_of`) are what the chain's
process knows and a header does not say: who was funded at genesis, with
what.
"""

from __future__ import annotations

from dataclasses import replace

from reference import chain as ref

from phant_tpu.replay.fixture import ReplayFixture
from phant_tpu.types.account import Account
from phant_tpu.types.block import Block, BlockHeader
from phant_tpu.types.transaction import LegacyTx


def genesis_accounts(holders: dict) -> dict:
    """address -> the program's Account, with the balances
    `reference.chain.Chain.__post_init__` gives: 10^24 a sender, 10^18 + i
    the i-th cold account, the counter's code and nothing else a contract."""
    cold = holders["cold"]
    accounts = {addr: Account(balance=10**24) for addr in holders["pool"]}
    for i in range(len(cold) // 20):
        accounts[cold[20 * i : 20 * i + 20]] = Account(balance=10**18 + i)
    for addr in holders["contracts"]:
        accounts[addr] = Account(balance=0, code=holders["code"])
    return accounts


def header_of(h: ref.Header) -> BlockHeader:
    """The program's header of the same encoding, byte for byte (checked:
    a field this leaves out or renames would change the hash the next
    block's `parent_hash` is held to)."""
    out = BlockHeader(
        parent_hash=h.parent_hash,
        uncle_hash=ref.EMPTY_UNCLE_HASH,
        fee_recipient=ref.COINBASE,
        state_root=h.state_root,
        transactions_root=h.transactions_root,
        receipts_root=h.receipts_root,
        logs_bloom=h.logs_bloom,
        block_number=h.number,
        gas_limit=ref.GAS_LIMIT,
        gas_used=h.gas_used,
        timestamp=h.timestamp,
        base_fee_per_gas=h.base_fee,
        withdrawals_root=ref.EMPTY_ROOT,
    )
    if out.encode() != h.encode():
        raise ValueError(f"header {h.number}: the program's encoding is not the reference's")
    return out


def tx_of(t: ref.Tx) -> LegacyTx:
    return LegacyTx(
        nonce=t.nonce,
        gas_price=ref.GAS_PRICE,
        gas_limit=t.gas_limit,
        to=t.to,
        value=t.value,
        data=t.data,
        v=t.v,
        r=t.r,
        s=t.s,
    )


def block_of(b: ref.Block) -> tuple:
    """(the program's Block, its witness as the replay engine takes it:
    the pre-state root and the nodes)."""
    block = Block(
        header=header_of(b.header),
        transactions=tuple(tx_of(t) for t in b.txs),
        withdrawals=(),
    )
    return block, (b.pre_root, list(b.witness))


def altered(b: ref.Block, what: str) -> ref.Block:
    """The reference's block with one thing altered and everything else
    re-derived around it, the alterations of `Block.body_altered` made on
    the block itself (a replay has no request body):

    witness        one byte flipped in the middle of the largest node
    signature      one byte of the first transaction's `r` flipped, the
                   transactions root re-derived
    state_root     the lowest bit of the header's post-state root flipped
    receipts_root  the lowest bit of the header's receipts root flipped
    gas_used       the header's gas used, plus one
    """
    h = b.header
    flip = lambda x: x[:-1] + bytes([x[-1] ^ 0x01])  # noqa: E731
    if what == "witness":
        nodes = list(b.witness)
        i = max(range(len(nodes)), key=lambda k: len(nodes[k]))
        raw = bytearray(nodes[i])
        raw[len(raw) // 2] ^= 0x01
        nodes[i] = bytes(raw)
        return replace(b, witness=nodes)
    if what == "signature":
        t = b.txs[0]
        txs = [replace(t, r=t.r ^ (0xFF << 64)), *b.txs[1:]]
        root = ref.ordered_root([x.encode() for x in txs])
        return replace(b, header=replace(h, transactions_root=root), txs=txs)
    if what == "state_root":
        return replace(b, header=replace(h, state_root=flip(h.state_root)))
    if what == "receipts_root":
        return replace(b, header=replace(h, receipts_root=flip(h.receipts_root)))
    if what == "gas_used":
        return replace(b, header=replace(h, gas_used=h.gas_used + 1))
    raise ValueError(f"no alteration {what!r}")


def fixture_of(genesis: ref.Header, holders: dict, blocks: list) -> ReplayFixture:
    """The replay fixture of a reference chain: its genesis header, the
    accounts its key holders were given, its blocks and their witnesses."""
    pairs = [block_of(b) for b in blocks]
    return ReplayFixture(
        chain_id=ref.CHAIN_ID,
        genesis=header_of(genesis),
        genesis_accounts=genesis_accounts(holders),
        blocks=[block for block, _w in pairs],
        witnesses=[w for _b, w in pairs],
        scheme="mpt",
    )
