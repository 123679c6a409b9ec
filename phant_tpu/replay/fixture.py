"""Replay fixture chains — the on-disk unit `python -m phant_tpu.replay`
consumes.

A fixture is a pickled dict carrying a genesis header, the genesis
account set, and an ordered block list (the picklable shapes
`build_synthetic_chain` below makes), optionally enriched with
per-block witnesses: `(claimed_root, nodes)` pairs generated against
each block's PARENT state under a named commitment scheme
(phant_tpu/commitment/). Witnessed fixtures let the replay engine drive
segment ingestion through the scheduler's witness lane — K blocks'
linked-multiproof checks coalescing into megabatches — in addition to
the sig/root megabatches an unwitnessed fixture already exercises.
"""

from __future__ import annotations

import pickle
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

FORMAT = "phant-replay-fixture"
VERSION = 1


@dataclass
class ReplayFixture:
    """One replayable chain segment: genesis + blocks (+ witnesses)."""

    chain_id: int
    genesis: object  # types.block.BlockHeader
    genesis_accounts: Dict[bytes, object]  # address -> types.account.Account
    blocks: List[object]  # types.block.Block, ascending
    #: per-block (claimed_root, nodes) against the PARENT state, or None
    witnesses: Optional[List[Tuple[bytes, List[bytes]]]] = None
    #: commitment scheme the witnesses were generated under
    scheme: Optional[str] = None

    def fresh_state(self):
        from phant_tpu.state.statedb import StateDB

        return StateDB(
            {a: acct.copy() for a, acct in self.genesis_accounts.items()}
        )

    def fresh_chain(self, verify_state_root: bool = True):
        from phant_tpu.blockchain.chain import Blockchain

        return Blockchain(
            self.chain_id,
            self.fresh_state(),
            self.genesis,
            verify_state_root=verify_state_root,
        )

    @property
    def total_txs(self) -> int:
        return sum(len(b.transactions) for b in self.blocks)


def build_synthetic_chain(
    n_blocks: int,
    txs_per_block: int,
    n_fillers: int = 0,
    seed: int = 0,
    touched_witnesses: bool = False,
) -> ReplayFixture:
    """A synthetic mainnet-shaped chain, made from `seed` and nothing on
    disk. Its blocks are EXECUTED here, on the program's own `Blockchain`,
    to fill their headers: a replay of a fixture made by this function
    compares the program with itself (pipelined against serial, one lane
    against another). The independent one is
    `benchmarks/harness/fixture_of_chain.py`, which puts the chain of the
    benchmark's plain reference into the same fixture without executing
    anything (`tests/test_replay_reference.py`; ROADMAP D12).

    Per block, `txs_per_block` value transfers PLUS half as many
    contract calls that SLOAD+SSTORE a counter (cold account + cold slot
    per tx under EIP-2929), so a replay exercises the EVM storage path,
    receipts with variable gas, and an evolving contract storage trie —
    not just balance arithmetic. Headers carry the exact gas/roots a
    replay must recompute, derived from actually executing each block on
    a builder chain (reference scope: src/blockchain/blockchain.zig:61-96,
    which TODO-disables the state-root check).

    `n_fillers` funded accounts that no transaction touches size the
    state trie (proof depth, and what a stateless witness leaves out).
    The builder's state keeps ONE retained trie (StateDB.flush_root_trie)
    that each block updates along its dirty paths, so the filler set is
    hashed once, not once per block.

    `touched_witnesses` attaches to each block the witness a consensus
    client would ship: the proof paths of exactly the accounts and
    storage slots the block touches, against its PARENT state —
    `(parent_state_root, nodes)` — instead of `attach_witnesses`' whole
    state."""
    from dataclasses import replace

    from phant_tpu.blockchain.chain import Blockchain, calculate_base_fee
    from phant_tpu.crypto import secp256k1 as secp
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.mpt.mpt import EMPTY_TRIE_ROOT, ordered_trie_root
    from phant_tpu.mpt.proof import generate_proof
    from phant_tpu.signer.signer import TxSigner, address_from_pubkey
    from phant_tpu.state.statedb import StateDB
    from phant_tpu.types.account import Account
    from phant_tpu.types.block import Block, BlockHeader
    from phant_tpu.types.receipt import logs_bloom
    from phant_tpu.types.transaction import LegacyTx

    chain_id = 1
    signer = TxSigner(chain_id)
    n_calls = max(txs_per_block // 2, 1)  # contract calls ride along
    tag = b"phant-synth" + seed.to_bytes(8, "big")
    keys = [
        int.from_bytes(keccak256(tag + b"key" + i.to_bytes(4, "big")), "big")
        % (secp.N - 1)
        + 1
        for i in range(txs_per_block + n_calls)
    ]
    senders = [address_from_pubkey(secp.pubkey_of(k)) for k in keys]
    genesis_accounts = {addr: Account(balance=10**24) for addr in senders}
    for i in range(n_fillers):
        addr = keccak256(tag + b"filler" + i.to_bytes(4, "big"))[:20]
        genesis_accounts[addr] = Account(balance=10**18 + i)
    recipient = b"\x99" * 20
    # counter contract: slot0 += 1 per call (cold SLOAD + dirty SSTORE per
    # tx under EIP-2929 — the storage path the transfers never touch)
    counter_addr = b"\xc0" * 20
    # PUSH1 0 SLOAD PUSH1 1 ADD PUSH1 0 SSTORE STOP
    counter_code = bytes.fromhex("600054600101600055") + b"\x00"
    genesis_accounts[counter_addr] = Account(balance=0, code=counter_code)

    gas_limit = 30_000_000
    gas_price = 10**9  # constant, >= every (decreasing) base fee
    genesis = BlockHeader(
        block_number=0,
        gas_limit=gas_limit,
        gas_used=0,
        timestamp=1_700_000_000,
        base_fee_per_gas=10**9,
        withdrawals_root=EMPTY_TRIE_ROOT,
    )

    # build blocks by EXECUTING them on a builder chain, so every header
    # carries its real post-state root (a replay can then run with full
    # state-root verification)
    builder_state = StateDB(
        {a: acct.copy() for a, acct in genesis_accounts.items()}
    )
    builder = Blockchain(chain_id, builder_state, genesis, verify_state_root=False)
    blocks = []
    witnesses: List[Tuple[bytes, List[bytes]]] = []
    parent = genesis
    touched = [*senders, recipient, counter_addr, genesis.fee_recipient]

    for b in range(1, n_blocks + 1):
        txs = []
        for j, k in enumerate(keys):
            is_call = j >= txs_per_block
            tx = LegacyTx(
                nonce=b - 1,
                gas_price=gas_price,
                gas_limit=60_000 if is_call else 21_000,
                to=counter_addr if is_call else recipient,
                value=0 if is_call else 1,
                data=b"",
                v=37,  # EIP-155 marker; sign() recomputes
                r=0,
                s=0,
            )
            txs.append(signer.sign(tx, k))
        if touched_witnesses:
            # the parent state, before this block mutates it: account
            # paths from the retained state trie, the counter's slot 0
            # from its retained storage trie
            trie = builder_state.flush_root_trie()
            nodes: Dict[bytes, None] = {}
            for addr in touched:
                for enc in generate_proof(trie, keccak256(addr)):
                    nodes[enc] = None
            counter = builder_state.accounts[counter_addr]
            if counter.storage:
                builder_state._storage_root_incremental(counter_addr, counter)
                strie = builder_state._storage_tries[counter_addr][1]
                for enc in generate_proof(strie, keccak256((0).to_bytes(32, "big"))):
                    nodes[enc] = None
            witnesses.append((trie.root_hash(), list(nodes)))
        draft = BlockHeader(
            parent_hash=parent.hash(),
            block_number=b,
            gas_limit=gas_limit,
            gas_used=0,  # filled from execution below
            timestamp=parent.timestamp + 12,
            base_fee_per_gas=calculate_base_fee(
                parent.gas_limit, parent.gas_used, parent.base_fee_per_gas
            ),
            transactions_root=ordered_trie_root([t.encode() for t in txs]),
            receipts_root=EMPTY_TRIE_ROOT,
            withdrawals_root=EMPTY_TRIE_ROOT,
            logs_bloom=logs_bloom([]),
        )
        # execute on the builder; the REAL gas/receipts/bloom/state root
        # become the header a replay must reproduce exactly
        result = builder.apply_body(
            Block(header=draft, transactions=tuple(txs), withdrawals=())
        )
        header = replace(
            draft,
            gas_used=result.gas_used,
            receipts_root=ordered_trie_root(
                [r.encode() for r in result.receipts]
            ),
            logs_bloom=result.logs_bloom,
            state_root=builder_state.state_root(),
        )
        builder.parent_header = header
        blocks.append(Block(header=header, transactions=tuple(txs), withdrawals=()))
        parent = header

    fix = ReplayFixture(
        chain_id=chain_id,
        genesis=genesis,
        genesis_accounts=genesis_accounts,
        blocks=blocks,
    )
    if touched_witnesses:
        fix.witnesses = witnesses
        fix.scheme = "mpt"  # hexary proofs from the builder's retained tries
    return fix


def attach_witnesses(fix: ReplayFixture, scheme=None) -> ReplayFixture:
    """Enrich a fixture with per-block full-state witnesses under
    `scheme` (default: the active PHANT_COMMITMENT scheme). Each block's
    claimed root commits its PARENT state — under the hexary mpt scheme
    that is byte-identical to the parent header's state_root; the binary
    scheme's roots are its own (the header chain stays hexary, the
    witness lane only checks linkage against the claimed root). Builds
    by replaying on a throwaway chain; O(blocks x state), fixture-prep
    cost, never on a replay path."""
    from phant_tpu.commitment import active_scheme

    sch = scheme if scheme is not None else active_scheme()
    chain = fix.fresh_chain(verify_state_root=False)
    witnesses: List[Tuple[bytes, List[bytes]]] = []
    for block in fix.blocks:
        root, nodes, _codes = sch.witness_of_state(chain.state.accounts)
        witnesses.append((root, list(nodes)))
        chain.run_block(block)
    fix.witnesses = witnesses
    fix.scheme = sch.name
    return fix


def save_fixture(path: str, fix: ReplayFixture) -> None:
    payload = {
        "format": FORMAT,
        "version": VERSION,
        "chain_id": fix.chain_id,
        "genesis": fix.genesis,
        "genesis_accounts": fix.genesis_accounts,
        "blocks": fix.blocks,
        "witnesses": fix.witnesses,
        "scheme": fix.scheme,
    }
    with open(path, "wb") as f:
        pickle.dump(payload, f)


def load_fixture(path: str) -> ReplayFixture:
    """Load a file `save_fixture` wrote."""
    with open(path, "rb") as f:
        payload = pickle.load(f)
    if not isinstance(payload, dict) or payload.get("format") != FORMAT:
        raise ValueError(f"{path}: not a {FORMAT} file")
    if payload.get("version") != VERSION:
        raise ValueError(
            f"{path}: fixture version {payload.get('version')!r} "
            f"(supported: {VERSION})"
        )
    return ReplayFixture(
        chain_id=payload["chain_id"],
        genesis=payload["genesis"],
        genesis_accounts=payload["genesis_accounts"],
        blocks=list(payload["blocks"]),
        witnesses=payload.get("witnesses"),
        scheme=payload.get("scheme"),
    )
