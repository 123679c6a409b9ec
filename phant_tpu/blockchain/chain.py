"""Block-level validation and execution (the core hot loop).

Equivalent surface to the reference Blockchain (reference:
src/blockchain/blockchain.zig:44-377): header validation (gas-limit bounds,
EIP-1559 base-fee recurrence, PoS fields, parent hash), the per-tx loop
(sender recovery -> intrinsic gas -> warm-set prefill -> EVM execution ->
refunds -> coinbase credit -> EIP-158 cleanup), withdrawals, and the
post-execution root checks. Goes beyond the reference by actually verifying
state root and logs bloom (TODO-disabled there,
reference: blockchain.zig:83-88).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional, Tuple

from phant_tpu import rlp
from phant_tpu.crypto.secp256k1 import SignatureError
from phant_tpu.evm import gas as G
from phant_tpu.evm.interpreter import Evm
from phant_tpu.evm.message import Environment, Message
from phant_tpu.evm.native_vm import BlockHost
from phant_tpu.evm.precompiles import precompile_addresses
from phant_tpu.blockchain.fork import Fork, FrontierFork
from phant_tpu.signer.signer import TxSigner
from phant_tpu.state.statedb import StateDB
from phant_tpu.types.block import Block, BlockHeader
from phant_tpu.types.receipt import Receipt, logs_bloom
from phant_tpu.types.transaction import (
    BlobTx,
    FeeMarketTx,
    SetCodeTx,
    Transaction,
    VERSIONED_HASH_VERSION_KZG,
    access_list_of,
    authorization_list_of,
    blob_gas_of,
    effective_gas_price,
    max_fee_per_gas,
)
from phant_tpu.types.withdrawal import GWEI
from phant_tpu.mpt.mpt import ordered_trie_root
from phant_tpu.utils.trace import clock_ns, cpu_clock_ns

ELASTICITY_MULTIPLIER = 2  # reference: params.zig:36
BASE_FEE_MAX_CHANGE_DENOMINATOR = 8  # reference: params.zig:37
GAS_LIMIT_ADJUSTMENT_FACTOR = 1024  # reference: blockchain.zig:140-145
GAS_LIMIT_MINIMUM = 5000


class BlockError(Exception):
    """Consensus-invalid block (maps to fixture expectException)."""


@dataclass
class BlockExecutionResult:
    """(reference: blockchain.zig:147-153)"""

    gas_used: int
    receipts: List[Receipt]
    logs_bloom: bytes
    requests_hash: Optional[bytes] = None  # EIP-7685 (Prague blocks only)


class Blockchain:
    """Holds chain config + parent header and runs blocks
    (reference: blockchain.zig:44-96)."""

    def __init__(
        self,
        chain_id: int,
        state: StateDB,
        parent_header: BlockHeader,
        fork: Optional[Fork] = None,
        verify_state_root: bool = True,
        config=None,
    ):
        self.chain_id = chain_id
        self.state = state
        self.parent_header = parent_header
        self.fork = fork if fork is not None else FrontierFork()
        self.signer = TxSigner(chain_id)
        self.verify_state_root = verify_state_root
        # chain config (fork-activation schedule); the stateless handler
        # uses it to pick the fork for witness-backed execution
        self.config = config
        self._vm_host = None  # the running block's (run_block)
        # nanoseconds run_block has spent in `state.state_root()` so far, on
        # the span clock and on the calling thread's CPU clock: the replay
        # engine reads it around a block to tell the root walk from execution
        self.root_clock = [0, 0]
        # a config naming a known public network arms the KZG dev-setup
        # guard: 0x0A must refuse the forgeable dev tau there (crypto/kzg
        # set_public_network; config-less fixture chains stay unguarded)
        if config is not None:
            from phant_tpu.config import PUBLIC_CHAIN_IDS

            if getattr(config, "chainId", None) in PUBLIC_CHAIN_IDS:
                from phant_tpu.crypto import kzg

                kzg.set_public_network(
                    getattr(config, "ChainName", None) or str(config.chainId)
                )

    # ------------------------------------------------------------------

    def run_block(
        self,
        block: Block,
        check_body_roots: bool = True,
        senders: Optional[List[Optional[bytes]]] = None,
    ) -> BlockExecutionResult:
        """Validate + execute + verify roots (reference: blockchain.zig:61-96).

        An invalid block leaves no trace: execution is journaled and rolled
        back on any failure. `check_body_roots=False` skips re-deriving the
        tx/withdrawal roots — used by the Engine API path, whose `to_block`
        derived exactly those roots from the same tx/withdrawal tuples one
        call earlier (the blockHash check covers header integrity there).
        `senders` optionally supplies prefetched sender addresses (None
        entries = invalid signature) — the run_blocks pipeline's window
        prefetch, or the serving sig lane's merged cross-request
        ecrecover (stateless.dispatch_sender_recovery ->
        ops/sig_engine.py), both join the block here."""
        self.validate_block_header(block.header)
        if block.uncles:
            raise BlockError("post-merge blocks must have no uncles")

        self.state.begin_block()
        # ONE host binding of the native VM for the block's transactions
        # (evm/native_vm.BlockHost: built at the first frame the native VM
        # runs, so none under the Python interpreter); it ends with the block
        self._vm_host = BlockHost()
        try:
            return self._execute_block(block, check_body_roots, senders)
        except BaseException:
            self.state.rollback_block()
            raise
        finally:
            self._vm_host.close()
            self._vm_host = None

    def run_blocks(
        self, blocks: List[Block], check_body_roots: bool = True
    ) -> List[BlockExecutionResult]:
        """Sequential block import with pipelined sender recovery: on
        `--crypto_backend=tpu`, whole windows of upcoming blocks' signatures
        are dispatched to the device ecrecover kernel while earlier blocks
        execute on the CPU — the device computes under the EVM's feet and
        per-dispatch latency is amortized over hundreds of txs. The
        reference's import loop is strictly serial per tx
        (reference: src/blockchain/blockchain.zig:61-96, :241); the batching
        axis across blocks is this framework's north-star addition.

        When a scheduler sig lane is installed (stateless.
        sender_lane_available), the SAME window pipeline engages even
        without a device: each window's rows go through
        `dispatch_sender_recovery`, so the rows are built once per WINDOW
        and the fused recovery runs on the scheduler's executor threads
        under the EVM's feet. Before the r18 fix this path fell through
        to the plain loop and paid a per-block signing-hash + recovery on
        the critical path with the lane sitting idle."""
        from phant_tpu.backend import crypto_backend, jax_device_ok
        from phant_tpu.stateless import (
            dispatch_sender_recovery,
            sender_lane_available,
        )

        results = []
        lane = sender_lane_available()
        if not lane and not (crypto_backend() == "tpu" and jax_device_ok()):
            for block in blocks:
                results.append(self.run_block(block, check_body_roots))
            return results

        window = int(os.environ.get("PHANT_TPU_PREFETCH_SIGS", "2048"))
        # split blocks into windows of >= `window` signatures; dispatch each
        # window's recovery in ONE fused device call, two windows in flight
        spans: List[Tuple[int, int]] = []  # [start_block, end_block)
        start, count = 0, 0
        for i, b in enumerate(blocks):
            count += len(b.transactions)
            if count >= window:
                spans.append((start, i + 1))
                start, count = i + 1, 0
        if start < len(blocks):
            spans.append((start, len(blocks)))

        def dispatch(span):
            s, e = span
            txs = [tx for b in blocks[s:e] for tx in b.transactions]
            if lane:
                # route the whole window through the sig lane: rows are
                # built once here and recovery runs on the scheduler's
                # executor threads; a shed/crashed lane degrades inside
                # the returned resolve (dispatch_sender_recovery), and a
                # lane that went away between windows falls through to
                # the direct dispatch below
                handle = dispatch_sender_recovery(self.chain_id, txs)
                if handle is not None:
                    return handle
            try:
                return self.signer.recover_senders_async(txs)
            except Exception as exc:  # staging onto a dead device can raise
                # synchronously; defer to resolve() so the CPU fallback
                # covers dispatch-time failures too
                def failed(e=exc):
                    raise e

                return failed

        def resolve(span, handle):
            """Materialize a window's senders; a device failure mid-replay
            (device lost, OOM, preemption) degrades to the CPU batch for
            the window instead of sinking the import — the reference has
            no device to lose (its crypto is always in-process,
            src/crypto/ecdsa.zig); fault tolerance here is the cost of the
            offload. The fallback pins THIS call to the CPU path instead of
            flipping the process-global backend (which would race the
            threaded Engine API server)."""
            try:
                return handle()
            except Exception:
                import logging

                from phant_tpu.backend import device_fallback

                device_fallback("chain_senders")
                logging.getLogger("phant.chain").warning(
                    "device sender-recovery failed for blocks %s-%s; "
                    "recovering on CPU",
                    span[0],
                    span[1] - 1,
                    exc_info=True,
                )
                txs = [tx for b in blocks[span[0] : span[1]] for tx in b.transactions]
                return self.signer.recover_senders_async(txs, force_cpu=True)()

        pending: List = []
        next_span = 0
        for k in range(min(2, len(spans))):
            pending.append(dispatch(spans[k]))
            next_span += 1

        for si, (s, e) in enumerate(spans):
            senders_flat = resolve(spans[si], pending.pop(0))
            if next_span < len(spans):  # keep the device one window ahead
                pending.append(dispatch(spans[next_span]))
                next_span += 1
            pos = 0
            for block in blocks[s:e]:
                n = len(block.transactions)
                results.append(
                    self.run_block(
                        block, check_body_roots, senders=senders_flat[pos : pos + n]
                    )
                )
                pos += n
        return results

    def _execute_block(
        self,
        block: Block,
        check_body_roots: bool,
        senders: Optional[List[Optional[bytes]]] = None,
    ) -> BlockExecutionResult:
        # record parent hash for BLOCKHASH (reference: blockchain.zig:71)
        self.fork.update_parent_block_hash(
            self.parent_header.block_number, self.parent_header.hash()
        )
        # fork-scoped system updates (EIP-4788 beacon root under Cancun);
        # journaled, so an invalid block rolls them back with everything else
        self.fork.on_block_start(block.header)

        result = self.apply_body(block, senders)

        header = block.header
        if result.gas_used != header.gas_used:
            raise BlockError(
                f"gas_used mismatch: computed {result.gas_used}, header {header.gas_used}"
            )
        if check_body_roots:
            tx_root = ordered_trie_root([tx.encode() for tx in block.transactions])
            if tx_root != header.transactions_root:
                raise BlockError("transactions root mismatch")
            if block.withdrawals is not None:
                wd_root = ordered_trie_root([w.encode() for w in block.withdrawals])
                if wd_root != header.withdrawals_root:
                    raise BlockError("withdrawals root mismatch")
        receipts_root = ordered_trie_root([r.encode() for r in result.receipts])
        if receipts_root != header.receipts_root:
            raise BlockError("receipts root mismatch")
        if result.logs_bloom != header.logs_bloom:
            raise BlockError("logs bloom mismatch")
        if result.requests_hash is not None:
            # EIP-7685: a Prague block must commit to its requests
            if header.requests_hash is None:
                raise BlockError("prague header missing requests_hash")
            if result.requests_hash != header.requests_hash:
                raise BlockError(
                    f"requests hash mismatch: computed "
                    f"{result.requests_hash.hex()}, header "
                    f"{header.requests_hash.hex()}"
                )
        elif header.requests_hash is not None:
            raise BlockError("requests_hash before prague")
        if self.verify_state_root:
            # beyond reference (TODO-disabled at blockchain.zig:83-85)
            t0, c0 = clock_ns(), cpu_clock_ns()
            try:
                computed = self.state.state_root()
            finally:
                self.root_clock[0] += clock_ns() - t0
                self.root_clock[1] += cpu_clock_ns() - c0
            if computed != header.state_root:
                raise BlockError(
                    f"state root mismatch: {computed.hex()} != {header.state_root.hex()}"
                )

        self.parent_header = block.header
        return result

    # ------------------------------------------------------------------

    def cancun_active(self, header: BlockHeader) -> bool:
        """Cancun dispatch: the chain config's schedule when present, else
        the header's own blob-gas fields (fixtures and synthetic chains are
        self-describing). The reference pins EVMC_SHANGHAI with a TODO
        (src/blockchain/vm.zig:472); this is that TODO done.

        The header-trusting fallback is for CONFIG-LESS chains only —
        trusted inputs by construction (fixtures, synthetic chains).
        Every network entry point (the Engine API server, __main__)
        constructs its Blockchain with a config, so untrusted payload
        bytes never pick their own fork here."""
        if self.config is not None:
            name = self.config.fork_at(header.block_number, header.timestamp)
            return name in ("cancun", "prague", "osaka")
        return header.excess_blob_gas is not None

    def prague_active(self, header: BlockHeader) -> bool:
        """Prague dispatch (EIP-7702 set-code txs, EIP-7623 calldata
        floor, EIP-7691 blob schedule, EIP-2935 ring). Config-less chains
        (fixtures/synthetic) follow the fork instance they were built
        with — the same rule blob_schedule uses, so a CancunFork chain
        can never half-activate Prague."""
        from phant_tpu.blockchain.fork import PragueFork

        if self.config is not None:
            name = self.config.fork_at(header.block_number, header.timestamp)
            return name in ("prague", "osaka")
        return isinstance(self.fork, PragueFork)

    def blob_schedule(self, header: BlockHeader) -> tuple:
        """(max_blob_gas, target_blob_gas, fee_update_fraction) for this
        block — EIP-7691 raised all three at Prague. Config-less chains
        (fixtures, synthetic chains) derive the schedule from the fork
        instance they were constructed with."""
        from phant_tpu.blockchain.fork import PragueFork

        if self.config is not None:
            name = self.config.fork_at(header.block_number, header.timestamp)
        elif isinstance(self.fork, PragueFork):
            name = "prague"
        else:
            name = "cancun"
        return G.blob_schedule(name)

    def validate_block_header(self, header: BlockHeader) -> None:
        """(reference: blockchain.zig:100-138; the blob-gas rules are
        EIP-4844, beyond the reference's Shanghai ceiling)"""
        parent = self.parent_header
        if self.cancun_active(header):
            if header.blob_gas_used is None or header.excess_blob_gas is None:
                raise BlockError("cancun header missing blob gas fields")
            max_blob_gas, target_blob_gas, _frac = self.blob_schedule(header)
            if header.blob_gas_used > max_blob_gas:
                raise BlockError("blob gas used above block maximum")
            if header.blob_gas_used % G.GAS_PER_BLOB != 0:
                raise BlockError("blob gas used not a blob multiple")
            expected_excess = G.calc_excess_blob_gas(
                parent.excess_blob_gas or 0,
                parent.blob_gas_used or 0,
                target=target_blob_gas,
            )
            if header.excess_blob_gas != expected_excess:
                raise BlockError(
                    f"excess blob gas mismatch: header {header.excess_blob_gas}, "
                    f"expected {expected_excess}"
                )
        elif header.blob_gas_used is not None or header.excess_blob_gas is not None:
            raise BlockError("blob gas fields before cancun")
        if header.base_fee_per_gas is None:
            raise BlockError("missing base fee (pre-London unsupported)")
        expected_base_fee = calculate_base_fee(
            parent.gas_limit, parent.gas_used,
            parent.base_fee_per_gas if parent.base_fee_per_gas is not None else 0,
        )
        if header.base_fee_per_gas != expected_base_fee:
            raise BlockError(
                f"base fee mismatch: header {header.base_fee_per_gas}, expected {expected_base_fee}"
            )
        if header.gas_used > header.gas_limit:
            raise BlockError("gas_used above gas_limit")
        check_gas_limit(header.gas_limit, parent.gas_limit)
        if header.timestamp <= parent.timestamp:
            raise BlockError("timestamp not after parent")
        if header.block_number != parent.block_number + 1:
            raise BlockError("block number not parent+1")
        if len(header.extra_data) > 32:
            raise BlockError("extra data too long")
        # PoS fields (reference: blockchain.zig:124-129)
        if header.difficulty != 0:
            raise BlockError("difficulty must be 0 post-merge")
        if header.nonce != b"\x00" * 8:
            raise BlockError("nonce must be zero post-merge")
        from phant_tpu.types.block import EMPTY_UNCLE_HASH

        if header.uncle_hash != EMPTY_UNCLE_HASH:
            raise BlockError("uncle hash must be empty-list hash")
        if header.parent_hash != parent.hash():
            raise BlockError("parent hash mismatch")

    # ------------------------------------------------------------------

    def apply_body(
        self, block: Block, senders: Optional[List[Optional[bytes]]] = None
    ) -> BlockExecutionResult:
        """(reference: blockchain.zig:155-205)"""
        header = block.header
        gas_available = header.gas_limit
        receipts: List[Receipt] = []
        cumulative_gas = 0
        all_logs = []

        # recover every sender up front — one fused batch (native, or device
        # when the tpu backend and batch size warrant it; reference recovers
        # per-tx, blockchain.zig:241). Prefetched senders arrive from two
        # producers: run_blocks (device recovery windows ahead of the
        # replay) and the serving sig lane (one merged ecrecover across
        # concurrent requests, dispatched at decode time — ops/
        # sig_engine.py). The None-entry error message below must stay
        # byte-identical to get_senders_batch's SignatureError text: the
        # lane's invalid-signature attribution contract rides on it.
        if senders is None:
            try:
                senders = self.signer.get_senders_batch(list(block.transactions))
            except SignatureError as e:
                raise BlockError(f"invalid signature: {e}") from e
        else:
            if len(senders) != len(block.transactions):
                raise BlockError("prefetched sender count mismatch")
            bad = [i for i, a in enumerate(senders) if a is None]
            if bad:
                raise BlockError(
                    f"invalid signature: unrecoverable signature at tx index {bad[0]}"
                )

        # block-constant fork context computed ONCE (the schedule scan and
        # the fake_exponential blob fee are per-header facts; the tx loop
        # is the replay hot path)
        cancun = self.cancun_active(header)
        if cancun:
            max_blob_gas, _target, fee_fraction = self.blob_schedule(header)
            bbf = G.blob_base_fee(header.excess_blob_gas or 0, fee_fraction)
        else:
            max_blob_gas, bbf = 0, 0
        blob_gas_used = 0
        for tx, sender in zip(block.transactions, senders):
            self.check_transaction(
                tx, header, gas_available, sender, cancun=cancun, blob_base_fee=bbf
            )
            blob_gas_used += blob_gas_of(tx)
            if cancun and blob_gas_used > max_blob_gas:
                raise BlockError("block blob gas above maximum")
            gas_used, tx_logs, succeeded = self.process_transaction(
                tx, sender, header, cancun=cancun, blob_base_fee=bbf
            )
            gas_available -= gas_used
            cumulative_gas += gas_used
            receipts.append(
                Receipt(
                    tx_type=tx.tx_type,
                    succeeded=succeeded,
                    cumulative_gas_used=cumulative_gas,
                    logs=tuple(tx_logs),
                )
            )
            all_logs.extend(tx_logs)

        if cancun and blob_gas_used != (header.blob_gas_used or 0):
            raise BlockError(
                f"blob gas used mismatch: computed {blob_gas_used}, "
                f"header {header.blob_gas_used}"
            )

        # withdrawals (reference: blockchain.zig:193-196)
        if block.withdrawals:
            for wd in block.withdrawals:
                self.state.add_balance(wd.address, wd.amount * GWEI)
                acct = self.state.get_account(wd.address)
                if acct is not None and acct.is_empty():
                    self.state.delete_account(wd.address)

        # EIP-7685 requests surface (Prague): deposits parsed from this
        # block's receipts, withdrawal/consolidation requests dequeued by
        # end-of-block system calls (phant_tpu/blockchain/requests.py)
        requests_hash = None
        if self.prague_active(header):
            requests_hash = self._collect_requests(receipts, header)

        return BlockExecutionResult(
            gas_used=cumulative_gas,
            receipts=receipts,
            logs_bloom=logs_bloom(all_logs),
            requests_hash=requests_hash,
        )

    def _collect_requests(self, receipts, header: BlockHeader) -> bytes:
        from phant_tpu.blockchain import requests as req
        from phant_tpu.utils.hexutils import hex_to_address

        deposit_addr = req.DEPOSIT_CONTRACT_ADDRESS
        if self.config is not None and getattr(
            self.config, "depositContractAddress", None
        ):
            deposit_addr = hex_to_address(self.config.depositContractAddress)
        try:
            deposits = req.extract_deposit_requests(receipts, deposit_addr)
        except req.RequestsError as e:
            raise BlockError(str(e)) from e
        withdrawals = self._system_call(req.WITHDRAWAL_REQUEST_ADDRESS, header)
        consolidations = self._system_call(
            req.CONSOLIDATION_REQUEST_ADDRESS, header
        )
        items = []
        if deposits:
            items.append(req.DEPOSIT_REQUEST_TYPE + deposits)
        if withdrawals:
            items.append(req.WITHDRAWAL_REQUEST_TYPE + withdrawals)
        if consolidations:
            items.append(req.CONSOLIDATION_REQUEST_TYPE + consolidations)
        return req.compute_requests_hash(items)

    def _system_call(self, target: bytes, header: BlockHeader) -> bytes:
        """EIP-7002/7251 end-of-block system call: caller = the system
        address, 30M gas, feeless, outside block-gas accounting; the
        output bytes ARE the request data.  A missing predeploy or a
        failing call invalidates the block (the requests cannot be
        proven absent)."""
        from phant_tpu.blockchain import requests as req
        from phant_tpu.evm.interpreter import Evm
        from phant_tpu.evm.message import REVISION_PRAGUE, Environment, Message

        state = self.state
        if not state.get_code(target):
            raise BlockError(f"missing system contract 0x{target.hex()}")
        state.start_tx()  # fresh warm sets / refund / logs for the call
        env = Environment(
            state=state,
            origin=req.SYSTEM_ADDRESS,
            coinbase=header.fee_recipient,
            block_number=header.block_number,
            gas_limit=header.gas_limit,
            gas_price=0,
            timestamp=header.timestamp,
            prev_randao=header.prev_randao,
            base_fee=header.base_fee_per_gas or 0,
            chain_id=self.chain_id,
            block_hash_fn=self.fork.get_block_hash,
            revision=REVISION_PRAGUE,
        )
        evm = Evm(env, self._vm_host)
        result = evm.execute_message(
            Message(
                caller=req.SYSTEM_ADDRESS,
                target=target,
                value=0,
                data=b"",
                gas=req.SYSTEM_CALL_GAS,
            )
        )
        if not result.success:
            raise BlockError(
                f"system call to 0x{target.hex()} failed: {result.error}"
            )
        return result.output

    # ------------------------------------------------------------------

    def check_transaction(
        self,
        tx: Transaction,
        header: BlockHeader,
        gas_available: int,
        sender: bytes,
        cancun: Optional[bool] = None,
        blob_base_fee: Optional[int] = None,
    ) -> None:
        """(reference: blockchain.zig:237-260 + validateTransaction :345-353;
        sender recovery itself happens batched in apply_body). `cancun` /
        `blob_base_fee` are block constants apply_body precomputes; direct
        callers may omit them."""
        if cancun is None:
            cancun = self.cancun_active(header)
        if tx.gas_limit > gas_available:
            raise BlockError("tx gas limit exceeds available block gas")
        base_fee = header.base_fee_per_gas or 0
        if isinstance(tx, (FeeMarketTx, BlobTx, SetCodeTx)):
            if tx.max_fee_per_gas < tx.max_priority_fee_per_gas:
                raise BlockError("max fee below priority fee")
            if tx.max_fee_per_gas < base_fee:
                raise BlockError("max fee below base fee")
        else:
            if tx.gas_price < base_fee:
                raise BlockError("gas price below base fee")

        if isinstance(tx, SetCodeTx):
            # EIP-7702 validity (no reference analog — type 4 postdates it)
            if not self.prague_active(header):
                raise BlockError("set-code tx before prague")
            if tx.to is None:
                raise BlockError("set-code tx cannot create")
            if not tx.authorization_list:
                raise BlockError("set-code tx without authorizations")

        blob_fee = 0
        if isinstance(tx, BlobTx):
            # EIP-4844 validity (no reference analog — type 3 postdates it)
            if not cancun:
                raise BlockError("blob tx before cancun")
            if tx.to is None:
                raise BlockError("blob tx cannot create")
            if not tx.blob_versioned_hashes:
                raise BlockError("blob tx without blobs")
            for h in tx.blob_versioned_hashes:
                if len(h) != 32 or h[0] != VERSIONED_HASH_VERSION_KZG:
                    raise BlockError("bad blob versioned hash version")
            if blob_base_fee is None:
                blob_base_fee = G.blob_base_fee(
                    header.excess_blob_gas or 0, self.blob_schedule(header)[2]
                )
            if tx.max_fee_per_blob_gas < blob_base_fee:
                raise BlockError("max blob fee below blob base fee")
            blob_fee = tx.blob_gas() * tx.max_fee_per_blob_gas

        # intrinsic validity (reference: validateTransaction blockchain.zig:345-353)
        is_create = tx.to is None
        if is_create and len(tx.data) > G.MAX_INITCODE_SIZE:
            raise BlockError("initcode exceeds EIP-3860 limit")
        intrinsic = G.intrinsic_gas(
            tx.data,
            is_create,
            access_list_of(tx),
            len(tx.data) if is_create else 0,
            n_authorizations=len(authorization_list_of(tx)),
        )
        if intrinsic > tx.gas_limit:
            raise BlockError("intrinsic gas exceeds limit")
        if self.prague_active(header) and G.calldata_floor_gas(tx.data) > tx.gas_limit:
            raise BlockError("gas limit below EIP-7623 calldata floor")

        sender_acct = self.state.get_account(sender)
        nonce = sender_acct.nonce if sender_acct else 0
        if nonce != tx.nonce:
            raise BlockError(f"nonce mismatch: tx {tx.nonce}, account {nonce}")
        if sender_acct is not None and sender_acct.code:
            # EIP-3607, as amended by EIP-7702 — but the designator
            # exemption exists only once Prague is live; pre-Prague every
            # code-bearing sender is rejected (consensus: other clients
            # reject such blocks too)
            if not (
                self.prague_active(header)
                and G.is_delegation_designator(sender_acct.code)
            ):
                raise BlockError("sender is not EOA (EIP-3607)")
        max_cost = tx.gas_limit * max_fee_per_gas(tx) + tx.value + blob_fee
        balance = sender_acct.balance if sender_acct else 0
        if balance < max_cost:
            raise BlockError("insufficient sender balance for gas + value")

    # ------------------------------------------------------------------

    def _apply_authorizations(self, tx: Transaction, state) -> int:
        """EIP-7702 per-tuple processing; returns the gas-refund credit.

        For each authorization: screen chain id (0 or ours) and nonce
        ceiling, recover the authority from its signature over
        keccak(0x05 ‖ rlp([chain_id, address, nonce])), warm the authority,
        and — if its code is empty or already a delegation and its nonce
        matches — install 0xef0100‖address (or clear it for the zero
        address) and bump the authority nonce. Existing authorities earn
        the PER_EMPTY_ACCOUNT_COST − PER_AUTH_BASE_COST refund. Any
        screening failure skips the TUPLE, never the tx."""
        from phant_tpu.signer.signer import recover_authority

        refund = 0
        for auth in authorization_list_of(tx):
            if auth.chain_id not in (0, self.chain_id):
                continue
            if auth.nonce >= 2**64 - 1:
                continue
            authority = recover_authority(auth)
            if authority is None:
                continue
            # the authority is warmed even when a later check skips the
            # tuple (EIP-7702: added to accessed_addresses regardless)
            state.access_address(authority)
            acct = state.get_account(authority)
            code = acct.code if acct else b""
            if code and not G.is_delegation_designator(code):
                continue  # a real contract cannot be delegated
            nonce = acct.nonce if acct else 0
            if nonce != auth.nonce:
                continue
            # refund keys on trie PRESENCE (EELS `account_exists`), not
            # non-emptiness: an existing-but-empty authority still refunds
            if acct is not None:
                refund += G.PER_EMPTY_ACCOUNT_COST - G.PER_AUTH_BASE_COST
            if auth.address == b"\x00" * 20:
                state.set_code(authority, b"")  # clear the delegation
            else:
                state.set_code(authority, G.DELEGATION_PREFIX + auth.address)
            state.increment_nonce(authority)
            state.touch(authority)
        return refund

    def process_transaction(
        self,
        tx: Transaction,
        sender: bytes,
        header: BlockHeader,
        cancun: Optional[bool] = None,
        blob_base_fee: Optional[int] = None,
    ) -> Tuple[int, list, bool]:
        """(reference: blockchain.zig:262-343). `cancun` / `blob_base_fee`
        are block constants apply_body precomputes; direct callers may omit
        them."""
        state = self.state
        state.start_tx()
        base_fee = header.base_fee_per_gas or 0
        gas_price = effective_gas_price(tx, base_fee)
        priority_fee = gas_price - base_fee
        if cancun is None:
            cancun = self.cancun_active(header)
        if blob_base_fee is None:
            blob_base_fee = (
                G.blob_base_fee(
                    header.excess_blob_gas or 0, self.blob_schedule(header)[2]
                )
                if cancun
                else 0
            )
        blob_fee_rate = blob_base_fee

        from phant_tpu.evm.message import (
            REVISION_CANCUN,
            REVISION_PRAGUE,
            REVISION_SHANGHAI,
        )

        if self.prague_active(header):
            revision = REVISION_PRAGUE
        elif cancun:
            revision = REVISION_CANCUN
        else:
            revision = REVISION_SHANGHAI
        env = Environment(
            state=state,
            origin=sender,
            coinbase=header.fee_recipient,
            block_number=header.block_number,
            gas_limit=header.gas_limit,
            gas_price=gas_price,
            timestamp=header.timestamp,
            prev_randao=header.prev_randao,
            base_fee=base_fee,
            chain_id=self.chain_id,
            block_hash_fn=self.fork.get_block_hash,
            revision=revision,
            blob_hashes=(
                tx.blob_versioned_hashes if isinstance(tx, BlobTx) else ()
            ),
            blob_base_fee=blob_fee_rate,
        )

        # buy gas, bump nonce (reference: blockchain.zig:266-301); the blob
        # fee (EIP-4844) is burned up front at the BLOCK's blob base fee and
        # never refunded — it is not execution gas
        state.sub_balance(sender, tx.gas_limit * gas_price)
        if isinstance(tx, BlobTx):
            state.sub_balance(sender, tx.blob_gas() * blob_fee_rate)
        state.increment_nonce(sender)

        # EIP-2929 warm-set prefill incl. EIP-3651 warm coinbase
        # (reference: blockchain.zig:293-301, params.zig:19-29)
        state.access_address(sender)
        state.access_address(header.fee_recipient)
        for addr in precompile_addresses(revision):
            state.access_address(addr)
        if tx.to is not None:
            state.access_address(tx.to)
        for addr, keys in access_list_of(tx):
            state.access_address(addr)
            for key in keys:
                state.access_storage_key(addr, int.from_bytes(key, "big"))

        intrinsic = G.intrinsic_gas(
            tx.data, tx.to is None, access_list_of(tx),
            len(tx.data) if tx.to is None else 0,
            n_authorizations=len(authorization_list_of(tx)),
        )
        exec_gas = tx.gas_limit - intrinsic

        # EIP-7702 authorization processing: after the sender nonce bump,
        # before execution. Tuple-level failures skip the tuple (the tx
        # stays valid); auth refunds survive a reverted execution because
        # the delegations themselves do (they are tx-level state, not part
        # of the message frame's journal scope).
        auth_refund = self._apply_authorizations(tx, state)

        if revision >= REVISION_PRAGUE and tx.to is not None:
            # EIP-7702: a delegated destination's delegate is warmed for
            # free at the tx top level (nested CALLs pay for it at the
            # calling instruction instead). After auth processing — this
            # very tx may have just installed the delegation on tx.to.
            to_code = state.get_code(tx.to)
            if G.is_delegation_designator(to_code):
                state.access_address(G.delegation_target(to_code))

        evm = Evm(env, self._vm_host)
        msg = Message(
            caller=sender,
            target=tx.to,
            value=tx.value,
            data=tx.data,
            gas=exec_gas,
        )
        result = evm.execute_message(msg)

        # refunds (reference: blockchain.zig:312-331; EIP-3529 quotient 5).
        # EIP-7702 auth refunds apply even when execution reverted — the
        # delegations they correspond to were still installed
        gas_used = tx.gas_limit - result.gas_left
        counter = (state.refund if result.success else 0) + auth_refund
        refund = min(counter, gas_used // G.REFUND_QUOTIENT)
        gas_used -= refund
        if revision >= REVISION_PRAGUE:
            # EIP-7623: calldata-heavy txs pay at least the floor price
            # (applied after refunds; check_transaction already rejected
            # gas limits below the floor)
            gas_used = max(gas_used, G.calldata_floor_gas(tx.data))
        state.add_balance(sender, (tx.gas_limit - gas_used) * gas_price)

        # coinbase priority fee (reference: blockchain.zig:325-331)
        state.touch(header.fee_recipient)
        if priority_fee * gas_used:
            state.add_balance(header.fee_recipient, priority_fee * gas_used)

        # selfdestructs delete accounts wholesale
        for addr in state.selfdestructs:
            state.delete_account(addr)

        # EIP-158 (reference: blockchain.zig:334-341 via statedb)
        state.destroy_touched_empty()

        logs = list(state.logs) if result.success else []
        return gas_used, logs, result.success


# ---------------------------------------------------------------------------


def calculate_base_fee(parent_gas_limit: int, parent_gas_used: int, parent_base_fee: int) -> int:
    """EIP-1559 recurrence (reference: blockchain.zig:107-123)."""
    parent_gas_target = parent_gas_limit // ELASTICITY_MULTIPLIER
    if parent_gas_used == parent_gas_target:
        return parent_base_fee
    if parent_gas_used > parent_gas_target:
        gas_used_delta = parent_gas_used - parent_gas_target
        delta = max(
            parent_base_fee * gas_used_delta // parent_gas_target // BASE_FEE_MAX_CHANGE_DENOMINATOR,
            1,
        )
        return parent_base_fee + delta
    gas_used_delta = parent_gas_target - parent_gas_used
    delta = (
        parent_base_fee * gas_used_delta // parent_gas_target // BASE_FEE_MAX_CHANGE_DENOMINATOR
    )
    return parent_base_fee - delta


def check_gas_limit(gas_limit: int, parent_gas_limit: int) -> None:
    """(reference: blockchain.zig:140-145)"""
    max_delta = parent_gas_limit // GAS_LIMIT_ADJUSTMENT_FACTOR
    if gas_limit >= parent_gas_limit + max_delta:
        raise BlockError("gas limit increased too much")
    if gas_limit <= parent_gas_limit - max_delta:
        raise BlockError("gas limit decreased too much")
    if gas_limit < GAS_LIMIT_MINIMUM:
        raise BlockError("gas limit below minimum")
