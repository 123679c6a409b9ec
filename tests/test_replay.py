"""run_blocks pipelined import: cross-block sender prefetch on the tpu
crypto backend must agree exactly with the serial cpu import (reference
import loop: src/blockchain/blockchain.zig:61-96; the prefetch pipeline is
this framework's addition)."""

from dataclasses import replace

import pytest

from phant_tpu.backend import set_crypto_backend
from phant_tpu.blockchain.chain import BlockError, Blockchain
from phant_tpu.replay.fixture import build_synthetic_chain
from phant_tpu.types.block import Block


def _fresh_chain(genesis, fresh_state):
    return Blockchain(1, fresh_state(), genesis, verify_state_root=False)


@pytest.fixture(scope="module")
def small_chain():
    fix = build_synthetic_chain(n_blocks=12, txs_per_block=3)
    return fix.genesis, fix.blocks, fix.fresh_state


def test_run_blocks_matches_serial(small_chain, monkeypatch):
    genesis, blocks, fresh_state = small_chain
    monkeypatch.setenv("PHANT_TPU_PREFETCH_SIGS", "8")  # force several windows

    serial = _fresh_chain(genesis, fresh_state)
    want = [serial.run_block(b) for b in blocks]

    set_crypto_backend("tpu")
    try:
        piped = _fresh_chain(genesis, fresh_state)
        got = piped.run_blocks(blocks)
    finally:
        set_crypto_backend("cpu")
    assert [r.gas_used for r in got] == [r.gas_used for r in want]
    assert [r.receipts for r in got] == [r.receipts for r in want]
    assert piped.parent_header == serial.parent_header


def test_run_blocks_invalid_signature_attributed(small_chain, monkeypatch):
    """A corrupt signature prefetched several blocks ahead must fail when
    ITS block runs, with earlier blocks already imported."""
    genesis, blocks, fresh_state = small_chain
    monkeypatch.setenv("PHANT_TPU_PREFETCH_SIGS", "6")
    bad_idx = 7
    bad_tx = replace(blocks[bad_idx].transactions[1], r=12345)
    tampered = list(blocks)
    tampered[bad_idx] = Block(
        header=blocks[bad_idx].header,
        transactions=(
            blocks[bad_idx].transactions[0],
            bad_tx,
            *blocks[bad_idx].transactions[2:],
        ),
        withdrawals=blocks[bad_idx].withdrawals,
    )
    set_crypto_backend("tpu")
    try:
        chain = _fresh_chain(genesis, fresh_state)
        with pytest.raises(BlockError):
            chain.run_blocks(tampered)
    finally:
        set_crypto_backend("cpu")
    # everything before the bad block landed
    assert chain.parent_header.block_number == bad_idx


def test_run_blocks_cpu_path(small_chain):
    genesis, blocks, fresh_state = small_chain
    chain = _fresh_chain(genesis, fresh_state)
    results = chain.run_blocks(blocks)
    assert len(results) == len(blocks)
    assert chain.parent_header == blocks[-1].header


def test_run_blocks_survives_device_loss(small_chain, monkeypatch):
    """Fault injection (SURVEY §5): the device dying mid-replay (device
    lost / preemption) must degrade to CPU recovery, not sink the import."""
    import phant_tpu.ops.secp256k1_jax as secp_jax

    genesis, blocks, fresh_state = small_chain
    monkeypatch.setenv("PHANT_TPU_PREFETCH_SIGS", "8")

    calls = {"n": 0}
    real = secp_jax.ecrecover_batch_async

    def flaky(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 2:  # second window's dispatch resolves to a crash
            return lambda: (_ for _ in ()).throw(RuntimeError("device lost"))
        if calls["n"] == 3:  # third window dies while STAGING the dispatch
            raise RuntimeError("device lost at dispatch")
        return real(*args, **kwargs)

    monkeypatch.setattr(secp_jax, "ecrecover_batch_async", flaky)
    set_crypto_backend("tpu")
    try:
        chain = _fresh_chain(genesis, fresh_state)
        results = chain.run_blocks(blocks)
    finally:
        set_crypto_backend("cpu")
    assert len(results) == len(blocks)
    assert chain.parent_header == blocks[-1].header
    assert calls["n"] >= 2  # the device path was genuinely exercised + failed
