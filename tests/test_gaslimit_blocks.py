"""Blocks at the gas limit (PR 44): the benchmark's `serve-mpt-gaslimit-1chip`
deployment at test size. A chain of blocks FULL of plain transfers between
distinct accounts (1,428 x 21,000 = 29,988,000 of 30,000,000 gas) from the
benchmark's reference copy `benchmarks/reference/chain_basefee.py`, which
prices every transaction at its block's base fee plus a tip because such
blocks raise the base fee an eighth a block: the reference alone first (what
senders lose is what recipients, the coinbase and the burn gain), then
through the real server and scheduler (the cpu crypto backend: what is
pinned here is the chain's rules on the served path, not a device program;
`tests/test_lane_ladders.py` pins the rung such a block's verdict takes).
"""

from __future__ import annotations

import json
import sys
import urllib.request
from dataclasses import replace
from pathlib import Path

import pytest

from phant_tpu.utils.trace import _labels_key, metrics

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
N_BLOCKS, N_TXS, GAS = 5, 1428, 29_988_000
PARAMS = dict(genesis_log2=12, sender_pool=2048, contracts=4, zipf_s=0,
              transfers_per_block=N_TXS, calls_per_block=0, cold_recipient_share=1.0)  # fmt: skip


@pytest.fixture(scope="module")
def bench_path():
    sys.path.insert(0, str(BENCH))
    yield
    sys.path.remove(str(BENCH))


@pytest.fixture(scope="module")
def chain(bench_path, tmp_path_factory):
    from reference import keccak
    from reference.chain_basefee import Chain

    keccak.load(tmp_path_factory.mktemp("keccak"))
    chain = Chain(4400000044, PARAMS)
    chain.extend(N_BLOCKS)
    return chain


# -- the reference copy alone ---------------------------------------------------


def test_every_block_is_full_and_the_base_fee_climbs_past_a_gwei(chain):
    from reference.chain_basefee import TIP

    fees = [b.header.base_fee for b in chain.blocks]
    assert fees[:3] == [875_000_000, 984_287_500, 1_107_225_008]  # EIP-1559, +12.49 % a block
    assert fees == sorted(fees) and fees[3] > 10**9
    for block in chain.blocks:
        assert block.header.gas_used == GAS and len(block.txs) == N_TXS
        assert len({t.sender for t in block.txs}) == len({t.to for t in block.txs}) == N_TXS
        assert {t.gas_price for t in block.txs} == {block.header.base_fee + TIP}
        assert all(t.value == 1 and not t.data for t in block.txs)


@pytest.mark.parametrize("i", range(N_BLOCKS))
def test_what_senders_lose_recipients_the_coinbase_and_the_burn_gain(chain, i):
    from reference.chain import COINBASE, State
    from reference.chain_basefee import TIP

    block = chain.blocks[i]
    before = State(chain.db, block.pre_root, {})
    after = State(chain.db, block.header.state_root, {})
    balance = lambda state, addr: (state.account(addr) or [0, 0])[1]  # noqa: E731
    gained = lambda addr: balance(after, addr) - balance(before, addr)  # noqa: E731
    lost = -sum(gained(t.sender) for t in block.txs)
    received = sum(gained(t.to) for t in block.txs)
    tipped = gained(COINBASE)
    burnt = block.header.gas_used * block.header.base_fee
    assert received == N_TXS and tipped == GAS * TIP
    assert lost == received + tipped + burnt
    for t in block.txs:
        assert after.account(t.sender)[0] == before.account(t.sender)[0] + 1


def test_a_transaction_under_the_base_fee_is_the_generators_fault(chain):
    from reference.chain import State
    from reference.chain_basefee import apply_transfer

    block = chain.blocks[3]
    state = State(dict(chain.db), block.pre_root, {})
    cheap = replace(block.txs[0], gas_price=10**9)
    with pytest.raises(ValueError, match="under the base fee"):
        apply_transfer(state, cheap, block.header.base_fee)


# -- through the server ----------------------------------------------------------


def _at_a_gwei(chain, block):
    """`block` with every transaction priced at the constant 1 gwei of
    `reference/chain.py` and signed anew, the transactions root and the
    block hash re-derived around them: wrong in its fee, before anything."""
    from reference.chain import CHAIN_ID, ordered_root

    signer_of = dict(zip(chain.pool, chain.signers))
    txs = []
    for t in block.txs:
        bare = replace(t, gas_price=10**9, v=0, r=0, s=0)
        r, s, recid = signer_of[t.sender].sign(bare.sighash())
        txs.append(replace(bare, v=35 + 2 * CHAIN_ID + recid, r=r, s=s))
    header = replace(block.header, transactions_root=ordered_root([t.encode() for t in txs]))
    return block.body(99, header=header, txs=txs)


@pytest.fixture(scope="module")
def served(chain):
    """Every block once in chain order through the server the CLI builds,
    then the last block at 1 gwei and altered in the benchmark's five ways."""
    from drivers.serve import PROBES
    from harness.clients import closed_loop

    from phant_tpu.__main__ import build_parser, build_server
    from phant_tpu.crypto import kzg

    public = kzg.public_network()  # a mainnet server names it for the whole process
    bodies = {i: b.body(i + 1) for i, b in enumerate(chain.blocks)}
    last = chain.blocks[-1]
    bodies[-1] = _at_a_gwei(chain, last)
    for j, (what, *_rest) in enumerate(PROBES):
        bodies[-j - 2] = last.body_altered(what, j + 1)
    counted = _labels_key("engine_api.request_body_bytes", {})
    before = metrics.snapshot()["counters"].get(counted, 0)
    argv = ["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0"]
    server = build_server(build_parser().parse_args(argv))
    server.serve_in_background()
    try:
        traffic = {"think_ms": 0}
        *_, records = closed_loop.run("127.0.0.1", server.port, bodies, [list(range(N_BLOCKS))], None, traffic)
        read = metrics.snapshot()["counters"].get(counted, 0) - before
        *_, refused = closed_loop.run("127.0.0.1", server.port, bodies, [sorted(k for k in bodies if k < 0)], None, traffic)
        with urllib.request.urlopen(f"http://127.0.0.1:{server.port}/metrics", timeout=30) as r:
            text = r.read().decode()
    finally:
        server.shutdown()
        kzg.set_public_network(public)
    return {"records": records, "refused": {r[1]: r for r in refused}, "bodies": bodies, "read": read, "metrics": text}


def test_every_full_block_is_valid_with_the_references_root(chain, served):
    from reference.chain import hx

    assert [r[1] for r in served["records"]] == list(range(N_BLOCKS))
    for _who, idx, _t0, _t1, code, reply in served["records"]:
        result = json.loads(reply)["result"]
        assert code == 200 and result["status"] == "VALID", (idx, reply[:300])
        assert result["stateRoot"] == hx(chain.blocks[idx].header.state_root), idx


def test_a_block_left_at_one_gwei_is_refused_for_its_fee(chain, served):
    assert chain.blocks[-1].header.base_fee > 10**9
    _who, _k, _t0, _t1, code, reply = served["refused"][-1]
    result = json.loads(reply)["result"]
    assert code == 200 and result["status"] == "INVALID", reply[:300]
    assert "gas price below base fee" in result["validationError"].lower()


@pytest.mark.parametrize("j", range(5))
def test_each_altered_full_block_is_refused_for_its_own_reason(served, j):
    from drivers.serve import PROBES

    what, _number, any_of, none_of = PROBES[j]
    _who, _k, _t0, _t1, code, reply = served["refused"][-j - 2]
    result = json.loads(reply)["result"]
    err = (result.get("validationError") or "").lower()
    assert code == 200 and result["status"] == "INVALID", (what, reply[:300])
    assert not any_of or any(w in err for w in any_of), (what, err)
    assert not any(w in err for w in none_of), (what, err)


def test_the_front_end_counts_the_bytes_of_the_bodies_it_read(served):
    """`engine_api.request_body_bytes`: the five bodies' lengths, exactly,
    and the family on /metrics under its help line."""
    from phant_tpu.utils.trace import METRIC_HELP

    assert served["read"] == sum(len(served["bodies"][i]) for i in range(N_BLOCKS))
    assert "engine_api.request_body_bytes" in METRIC_HELP
    assert "phant_engine_api_request_body_bytes_total " in served["metrics"]
