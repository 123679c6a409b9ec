"""The generator: the same chain from the same seed, another from another;
every block VALID on the program's plain host path (cpu crypto backend, host
trie walk), with the reference's root; each altered body refused for its
own reason."""

import json

import pytest

from reference import keccak
from reference.chain import Chain, hx

PARAMS = dict(
    genesis_log2=10, sender_pool=300, contracts=4, zipf_s=1.0, transfers_per_block=150,
    calls_per_block=75, cold_recipient_share=0.5, slots_per_contract=64,
)


@pytest.fixture(scope="module")
def chains(tmp_path_factory):
    keccak.load(tmp_path_factory.mktemp("refkeccak"))
    out = {}
    for seed in (1, 1, 3000000019):
        c = Chain(seed, dict(PARAMS))
        c.extend(3)
        out.setdefault(seed, []).append(c)
    return out


def test_same_seed_same_chain(chains):
    a, b = chains[1]
    assert [x.body(1) for x in a.blocks] == [x.body(1) for x in b.blocks]
    assert a.genesis.hash() == b.genesis.hash()


def test_other_seed_other_chain(chains):
    (a, _), (c,) = chains[1], chains[3000000019]
    assert a.genesis.state_root != c.genesis.state_root
    assert a.blocks[0].header.hash() != c.blocks[0].header.hash()


def test_blocks_fill_their_gas(chains):
    for b in chains[1][0].blocks:
        assert len(b.txs) == 225 and 5_000_000 < b.header.gas_used < 8_000_000


@pytest.fixture(scope="module")
def handle():
    from phant_tpu.__main__ import build_parser, build_server
    from phant_tpu.engine_api import handle_request

    server = build_server(
        build_parser().parse_args(["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0"])
    )
    server.serve_in_background()
    yield lambda body: handle_request(server.blockchain, json.loads(body))[1]["result"]
    server.shutdown()


def test_program_host_path_agrees(chains, handle):
    for seed in (1, 3000000019):
        for i, b in enumerate(chains[seed][0].blocks):
            r = handle(b.body(i))
            assert r["status"] == "VALID", r
            assert r["stateRoot"] == hx(b.header.state_root)
    b = chains[1][0].blocks[1]
    bad = handle(b.body_altered("witness", 7))
    assert bad["status"] == "INVALID" and "witness" in bad["validationError"]
    bad = handle(b.body_altered("signature", 8))
    assert bad["status"] == "INVALID" and "blockHash" not in bad["validationError"]
    for what, word in (("state_root", "state root"), ("receipts_root", "receipt"), ("gas_used", "gas")):
        bad = handle(b.body_altered(what, 9))
        assert bad["status"] == "INVALID" and word in bad["validationError"].lower(), (what, bad)
        assert "blockHash" not in bad["validationError"]
