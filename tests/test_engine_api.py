"""Engine API + config + CLI tests.

Mirrors the reference's engine-API round-trip test (reference:
src/engine_api/engine_api.zig:87-134): build a real `engine_newPayloadV2`
JSON-RPC request, decode it through the hex intermediate, and drive it
through the handler against a fresh Blockchain — plus an actual HTTP
round-trip (reference serves via httpz, main.zig:143-149) and chain-config
parity checks (reference: src/config/config.zig).
"""

from __future__ import annotations

import json
import threading
import urllib.request

import pytest

from phant_tpu.blockchain.chain import Blockchain
from phant_tpu.config import (
    ChainConfig,
    ChainId,
    DeprecatedNetwork,
    UnsupportedNetwork,
)
from phant_tpu.engine_api import (
    ExecutionPayload,
    get_client_version_v1_handler,
    handle_request,
    new_payload_v2_handler,
    payload_from_json,
)
from phant_tpu.engine_api.server import EngineAPIServer
from phant_tpu.mpt.mpt import ordered_trie_root
from phant_tpu.state.statedb import StateDB
from phant_tpu.types.block import BlockHeader
from phant_tpu.types.receipt import logs_bloom
from phant_tpu.utils.hexutils import bytes_to_hex
from phant_tpu.__main__ import build_parser, make_genesis_parent_header


# ---------------------------------------------------------------------------
# config


def test_mainnet_chainspec():
    cfg = ChainConfig.from_chain_id(ChainId.Mainnet)
    assert cfg.ChainName == "mainnet"
    assert cfg.chainId == 1
    assert cfg.londonBlock == 12965000
    assert cfg.shanghaiTime == 1681338455
    assert cfg.terminalTotalDifficultyPassed is True


def test_sepolia_and_errors():
    cfg = ChainConfig.from_chain_id(ChainId.Sepolia)
    assert cfg.chainId == int(ChainId.Sepolia)
    assert cfg.londonBlock == 0
    with pytest.raises(DeprecatedNetwork):
        ChainConfig.from_chain_id(ChainId.Goerli)
    with pytest.raises(UnsupportedNetwork):
        ChainConfig.from_chain_id(ChainId.Holesky)


def test_fork_at():
    cfg = ChainConfig.from_chain_id(ChainId.Mainnet)
    assert cfg.fork_at(0, 0) == "frontier"
    assert cfg.fork_at(1_150_000, 0) == "homestead"
    assert cfg.fork_at(15_537_394, 1663224162) == "gray_glacier"
    assert cfg.fork_at(17_034_870, 1681338455) == "shanghai"
    assert cfg.is_shanghai(1681338455)
    assert not cfg.is_shanghai(1681338454)


def test_config_dump_and_unknown_fields():
    cfg = ChainConfig.from_chainspec(
        json.dumps({"ChainName": "t", "chainId": 7, "londonBlock": 5, "bogus": 1})
    )
    assert cfg.chainId == 7 and cfg.londonBlock == 5
    table = ChainConfig.from_chain_id(ChainId.Mainnet).dump()
    assert "london" in table and "12965000" in table and "shanghai" in table


def test_cli_parser_defaults():
    args = build_parser().parse_args([])
    assert args.engine_api_port == 8551
    assert args.network_id == 1
    assert args.crypto_backend == "cpu"
    args = build_parser().parse_args(["-p", "9999", "--crypto_backend", "tpu"])
    assert args.engine_api_port == 9999 and args.crypto_backend == "tpu"


# ---------------------------------------------------------------------------
# engine API


def _fresh_chain() -> Blockchain:
    """Blockchain over the reference's zero parent (main.zig:120-141)."""
    return Blockchain(
        chain_id=int(ChainId.Testing),
        state=StateDB(),
        parent_header=make_genesis_parent_header(),
        verify_state_root=False,
    )


def _serve(server) -> None:
    """`serve_in_background` with the accept loop's poll cut from 0.5 s to
    10 ms: `shutdown` waits out one poll, which tests nothing."""
    threading.Thread(
        target=server._server.serve_forever, args=(0.01,), daemon=True
    ).start()


def _valid_payload_json() -> dict:
    """A consensus-valid empty-tx payload with one withdrawal on top of the
    zero parent, in Engine API JSON form."""
    parent = make_genesis_parent_header()
    wd = {
        "index": "0x0",
        "validatorIndex": "0x7",
        "address": "0x" + "aa" * 20,
        "amount": "0x3b9aca00",  # 1 ETH in gwei
    }
    return {
        "parentHash": bytes_to_hex(parent.hash()),
        "feeRecipient": "0x" + "bb" * 20,
        "stateRoot": "0x" + "00" * 32,
        "receiptsRoot": bytes_to_hex(ordered_trie_root([])),
        "logsBloom": bytes_to_hex(logs_bloom([])),
        "prevRandao": "0x" + "00" * 32,
        "blockNumber": "0x1",
        "gasLimit": hex(parent.gas_limit),
        "gasUsed": "0x0",
        "timestamp": "0x1",
        "extraData": "0x",
        "baseFeePerGas": "0x7",
        "blockHash": "0x" + "cc" * 32,  # patched to the real hash below
        "transactions": [],
        "withdrawals": [wd],
    }


def _with_real_block_hash(params: dict) -> dict:
    """Fill blockHash = keccak(rlp(header)) as a real CL client would."""
    computed = payload_from_json(params).to_block().header.hash()
    return {**params, "blockHash": bytes_to_hex(computed)}


def test_payload_from_json_roundtrip():
    payload = payload_from_json(_valid_payload_json())
    assert isinstance(payload, ExecutionPayload)
    assert payload.block_number == 1
    assert payload.base_fee_per_gas == 7
    assert payload.withdrawals is not None and len(payload.withdrawals) == 1
    assert payload.withdrawals[0].amount == 0x3B9ACA00
    block = payload.to_block()
    assert block.header.transactions_root == ordered_trie_root([])
    assert block.header.withdrawals_root == ordered_trie_root(
        [payload.withdrawals[0].encode()]
    )


def test_new_payload_v2_valid_applies_withdrawal():
    chain = _fresh_chain()
    payload = payload_from_json(_with_real_block_hash(_valid_payload_json()))
    status = new_payload_v2_handler(chain, payload)
    assert status.status == "VALID", status.validation_error
    assert status.latest_valid_hash == payload.block_hash
    acct = chain.state.get_account(b"\xaa" * 20)
    assert acct is not None and acct.balance == 10**18


def test_new_payload_v2_rejects_wrong_block_hash():
    """Engine API spec: blockHash must equal keccak(rlp(header))."""
    chain = _fresh_chain()
    payload = payload_from_json(_valid_payload_json())  # bogus 0xcc..cc hash
    status = new_payload_v2_handler(chain, payload)
    assert status.status == "INVALID"
    assert "blockHash" in (status.validation_error or "")
    # and nothing was executed
    assert chain.state.get_account(b"\xaa" * 20) is None
    assert chain.parent_header.block_number == 0


def test_new_payload_v2_invalid_base_fee():
    chain = _fresh_chain()
    bad = _valid_payload_json()
    bad["baseFeePerGas"] = "0x8"
    status = new_payload_v2_handler(chain, payload_from_json(_with_real_block_hash(bad)))
    assert status.status == "INVALID"
    assert "base fee" in (status.validation_error or "")


def test_new_payload_v2_invalid_rolls_back_state():
    """An INVALID payload leaves no trace: the withdrawal credited during
    apply_body must be rolled back when a post-execution check fails."""
    chain = _fresh_chain()
    bad = _valid_payload_json()
    bad["gasUsed"] = "0x5208"  # header claims gas that was never consumed
    status = new_payload_v2_handler(chain, payload_from_json(_with_real_block_hash(bad)))
    assert status.status == "INVALID"
    assert chain.state.get_account(b"\xaa" * 20) is None
    assert chain.parent_header.block_number == 0
    # the same payload, corrected, then applies exactly once
    good = payload_from_json(_with_real_block_hash(_valid_payload_json()))
    assert new_payload_v2_handler(chain, good).status == "VALID"
    assert chain.state.get_account(b"\xaa" * 20).balance == 10**18


def test_fork_for_config():
    from phant_tpu.blockchain.fork import (
        CancunFork,
        FrontierFork,
        PragueFork,
        fork_for,
    )

    cfg = ChainConfig.from_chain_id(ChainId.Mainnet)
    state = StateDB()
    assert isinstance(fork_for(cfg, state, 0, 0), FrontierFork)
    assert isinstance(fork_for(cfg, state, 0, cfg.shanghaiTime), FrontierFork)
    assert isinstance(fork_for(cfg, state, 0, cfg.cancunTime), CancunFork)
    # Prague is advertised since r5 (7702/7623/2935/2537/7685 executable);
    # pre-Prague Cancun timestamps still dispatch CancunFork
    assert cfg.pragueTime is not None
    assert isinstance(fork_for(cfg, state, 0, cfg.pragueTime - 1), CancunFork)
    assert isinstance(fork_for(cfg, state, 0, cfg.pragueTime), PragueFork)


def test_crypto_backend_dispatch():
    """--crypto_backend=tpu routes keccak256_batch to the JAX kernel and
    agrees bit-for-bit with the CPU path."""
    from phant_tpu.backend import crypto_backend, set_crypto_backend
    from phant_tpu.crypto.keccak import keccak256_batch, keccak256_batch_cpu

    payloads = [bytes([i]) * (i + 1) for i in range(8)]
    cpu = keccak256_batch_cpu(payloads)
    assert keccak256_batch(payloads) == cpu  # default backend is cpu
    set_crypto_backend("tpu")
    try:
        assert crypto_backend() == "tpu"
        assert keccak256_batch(payloads) == cpu
    finally:
        set_crypto_backend("cpu")
    with pytest.raises(ValueError):
        set_crypto_backend("gpu")


def test_handle_request_dispatch():
    chain = _fresh_chain()
    req = {
        "jsonrpc": "2.0",
        "id": 1,
        "method": "engine_newPayloadV2",
        "params": [_with_real_block_hash(_valid_payload_json())],
    }
    code, resp = handle_request(chain, req)
    assert code == 200 and resp["result"]["status"] == "VALID"

    # known-but-unimplemented -> HTTP 500 (reference: main.zig:72)
    code, resp = handle_request(chain, {"id": 2, "method": "engine_getPayloadV2"})
    assert code == 500 and "error" in resp
    # unknown method -> JSON-RPC method-not-found
    code, resp = handle_request(chain, {"id": 3, "method": "eth_bogus"})
    assert code == 200 and resp["error"]["code"] == -32601


def test_client_version():
    ver = get_client_version_v1_handler()
    assert ver.code == "PH"
    assert ver.version.startswith("0.0.1")
    assert ver.string().startswith("PH-")
    chain = _fresh_chain()
    code, resp = handle_request(
        chain, {"id": 9, "method": "engine_getClientVersionV1", "params": []}
    )
    assert code == 200 and resp["result"][0]["code"] == "PH"


def test_witness_engine_stats_rpc():
    chain = _fresh_chain()
    code, resp = handle_request(
        chain, {"id": 4, "method": "phant_witnessEngineStats", "params": []}
    )
    assert code == 200
    st = resp["result"]
    for key in ("hashed", "hits", "evictions", "hit_rate", "interned_nodes"):
        assert key in st, st
    # the shared engine is live: verifying a witness moves the counters
    from phant_tpu import rlp
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.mpt.mpt import Trie
    from phant_tpu.mpt.proof import generate_proof
    from phant_tpu.stateless import verify_witness_nodes

    t = Trie()
    for i in range(32):
        t.put(keccak256(bytes([i])), rlp.encode(rlp.encode_uint(i + 1)))
    nodes = list(dict.fromkeys(generate_proof(t, keccak256(bytes([0])))))
    assert verify_witness_nodes(t.root_hash(), nodes)
    _code, resp2 = handle_request(
        chain, {"id": 5, "method": "phant_witnessEngineStats", "params": []}
    )
    assert resp2["result"]["hashed"] >= st["hashed"] + len(nodes) - 1


def test_http_server_roundtrip():
    """Full HTTP POST round-trip (reference: main.zig:143-149 via httpz)."""
    chain = _fresh_chain()
    server = EngineAPIServer(chain, host="127.0.0.1", port=0)
    _serve(server)
    try:
        body = json.dumps(
            {
                "jsonrpc": "2.0",
                "id": 1,
                "method": "engine_newPayloadV2",
                "params": [_with_real_block_hash(_valid_payload_json())],
            }
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            out = json.loads(resp.read())
        assert out["result"]["status"] == "VALID"
        assert chain.parent_header.block_number == 1

        # JSON-RPC batch (array body) -> -32600, connection stays healthy
        batch = json.dumps([{"id": 2, "method": "engine_getClientVersionV1"}]).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/",
            data=batch,
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(req, timeout=10)
        assert exc_info.value.code == 400
        assert json.loads(exc_info.value.read())["error"]["code"] == -32600
    finally:
        server.shutdown()


def test_metrics_and_healthz_endpoints():
    """GET /metrics serves Prometheus text exposition and GET /healthz a
    liveness probe from the SAME EngineAPIServer; the request counter and
    latency histogram move after a newPayload POST."""
    from phant_tpu.utils.trace import metrics

    metrics.reset()
    chain = _fresh_chain()
    server = EngineAPIServer(chain, host="127.0.0.1", port=0)
    _serve(server)
    try:
        base = f"http://127.0.0.1:{server.port}"
        health = json.loads(urllib.request.urlopen(base + "/healthz", timeout=10).read())
        assert health["status"] == "ok"
        assert "uptime_s" in health and "version" in health

        def scrape() -> str:
            with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
                assert resp.headers["Content-Type"].startswith("text/plain")
                return resp.read().decode()

        before = scrape()
        assert (
            'phant_engine_api_requests_total{method="engine_newPayloadV2"}'
            not in before
        )

        body = json.dumps(
            {
                "jsonrpc": "2.0",
                "id": 1,
                "method": "engine_newPayloadV2",
                "params": [_with_real_block_hash(_valid_payload_json())],
            }
        ).encode()
        req = urllib.request.Request(
            base + "/", data=body, headers={"Content-Type": "application/json"}
        )
        out = json.loads(urllib.request.urlopen(req, timeout=10).read())
        assert out["result"]["status"] == "VALID"

        # the latency histogram is observed when the handler has WRITTEN
        # the reply, which the client may read first: wait for it, do not
        # race it (PERF.md section 7 (f), PR 25)
        import time

        give_up = time.monotonic() + 10
        after = scrape()
        while (
            "phant_engine_api_request_seconds_count 1" not in after
            and time.monotonic() < give_up
        ):
            time.sleep(0.01)
            after = scrape()
        assert (
            'phant_engine_api_requests_total{method="engine_newPayloadV2"} 1'
            in after
        )
        # the POST was latency-histogrammed and help/type lines are present
        assert "# TYPE phant_engine_api_request_seconds histogram" in after
        assert "phant_engine_api_request_seconds_count 1" in after
        assert "# HELP phant_engine_api_requests_total" in after
        # unknown GET paths 404 without killing the server
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert exc_info.value.code == 404
        assert json.loads(urllib.request.urlopen(base + "/healthz", timeout=10).read())[
            "status"
        ] == "ok"
    finally:
        server.shutdown()


def test_standalone_metrics_server():
    """`--metrics` surface: serve_metrics binds /metrics + /healthz on a
    dedicated port with no Engine API attached."""
    from phant_tpu.engine_api.server import serve_metrics

    srv = serve_metrics(host="127.0.0.1", port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(base + "/metrics", timeout=10).read().decode()
        assert text.endswith("\n")
        health = json.loads(urllib.request.urlopen(base + "/healthz", timeout=10).read())
        assert health["status"] == "ok"
    finally:
        srv.shutdown()


def test_cli_observability_flags():
    args = build_parser().parse_args(
        ["--metrics", "--metrics-port", "9777", "--trace-logdir", "/tmp/tr"]
    )
    assert args.metrics and args.metrics_port == 9777
    assert args.trace_logdir == "/tmp/tr"
    args = build_parser().parse_args([])
    assert not args.metrics and args.trace_logdir is None


def test_newpayload_v3_cancun_roundtrip():
    """engine_newPayloadV3: the side-channel parentBeaconBlockRoot must fold
    into the header (it is part of blockHash), the expected blob-hash list
    must be checked, and a valid Cancun payload applies."""
    from dataclasses import replace

    chain = _fresh_chain()
    params = _valid_payload_json()
    params["blobGasUsed"] = "0x0"
    params["excessBlobGas"] = "0x0"
    beacon_root = b"\x5b" * 32
    header = replace(
        payload_from_json(params).to_block().header,
        parent_beacon_block_root=beacon_root,
    )
    params["blockHash"] = bytes_to_hex(header.hash())
    req = {
        "jsonrpc": "2.0",
        "id": 9,
        "method": "engine_newPayloadV3",
        "params": [params, [], bytes_to_hex(beacon_root)],
    }
    http, body = handle_request(chain, req)
    assert http == 200, body
    assert body["result"]["status"] == "VALID", body
    assert chain.parent_header.parent_beacon_block_root == beacon_root
    assert chain.parent_header.excess_blob_gas == 0

    # a wrong expected-blob-hash list must be INVALID before execution
    chain2 = _fresh_chain()
    req_bad = {**req, "params": [params, ["0x" + "01" * 32], bytes_to_hex(beacon_root)]}
    _http, body2 = handle_request(chain2, req_bad)
    assert body2["result"]["status"] == "INVALID"
    assert "blob versioned hashes" in body2["result"]["validationError"]


def test_newpayload_v4_executionrequests_validation():
    """engine_newPayloadV4: the executionRequests side channel must be
    strictly type-ascending with non-empty data, and its hash folds into
    the header before the V3/V2 path runs (a mismatched blockHash proves
    the fold happened — the same payload bytes hash differently once
    requests_hash is set)."""
    chain = _fresh_chain()
    params = _valid_payload_json()
    params["blobGasUsed"] = "0x0"
    params["excessBlobGas"] = "0x0"
    beacon = bytes_to_hex(b"\x5b" * 32)
    base = {"jsonrpc": "2.0", "id": 11, "method": "engine_newPayloadV4"}

    # misordered types
    req = {**base, "params": [params, [], beacon, ["0x01aa", "0x00bb"]]}
    _http, body = handle_request(chain, req)
    assert body["result"]["status"] == "INVALID"
    assert "type-ascending" in body["result"]["validationError"]

    # an item with no data after the type byte
    req = {**base, "params": [params, [], beacon, ["0x00"]]}
    _http, body = handle_request(chain, req)
    assert body["result"]["status"] == "INVALID"
    assert "without data" in body["result"]["validationError"]

    # well-formed requests fold their hash into the header: with the
    # payload's blockHash computed WITHOUT requests_hash (as a CL would
    # over these bytes), the fold — and only the fold — makes it mismatch
    params = _with_real_block_hash(params)
    req = {**base, "params": [params, [], beacon, ["0x00aa", "0x01bb"]]}
    _http, body = handle_request(chain, req)
    assert body["result"]["status"] == "INVALID"
    assert "blockHash mismatch" in body["result"]["validationError"]


def test_newpayload_fork_timestamp_rule_returns_38005():
    """Engine API 'Unsupported fork' rule: V3 serves exactly the Cancun
    window and V4 exactly Prague — a timestamp on either side of the
    window returns -38005 before any processing, in both directions."""
    from phant_tpu.config import ChainConfig
    from phant_tpu.engine_api import UNSUPPORTED_FORK_CODE

    cfg = ChainConfig(
        ChainName="forktest",
        chainId=int(ChainId.Testing),
        cancunTime=1000,
        pragueTime=2000,
        osakaTime=3000,
    )
    chain = Blockchain(
        chain_id=int(ChainId.Testing),
        state=StateDB(),
        parent_header=make_genesis_parent_header(),
        verify_state_root=False,
        config=cfg,
    )
    beacon = bytes_to_hex(b"\x5b" * 32)

    def v3_req(ts: int) -> dict:
        params = _valid_payload_json()
        params["timestamp"] = hex(ts)
        params["blobGasUsed"] = "0x0"
        params["excessBlobGas"] = "0x0"
        return {
            "jsonrpc": "2.0",
            "id": 21,
            "method": "engine_newPayloadV3",
            "params": [params, [], beacon],
        }

    def v4_req(ts: int) -> dict:
        req = v3_req(ts)
        return {**req, "method": "engine_newPayloadV4",
                "params": req["params"] + [[]]}

    # V3 below Cancun and at/after Prague: both directions unsupported
    for ts in (999, 2000):
        http, body = handle_request(chain, v3_req(ts))
        assert http == 200
        assert body["error"]["code"] == UNSUPPORTED_FORK_CODE, (ts, body)
        assert body["error"]["message"] == "Unsupported fork"
    # V3 inside the Cancun window processes normally (no -38005; this
    # payload's parent disagrees with the fork schedule, so execution may
    # report INVALID — the point is the fork gate let it through)
    _http, body = handle_request(chain, v3_req(1500))
    assert "result" in body, body

    # V4 below Prague and at/after Osaka: both directions unsupported
    for ts in (1500, 3000):
        http, body = handle_request(chain, v4_req(ts))
        assert http == 200
        assert body["error"]["code"] == UNSUPPORTED_FORK_CODE, (ts, body)
    _http, body = handle_request(chain, v4_req(2500))
    assert "result" in body, body

    # config-less fixture chains skip the rule entirely
    _http, body = handle_request(_fresh_chain(), v3_req(1))
    assert "error" not in body or body["error"]["code"] != UNSUPPORTED_FORK_CODE


def test_consensus_data_unavailable_propagates(evm_backend_cpu):
    """A Prague block calling the gated map-to-curve precompile must abort
    validation loudly (not fake a post-state) on BOTH EVM backends — on
    the native backend the exception crosses the C frame via the error
    stash (native_vm.py)."""
    from phant_tpu.evm.interpreter import Evm
    from phant_tpu.evm.message import (
        REVISION_PRAGUE,
        Environment,
        Message,
    )
    from phant_tpu.evm.precompiles_bls import ConsensusDataUnavailable
    from phant_tpu.state.statedb import StateDB

    # caller bytecode: CALL(gas, 0x10, 0, 0, 64, 0, 0); STOP
    code = bytes.fromhex("5f5f60405f5f601062030d40f100")
    caller = b"\xca" * 20
    state = StateDB()
    state.create_account(caller)
    state.set_code(caller, code)
    env = Environment(state=state, revision=REVISION_PRAGUE)
    evm = Evm(env)
    state.start_tx()
    with pytest.raises(ConsensusDataUnavailable):
        evm.execute_message(
            Message(caller=b"\x11" * 20, target=caller, value=0,
                    data=b"", gas=5_000_000)
        )
