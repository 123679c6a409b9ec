"""chip_smoke.py — the served stateless path, once, on the attached TPU.

One process, no children. Builds a mainnet-shaped chain from `--seed`,
computes the plain host reference, builds the Engine API server the way
`python -m phant_tpu --crypto_backend=tpu --evm_backend=native` does, POSTs
`engine_executeStatelessPayloadV1` bodies over loopback HTTP, and holds
every answer — verdict, post-state root, recovered senders — to the
reference. Then it proves from the server's own /metrics and engine stats
that the work ran on the device, and runs each device program once more
directly against the native host result.

Nothing is caught: any exception or failed check ends the run non-zero
with no `"ok": true`. The last line of a green run is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.

`--mesh 4` runs ONLY the four-chip phase (`--sched-mesh 4`) and its
comparison with the same host reference.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from dataclasses import replace

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: genesis size: 2^20 funded accounts. Mainnet holds ~3x10^8; the cut is
#: what one process builds in about a minute with the Python trie.
GENESIS_LOG2 = 20
N_BLOCKS = 8
TRANSFERS_PER_BLOCK = 150  # + 75 counter-contract calls, 30M gas limit


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    log(f"  ok: {what}")


# ---------------------------------------------------------------------------
# data and the plain reference
# ---------------------------------------------------------------------------


def build_data(seed: int):
    from phant_tpu.backend import set_evm_backend
    from phant_tpu.replay.fixture import build_synthetic_chain

    # the builder chain executes every block to fill in its header roots;
    # the native interpreter keeps that to seconds
    set_evm_backend("native")
    n_fillers = (1 << GENESIS_LOG2) - TRANSFERS_PER_BLOCK - TRANSFERS_PER_BLOCK // 2 - 1
    t0 = time.perf_counter()
    fix = build_synthetic_chain(
        N_BLOCKS,
        TRANSFERS_PER_BLOCK,
        n_fillers=n_fillers,
        seed=seed,
        touched_witnesses=True,
    )
    log(
        f"data: seed={seed} genesis=2^{GENESIS_LOG2} accounts "
        f"({len(fix.genesis_accounts)}), {len(fix.blocks)} blocks x "
        f"{len(fix.blocks[0].transactions)} txs, gas limit "
        f"{fix.blocks[0].header.gas_limit}, built in "
        f"{time.perf_counter() - t0:.1f}s"
    )
    log(
        f"cut: genesis is 2^{GENESIS_LOG2} accounts where mainnet holds ~3x10^8 "
        "(one process builds this trie in about a minute)"
    )
    sizes = [(len(n), sum(map(len, n))) for _r, n in fix.witnesses]
    log(f"witnesses: (nodes, bytes) per block = {sizes}")
    return fix


def tamper_signature(block):
    """`block` with one byte of its first transaction's `r` flipped and the
    header re-derived around it, so the body is self-consistent and only
    the signature is wrong."""
    from phant_tpu.mpt.mpt import ordered_trie_root

    tx = block.transactions[0]
    bad = replace(tx, r=tx.r ^ (0xFF << 64))
    txs = (bad, *block.transactions[1:])
    header = replace(
        block.header,
        transactions_root=ordered_trie_root([t.encode() for t in txs]),
    )
    return replace(block, header=header, transactions=txs)


def tamper_witness(nodes):
    """The witness with one byte flipped in the middle of its largest node."""
    nodes = list(nodes)
    i = max(range(len(nodes)), key=lambda k: len(nodes[k]))
    raw = bytearray(nodes[i])
    raw[len(raw) // 2] ^= 0x01
    nodes[i] = bytes(raw)
    return nodes


def host_reference(fix, bad_sig_block, bad_witness):
    """The plain reference, on the host, under the cpu crypto backend:
    serial `Blockchain.run_blocks` with the host trie walk verifying every
    header root, the Python interpreter, and the host sender recovery."""
    from phant_tpu.backend import crypto_backend, set_evm_backend
    from phant_tpu.blockchain.chain import BlockError
    from phant_tpu.mpt.proof import verify_witness_linked

    assert crypto_backend() == "cpu"
    set_evm_backend("python")
    t0 = time.perf_counter()
    chain = fix.fresh_chain(verify_state_root=True)
    # the tampered-signature body first: it must be rejected and leave no
    # trace (the eight roots below would not verify otherwise)
    try:
        chain.run_blocks([bad_sig_block])
    except BlockError as e:
        bad_sig_reason = str(e)
    else:
        raise AssertionError("reference accepted the tampered-signature block")
    roots, senders = [], []
    for block in fix.blocks:
        senders.append(chain.signer.get_senders_batch(list(block.transactions)))
        chain.run_blocks([block])
        roots.append(chain.state.state_root())
        assert roots[-1] == block.header.state_root
    bad_root, bad_nodes = bad_witness
    assert not verify_witness_linked(bad_root, bad_nodes)
    assert verify_witness_linked(*fix.witnesses[0])
    log(
        f"reference: {len(roots)} blocks VALID on the host "
        f"(python EVM, host trie walk) in {time.perf_counter() - t0:.1f}s; "
        f"tampered signature rejected: {bad_sig_reason!r}; tampered witness "
        "does not link"
    )
    return roots, senders


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------


def rpc_body(block, parent, witness, codes, rpc_id: int) -> bytes:
    from phant_tpu.utils.hexutils import bytes_to_hex

    h = block.header
    payload = {
        "parentHash": bytes_to_hex(h.parent_hash),
        "feeRecipient": bytes_to_hex(h.fee_recipient),
        "stateRoot": bytes_to_hex(h.state_root),
        "receiptsRoot": bytes_to_hex(h.receipts_root),
        "logsBloom": bytes_to_hex(h.logs_bloom),
        "prevRandao": bytes_to_hex(h.mix_hash),
        "blockNumber": hex(h.block_number),
        "gasLimit": hex(h.gas_limit),
        "gasUsed": hex(h.gas_used),
        "timestamp": hex(h.timestamp),
        "extraData": "0x",
        "baseFeePerGas": hex(h.base_fee_per_gas),
        "blockHash": bytes_to_hex(h.hash()),
        "transactions": [bytes_to_hex(tx.encode()) for tx in block.transactions],
        "withdrawals": [],
    }
    pre_root, nodes = witness
    witness_json = {
        "headers": [bytes_to_hex(parent.encode())],
        "preStateRoot": bytes_to_hex(pre_root),
        "state": [bytes_to_hex(n) for n in nodes],
        "codes": [bytes_to_hex(c) for c in codes],
    }
    return json.dumps(
        {
            "jsonrpc": "2.0",
            "id": rpc_id,
            "method": "engine_executeStatelessPayloadV1",
            "params": [payload, witness_json],
        }
    ).encode()


def post(base: str, body: bytes) -> dict:
    """One JSON-RPC call, once. Any HTTP error — a shed (`-32051` over 503)
    included — ends the run: a cold server answers late, never 503."""
    req = urllib.request.Request(
        base, data=body, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=1100) as resp:
            reply = json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise AssertionError(f"HTTP {e.code}: {e.read()[:300]!r}") from e
    if "result" not in reply:
        raise AssertionError(f"JSON-RPC error: {reply}")
    return reply["result"]


def request_bodies(fix, codes) -> list:
    parents = [fix.genesis, *(b.header for b in fix.blocks[:-1])]
    return [
        rpc_body(b, p, w, codes, i + 1)
        for i, (b, p, w) in enumerate(zip(fix.blocks, parents, fix.witnesses))
    ]


def post_concurrently(base: str, bodies: list) -> list:
    """One client thread per body, all at once (so a wave forms); the
    replies in body order. A client's exception is re-raised here."""
    replies = [None] * len(bodies)
    errors = []

    def client(i):
        try:
            replies[i] = post(base, bodies[i])
        except BaseException as e:  # re-raised below, on the main thread
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return replies


def get_metrics(base: str) -> dict:
    """{(family, frozenset(labels)): value} from the server's /metrics."""
    with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
        text = resp.read().decode()
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        labels = frozenset()
        if "{" in name:
            name, _, rest = name.partition("{")
            labels = frozenset(rest.rstrip("}").split(","))
        out[(name, labels)] = float(value)
    return out


def metric_sum(m: dict, family: str, label: str | None = None) -> float:
    return sum(
        v for (name, labels), v in m.items()
        if name == family and (label is None or label in labels)
    )


def by_label(m: dict, family: str) -> dict:
    return {
        ",".join(sorted(labels)): v
        for (name, labels), v in m.items()
        if name == family
    }


def serve(sched_args: list):
    from phant_tpu.__main__ import build_parser, build_server

    argv = [
        "--crypto_backend=tpu",
        "--evm_backend=native",
        "--engine_api_port", "0",
        *sched_args,
    ]
    log(f"server: python -m phant_tpu {' '.join(argv)}")
    server = build_server(build_parser().parse_args(argv))
    server.serve_in_background()
    return server, f"http://127.0.0.1:{server.port}"


#: jax's own account of this process's compiles: persistent-cache hits and
#: writes, and the seconds the backend spent compiling what missed
COMPILES = {"cache_hits": 0, "cache_writes": 0, "compile_s": 0.0}


def count_compiles() -> None:
    import jax.monitoring

    def on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            COMPILES["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            COMPILES["cache_writes"] += 1

    def on_duration(name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILES["compile_s"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def cache_entries() -> tuple:
    from phant_tpu.ops._cache import compilation_cache_dir

    d = compilation_cache_dir()
    return d, len(os.listdir(d)) if os.path.isdir(d) else 0


def check_answers(replies, roots, first_block: int) -> None:
    from phant_tpu.utils.hexutils import bytes_to_hex

    for i, result in enumerate(replies):
        k = first_block + i
        if result["status"] != "VALID":
            raise AssertionError(f"block {k + 1}: {result}")
        if result["stateRoot"] != bytes_to_hex(roots[k]):
            raise AssertionError(
                f"block {k + 1}: served root {result['stateRoot']} != "
                f"reference {roots[k].hex()}"
            )
    log(
        f"  ok: blocks {first_block + 1}..{first_block + len(replies)} VALID, "
        "post-state roots equal the host reference byte for byte"
    )


#: run-time degradations, and requests the scheduler refused (overload,
#: deadline, executor down): a healthy server under this load has none
_DEGRADATION_FAMILIES = (
    "phant_backend_device_fallbacks_total",
    "phant_replay_lane_fallbacks_total",
    "phant_sched_executor_crashes_total",
    "phant_sched_rejected_total",
)


def degradations(m: dict) -> dict:
    """The run-time degradation counters, by family and labels."""
    return {
        f"{name}{{{','.join(sorted(labels))}}}": v
        for (name, labels), v in m.items()
        if name in _DEGRADATION_FAMILIES
    }


def check_degradations(m: dict) -> None:
    now = degradations(m)
    check(
        not any(now.values()),
        f"degradation and rejection counters all zero: {now or 0}",
    )


def served_phase(fix, codes, roots, senders, bad_sig_block, bad_witness):
    import jax

    from phant_tpu import backend
    from phant_tpu.stateless import dispatch_sender_recovery

    bodies = request_bodies(fix, codes)
    log(f"request bodies: {[len(b) for b in bodies]} bytes")
    cache_dir, entries_before = cache_entries()
    # a wave closes as soon as the 7 concurrent requests are queued; a lone
    # request waits out the assembly window in each lane
    server, base = serve(
        ["--sched-max-batch", "7", "--sched-max-wait-ms", "500",
         "--sched-adaptive-wait", "0"]
    )
    try:
        t0 = time.perf_counter()
        first = post(base, bodies[0])
        cold_s = time.perf_counter() - t0
        log(f"block 1 alone (cold: compiles included): {cold_s:.2f}s")
        check_answers([first], roots, 0)

        t0 = time.perf_counter()
        replies = post_concurrently(base, bodies[1:])
        log(f"blocks 2-8 from 7 client threads: {time.perf_counter() - t0:.2f}s")
        check_answers(replies, roots, 1)

        t0 = time.perf_counter()
        again = post(base, bodies[0])
        warm_s = time.perf_counter() - t0
        log(f"block 1 alone again (warm): {warm_s:.2f}s")
        check_answers([again], roots, 0)

        bad = post(
            base, rpc_body(fix.blocks[0], fix.genesis, bad_witness, codes, 101)
        )
        log(f"tampered witness node -> {bad['status']}: {bad['validationError']!r}")
        check(
            bad["status"] == "INVALID" and "witness" in bad["validationError"],
            "flipped witness byte is INVALID for the witness",
        )
        bad2 = post(
            base,
            rpc_body(bad_sig_block, fix.genesis, fix.witnesses[0], codes, 102),
        )
        log(f"tampered signature -> {bad2['status']}: {bad2['validationError']!r}")
        check(
            bad2["status"] == "INVALID"
            and bad2["validationError"] != bad["validationError"]
            and "blockHash" not in bad2["validationError"],
            "flipped signature byte is INVALID for its own reason",
        )

        # the senders the server's sig lane recovers (the call the request
        # path makes at decode time), against the host reference
        for k, block in enumerate(fix.blocks):
            got = dispatch_sender_recovery(fix.chain_id, block.transactions)()
            if got != senders[k]:
                raise AssertionError(f"block {k + 1}: sig-lane senders differ")
        log(
            f"  ok: sig-lane senders of all {len(fix.blocks)} blocks equal the "
            "host reference byte for byte"
        )

        m = get_metrics(base)
        stats = post_stats(base)
        sched = server.scheduler.stats_snapshot()
    finally:
        server.shutdown()

    log(f"witness engine stats: {json.dumps(stats, default=str)}")
    log(f"scheduler stats: {json.dumps(sched, default=str)}")
    resident = stats.get("resident", {})
    check(resident.get("uploaded_nodes", 0) > 0, "witness_resident.uploaded_nodes > 0")
    check(stats.get("device_batches", 0) > 0, "witness engine device_batches > 0")
    sig = by_label(m, "phant_witness_engine_sig_batches_total")
    log(f"witness_engine.sig_batches by backend: {sig}")
    check(
        metric_sum(m, "phant_witness_engine_sig_batches_total", 'backend="device"') >= 1,
        'witness_engine.sig_batches{backend="device"} >= 1',
    )
    root = by_label(m, "phant_witness_engine_root_batches_total")
    log(f"witness_engine.root_batches by backend: {root}")
    up_bps, rtt = backend.device_link_profile()
    log(
        "cost-model inputs: link upload "
        f"{up_bps:.0f} bytes/s, round trip {rtt * 1e3:.3f} ms, device keccak "
        f"{backend.device_hash_bps():.0f} B/s, native {backend.NATIVE_HASH_BPS:.0f} B/s; "
        f"offload decisions {by_label(m, 'phant_backend_offload_decisions_total')}"
    )
    check_degradations(m)
    _d, entries_after = cache_entries()
    log(
        f"compile cache: {cache_dir} entries {entries_before} -> {entries_after}; "
        f"persistent-cache hits {COMPILES['cache_hits']}, writes "
        f"{COMPILES['cache_writes']}, backend compile {COMPILES['compile_s']:.1f}s; "
        f"first request cold {cold_s:.2f}s, warm {warm_s:.2f}s"
    )
    log(f"devices: {jax.devices()}")


def post_stats(base: str) -> dict:
    body = json.dumps(
        {"jsonrpc": "2.0", "id": 900, "method": "phant_witnessEngineStats", "params": []}
    ).encode()
    return post(base, body)


# ---------------------------------------------------------------------------
# the three device programs, directly
# ---------------------------------------------------------------------------


def keccak_program_text(blob, offsets, lens) -> str:
    from phant_tpu.ops.witness_jax import WITNESS_MAX_CHUNKS, witness_digests

    return (
        witness_digests.lower(blob, offsets, lens, max_chunks=WITNESS_MAX_CHUNKS)
        .compile()
        .as_text()
    )


def direct_phase(fix, codes, senders) -> None:
    import numpy as np

    from phant_tpu.blockchain.chain import Blockchain
    from phant_tpu.blockchain.fork import fork_for
    from phant_tpu.config import ChainConfig, ChainId
    from phant_tpu.crypto.keccak import RATE
    from phant_tpu.ops.keccak_jax import digests_to_bytes
    from phant_tpu.ops.mpt_jax import execute_plan_outputs_host
    from phant_tpu.ops.root_engine import RootEngine
    from phant_tpu.ops.secp256k1_jax import ecrecover_batch
    from phant_tpu.ops.witness_jax import WITNESS_MAX_CHUNKS, _pow2ceil, witness_digests
    from phant_tpu.stateless import WitnessStateDB, witness_node_db
    from phant_tpu.utils.native import load_native

    native = load_native()
    block, (pre_root, nodes) = fix.blocks[0], fix.witnesses[0]

    # 1. keccak over block 1's witness nodes, packed the way the resident
    # table packs a novel batch (pow2 blob, pow2 row count)
    raw = b"".join(nodes)
    blob = np.zeros(_pow2ceil(len(raw) + WITNESS_MAX_CHUNKS * RATE), np.uint8)
    blob[: len(raw)] = np.frombuffer(raw, np.uint8)
    lens = np.zeros(_pow2ceil(len(nodes)), np.int32)
    lens[: len(nodes)] = [len(n) for n in nodes]
    offsets = np.zeros_like(lens)
    np.cumsum(lens[:-1], out=offsets[1:])
    t0 = time.perf_counter()
    got = digests_to_bytes(
        np.asarray(witness_digests(blob, offsets, lens, max_chunks=WITNESS_MAX_CHUNKS))
    )[: len(nodes)]
    check(
        got == list(native.keccak256_batch_fast(nodes)),
        f"device keccak of {len(nodes)} witness nodes equals the native host "
        f"digests ({time.perf_counter() - t0:.2f}s)",
    )
    check(
        "tpu_custom_call" in keccak_program_text(blob, offsets, lens),
        "the compiled keccak program contains tpu_custom_call",
    )

    # 2. the fused post-root plan of block 1, forced onto the device
    state = WitnessStateDB(
        pre_root, list(nodes), list(codes), node_db=witness_node_db(list(nodes))
    )
    config = ChainConfig.from_chain_id(int(ChainId.Mainnet))
    fork = fork_for(config, state, block.header.block_number, block.header.timestamp)
    chain = Blockchain(
        fix.chain_id, state, fix.genesis, fork=fork, verify_state_root=False, config=config
    )
    chain.run_block(block, senders=senders[0])
    prp = state.post_root_plan()
    assert prp is not None
    t0 = time.perf_counter()
    (device_rows,) = RootEngine(device_floor=0).root_many([prp.plan])
    check(
        [bytes(d) for d in device_rows] == execute_plan_outputs_host(prp.plan),
        f"device root plan ({prp.levels} levels, {len(prp.plan.blob)} blob bytes) "
        f"equals the host plan walk ({time.perf_counter() - t0:.2f}s)",
    )
    check(
        state.apply_post_root(prp, device_rows) == block.header.state_root,
        "the device plan's post root is block 1's header state root",
    )

    # 3. the ecrecover batch over block 1's signatures
    rows = chain.signer.signature_rows(list(block.transactions))
    t0 = time.perf_counter()
    got = ecrecover_batch(rows.msgs, rows.rs, rows.ss, rows.recids)
    want = native.ecrecover_batch(rows.msgs, rows.rs, rows.ss, rows.recids)
    check(
        got == list(want) == senders[0],
        f"device ecrecover of {rows.n} signatures equals the native host batch "
        f"and the reference senders ({time.perf_counter() - t0:.2f}s)",
    )


# ---------------------------------------------------------------------------
# the four-chip phase
# ---------------------------------------------------------------------------


def mesh_phase(fix, codes, roots, n: int) -> None:
    import jax

    bodies = request_bodies(fix, codes)
    # one request per batch: the pool's bucket affinity keeps a shape on its
    # home lane until that lane's backlog passes the spill depth, so eight
    # single-request batches in flight are what spreads over the lanes
    server, base = serve(
        ["--sched-mesh", str(n), "--sched-max-batch", "1", "--sched-mesh-spill", "1"]
    )
    try:
        pool = server.scheduler._pool
        for rnd in range(2):
            t0 = time.perf_counter()
            replies = post_concurrently(base, bodies)
            log(
                f"mesh round {rnd + 1}: 8 blocks from 8 client threads: "
                f"{time.perf_counter() - t0:.2f}s"
            )
            check_answers(replies, roots, 0)
        # the boot prewarm compiled the sharded executables (the Pallas
        # kernel under shard_map) on its own thread, beside the traffic
        pool._prewarm_thread.join()
        check(
            (pool.prewarm_compiled or 0) > 0,
            f"mesh prewarm compiled {pool.prewarm_compiled} sharded executables",
        )
        m = get_metrics(base)
        lanes = []
        for engine in pool.engines():
            res = engine._resident
            devs = (
                sorted({str(d) for a in res.arrays() for d in a.devices()})
                if res is not None and res.rows()
                else []
            )
            lanes.append((engine.stats_snapshot().get("device"), devs))
    finally:
        server.shutdown()
    dispatch = by_label(m, "phant_sched_device_dispatch_total")
    log(f"sched.device_dispatch: {dispatch}")
    log(f"lane -> resident table devices: {lanes}")
    check(
        sum(1 for v in dispatch.values() if v > 0) >= n,
        f"sched.device_dispatch is non-zero for {n} distinct devices",
    )
    homes = [devs for _lane, devs in lanes]
    check(
        all(len(d) == 1 for d in homes) and len({d[0] for d in homes}) == n,
        f"each of the {n} lanes' resident tables lives on its own device",
    )
    check_degradations(m)
    log(
        f"compiles: persistent-cache hits {COMPILES['cache_hits']}, writes "
        f"{COMPILES['cache_writes']}, backend compile {COMPILES['compile_s']:.1f}s"
    )
    log(f"devices: {jax.devices()}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--mesh", type=int, default=0,
        help="run ONLY the N-chip phase (--sched-mesh N) and its comparison",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform, kind, count = devices[0].platform, devices[0].device_kind, len(devices)
    log(f"jax {jax.__version__}: platform={platform} device_kind={kind} count={count}")
    if platform != "tpu":
        log(f"chip_smoke needs the tpu platform, jax found {platform!r}")
        return 1
    if args.mesh and count != args.mesh:
        log(f"--mesh {args.mesh} needs {args.mesh} chips, jax found {count}")
        return 1
    t_start = time.perf_counter()
    run(args.seed, args.mesh)
    log(f"total: {time.perf_counter() - t_start:.1f}s")
    print(
        json.dumps(
            {"ok": True, "device": {"platform": platform, "kind": kind, "count": count}}
        ),
        flush=True,
    )
    return 0


def run(seed: int, mesh: int) -> None:
    import logging

    from phant_tpu.evm.native_vm import native_available
    from phant_tpu.utils.native import build_native, load_native

    logging.basicConfig(level=logging.INFO, format="%(levelname)s %(name)s: %(message)s")
    count_compiles()
    # the native library, built for THIS host from the committed sources:
    # the EVM leg of the served path is the native interpreter, and a drop
    # to the Python one would be a different system
    t0 = time.perf_counter()
    path = build_native()
    if load_native() is None or not native_available():
        raise RuntimeError(f"the native library {path} does not load")
    log(f"native library: {path} ({time.perf_counter() - t0:.1f}s)")

    fix = build_data(seed)
    codes = sorted({a.code for a in fix.genesis_accounts.values() if a.code})
    bad_sig_block = tamper_signature(fix.blocks[0])
    bad_witness = (fix.witnesses[0][0], tamper_witness(fix.witnesses[0][1]))
    roots, senders = host_reference(fix, bad_sig_block, bad_witness)
    if mesh:
        mesh_phase(fix, codes, roots, mesh)
    else:
        served_phase(fix, codes, roots, senders, bad_sig_block, bad_witness)
        direct_phase(fix, codes, senders)


if __name__ == "__main__":
    sys.exit(main())
