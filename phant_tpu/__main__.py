"""CLI entry point: `python -m phant_tpu`.

Equivalent surface to the reference's main (reference: src/main.zig:78-150):
flag parsing (`--engine_api_port/-p`, `--network_id`, `--chainspec`,
reference: main.zig:78-92), chain-config resolution + fork-table dump
(main.zig:109-118), empty StateDB + zero parent header (main.zig:120-140),
Blockchain construction (main.zig:141) and the Engine API HTTP server
(main.zig:143-149). Adds `--crypto_backend` per the north star.
"""

from __future__ import annotations

import argparse
import logging
import sys
import time

from phant_tpu.backend import set_crypto_backend, set_evm_backend
from phant_tpu.blockchain.chain import Blockchain
from phant_tpu.blockchain.fork import fork_for
from phant_tpu.config import ChainConfig, ChainId
from phant_tpu.engine_api.server import EngineAPIServer
from phant_tpu.state.statedb import StateDB
from phant_tpu.types.block import BlockHeader
from phant_tpu.utils.trace import jax_profile
from phant_tpu.version import RELEASE, revision

log = logging.getLogger("phant_tpu")


def build_parser() -> argparse.ArgumentParser:
    """(reference: PhantArgs, main.zig:78-92)"""
    p = argparse.ArgumentParser(
        prog="phant_tpu", description="TPU-native Ethereum execution client"
    )
    p.add_argument(
        "-p",
        "--engine_api_port",
        type=int,
        default=8551,
        help="Specify the port to listen to for Engine API messages",
    )
    p.add_argument(
        "--network_id",
        type=int,
        default=int(ChainId.Mainnet),
        help="Specify the chain id of the network",
    )
    p.add_argument(
        "--chainspec", type=str, default=None,
        help="Specify a custom chainspec JSON file",
    )
    p.add_argument(
        "--crypto_backend",
        choices=("cpu", "tpu"),
        default="cpu",
        help="Backend for the stateless crypto hot loop (keccak/MPT/ecrecover)",
    )
    p.add_argument(
        "--evm_backend",
        choices=("python", "native"),
        default="native",
        help="EVM bytecode interpreter: native C++ core (evmone-equivalent) "
        "or the pure-Python reference interpreter",
    )
    p.add_argument(
        "--commitment",
        choices=("mpt", "binary"),
        default=None,
        help="Commitment scheme for stateless state verification "
        "(phant_tpu/commitment/): hexary keccak MPT (the default) or "
        "fixed-shape binary Merkle. Applies to every "
        "engine_executeStatelessPayloadV1 this node serves — witnesses "
        "and header state roots must commit under the same scheme. "
        "Default: PHANT_COMMITMENT or mpt",
    )
    # the Engine API is a localhost-trust interface; bind loopback by default
    p.add_argument("--host", type=str, default="127.0.0.1", help="Bind address")
    # observability surface (the Engine API port always serves GET /metrics
    # and /healthz; these flags add a standalone scrape port + device traces)
    p.add_argument(
        "--metrics",
        action="store_true",
        help="Also serve GET /metrics and /healthz on a dedicated port "
        "(--metrics-port), separate from the CL-trust Engine API port",
    )
    p.add_argument(
        "--metrics-port",
        type=int,
        default=9465,
        help="Port for the standalone metrics server (with --metrics)",
    )
    p.add_argument(
        "--trace-logdir",
        type=str,
        default=None,
        help="Capture a JAX/XLA device trace of the serving process into "
        "this directory (view with TensorBoard or Perfetto)",
    )
    p.add_argument(
        "--slo-budget-ms",
        type=float,
        default=None,
        help="Wall-clock SLO budget per verify_block request: a request "
        "past it is captured as a full span tree into the /debug/slow "
        "exemplar ring (obs/critpath.py; per-phase overrides via "
        "PHANT_SLO_BUDGET_MS_<PHASE>). 0 disables capture. "
        "Default: PHANT_SLO_BUDGET_MS or 0",
    )
    p.add_argument(
        "--profile-dir",
        type=str,
        default=None,
        help="Directory for on-demand profiler captures "
        "(POST /debug/profile?seconds=T — single-flight, window capped "
        "by PHANT_PROFILE_MAX_S). Default: PHANT_PROFILE_DIR or "
        "build/profile",
    )
    p.add_argument(
        "--timeline-sample-n",
        type=int,
        default=None,
        help="Uniform 1-in-N tail-sampling rate of the timeline recorder "
        "(GET /debug/timeline): SLO violators, crashed requests, and "
        "per-phase p99 exemplars are always kept; 1 keeps everything, "
        "0 keeps only the always-kept tiers. "
        "Default: PHANT_TIMELINE_SAMPLE_N or 16",
    )
    p.add_argument(
        "--timeline-dir",
        type=str,
        default=None,
        help="Spool every timeline export to rotated JSON files under "
        "this directory (newest PHANT_TIMELINE_KEEP kept). "
        "Default: PHANT_TIMELINE_DIR or off",
    )
    p.add_argument(
        "--flight-ring",
        type=int,
        default=None,
        help="Capacity (records) of the /debug/flight postmortem ring, "
        "resolved once at server construction; /healthz echoes all "
        "debug-ring capacities. Default: PHANT_FLIGHT_RING or 2048",
    )
    # continuous-batching scheduler (phant_tpu/serving/): the knobs of the
    # admission-queue -> batch-assembler -> executor pipeline
    p.add_argument(
        "--sched-max-batch",
        type=int,
        default=128,
        help="Max verification requests coalesced into one engine/device "
        "batch (scheduler batch assembler)",
    )
    p.add_argument(
        "--sched-max-wait-ms",
        type=float,
        default=5.0,
        help="Max time an under-full batch waits for more requests; bounds "
        "the latency a lone request pays for batching",
    )
    p.add_argument(
        "--sched-queue-depth",
        type=int,
        default=512,
        help="Admission-queue bound; a full queue rejects with JSON-RPC "
        "-32050 (overload shedding) instead of building latency",
    )
    p.add_argument(
        "--sched-pipeline-depth",
        type=int,
        default=None,
        help="Witness batches in flight between pack and resolve: depth "
        ">= 2 overlaps host packing of batch N+1 with device compute / "
        "digest resolve of batch N; 1 serializes (the pre-pipeline "
        "behavior). Default: PHANT_SCHED_PIPELINE_DEPTH or 2",
    )
    p.add_argument(
        "--sched-prefetch",
        type=int,
        choices=(0, 1),
        default=None,
        help="4th pipeline stage: a prefetch worker runs batch N+1's "
        "witness decode + intern-table novelty pre-scan while batch N "
        "is in dispatch/resolve (on whenever the pipeline depth is >= "
        "2; the pre-scan is advisory — pack's lock-held re-check stays "
        "the authoritative commit). 0 pins the 3-stage pipeline. "
        "Default: PHANT_SCHED_PREFETCH or 1",
    )
    # mesh-sharded dispatch (phant_tpu/serving/mesh_exec.py): one
    # pipelined executor per device, each with a device-pinned engine
    p.add_argument(
        "--sched-mesh",
        type=int,
        default=None,
        metavar="N",
        help="Fan witness dispatch out over N mesh devices: one pipelined "
        "executor per device, each owning a WitnessEngine pinned to that "
        "device, with stable bucket-affinity routing (a witness shape "
        "keeps hitting the same device's intern table) plus least-loaded "
        "spillover. 0 = the single-executor path. "
        "Default: PHANT_SCHED_MESH or 0",
    )
    p.add_argument(
        "--sched-mesh-dispatch",
        choices=("affinity", "megabatch"),
        default=None,
        help="Mesh dispatch mode: 'affinity' routes each assembled batch "
        "to one device; 'megabatch' additionally sends a single-bucket "
        "batch that fills --sched-max-batch through ONE whole-mesh "
        "sharded fused kernel call. Default: PHANT_SCHED_MESH_DISPATCH "
        "or affinity",
    )
    p.add_argument(
        "--sched-megabatch-backlog-k",
        type=int,
        default=None,
        metavar="K",
        help="With --sched-mesh-dispatch megabatch, ALSO fire the "
        "whole-mesh fused dispatch whenever queued same-bucket work "
        "(current batch + still-queued same-bucket jobs) reaches mesh "
        "width x K — fusion engages under sustained overload without "
        "sizing --sched-max-batch. 0 keeps the full-batch-only trigger. "
        "Default: PHANT_SCHED_MEGABATCH_BACKLOG_K or 0",
    )
    p.add_argument(
        "--sched-mesh-spill",
        type=int,
        default=None,
        help="Home-device backlog (batches) at which a bucket's batch "
        "spills to the least-loaded device instead. Default: "
        "PHANT_SCHED_MESH_SPILL or 2",
    )
    # multi-tenant QoS (phant_tpu/serving/qos.py): per-tenant lanes,
    # quotas, weighted fair dequeue, and the adaptive batching wait
    p.add_argument(
        "--sched-tenant-quota",
        type=int,
        default=None,
        help="Max queued witness requests PER TENANT lane (X-Phant-Tenant "
        "header); 0 = only the global queue depth bounds a lane. "
        "Default: PHANT_SCHED_TENANT_QUOTA or 0",
    )
    p.add_argument(
        "--sched-tenant-weights",
        type=str,
        default=None,
        help="Weighted-fair dequeue shares as name:weight,... (e.g. "
        "'cl:4,indexer:1'); unlisted tenants weigh 1. Default: "
        "PHANT_SCHED_TENANT_WEIGHTS",
    )
    p.add_argument(
        "--sched-adaptive-wait",
        type=int,
        choices=(0, 1),
        default=None,
        help="1 = shrink the batch-assembly wait as the queue deepens and "
        "widen it when idle (the inference-serving policy); 0 = static "
        "--sched-max-wait-ms. Default: PHANT_SCHED_ADAPTIVE_WAIT or 1",
    )
    p.add_argument(
        "--sched-min-wait-ms",
        type=float,
        default=None,
        help="Adaptive-wait floor once the queue holds ~one full batch. "
        "Default: PHANT_SCHED_MIN_WAIT_MS or 0.2",
    )
    p.add_argument(
        "--http-timeout-s",
        type=float,
        default=None,
        help="Socket read/write deadline per Engine API connection; a "
        "stalled (slow-loris) client frees its handler thread after this "
        "long. <=0 disables. Default: PHANT_HTTP_TIMEOUT_S or 30",
    )
    return p


def make_genesis_parent_header() -> BlockHeader:
    """The zeroed pre-genesis parent the reference starts from
    (reference: main.zig:122-140)."""
    return BlockHeader(
        gas_limit=0x1C9C380,
        base_fee_per_gas=7,
        withdrawals_root=b"\x00" * 32,
    )


def build_server(args) -> EngineAPIServer:
    """Everything `python -m phant_tpu` does before it serves, from parsed
    args: select the backends (a `tpu` backend without a TPU raises HERE,
    before the port is bound), resolve the chain config, build the chain,
    the scheduler config and the bound Engine API server. `main` serves
    what this returns; chip_smoke.py posts to it over loopback."""
    set_crypto_backend(args.crypto_backend)
    set_evm_backend(args.evm_backend)
    if args.commitment is not None:
        # the flag wins over the env; stateless.py / spec tooling read the
        # active scheme through phant_tpu.commitment.active_scheme()
        import os

        os.environ["PHANT_COMMITMENT"] = args.commitment
    from phant_tpu.commitment import active_scheme

    log.info("commitment scheme: %s", active_scheme().name)

    # chain config resolution (reference: main.zig:109-114)
    if args.chainspec is not None:
        config = ChainConfig.from_chainspec_file(args.chainspec)
    else:
        config = ChainConfig.from_chain_id(args.network_id)

    log.info("phant-tpu %s (%s)", RELEASE, revision())
    log.info("chain: %s (id %d)", config.ChainName, config.chainId)
    print(config.dump())  # (reference: config.dump(), main.zig:118)

    state = StateDB()
    fork = fork_for(config, state, 0, int(time.time()))
    log.info("active fork: %s", type(fork).__name__)
    chain = Blockchain(
        chain_id=config.chainId,
        state=state,
        parent_header=make_genesis_parent_header(),
        fork=fork,
        # stateless serving starts from an untracked state: roots for
        # arbitrary payloads can't be checked without the parent state
        verify_state_root=False,
        config=config,
    )

    from phant_tpu.serving import SchedulerConfig, parse_weights

    sched_kwargs = dict(
        max_batch=args.sched_max_batch,
        max_wait_ms=args.sched_max_wait_ms,
        queue_depth=args.sched_queue_depth,
    )
    if args.sched_pipeline_depth is not None:
        sched_kwargs["pipeline_depth"] = args.sched_pipeline_depth
    if args.sched_prefetch is not None:
        sched_kwargs["prefetch"] = bool(args.sched_prefetch)
    # mesh dispatch: a flag wins over its PHANT_SCHED_MESH* env default
    if args.sched_mesh is not None:
        sched_kwargs["mesh_devices"] = args.sched_mesh
    if args.sched_mesh_dispatch is not None:
        sched_kwargs["mesh_dispatch"] = args.sched_mesh_dispatch
    if args.sched_mesh_spill is not None:
        sched_kwargs["mesh_spill_depth"] = args.sched_mesh_spill
    if args.sched_megabatch_backlog_k is not None:
        sched_kwargs["megabatch_backlog_k"] = args.sched_megabatch_backlog_k
    # QoS knobs: a flag wins over its PHANT_SCHED_* env default
    if args.sched_tenant_quota is not None:
        sched_kwargs["tenant_quota"] = args.sched_tenant_quota
    if args.sched_tenant_weights is not None:
        sched_kwargs["tenant_weights"] = parse_weights(args.sched_tenant_weights)
    if args.sched_adaptive_wait is not None:
        sched_kwargs["adaptive_wait"] = bool(args.sched_adaptive_wait)
    if args.sched_min_wait_ms is not None:
        sched_kwargs["min_wait_ms"] = args.sched_min_wait_ms
    if args.http_timeout_s is not None:
        # the handler reads the env per accepted connection
        import os

        os.environ["PHANT_HTTP_TIMEOUT_S"] = str(args.http_timeout_s)
    obs_flags = (
        ("PHANT_SLO_BUDGET_MS", args.slo_budget_ms),
        ("PHANT_PROFILE_DIR", args.profile_dir),
        ("PHANT_TIMELINE_SAMPLE_N", args.timeline_sample_n),
        ("PHANT_TIMELINE_DIR", args.timeline_dir),
        ("PHANT_FLIGHT_RING", args.flight_ring),
    )
    if any(v is not None for _k, v in obs_flags):
        # observability knobs ride the env (the server re-resolves the
        # memoized obs configs — attribution, timeline, flight ring —
        # ONCE at construction)
        import os

        for key, val in obs_flags:
            if val is not None:
                os.environ[key] = str(val)
    sched_config = SchedulerConfig(**sched_kwargs)
    server = EngineAPIServer(
        chain,
        host=args.host,
        port=args.engine_api_port,
        sched_config=sched_config,
    )
    log.info("Engine API listening on %s:%d", args.host, server.port)
    return server


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO, format="%(levelname)s %(name)s: %(message)s")
    args = build_parser().parse_args(argv)
    server = build_server(args)
    metrics_server = None
    if args.metrics:
        from phant_tpu.engine_api.server import serve_metrics

        metrics_server = serve_metrics(host=args.host, port=args.metrics_port)
    # SIGTERM (orchestrator stop, driver timeout) leaves a postmortem: dump
    # the obs flight ring to build/flight/, then take the same graceful
    # shutdown path as ^C (drain the scheduler, release the socket)
    import signal

    from phant_tpu.obs import flight

    def _on_sigterm(_signum, _frame):
        flight.dump("sigterm")
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _on_sigterm)
    # SIGINT root cause of the mesh-e2e "shutdown hang" (PR 9): a server
    # launched as a shell background job (`python -m phant_tpu ... &` in a
    # non-interactive shell) inherits SIGINT=SIG_IGN per POSIX, and
    # CPython honors an inherited SIG_IGN by never installing the
    # KeyboardInterrupt handler — so ^C/`kill -INT` is silently ignored
    # FOREVER (faulthandler showed the main thread idle in selector.poll,
    # every scheduler/lane thread parked in its timed wait; nothing was
    # actually wedged). Install the handler explicitly, the same way
    # long-running daemons that still want graceful-stop semantics do.
    def _on_sigint(_signum, _frame):
        # a second ^C mid-drain must not abort shutdown (it lands inside
        # scheduler.shutdown's joins and leaks the socket, rc 130):
        # the first SIGINT starts the drain, later ones are ignored
        signal.signal(signal.SIGINT, signal.SIG_IGN)
        raise KeyboardInterrupt

    signal.signal(signal.SIGINT, _on_sigint)

    try:
        # --trace-logdir wraps the whole serving run in the JAX profiler
        # (no-op without the flag) so TPU kernel dispatches of served
        # payloads land in a TensorBoard/Perfetto trace
        with jax_profile(args.trace_logdir):
            server.serve_forever()
    except KeyboardInterrupt:
        server.shutdown()
        if metrics_server is not None:
            metrics_server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
