"""CLI: replay a fixture chain through the segment pipeline.

    python -m phant_tpu.replay <fixture-chain> --segment K

The fixture is a `phant_tpu.replay.fixture` pickle. `--scheduler`
installs a VerificationScheduler so segments ride the real sig/witness lanes
(`--mesh N` puts a MeshExecutorPool behind it); without it every stage
uses its local megabatch fallback. `--serial-check` re-imports the same
chain through serial `run_blocks` and asserts final-state-root
byte-identity — the CLI face of the differential contract the tests pin.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m phant_tpu.replay", description=__doc__
    )
    ap.add_argument("fixture", help="fixture-chain file (replay/fixture.py)")
    ap.add_argument(
        "--segment",
        type=int,
        default=None,
        help="blocks per segment (default: PHANT_REPLAY_SEGMENT or 32)",
    )
    ap.add_argument(
        "--depth",
        type=int,
        default=None,
        help="segments in flight (default: PHANT_REPLAY_DEPTH or 2)",
    )
    ap.add_argument(
        "--root",
        choices=("auto", "host", "defer"),
        default="auto",
        help="segment root mode: host walk per block, or deferred "
        "device megabatches per segment. auto defers only on a live device "
        "AND where a block's plan is over what the block dirtied; a state "
        "that retains its trie keeps the host walk, on the chip too",
    )
    ap.add_argument(
        "--no-witnesses",
        action="store_true",
        help="ignore fixture witnesses (sig/root megabatches only)",
    )
    ap.add_argument(
        "--scheduler",
        action="store_true",
        help="install a VerificationScheduler (sig + witness lanes)",
    )
    ap.add_argument(
        "--mesh",
        type=int,
        default=0,
        metavar="N",
        help="with --scheduler: N per-device mesh lanes",
    )
    # the server's two backend flags, values and defaults
    # (phant_tpu/__main__.py): the same process-wide choice
    ap.add_argument(
        "--crypto_backend",
        choices=("cpu", "tpu"),
        default="cpu",
        help="Backend for the stateless crypto hot loop (keccak/MPT/ecrecover)",
    )
    ap.add_argument(
        "--evm_backend",
        choices=("python", "native"),
        default="native",
        help="EVM bytecode interpreter: native C++ core (evmone-equivalent) "
        "or the pure-Python reference interpreter",
    )
    ap.add_argument(
        "--serial-check",
        action="store_true",
        help="also run serial run_blocks; assert final-root identity",
    )
    ap.add_argument(
        "--stats", action="store_true", help="print replay.* metrics"
    )
    return ap


def build_engine(args):
    """Everything `python -m phant_tpu.replay` does before it runs a
    chain, from parsed args: select the backends (a `tpu` backend without
    a TPU raises HERE), install the scheduler where `--scheduler` asks for
    one, and build the engine. Returns (scheduler or None, ReplayEngine);
    the caller uninstalls and shuts down the scheduler it was given.
    `main` runs what this returns; benchmarks/drivers/replay.py too."""
    from phant_tpu.backend import set_crypto_backend, set_evm_backend
    from phant_tpu.replay import DEFAULT_SEGMENT_BLOCKS, ReplayEngine

    set_crypto_backend(args.crypto_backend)
    set_evm_backend(args.evm_backend)
    segment = args.segment
    if segment is None:
        segment = int(
            os.environ.get("PHANT_REPLAY_SEGMENT", str(DEFAULT_SEGMENT_BLOCKS))
        )
    sched = None
    if args.scheduler:
        # the lane decision is stateless._batched_sig_wanted; on a pure
        # CPU host the lane must be asked for explicitly
        os.environ.setdefault("PHANT_BATCHED_SIG", "1")
        from phant_tpu import serving
        from phant_tpu.ops.sig_engine import SigEngine
        from phant_tpu.ops.witness_engine import WitnessEngine

        sched = serving.VerificationScheduler(
            engine=WitnessEngine(),
            config=serving.SchedulerConfig(
                max_batch=max(16, segment),
                max_wait_ms=20.0,
                pipeline_depth=2,
                mesh_devices=args.mesh,
                sig_engine_factory=lambda: SigEngine(device_floor=0),
            ),
        )
        serving.install(sched)
        try:
            # as the server's boot: the table's programs on every rung of
            # their ladders before a segment can wait for one (which
            # witnesses share a wave is up to the 20 ms window)
            serving.boot_lanes(sched)
        except BaseException:
            serving.uninstall(sched)
            sched.shutdown()
            raise
    eng = ReplayEngine(
        segment_blocks=segment,
        pipeline_depth=args.depth,
        root_mode=None if args.root == "auto" else args.root,
    )
    return sched, eng


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    from phant_tpu.backend import (
        crypto_backend,
        evm_backend,
        set_crypto_backend,
        set_evm_backend,
    )
    from phant_tpu.replay import load_fixture
    from phant_tpu.replay.lowering import auto_root_mode

    fix = load_fixture(args.fixture)
    backends = crypto_backend(), evm_backend()  # a caller's, given back at the end
    sched = None
    try:
        sched, eng = build_engine(args)
        print(
            f"[replay] {args.fixture}: {len(fix.blocks)} blocks, "
            f"{fix.total_txs} txs, segment={eng.segment_blocks}"
            + (f", witnesses({fix.scheme})" if fix.witnesses else "")
        )
        chain = fix.fresh_chain()
        if args.root == "auto":
            _mode, passed_over = auto_root_mode()
            if passed_over:
                print(f"[replay] --root auto: {passed_over}")
        t0 = time.perf_counter()
        report = eng.run(
            chain,
            fix.blocks,
            witnesses=None if args.no_witnesses else fix.witnesses,
        )
        dt = time.perf_counter() - t0
        bps = report.blocks_ok / dt if dt > 0 else 0.0
        print(
            f"[replay] {report.blocks_ok}/{len(fix.blocks)} blocks ok in "
            f"{dt:.3f}s ({bps:.1f} blocks/s, {report.segments} segments, "
            f"{report.txs} txs)"
        )
        print(f"[replay] final state root {report.final_state_root.hex()}")
        for v in report.verdicts:
            if not v.ok:
                print(
                    f"[replay] block #{v.block_number} (index {v.index}) "
                    f"FAILED: {v.error}"
                )
        if args.stats:
            from phant_tpu.utils.trace import metrics

            snap = metrics.snapshot()
            for family in ("counters", "gauges", "timers", "histograms"):
                for name, val in sorted(snap.get(family, {}).items()):
                    if str(name).startswith("replay."):
                        print(f"[replay] {name} = {val}")
        if args.serial_check:
            serial_chain = fix.fresh_chain()
            t0 = time.perf_counter()
            try:
                serial_chain.run_blocks(fix.blocks)
                serial_ok = True
            except Exception as exc:
                serial_ok = False
                print(f"[replay] serial run_blocks stopped: {exc}")
            sdt = time.perf_counter() - t0
            serial_root = serial_chain.state.state_root()
            print(
                f"[replay] serial run_blocks: {sdt:.3f}s; final root "
                f"{serial_root.hex()}"
            )
            if serial_root != report.final_state_root or (
                serial_ok is not report.ok
            ):
                print("[replay] MISMATCH vs serial run_blocks")
                return 2
            print("[replay] serial-check: final-state-root identity OK")
        return 0 if report.ok else 1
    finally:
        if sched is not None:
            from phant_tpu import serving

            serving.uninstall(sched)
            sched.shutdown()
        set_crypto_backend(backends[0])
        set_evm_backend(backends[1])


if __name__ == "__main__":
    sys.exit(main())
