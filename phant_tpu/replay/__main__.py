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


def main(argv=None) -> int:
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
        "device megabatches per segment (auto keys on a live device)",
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
    ap.add_argument(
        "--serial-check",
        action="store_true",
        help="also run serial run_blocks; assert final-root identity",
    )
    ap.add_argument(
        "--stats", action="store_true", help="print replay.* metrics"
    )
    args = ap.parse_args(argv)

    from phant_tpu.replay import DEFAULT_SEGMENT_BLOCKS, ReplayEngine, load_fixture

    segment = args.segment
    if segment is None:
        segment = int(
            os.environ.get("PHANT_REPLAY_SEGMENT", str(DEFAULT_SEGMENT_BLOCKS))
        )
    fix = load_fixture(args.fixture)
    print(
        f"[replay] {args.fixture}: {len(fix.blocks)} blocks, "
        f"{fix.total_txs} txs, segment={segment}"
        + (f", witnesses({fix.scheme})" if fix.witnesses else "")
    )

    root_mode = None if args.root == "auto" else args.root
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
        chain = fix.fresh_chain()
        eng = ReplayEngine(
            segment_blocks=segment,
            pipeline_depth=args.depth,
            root_mode=root_mode,
        )
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


if __name__ == "__main__":
    sys.exit(main())
