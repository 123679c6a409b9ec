"""Many seeds of one cell in ONE process (the server and its compiled
programs are shared; each seed gets its own chain, warm-up, a short window
and the whole comparison), then the controls on some of them:

    python3 benchmarks/prove.py --workload <cell> --seeds 1,2,3 --seconds 10 \\
        [--controls trust_header,flip_root --control-seeds 4,5,6]

One JSON line per seed: {"seed", "control", "correct", "compared"}. It is how
the limits' readings are taken on the chip (PERF.md); the driver's check
never runs it. Exit code 0 when every sound seed is correct and every
control seed is not.

`--trace-specs '[{"seconds": 5}, {"seconds": 5, "host_tracer_level": 0}]'`
traces the windows of the first sound seeds, each with the traffic's `trace`
group overridden so, and prints each stretch's device readings: how the
profiler's settings were chosen."""

from __future__ import annotations

import argparse
import importlib
import json
import logging
import os
import shutil
import sys

import run


def traced(cell, obs: dict, trace_dir: str) -> dict:
    """The stretch's reduction and the cell's device metrics read from it."""
    import time

    from harness import readers, trace_reduce

    t0 = time.monotonic()
    xplane = trace_reduce.find_xplane(trace_dir)
    size = os.path.getsize(xplane)
    reduced = trace_reduce.reduce_file(xplane, str(run.HERE / "programs"))
    shutil.rmtree(trace_dir, ignore_errors=True)
    out = {"spec": cell.traffic["trace"], "xplane_bytes": size, "stretch": obs["stretch"],
           "reduce_s": time.monotonic() - t0}  # fmt: skip
    if reduced is None or obs["stretch"] is None:
        return out
    obs = {**obs, "rehearsal": False, "trace": {**reduced, **obs["stretch"]}}
    out["busy_s"], out["executions"] = reduced["busy_s"], reduced["executions"]
    out["device_s_by_module"] = reduced["device_s_by_module"]
    out["metrics"] = {
        spec["name"]: readers.read(spec, obs)
        for spec in cell.metrics("per_layer", "layer_metrics")
        if spec["source"] == "device_trace"
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--chain-blocks", type=int, default=None, help="shorter chains for short windows")
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--trace-specs", default="[]")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.Cell(argparse.Namespace(workload=args.workload, seed=0, rehearse=args.rehearse), bench)
    if args.chain_blocks:
        cell.traffic["chain_blocks"] = args.chain_blocks
    found = run.enter_jax(cell, args.rehearse)
    if found is None:
        return 2
    platform = found[0]
    cell.compiles = run.Compiles()
    logging.basicConfig(level=logging.WARNING)
    driver = importlib.import_module(f"drivers.{cell.config['driver']}").Driver(cell)
    as_expected = True
    try:
        driver.start_program()
        plan = [(s, None) for s in ints(args.seeds)] + [
            (s, c) for c in args.controls.split(",") if c for s in ints(args.control_seeds)
        ]
        specs = json.loads(args.trace_specs)
        if specs:
            cell.gc = run.GcPauses()  # as a traced run has them
        for i, (seed, control) in enumerate(plan):
            driver.load_chain(seed)
            undo, trace_dir = None, None
            if control:
                undo = importlib.import_module(f"controls.{control}").apply(run.log)
            elif i < len(specs):
                cell.traffic["trace"] = {**cell.traffic["trace"], **specs[i]}
                trace_dir = str(cell.out_dir / "trace")
                shutil.rmtree(trace_dir, ignore_errors=True)
                os.makedirs(trace_dir)
            seconds = args.seconds
            if trace_dir:  # a window that holds the stretch
                t = cell.traffic["trace"]
                seconds = max(seconds, t["start_s"] + t["seconds"] + 3)
            try:
                obs = driver.measure(seconds, trace_dir)
                comparisons, attempted, failed = driver.verify()
            finally:
                if undo:
                    undo()
            correct = run.all_within(comparisons)
            as_expected &= correct == (control is None)
            line = {
                "seed": seed, "control": control, "correct": correct, "attempted": attempted,
                "failed": failed, "compiles_in_window": obs["compiles"],
                "compared": {n: [v, lim] for n, v, lim, _h in comparisons},
            }  # fmt: skip
            if trace_dir:
                line["traced"] = traced(cell, obs, trace_dir)
            print(json.dumps(line), flush=True)
    finally:
        driver.close()
    print(json.dumps({"as_expected": as_expected, "platform": platform}), flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
