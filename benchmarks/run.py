"""The benchmark's one command:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data, found by name through
BENCHMARK.json: the configuration (configs/<config>.json, which names its
driver), the traffic mix (traffic/<traffic>.json) and each metric
(end_to_end/<name>.json, layer_metrics/<name>.json). See README.md.

The last line of standard output is the result; what else is worth keeping
goes on earlier lines. `--rehearse` runs the same path on the CPU at a tiny
genesis: it prints platform "cpu", reports no device metric and is never a
measurement.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import importlib
import json
import logging
import os
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT))


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Compiles:
    """jax's own account of the programs this process builds: one event for
    each backend compilation and for each load from the persistent cache
    (both mean a shape the process had not run yet), and the names jax logs
    as it compiles them."""

    def __init__(self):
        import jax
        import jax.monitoring

        self.events = []  # (monotonic time, kind)
        self.names = []  # (index into events at the time, name)
        self.compile_s = 0.0

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.events.append((time.monotonic(), "cache_load"))

        def on_duration(name, secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.events.append((time.monotonic(), "backend_compile"))
                self.compile_s += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

        outer = self

        class Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    outer.names.append((len(outer.events), msg.split(" with ")[0][10:]))

        jax.config.update("jax_log_compiles", True)
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch", "jax._src.compiler"):
            lg = logging.getLogger(name)
            lg.addHandler(Names())
            lg.propagate = False  # thousands of lines on a cold cache

    def count(self) -> int:
        return len(self.events)

    def names_since(self, count: int) -> list:
        return [n for at, n in self.names if at >= count]


class GcPauses:
    """The collector's pauses in this process (which is the server's):
    (start, seconds, generation) of every collection, from gc.callbacks."""

    def __init__(self):
        import gc

        self.pauses = []
        self._t0 = None

        def on_gc(phase, info):
            if phase == "start":
                self._t0 = time.monotonic()
            elif self._t0 is not None:
                self.pauses.append((self._t0, time.monotonic() - self._t0, info["generation"]))

        gc.callbacks.append(on_gc)

    def between(self, t0: float, t1: float) -> list:
        return [p for p in self.pauses if t0 <= p[0] <= t1]

    def full_count(self) -> int:
        """Full (oldest generation) collections ended so far."""
        return sum(1 for p in self.pauses if p[2] == 2)


class Cell:
    """What a driver is given."""

    def __init__(self, args, bench: dict):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in by_name:
            raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
        self.entry = by_name[args.workload]
        self.bench = bench
        self.seed = args.seed
        self.rehearsal = args.rehearse
        self.log = log
        self.config = load_json(HERE / "configs" / f"{self.entry['config']}.json")
        self.traffic = load_json(HERE / "traffic" / f"{self.entry['traffic']}.json")
        if self.rehearsal:
            self.config.update(self.config.get("rehearsal", {}))
            self.traffic.update(self.traffic.get("rehearsal", {}))
        self.chips = self.entry["chips"]
        self.build_dir = str(ROOT / "build" / "bench")
        self.out_dir = ROOT / "build" / "bench" / "runs" / args.workload
        self.compiles = None
        self.gc = None  # GcPauses in a traced run: a callback per collection is not free

    def metrics(self, group: str, folder: str) -> list:
        """The specs of this cell's metrics of one group of BENCHMARK.json."""
        out = []
        for m in self.bench[group]:
            if "workloads" in m and self.entry["name"] not in m["workloads"]:
                continue
            spec = load_json(HERE / folder / f"{m['name']}.json")
            out.append({**spec, "unit": m["unit"]})
        return out


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in benchmarks/peaks.json")
    return table[kind]


def enter_jax(cell: Cell, rehearse: bool):
    """Place the compile cache, import jax and look for the chips the cell
    asks for: (platform, kind, count, devices), or None where a measuring
    run finds no TPU or too few chips."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PHANT_ALLOW_JAX_CPU"] = "1"
    # one fixed directory inside the checkout, unless the caller placed it
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / "build" / "jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for key, value in cell.config.get("env", {}).items():
        os.environ[key] = value  # the deployment's own settings
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)

    import jax

    devices = jax.devices()
    platform, kind, count = devices[0].platform, devices[0].device_kind, len(devices)
    log(f"jax {jax.__version__}: platform={platform} device_kind={kind} count={count}")
    if not rehearse:
        if platform != "tpu":
            log(f"a measuring run needs the tpu platform; jax found {platform!r}")
            return None
        if count < cell.chips:
            log(f"{cell.entry['name']} needs {cell.chips} chips; jax found {count}")
            return None
        log(f"peaks ({kind}): {json.dumps(peaks_for(kind))}")
    return platform, kind, count, devices


def all_within(comparisons) -> bool:
    return all(
        (v >= lim) if how == "at_least" else (v <= lim) for _n, v, lim, how in comparisons
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help="CPU, tiny genesis; never a measurement")
    ap.add_argument("--control", default=None, help="controls/<name>.py: break the program on purpose; the run must come out not correct")
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = Cell(args, bench)

    found = enter_jax(cell, args.rehearse)
    if found is None:
        return 2
    platform, kind, count, devices = found
    cell.compiles = Compiles()
    logging.basicConfig(level=logging.WARNING, format="%(levelname)s %(name)s: %(message)s")

    trace_dir = None
    if args.trace:
        cell.gc = GcPauses()
        trace_dir = str(cell.out_dir / "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)

    driver = importlib.import_module(f"drivers.{cell.config['driver']}").Driver(cell)
    try:
        driver.prepare()
        setup_s = time.monotonic() - T_START
        log(
            f"setup: {setup_s:.1f}s; {cell.compiles.count()} programs built "
            f"({cell.compiles.compile_s:.1f}s of backend compile)"
        )
        if args.control:  # after a sound warm-up: the window and the check see the fault
            importlib.import_module(f"controls.{args.control}").apply(log)
        obs = driver.measure(args.seconds, trace_dir)
        obs.update(setup_s=setup_s, rehearsal=args.rehearse, trace=None)
        peak = max(
            ((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices),
            default=0,
        )
        t0 = time.monotonic()
        comparisons, attempted, failed = driver.verify()
        log(f"check: {time.monotonic() - t0:.1f}s after the window")
    finally:
        driver.close()

    device = {"platform": platform, "kind": kind, "count": count, "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        from harness import trace_reduce

        t0 = time.monotonic()
        xplane = trace_reduce.find_xplane(trace_dir)
        log(f"trace: {xplane} ({os.path.getsize(xplane)} bytes)")
        reduced = trace_reduce.reduce_file(xplane, str(HERE / "programs"))
        stretch = obs["stretch"]
        if reduced is not None and stretch is not None:
            obs["trace"] = {**reduced, **stretch}
            if not args.rehearse:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = stretch["window_s"]
            top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]  # noqa: E731
            breakdown = {
                "device_ops": [[k, v] for k, v in top(reduced["device_s_by_program"])],
                "idle_gaps": [[k, v] for k, v in top(reduced["idle_s_by_gap"])],
            }
            log(
                f"trace: {reduced['executions']} program executions in "
                f"{stretch['window_s']:.2f}s, {stretch['requests']:.2f} requests in flight "
                f"(reduced in {time.monotonic() - t0:.1f}s); device seconds by XLA module: "
                f"{json.dumps(reduced['device_s_by_module'])}"
            )
            if stretch["pace"] is not None and stretch["pace"] < stretch["min_pace"]:
                log(
                    f"trace: the stretch ran at {stretch['pace']:.2f} of the window's pace, under "
                    f"{stretch['min_pace']}: the profiler held the host back, and the device "
                    "metrics of this run are withheld"
                )
        else:
            log("trace: no operation ran on a device in the traced stretch")
        shutil.rmtree(trace_dir, ignore_errors=True)

    from harness import readers

    group, folder = ("per_layer", "layer_metrics") if args.trace else ("end_to_end", "end_to_end")
    metrics = {}
    for spec in cell.metrics(group, folder):
        value = readers.read(spec, obs)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    if obs["compiles"]:
        log(f"programs built inside the window: {obs['compiled_names']}")

    correct = all_within(comparisons)
    compared = {n: {"value": v, "limit": lim, "is": how} for n, v, lim, how in comparisons}
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["workload"] = cell.entry["name"]
    result["seed"] = args.seed
    result["window_s"] = obs["window_s"]
    result["compared"] = compared
    for n, v, lim, how in comparisons:
        print(f"compared: {n} = {v} ({how.replace('_', ' ')} {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
