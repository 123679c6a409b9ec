#!/usr/bin/env python3
"""What each thread asked of the interpreter lock in one window of a cell.

    python3 scripts/lock_budget.py --workload <cell> --seed <n> --seconds <s> [--trace 1] [--rehearse]

Runs `benchmarks/run.py` as it stands (same arguments, same result line) and,
from the window's own two scrapes of /metrics, writes the growth of every
family that carries the second clock (PR 38) to
`chiprun_out/lock_budget/<cell>-<seed>-trace<0|1>.json` and prints it as one
table, ms a request: each critical-path phase, front-end mark, lane stage
and visit to the device by wall, CPU and off-CPU (wall less CPU: the
thread's wait; in a phase that waits for nothing by design, its wait for
the lock), the extension's own lock clocks by site, its scalar keccaks by
what they did with the lock (a count), the collector, and the process's
CPU. It needs a TPU unless `--rehearse` is given; host figures of the
machine it runs on, never a device number.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))
sys.path.insert(1, str(ROOT))

#: (row group, the label(s) that name a row, wall / cpu / off-CPU family)
SPLITS = (
    ("handler", ("phase",), "phant_critpath_phase_{}seconds_sum"),
    ("front end", ("phase",), "phant_engine_api_phase_{}seconds_sum"),
    ("lane", ("lane", "stage"), "phant_lanes_stage_{}seconds_sum"),
    ("device", ("lane", "op"), "phant_device_host_{}seconds_sum"),
)


def budget(obs: dict) -> dict:
    """The growth between the window's scrapes, by row, in seconds."""
    s0, s1 = obs["scrape0"], obs["scrape1"]

    def grown(family: str) -> dict:
        return {
            labels: v - s0.get((name, labels), 0.0)
            for (name, labels), v in s1.items()
            if name == family
        }

    rows = {}
    for group, keys, family in SPLITS:
        wall = grown(family.format(""))
        cpu = grown(family.format("cpu_"))
        off = grown(family.format("offcpu_"))
        for labels, w in sorted(wall.items(), key=lambda kv: sorted(kv[0])):
            name = ".".join(dict(labels)[k] for k in keys)
            rows[f"{group} {name}"] = {"wall_s": w, "cpu_s": cpu.get(labels), "offcpu_s": off.get(labels)}
    sites = {}
    for labels, v in grown("phant_native_unlocked_seconds").items():
        sites[dict(labels)["site"]] = {"unlocked_s": v}
    for labels, v in grown("phant_native_lock_retake_seconds").items():
        sites.setdefault(dict(labels)["site"], {})["retake_s"] = v
    one = lambda family: sum(grown(family).values())  # noqa: E731
    return {
        "window_s": obs["window_s"],
        "requests": one("phant_critpath_requests_total"),
        "rows": rows,
        "native": sites,
        "keccak_calls": {dict(labels)["lock"]: v for labels, v in grown("phant_native_keccak_calls").items()},
        "gc_pause_s": one("phant_runtime_gc_pause_seconds_sum"),
        "process_cpu_s": one("phant_runtime_process_cpu_seconds"),
        "process_system_cpu_s": sum(
            v for labels, v in grown("phant_runtime_process_cpu_seconds").items() if ("mode", "system") in labels
        ),
    }


def table(b: dict) -> str:
    n = b["requests"] or 1
    ms = lambda v: "      -" if v is None else f"{v / n * 1e3:7.2f}"  # noqa: E731
    out = [f"{'ms a request':32s} {'wall':>7s} {'cpu':>7s} {'off-CPU':>7s}"]
    for name, r in b["rows"].items():
        out.append(f"{name:32s} {ms(r['wall_s'])} {ms(r['cpu_s'])} {ms(r['offcpu_s'])}")
    for site, r in sorted(b["native"].items()):
        out.append(f"{'native ' + site:32s} unlocked {ms(r.get('unlocked_s'))} retake {ms(r.get('retake_s'))}")
    for lock, calls in sorted(b["keccak_calls"].items()):
        out.append(f"{'scalar keccaks, lock ' + lock:32s} {calls / n:7.1f} a request")
    out.append(f"{'collector':32s} {ms(b['gc_pause_s'])}")
    out.append(
        f"window {b['window_s']:.2f} s, {b['requests']:.0f} requests; the process ran "
        f"{b['process_cpu_s']:.2f} CPU seconds ({b['process_cpu_s'] / b['window_s']:.2f} cores), "
        f"{b['process_system_cpu_s']:.2f} of them in the kernel"
    )
    return "\n".join(out)


def main() -> int:
    import run
    from drivers import serve

    argv = sys.argv[1:]
    value = lambda flag, default: argv[argv.index(flag) + 1] if flag in argv else default  # noqa: E731
    out = ROOT / "chiprun_out" / "lock_budget"
    path = out / f"{value('--workload', 'cell')}-{value('--seed', '0')}-trace{value('--trace', '0')}.json"
    measure = serve.Driver.measure

    def measured(self, seconds, trace_dir):
        obs = measure(self, seconds, trace_dir)
        b = budget(obs)
        out.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(b, indent=1) + "\n")
        run.log(f"lock budget -> {path}\n{table(b)}")
        return obs

    serve.Driver.measure = measured
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
