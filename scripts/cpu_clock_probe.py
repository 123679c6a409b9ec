#!/usr/bin/env python3
"""What the thread CPU clock is on this host, and what it charges at the
interpreter lock (PR 38; PERF.md section 7, ac and p).

    python3 scripts/cpu_clock_probe.py [--phase-cost TREE]

One JSON line. `clock`: the cost of a read, the steps the clock takes while a
thread spins (a host whose CPU clock is a scheduler tick steps by the tick),
and CPU over wall summed over many short spins (a sound clock reads about 1).
`lock`: N threads each do the same fixed Python work, with and without a
`ctypes` call (which gives the lock away) every 2,000 iterations; under one
lock their CPU should sum to N times one thread's alone, whatever the wall:
more says the clock charges a thread that stands at the lock, or that a
hand-over burns CPU. `--phase-cost TREE`: microseconds of one
`Metrics.phase` enter + exit of the tree's `phant_tpu` (outside a span, inside
one, a lane's stage timer). Host figures of the machine it runs on, through
the chip tool for the chip's host; never a device number.
"""

from __future__ import annotations

import ctypes
import json
import os
import sys
import threading
import time


def clock() -> dict:
    out = {"uname": " ".join(os.uname()[:3])}
    steps, last, reads = [], time.thread_time_ns(), 0
    end = time.monotonic_ns() + 500_000_000
    while time.monotonic_ns() < end:
        c = time.thread_time_ns()
        reads += 1
        if c != last:
            steps.append(c - last)
            last = c
    steps.sort()
    out["reads"], out["steps"] = reads, len(steps)
    if steps:
        out["step_ns_min_p50_max"] = [steps[0], steps[len(steps) // 2], steps[-1]]
    for spin_us in (100, 1000, 5000, 25000):
        wall = cpu = 0
        for _ in range(max(20, 400_000 // spin_us)):
            t0, c0 = time.monotonic_ns(), time.thread_time_ns()
            while time.monotonic_ns() < t0 + spin_us * 1000:
                pass
            cpu += time.thread_time_ns() - c0
            wall += time.monotonic_ns() - t0
        out[f"spin_{spin_us}us_cpu_over_wall"] = round(cpu / wall, 4)
    for name, read in (("thread_time_ns", time.thread_time_ns), ("monotonic_ns", time.monotonic_ns)):
        t0 = time.perf_counter()
        for _ in range(20000):
            read()
        out[name + "_us"] = round((time.perf_counter() - t0) / 20000 * 1e6, 3)
    return out


def lock(iterations: int = 8_000_000) -> list:
    libc = ctypes.CDLL(None)

    def work(n: int, every: int) -> None:
        x = 0
        for i in range(n):
            x += i * i % 7
            if every and i % every == 0:
                libc.getppid()

    runs = []
    for threads in (1, 4, 16):
        for every in (0, 2000):
            n = iterations // (4 if threads == 16 else 1)
            got: list = []
            gate = threading.Barrier(threads)

            def body():
                gate.wait()
                t0, c0 = time.monotonic_ns(), time.thread_time_ns()
                work(n, every)
                got.append((time.monotonic_ns() - t0, time.thread_time_ns() - c0))

            p0, w0 = os.times(), time.monotonic_ns()
            ts = [threading.Thread(target=body) for _ in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            p1 = os.times()
            runs.append(
                {
                    "threads": threads, "handover_every": every, "iterations_a_thread": n,
                    "wall_s": round((time.monotonic_ns() - w0) / 1e9, 3),
                    "thread_cpu_sum_s": round(sum(c for _w, c in got) / 1e9, 3),
                    "process_user_s": round(p1.user - p0.user, 3),
                    "process_system_s": round(p1.system - p0.system, 3),
                }
            )  # fmt: skip
    return runs


def phase_cost(tree: str, n: int = 200_000) -> dict:
    sys.path.insert(0, tree)
    from phant_tpu.utils.trace import metrics, span

    def loop(name: str) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with metrics.phase(name):
                pass
        return round((time.perf_counter() - t0) / n * 1e6, 3)

    out: dict = {"tree": tree, "outside": [], "inside": [], "lane_stage": []}
    for _rep in range(3):
        out["outside"].append(loop("stateless.sig_rows"))
        with span("verify_block"):
            out["inside"].append(loop("stateless.sig_rows"))
        out["lane_stage"].append(loop("witness_engine.pack"))
    return out


if __name__ == "__main__":
    if "--phase-cost" in sys.argv:
        print(json.dumps({"phase_us": phase_cost(sys.argv[sys.argv.index("--phase-cost") + 1])}))
    else:
        print(json.dumps({"switchinterval": sys.getswitchinterval(), "clock": clock(), "lock": lock()}))
