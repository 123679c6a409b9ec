"""Host memory under the tenure policy over a long chain, on the CPU.

    python3 scripts/soak_collector.py --blocks 1200 --genesis-log2 16

The Engine API server as `python -m phant_tpu --crypto_backend=cpu
--evm_backend=native` builds it, in this process, posted the benchmark's own
synthetic chain (benchmarks/reference/chain.py, made in a process of its
own) by one closed-loop client. Every `--every` requests it prints one JSON
line: requests, the slowest of them, resident set size, tenured objects
(`gc.get_freeze_count()`), objects the collector still walks, tenures,
generation flushes of the interned set, collections by generation, and the
full collections' count and seconds so far. After the chain it stands idle
until the policy's own deep collection has run (its interval is shortened
for that, nothing else is touched) and prints the same line again: what the
deep collection gave back is the difference in tenured objects.

PHANT_WITNESS_CACHE bounds the interned set: set it below the rows the chain
fills (about 1,000 novel rows a block) to see generation flushes.
PHANT_ENGINE_NATIVE=0 keeps the engine's tables in Python. Host memory and
counts only: a CPU run says nothing of times on the chip.
"""

from __future__ import annotations

import argparse
import gc
import http.client
import json
import multiprocessing
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]


def rss_mb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 2**20


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", type=int, default=1200)
    ap.add_argument("--genesis-log2", type=int, default=16)
    ap.add_argument("--seed", type=int, default=3000002701)
    ap.add_argument("--every", type=int, default=100)
    args = ap.parse_args()

    os.environ.setdefault("PHANT_BATCHED_ROOT", "0")
    from harness import chainproc
    from phant_tpu.__main__ import build_parser, build_server
    from phant_tpu.serving import collector
    from phant_tpu.utils.native import build_native
    from phant_tpu.utils.trace import metrics

    build_native()
    with open(os.path.join(ROOT, "benchmarks", "traffic", "lone.json")) as f:
        traffic = json.load(f)
    params = {
        "genesis_log2": args.genesis_log2,
        "sender_pool": 2048,
        "contracts": 16,
        **traffic["chain"],
    }
    ctx = multiprocessing.get_context("spawn")
    pipe, far = ctx.Pipe()
    build_dir = os.path.join(ROOT, "build", "bench")
    maker = ctx.Process(
        target=chainproc.make, args=(far, build_dir, args.seed, params, args.blocks), daemon=True
    )
    maker.start()
    far.close()  # so that the chain process's death reads as EOFError here, not as a wait

    server = build_server(
        build_parser().parse_args(
            ["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0"]
        )
    )
    server.serve_in_background()
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=600)

    def line(**kw) -> None:
        snap = metrics.snapshot()
        full = snap["histograms"].get('runtime.gc_pause_seconds{generation="2"}', {})
        print(
            json.dumps(
                {
                    **kw,
                    "rss_mb": round(rss_mb(), 1),
                    "tenured_objects": gc.get_freeze_count(),
                    "walked_objects": len(gc.get_objects()),
                    "tenures": snap["counters"].get("runtime.gc_tenures", 0),
                    "deep": snap["counters"].get("runtime.gc_deep_collections", 0),
                    "evictions": {
                        k: v for k, v in snap["counters"].items() if "evictions" in k
                    },
                    "collections": [g["collections"] for g in gc.get_stats()],
                    "full_collections": full.get("count", 0),
                    "full_s": round(full.get("sum", 0.0), 3),
                }
            ),
            flush=True,
        )

    try:
        kind, _genesis = pipe.recv()
        if kind != "genesis":
            raise SystemExit(f"chain process sent {kind} first")
        t0, worst = time.monotonic(), 0.0
        for n in range(1, args.blocks + 1):
            _kind, _block, body = pipe.recv()
            del _block
            t = time.monotonic()
            conn.request("POST", "/", body=body, headers={"Content-Type": "application/json"})
            reply = json.loads(conn.getresponse().read())
            worst = max(worst, time.monotonic() - t)
            status = reply["result"]["status"]
            if status != "VALID":
                raise SystemExit(f"block {n}: {status}: {reply}")
            if n % args.every == 0 or n == args.blocks:
                line(
                    requests=n,
                    elapsed_s=round(time.monotonic() - t0, 1),
                    worst_ms=round(worst * 1e3),
                )
                worst = 0.0
        # nobody is waiting now: let the policy's own idle tick run the deep
        # collection, without waiting out its five minutes
        collector.DEEP_INTERVAL_S = 0.0
        give_up = time.monotonic() + 60
        while (
            not metrics.snapshot()["counters"].get("runtime.gc_deep_collections")
            and time.monotonic() < give_up
        ):
            time.sleep(0.25)
        deep = metrics.snapshot()["histograms"].get(
            'runtime.gc_pause_seconds{generation="deep"}', {}
        )
        line(after="deep collection", deep_s=round(deep.get("sum", 0.0), 3))
    finally:
        conn.close()
        server.shutdown()
        maker.kill()
    print(
        json.dumps(
            {
                "after": "shutdown",
                "tenured_objects": gc.get_freeze_count(),
                "thresholds": gc.get_threshold(),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
