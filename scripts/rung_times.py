#!/usr/bin/env python3
"""Time each served device program on each rung of its ladder.

    python3 scripts/rung_times.py [--sig-rungs 256,512,1024] [--reps 20]

On the device this process has (the chip, through the chip tool; it fails
on the CPU unless PHANT_ALLOW_JAX_CPU=1): the resident table's update and
gather on every rung of `ROW_LADDER` and its verdict on every rung of
`VERDICT_LADDER`, on a table at the cap a server's is born at and on empty
rows (`ResidentTable.prewarm`'s launches: the programs are branch-free, so
a launch costs what a full one costs), and `ecrecover_kernel` on the rungs
named (default: `SIG_LADDER`; a rung outside it is what the question
"what would a wider rung buy" needs: 104-131 s of backend compile each on
an empty cache). For each: seconds to the first result (the build or the
cache load) and milliseconds a launch over `--reps` launches queued
together and waited for once, by the host's clock: device time plus the
launch overhead, not a device trace's number. One JSON line at the end.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def timed(launch, reps: int) -> dict:
    import jax

    t0 = time.monotonic()
    jax.block_until_ready(launch())
    first_s = time.monotonic() - t0
    t0 = time.monotonic()
    jax.block_until_ready([launch() for _ in range(reps)])
    return {"first_s": round(first_s, 3), "ms_a_launch": round((time.monotonic() - t0) / reps * 1e3, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sig-rungs", default="", help="comma-separated signature rows (default: SIG_LADDER)")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.ops import witness_resident as wr
    from phant_tpu.ops.secp256k1_jax import SIG_LADDER, ecrecover_kernel, ints_to_limbs

    set_crypto_backend("tpu")
    out = {"device": jax.devices()[0].device_kind, "reps": args.reps}
    table = wr.ResidentTable()
    with table._lock:
        table._grow_locked(0)
    out["table_rows"] = table._cap
    for rung in wr.ROW_LADDER:
        none = np.full(rung, -1, np.int32)
        words = np.zeros((rung, wr._ROW_BYTES // 4), np.uint32)
        lens = np.zeros(rung, np.int32)
        out[f"update {rung}"] = timed(lambda: table._launch_update(words, lens, none), args.reps)
        out[f"gather {rung}"] = timed(lambda: table._launch_gather(none), args.reps)
    for rows, blocks in wr.VERDICT_LADDER:
        none, ids = np.full(rows, -1, np.int32), np.zeros(rows, np.int32)
        roots = np.zeros((blocks, 8), np.uint32)
        out[f"verdict {rows}x{blocks}"] = timed(lambda: table._launch_verdict(none, ids, roots), args.reps)
        print(json.dumps(out), file=sys.stderr, flush=True)
    for rung in [int(r) for r in args.sig_rungs.split(",") if r] or SIG_LADDER:
        ones = jnp.asarray(ints_to_limbs([1] * rung))
        parity = jnp.zeros(rung, jnp.uint32)
        out[f"ecrecover {rung}"] = timed(lambda: ecrecover_kernel(ones, ones, ones, parity), args.reps)
        print(json.dumps(out), file=sys.stderr, flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
