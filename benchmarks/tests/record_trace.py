"""Record the small trace kept in tests/data/ (run once on the chip):
two jitted programs, a pause between them, so that busy time, time by
program and one long idle gap are all there to reduce.

    python3 benchmarks/tests/record_trace.py <out.xplane.pb>
"""

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


@jax.jit
def witness_digests_standin(x):
    return (x @ x).sum()


@jax.jit
def other_program(x):
    return jnp.sort(x, axis=0).sum()


def main(out: str) -> None:
    x = jnp.ones((2048, 2048), jnp.float32)
    witness_digests_standin(x).block_until_ready()
    other_program(x).block_until_ready()
    with tempfile.TemporaryDirectory(dir=".") as d:
        jax.profiler.start_trace(d)
        for _ in range(3):
            witness_digests_standin(x).block_until_ready()
        time.sleep(0.2)
        other_program(x).block_until_ready()
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        shutil.copy(path, out)


if __name__ == "__main__":
    main(sys.argv[1])
