"""Slope-timing probe for the device keccak kernels (honest resident rate).

Per-invocation device time is isolated from the host<->device link by chaining k
data-dependent batch invocations inside ONE jit call and fitting the slope
between k=1 and k=257 (ground-truth-verified against a numpy u64 keccak
emulation of the full 257-deep chain — see git history of this round).

Usage: python scripts/pallas_probe.py [jnp|pallas|both] [N]
Env: PHANT_KECCAK_PALLAS_SUB to sweep tile height.
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def slope(kernel_fn, wd, nd, N, C, label, khi=257):
    @functools.partial(jax.jit, static_argnames=("k",))
    def chain(w, n, k):
        def body(_, carry):
            w_c, acc = carry
            out = kernel_fn(w_c, n, max_chunks=C)
            return (w_c ^ out[:, None, :1], acc ^ out)

        _, acc = jax.lax.fori_loop(0, k, body, (w, jnp.zeros((N, 8), jnp.uint32)))
        return acc[:1, :1]

    ts = {}
    for k in (1, khi):
        np.asarray(chain(wd, nd, k))
        best = 1e9
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(chain(wd, nd, k))
            best = min(best, time.perf_counter() - t0)
        ts[k] = best
    per = (ts[khi] - ts[1]) / (khi - 1)
    print(
        f"{label}: per-kernel {per * 1e3:.3f} ms -> {N / per / 1e6:.2f}M hashes/s "
        f"(k=1 {ts[1] * 1e3:.0f}ms, k={khi} {ts[khi] * 1e3:.0f}ms)"
    )
    return per


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    N = int(sys.argv[2]) if len(sys.argv) > 2 else 16384
    from phant_tpu.ops.keccak_jax import keccak256_chunked, pack_payloads

    rng = np.random.default_rng(17)
    payloads = [rng.bytes(int(rng.integers(32, 577))) for _ in range(N)]
    words, nchunks, _ = pack_payloads(payloads, 5)
    wd, nd = jnp.asarray(words), jnp.asarray(nchunks)

    if which in ("pallas", "both"):
        import phant_tpu.ops.keccak_pallas as kp

        sub = os.environ.get("PHANT_KECCAK_PALLAS_SUB", "8")
        slope(kp.keccak256_chunked_pallas, wd, nd, N, 5, f"pallas SUB={sub}")
    if which in ("jnp", "both"):
        slope(keccak256_chunked, wd, nd, N, 5, "jnp")


if __name__ == "__main__":
    main()
