"""Batched keccak256 as a hand-tiled Pallas TPU kernel.

This kernel keeps the whole sponge state in VMEM/vregs for the entire
absorb loop: one grid step owns a tile of SUB*128 hash instances, reads
their padded rate chunks once from its VMEM block, and writes only the
8-word digests back.  Slope-timed on a v5e-1 it does 44.4M hashes/s at
MPT node shapes (~13.5 GB/s of keccak input) — 1.25x the jnp/XLA program
in ops/keccak_jax.py and ~34x the host 8-way AVX-512 batch — figures
from before PR 5, taken by chaining data-dependent batches in one
dispatch: a forced readback per call
times the host<->device round trip, not the ~0.4ms kernel.

Layout: instances are laid across (sublane, lane) = (SUB, 128) tiles —
each Keccak lane half is a full (SUB, 128) u32 vector, so every bitwise
op in the round function is a dense VPU op with zero cross-lane traffic
(Keccak's permutation never mixes instances; rotations are static shifts
within each u32 pair).

Differential-tested bit-exactly against the CPU/native backends
(tests/test_keccak_pallas.py).  Reference scope equivalence:
src/crypto/hasher.zig:4-17 — the batching axis and the device path are
this framework's addition per the north star (SURVEY §7.8a).
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from phant_tpu.ops.keccak_jax import (
    _RC_HI,
    _RC_LO,
    _ROT,
    RATE_WORDS,
    _rotl64,
)

# instances per grid step = SUB * 128.  8 sublanes is the native u32 tile
# and the measured winner: the slope-timed sweep on a v5e-1 (16384-instance
# 5-chunk batch, ground-truth-verified chained timing) measured SUB=8/16/32
# at 44.4 / 40.5 / 33.0 M hashes/s.
import os as _os

_SUB = int(_os.environ.get("PHANT_KECCAK_PALLAS_SUB", "8"))

# interpreter mode: lets the CPU-mesh test suite differentially verify the
# kernel body without Mosaic/TPU (slow — tests only)
_INTERPRET = _os.environ.get("PHANT_PALLAS_INTERPRET", "0") == "1"


def _round_body(lo: List, hi: List, rc_lo, rc_hi) -> None:
    """One Keccak-f[1600] round, in place; RC is a traced scalar.

    Same structure as keccak_jax._keccak_round.  Kept as the fori_loop
    body: unrolling all 24 rounds per chunk blows the kernel past ~25k
    vector ops, where Mosaic's scheduling falls off a ~400x cliff
    (measured on a v5e-1: C=2 unrolled 240M perms/s, C=3 unrolled 0.7M).
    """
    # theta
    clo = [lo[x] ^ lo[x + 5] ^ lo[x + 10] ^ lo[x + 15] ^ lo[x + 20] for x in range(5)]
    chi_ = [hi[x] ^ hi[x + 5] ^ hi[x + 10] ^ hi[x + 15] ^ hi[x + 20] for x in range(5)]
    for x in range(5):
        r1lo, r1hi = _rotl64(clo[(x + 1) % 5], chi_[(x + 1) % 5], 1)
        dlo = clo[(x - 1) % 5] ^ r1lo
        dhi = chi_[(x - 1) % 5] ^ r1hi
        for y in range(5):
            lo[x + 5 * y] = lo[x + 5 * y] ^ dlo
            hi[x + 5 * y] = hi[x + 5 * y] ^ dhi
    # rho + pi
    blo: List = [None] * 25
    bhi: List = [None] * 25
    for x in range(5):
        for y in range(5):
            src = x + 5 * y
            dst = y + 5 * ((2 * x + 3 * y) % 5)
            blo[dst], bhi[dst] = _rotl64(lo[src], hi[src], _ROT[src])
    # chi
    for y in range(5):
        row_lo = [blo[x + 5 * y] for x in range(5)]
        row_hi = [bhi[x + 5 * y] for x in range(5)]
        for x in range(5):
            lo[x + 5 * y] = row_lo[x] ^ (~row_lo[(x + 1) % 5] & row_lo[(x + 2) % 5])
            hi[x + 5 * y] = row_hi[x] ^ (~row_hi[(x + 1) % 5] & row_hi[(x + 2) % 5])
    # iota
    lo[0] = lo[0] ^ rc_lo
    hi[0] = hi[0] ^ rc_hi


def _f1600(lo: List, hi: List, rc_ref) -> tuple:
    """24 rounds as a fori_loop carrying the 50-vector state in vregs."""

    def body(rnd, carry):
        lo_t, hi_t = carry
        lo_l, hi_l = list(lo_t), list(hi_t)
        _round_body(lo_l, hi_l, rc_ref[rnd, 0], rc_ref[rnd, 1])
        return (tuple(lo_l), tuple(hi_l))

    lo_t, hi_t = jax.lax.fori_loop(0, 24, body, (tuple(lo), tuple(hi)))
    return list(lo_t), list(hi_t)


def _make_kernel(max_chunks: int):
    def kernel(words_ref, nch_ref, rc_ref, out_ref):
        # words_ref: (1, C, 34, SUB, 128) u32 — rate chunks, word-major
        # nch_ref:   (1, SUB, 128) i32     — live chunk count per instance
        # rc_ref:    (24, 2) u32 in SMEM   — round constants (lo, hi)
        # out_ref:   (1, 8, SUB, 128) u32  — digest words
        nch = nch_ref[0]
        zeros = jnp.zeros((_SUB, 128), jnp.uint32)
        lo = [zeros] * 25
        hi = [zeros] * 25
        for c in range(max_chunks):
            nlo = list(lo)
            nhi = list(hi)
            for i in range(RATE_WORDS):
                nlo[i] = nlo[i] ^ words_ref[0, c, 2 * i]
                nhi[i] = nhi[i] ^ words_ref[0, c, 2 * i + 1]
            nlo, nhi = _f1600(nlo, nhi, rc_ref)
            if c == 0:
                lo, hi = nlo, nhi  # every payload has >= 1 chunk
            else:
                live = nch > c
                lo = [jnp.where(live, n, o) for n, o in zip(nlo, lo)]
                hi = [jnp.where(live, n, o) for n, o in zip(nhi, hi)]
        for i in range(4):
            out_ref[0, 2 * i] = lo[i]
            out_ref[0, 2 * i + 1] = hi[i]

    return kernel


@functools.partial(jax.jit, static_argnames=("max_chunks",))
def keccak256_chunked_pallas(
    words: jax.Array, nchunks: jax.Array, *, max_chunks: int
) -> jax.Array:
    """Drop-in for keccak_jax.keccak256_chunked on the Pallas path.

    Args:
      words: (B, max_chunks, 34) uint32 — keccak-padded rate chunks (LE u32).
      nchunks: (B,) int32 — live chunks per instance (>= 1).
      max_chunks: static bucket bound.

    Returns:
      (B, 8) uint32 digests, bit-identical to the jnp and CPU backends.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = words.shape[0]
    C = max_chunks
    tile = _SUB * 128
    Bp = -(-B // tile) * tile  # pad batch to a whole number of tiles
    if Bp != B:
        words = jnp.pad(words, ((0, Bp - B), (0, 0), (0, 0)))
        # padded instances absorb chunk 0 of zeros (harmless, discarded)
        nchunks = jnp.pad(nchunks, (0, Bp - B), constant_values=1)
    nt = Bp // tile
    # instance b = (t, s, l): words -> (NT, C, 34, SUB, 128), one transpose
    # on device (cheap, HBM-bandwidth) so each kernel read is a dense tile
    w = words.reshape(nt, _SUB, 128, C, 34).transpose(0, 3, 4, 1, 2)
    n = nchunks.astype(jnp.int32).reshape(nt, _SUB, 128)
    rc = jnp.asarray(np.stack([_RC_LO, _RC_HI], axis=1))  # (24, 2) u32

    out = pl.pallas_call(
        _make_kernel(C),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec(
                (1, C, 34, _SUB, 128),
                lambda t: (t, 0, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((1, _SUB, 128), lambda t: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((24, 2), lambda t: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, 8, _SUB, 128), lambda t: (t, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        # the output varies over the same manual mesh axes as the input
        # (shard_map's check_vma needs it said; empty outside shard_map)
        out_shape=jax.ShapeDtypeStruct(
            (nt, 8, _SUB, 128), jnp.uint32, vma=jax.typeof(w).vma
        ),
        interpret=_INTERPRET,
    )(w, n, rc)
    return out.transpose(0, 2, 3, 1).reshape(Bp, 8)[:B]


def pallas_available() -> bool:
    """Whether the device keccak is this Pallas kernel.

    On the `tpu` platform it IS, unconditionally: there is no trial run
    whose failure could quietly select the jnp program instead — a Mosaic
    refusal surfaces from the first real dispatch and propagates. (The
    trial run this replaces was also first reached INSIDE a jit trace —
    the ecrecover and witness programs call keccak256_chunked_auto
    mid-graph — where it handed back a tracer, raised, and was swallowed:
    on the chip every lane had been running the jnp program, PR 24.)
    `False` only on a platform without Mosaic and without interpret mode
    (the CPU-mesh test backend, where callers run the jnp kernel).
    """
    return _INTERPRET or jax.default_backend() == "tpu"
