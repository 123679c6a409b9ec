"""Batched keccak256 on TPU via JAX (bit-sliced, u32 lane pairs).

This is the device half of the crypto hot loop (BASELINE.md config #2):
keccak256 over thousands of variable-length payloads at once. TPUs have no
64-bit integer lanes, so each Keccak lane is a (lo, hi) pair of uint32
vectors of shape (B,); the whole f[1600] permutation is unrolled (static
rotations become shifts XLA fuses into a single elementwise program).

Variable lengths are handled host-side by padding into a fixed number of
136-byte rate chunks (`pack_payloads`); absorption of chunk c is masked per
instance by `c < nchunks`, so one compiled program serves every payload
length up to the bucket bound. Differential-tested bit-exactly against the
CPU backends (tests/test_keccak_jax.py).

Reference scope equivalence: src/crypto/hasher.zig:4-17 (scalar CPU hashing)
— the batching axis is this framework's addition per the north star.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from phant_tpu.crypto.keccak import RATE, _KECCAK_RC as _RC

RATE_WORDS = RATE // 8  # 17 lanes absorbed per chunk

# rotation offset for lane x+5y (same table as native/keccak.cc kRot).
# A tuple, not a list: this is traced into the jitted kernels, and a
# mutable table read at trace time is a stale-closure hazard (JITHYGIENE)
_ROT = (
    0, 1, 62, 28, 27,
    36, 44, 6, 55, 20,
    3, 10, 43, 25, 39,
    41, 45, 15, 21, 8,
    18, 2, 61, 56, 14,
)


def _rotl64(lo, hi, r: int):
    """Rotate a 64-bit lane stored as (lo, hi) u32 pair by static r."""
    r %= 64
    if r == 0:
        return lo, hi
    if r == 32:
        return hi, lo
    if r < 32:
        nlo = (lo << r) | (hi >> (32 - r))
        nhi = (hi << r) | (lo >> (32 - r))
        return nlo, nhi
    r -= 32
    nlo = (hi << r) | (lo >> (32 - r))
    nhi = (lo << r) | (hi >> (32 - r))
    return nlo, nhi


_RC_LO = np.array([rc & 0xFFFFFFFF for rc in _RC], dtype=np.uint32)
_RC_HI = np.array([rc >> 32 for rc in _RC], dtype=np.uint32)


def _keccak_round(lo: List, hi: List, rc_lo, rc_hi) -> Tuple[List, List]:
    """One Keccak-f round; rotations are static, the round constant is traced."""
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
    blo = [None] * 25
    bhi = [None] * 25
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
    return lo, hi


def keccak_f1600_loop(lo: List, hi: List) -> Tuple[List, List]:
    """f[1600] as a fori_loop over rounds (compiles 24x smaller than unrolled)."""
    rc_lo = jnp.asarray(_RC_LO)
    rc_hi = jnp.asarray(_RC_HI)

    def body(rnd, carry):
        lo_t, hi_t = carry
        nlo, nhi = _keccak_round(list(lo_t), list(hi_t), rc_lo[rnd], rc_hi[rnd])
        return (tuple(nlo), tuple(nhi))

    lo_t, hi_t = jax.lax.fori_loop(0, 24, body, (tuple(lo), tuple(hi)))
    return list(lo_t), list(hi_t)


@functools.partial(jax.jit, static_argnames=("max_chunks",))
def keccak256_chunked(words: jax.Array, nchunks: jax.Array, *, max_chunks: int) -> jax.Array:
    """Batched keccak256.

    Args:
      words: (B, max_chunks, 34) uint32 — payloads already keccak-padded and
        split into 136-byte rate chunks, little-endian u32 words.
      nchunks: (B,) int32 — number of real chunks per instance (>=1).
      max_chunks: static bucket bound.

    Returns:
      (B, 8) uint32 — digests as little-endian u32 words.
    """
    # derive the zero state from the input so it inherits the input's
    # varying-manual-axes under shard_map (a fresh constant would be
    # replicated and break the fori_loop carry typing)
    zeros = words[:, 0, 0] ^ words[:, 0, 0]
    lo = [zeros] * 25
    hi = [zeros] * 25
    for c in range(max_chunks):
        live = nchunks > c  # (B,) — instances still absorbing at chunk c
        # absorb chunk c where live
        new_lo = list(lo)
        new_hi = list(hi)
        for i in range(RATE_WORDS):
            new_lo[i] = lo[i] ^ words[:, c, 2 * i]
            new_hi[i] = hi[i] ^ words[:, c, 2 * i + 1]
        new_lo, new_hi = keccak_f1600_loop(new_lo, new_hi)
        lo = [jnp.where(live, n, o) for n, o in zip(new_lo, lo)]
        hi = [jnp.where(live, n, o) for n, o in zip(new_hi, hi)]
    out = []
    for i in range(4):
        out.append(lo[i])
        out.append(hi[i])
    return jnp.stack(out, axis=1)


def keccak256_chunked_auto(
    words: jax.Array, nchunks: jax.Array, *, max_chunks: int
) -> jax.Array:
    """Device keccak dispatch: the Pallas kernel on the `tpu` platform
    (where it IS the keccak — a Mosaic failure propagates, see
    keccak_pallas.pallas_available), the jnp program on the CPU platform
    (CPU-mesh tests without interpret mode).  Same contract and
    bit-identical output on both paths; composes inside jit (the fused
    witness/ecrecover programs call this mid-graph)."""
    from phant_tpu.ops.keccak_pallas import keccak256_chunked_pallas, pallas_available

    if pallas_available():
        return keccak256_chunked_pallas(words, nchunks, max_chunks=max_chunks)
    return keccak256_chunked(words, nchunks, max_chunks=max_chunks)


# ---------------------------------------------------------------------------
# host-side packing
# ---------------------------------------------------------------------------


def pad_payload(data: bytes, nchunks: int) -> bytes:
    """Keccak multi-rate padding into exactly nchunks rate blocks."""
    total = nchunks * RATE
    padded = bytearray(total)
    padded[: len(data)] = data
    padded[len(data)] ^= 0x01
    padded[total - 1] ^= 0x80
    return bytes(padded)


def chunks_for_len(n: int) -> int:
    """Chunks needed for an n-byte payload (padding always adds >=1 bit)."""
    return n // RATE + 1


def pack_payloads(
    payloads: Sequence[bytes], max_chunks: int | None = None
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pack variable-length payloads into the fixed-shape device layout.

    Returns (words (B, C, 34) u32, nchunks (B,) i32, C)."""
    B = len(payloads)
    need = [chunks_for_len(len(p)) for p in payloads]
    if max_chunks is not None:
        C = max_chunks
    else:
        # round the bucket up to a power of two so repeated ad-hoc calls hit a
        # small set of compiled shapes instead of retracing per max length
        worst = max(need, default=1)
        C = 1
        while C < worst:
            C *= 2
    if max(need, default=1) > C:
        raise ValueError(f"payload needs {max(need)} chunks > bucket bound {C}")
    from phant_tpu.utils.native import load_native

    native = load_native()
    if native is not None:
        # native C-ABI packer (the new framework's glue.c equivalent)
        buf, nchunks = native.pack_keccak(payloads, C)
    else:
        buf = np.zeros((B, C * RATE), dtype=np.uint8)
        nchunks = np.zeros((B,), dtype=np.int32)
        for i, p in enumerate(payloads):
            k = chunks_for_len(len(p))
            nchunks[i] = k
            buf[i, : k * RATE] = np.frombuffer(pad_payload(p, k), dtype=np.uint8)
    words = buf.reshape(B, C, RATE).view(np.uint32).reshape(B, C, 34)
    return words, nchunks, C


def digests_to_bytes(digests: np.ndarray) -> List[bytes]:
    """(B, 8) u32 LE words -> list of 32-byte digests."""
    arr = np.asarray(digests, dtype="<u4")
    return [arr[i].tobytes() for i in range(arr.shape[0])]


class DeviceDigests:
    """An UNRESOLVED batched-keccak dispatch: the device is (possibly
    still) computing; `resolve()` performs the host readback — the honest
    sync — and returns the digest list. The same async-dispatch shape as
    secp256k1_jax.ecrecover_batch_async: enqueue now, pay the sync later,
    so callers (the witness engine's pipelined resolve stage) overlap
    host work of batch N+1 with device compute of batch N.

    `on_resolve` (optional) runs after the readback — the witness engine
    uses it to return its staging buffers to the reuse pool only once the
    device can no longer be reading them."""

    __slots__ = ("out", "n", "on_resolve")

    def __init__(self, out, n: int, on_resolve=None):
        self.out = out  # (B, 8) u32 device array, B >= n
        self.n = n
        self.on_resolve = on_resolve

    def resolve(self) -> List[bytes]:
        from phant_tpu.utils.trace import device_host, metrics

        with metrics.phase("keccak.host_readback"):
            # the timed readback IS the honest sync (see phase name)
            with device_host("witness", "sync"):
                words = np.asarray(self.out)  # phantlint: disable=HOSTSYNC — timed digest readback
            digests = digests_to_bytes(words)[: self.n]
        if self.on_resolve is not None:
            # fire ONCE: a second resolve() returning the same staging
            # lease to the pool twice would alias buffers across batches
            cb, self.on_resolve = self.on_resolve, None
            cb()
        return digests


def keccak256_batch_jax_async(
    payloads: Sequence[bytes], max_chunks: int | None = None
) -> DeviceDigests:
    """Enqueue a batched keccak on the device WITHOUT any host sync:
    returns a DeviceDigests handle whose `resolve()` pays the readback.
    `keccak256_batch_jax` is this plus an immediate resolve."""
    from phant_tpu.utils.trace import device_host, metrics

    platform = jax.default_backend()
    metrics.count("keccak.batches", backend=platform)
    metrics.count("keccak.bytes", sum(map(len, payloads)), backend=platform)
    words, nchunks, C = pack_payloads(payloads, max_chunks)
    with metrics.phase("keccak.device_dispatch"), device_host("witness", "enqueue"):
        out = keccak256_chunked_auto(
            jnp.asarray(words), jnp.asarray(nchunks), max_chunks=C
        )
    return DeviceDigests(out, len(payloads))


def keccak256_batch_jax(payloads: Sequence[bytes], max_chunks: int | None = None) -> List[bytes]:
    """Convenience end-to-end helper (host pack -> device hash -> bytes).

    Dispatches through keccak256_chunked_auto (Pallas on real TPUs).
    Counts batches/bytes per device platform and splits the upload+dispatch
    timer from the forced-readback timer in the metrics registry."""
    if not payloads:
        return []
    return keccak256_batch_jax_async(payloads, max_chunks).resolve()


# ---------------------------------------------------------------------------
# device-resident digest index (open addressing over digest fingerprints)
#
# The primitives behind the device-resident intern table
# (ops/witness_resident.py): a flat power-of-two bucket array maps a
# 64-bit digest FINGERPRINT (the first two little-endian digest words —
# crypto-derived, so uniformly distributed) to a resident row slot, with
# linear probing. Insertion is vectorized first-empty-claim via scatter-min
# (lowest slot id wins a contested bucket; losers retry the next probe
# position), so a whole novel batch inserts in INDEX_PROBES fused rounds
# with zero host round trips. Lookup probes the same fixed sequence and
# verifies the full 64-bit fingerprint against the per-row `fps` store —
# a miss (or a fingerprint past the probe bound) resolves to -1, which the
# resident verdict treats as NOT PRESENT (the block fails, never silently
# passes). These compose inside jit: the resident update/verdict programs
# call them mid-graph exactly like keccak256_chunked_auto.
# ---------------------------------------------------------------------------

#: bucket value marking an empty index slot. Chosen LARGE (not -1) so the
#: claim scatter can be a pure `.at[pos].min(slot)` — min(occupied, EMPTY)
#: keeps the occupant, min(EMPTY, slot) claims, and a contested bucket
#: deterministically goes to the lowest slot id.
INDEX_EMPTY = 1 << 30

#: probe-sequence bound (the most rounds an insert or a lookup makes).
#: With the index sized
#: at 4x the row capacity (load factor <= 0.25; measured: 2x/16 probes
#: dropped 17 of 32k inserts — linear-probe clusters grow fast with
#: load), clusters beyond this bound are vanishingly rare; inserts that
#: exhaust it are COUNTED (dropped), and a dropped row simply misses on
#: device lookup — the host row path never depends on the index.
INDEX_PROBES = 32


def fingerprint_mix(d0: jax.Array, d1: jax.Array) -> jax.Array:
    """(N,) u32 bucket hash of a 64-bit fingerprint (murmur3 finalizer
    over the two u32 halves). Pure lane math — stays on device."""
    h = d0 ^ (d1 * jnp.uint32(0x9E3779B9))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def index_insert(
    index: jax.Array, new_fps: jax.Array, slots: jax.Array, live: jax.Array
):
    """Insert fingerprint->slot entries into the open-addressed index.

    index: (nslots,) int32 buckets (INDEX_EMPTY = free), nslots a power
      of two. new_fps: (N, 2) u32 fingerprints. slots: (N,) int32 row
      slots. live: (N,) bool — padding rows never insert.

    Returns (index, dropped): dropped counts rows still unplaced after
    INDEX_PROBES rounds (they stay resident by ROW — only device-side
    lookup misses them). The rounds stop when nothing is pending: at the
    table's load (under 0.25) one or two place a whole batch."""
    mask = jnp.uint32(index.shape[0] - 1)
    h = fingerprint_mix(new_fps[:, 0], new_fps[:, 1])
    empty = jnp.int32(INDEX_EMPTY)

    def body(carry):
        # a loop, not an unrolled Python loop: one compiled body (the
        # unrolled form made XLA chew through PROBES scatter/gather
        # rounds at trace time — minutes of compile on the CPU backend)
        rnd, index, pending = carry
        pos = ((h + rnd) & mask).astype(jnp.int32)
        cur = index[pos]
        want = pending & (cur >= empty)
        bid = jnp.where(want, slots, empty)
        index = index.at[pos].min(bid)
        won = want & (index[pos] == slots)
        return rnd + 1, index, pending & ~won

    _, index, pending = jax.lax.while_loop(
        lambda c: (c[0] < INDEX_PROBES) & c[2].any(),
        body,
        (jnp.uint32(0), index, live),
    )
    return index, pending.sum(dtype=jnp.int32)


def index_lookup(index: jax.Array, fps: jax.Array, q: jax.Array) -> jax.Array:
    """(B,) int32 resident slots for query fingerprints `q` (B, 2), or -1
    when absent. `fps` is the per-row (cap, 2) fingerprint store; a probe
    hit requires FULL 64-bit fingerprint equality, so a bucket holding a
    colliding-bucket neighbor just advances the probe."""
    cap = fps.shape[0]
    mask = jnp.uint32(index.shape[0] - 1)
    h = fingerprint_mix(q[:, 0], q[:, 1])
    empty = jnp.int32(INDEX_EMPTY)

    def body(rnd, found):
        pos = ((h + rnd.astype(jnp.uint32)) & mask).astype(jnp.int32)
        s = index[pos]
        sc = jnp.clip(s, 0, cap - 1)
        match = (s < empty) & (fps[sc, 0] == q[:, 0]) & (fps[sc, 1] == q[:, 1])
        return jnp.where((found < 0) & match, s, found)

    return jax.lax.fori_loop(
        0, INDEX_PROBES, body, jnp.full(q.shape[0], -1, jnp.int32)
    )
