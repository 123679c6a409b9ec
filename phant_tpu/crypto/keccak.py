"""Keccak-256 (the pre-NIST Keccak with 0x01 domain padding, as used by Ethereum).

Three backends, selected transparently:

1. ``native``  — C++ implementation in native/keccak.cc. This is the CPU
                 fast path (the reference links ethash's C keccak for evmone
                 and uses Zig std's Keccak256 for the client side, reference:
                 build.zig:94, src/crypto/hasher.zig:1-17). The scalar
                 `keccak256` enters it through the CPython extension
                 (native/pyext.cc `keccak256`, utils/native.load_ext): a
                 short input is hashed with the interpreter lock held, one of
                 `KECCAK_UNLOCK_BYTES` or more with it released, and
                 `native.keccak_calls{lock=held|released}` counts both. A
                 served request makes about a thousand scalar hashes of
                 under 640 bytes; through `ctypes`, which gives the lock away
                 at every call, each one queued behind the other handlers
                 (PERF.md section 7 p). Where the extension does not load
                 (no Python headers) the `ctypes` library serves; the batch
                 entries are the `ctypes` library's either way.
2. ``python``  — pure-Python fallback, also the readable spec used to
                 differential-test the native and TPU paths.
3. the TPU path lives in phant_tpu/ops/keccak_jax.py and is batched; this
   module is the scalar/host-side API mirroring hasher.zig's
   `keccak256` / `keccak256WithPrefix` (reference: src/crypto/hasher.zig:4-17).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from phant_tpu.utils.native import load_ext, load_native

RATE = 136  # bytes; keccak-256 rate (1600 - 2*256 bits)

_KECCAK_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A, 0x8000000080008000,
    0x000000000000808B, 0x0000000080000001, 0x8000000080008081, 0x8000000000008009,
    0x000000000000008A, 0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089, 0x8000000000008003,
    0x8000000000008002, 0x8000000000000080, 0x000000000000800A, 0x800000008000000A,
    0x8000000080008081, 0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]

# Rotation offsets r[x][y] for lane A[x, y].
_ROT = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]

_MASK = (1 << 64) - 1


def _rotl(value: int, shift: int) -> int:
    if shift == 0:
        return value
    return ((value << shift) | (value >> (64 - shift))) & _MASK


def keccak_f1600(lanes: List[int]) -> List[int]:
    """Keccak-f[1600] permutation over 25 lanes indexed A[x + 5*y]."""
    a = lanes
    for rnd in range(24):
        # theta
        c = [a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rotl(c[(x + 1) % 5], 1) for x in range(5)]
        a = [a[i] ^ d[i % 5] for i in range(25)]
        # rho + pi: B[y, 2x+3y] = rot(A[x, y])
        b = [0] * 25
        for x in range(5):
            for y in range(5):
                b[y + 5 * ((2 * x + 3 * y) % 5)] = _rotl(a[x + 5 * y], _ROT[x][y])
        # chi
        a = [
            b[x + 5 * y] ^ ((~b[(x + 1) % 5 + 5 * y] & _MASK) & b[(x + 2) % 5 + 5 * y])
            for y in range(5)
            for x in range(5)
        ]
        # note: list comprehension above iterates x fastest -> index x + 5*y
        a[0] ^= _KECCAK_RC[rnd]
    return a


def pad_keccak(data: bytes, rate: int = RATE) -> bytes:
    """Multi-rate padding with the Keccak (0x01 ... 0x80) domain byte."""
    pad_len = rate - (len(data) % rate)
    if pad_len == 1:
        return data + b"\x81"
    return data + b"\x01" + b"\x00" * (pad_len - 2) + b"\x80"


def _keccak256_python(data: bytes) -> bytes:
    padded = pad_keccak(data)
    lanes = [0] * 25
    for chunk_start in range(0, len(padded), RATE):
        chunk = padded[chunk_start : chunk_start + RATE]
        for i in range(RATE // 8):
            lanes[i] ^= int.from_bytes(chunk[8 * i : 8 * i + 8], "little")
        lanes = keccak_f1600(lanes)
    out = b"".join(lane.to_bytes(8, "little") for lane in lanes[:4])
    return out


_native = load_native()
# what hashes is chosen by what loaded, as the EVM's binding is:
# PHANT_ENGINE_EXT masks the witness driver and the node encoder, not this
_ext_keccak256 = getattr(load_ext(), "keccak256", None)


def keccak256(data: bytes) -> bytes:
    """keccak256 over bytes (reference: src/crypto/hasher.zig:4-8): the
    extension's, else the `ctypes` library's, else the Python spec."""
    if _ext_keccak256 is not None:
        return _ext_keccak256(data)
    if _native is not None:
        return _native.keccak256(data)
    return _keccak256_python(data)


def keccak256_python(data: bytes) -> bytes:
    """Always the pure-Python path (for differential tests)."""
    return _keccak256_python(data)


def keccak256_with_prefix(prefix: int, data: bytes) -> bytes:
    """keccak256 of a one-byte prefix || data, for EIP-2718 typed-tx hashing
    (reference: src/crypto/hasher.zig:10-17)."""
    return keccak256(bytes([prefix]) + data)


def keccak256_batch(payloads: Sequence[bytes]) -> List[bytes]:
    """Hash many payloads on the selected backend: the TPU kernel when
    `--crypto_backend=tpu` (phant_tpu/ops/keccak_jax.py), else the CPU
    fast path (native loop if available)."""
    from phant_tpu.backend import crypto_backend

    if crypto_backend() == "tpu":
        from phant_tpu.ops.keccak_jax import keccak256_batch_jax

        return keccak256_batch_jax(payloads)
    return keccak256_batch_cpu(payloads)


def keccak256_batch_cpu(payloads: Sequence[bytes]) -> List[bytes]:
    """Always the CPU path (native loop if available) — the baseline side
    of CPU-vs-TPU differential tests."""
    if _native is not None:
        return _native.keccak256_batch_fast(payloads)
    return [_keccak256_python(p) for p in payloads]


EMPTY_KECCAK = bytes.fromhex(
    "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
)
