"""secp256k1 ECDSA: recover / sign / verify, pure Python CPU backend.

The reference binds bitcoin-core libsecp256k1 through a Zig wrapper
(reference: build.zig.zon:9-12, src/crypto/ecdsa.zig:10-36). Here the CPU
backend is a from-scratch implementation (correctness oracle + test signer);
the batched TPU backend lives in phant_tpu/ops/ecrecover_jax.py and is
differential-tested against this module. Not constant-time — consensus
verification only ever handles public data.
"""

from __future__ import annotations

import hashlib
import hmac
from dataclasses import dataclass
from typing import Optional, Tuple

# Curve: y^2 = x^3 + 7 over F_p
P = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

HALF_N = N // 2


class SignatureError(ValueError):
    """Invalid signature field or unrecoverable point."""


Point = Optional[Tuple[int, int]]  # None = point at infinity (affine)


def _inv(a: int, m: int) -> int:
    # extended Euclid in C (~10x the Fermat exponentiation it replaces);
    # 0 maps to 0 exactly as pow(0, m - 2, m) did
    a %= m
    return pow(a, -1, m) if a else 0


def _point_add(p1: Point, p2: Point) -> Point:
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        # doubling
        lam = (3 * x1 * x1) * _inv(2 * y1, P) % P
    else:
        lam = (y2 - y1) * _inv(x2 - x1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    y3 = (lam * (x1 - x3) - y1) % P
    return (x3, y3)


def _point_mul(k: int, point: Point) -> Point:
    result: Point = None
    addend = point
    while k:
        if k & 1:
            result = _point_add(result, addend)
        addend = _point_add(addend, addend)
        k >>= 1
    return result


def _lift_x(x: int, y_odd: bool) -> Tuple[int, int]:
    """Recover (x, y) on the curve from x and y-parity; p ≡ 3 (mod 4) so the
    square root is a single exponentiation."""
    y_sq = (pow(x, 3, P) + 7) % P
    y = pow(y_sq, (P + 1) // 4, P)
    if (y * y) % P != y_sq:
        raise SignatureError("x is not on the curve")
    if bool(y & 1) != y_odd:
        y = P - y
    return (x, y)


def validate_signature_fields(r: int, s: int, *, require_low_s: bool = True) -> None:
    """r/s range checks + EIP-2 low-s malleability rule
    (reference: src/crypto/ecdsa.zig:28-36)."""
    if not (1 <= r < N):
        raise SignatureError("r out of range")
    if not (1 <= s < N):
        raise SignatureError("s out of range")
    if require_low_s and s > HALF_N:
        raise SignatureError("s too high (EIP-2)")


def recover_pubkey_python(msg_hash: bytes, r: int, s: int, recovery_id: int) -> bytes:
    """Pure-Python ecrecover (the readable oracle for the native and TPU
    backends) -> 65-byte uncompressed pubkey (0x04 || X || Y)."""
    if recovery_id not in (0, 1, 2, 3):
        raise SignatureError(f"bad recovery id {recovery_id}")
    validate_signature_fields(r, s, require_low_s=False)
    x = r + (N if recovery_id >= 2 else 0)
    if x >= P:
        raise SignatureError("r + jN exceeds field")
    R = _lift_x(x, bool(recovery_id & 1))
    z = int.from_bytes(msg_hash, "big") % N
    r_inv = _inv(r, N)
    # Q = r^-1 (s*R - z*G)
    sR = _point_mul(s, R)
    zG = _point_mul(z, (GX, GY))
    neg_zG = None if zG is None else (zG[0], (P - zG[1]) % P)
    Q = _point_mul(r_inv, _point_add(sR, neg_zG))
    if Q is None:
        raise SignatureError("recovered point at infinity")
    return b"\x04" + Q[0].to_bytes(32, "big") + Q[1].to_bytes(32, "big")


def recover_pubkey(msg_hash: bytes, r: int, s: int, recovery_id: int) -> bytes:
    """ecrecover -> 65-byte uncompressed pubkey (0x04 || X || Y); native C++
    fast path when the toolchain is available (reference links C
    libsecp256k1 the same way, src/crypto/ecdsa.zig:19-26)."""
    from phant_tpu.utils.native import load_native

    native = load_native()
    # the C side reads exactly 32 bytes; odd-length hashes (legal for the
    # Python path, which treats them as big-endian ints) stay in Python
    if native is not None and len(msg_hash) == 32:
        if recovery_id not in (0, 1, 2, 3):
            raise SignatureError(f"bad recovery id {recovery_id}")
        if not (0 <= r < 2**256 and 0 <= s < 2**256):
            raise SignatureError("r/s out of u256 range")
        pub = native.ecrecover(msg_hash, r, s, recovery_id)
        if pub is None:
            raise SignatureError("unrecoverable signature")
        return b"\x04" + pub
    return recover_pubkey_python(msg_hash, r, s, recovery_id)


def _rfc6979_k(msg_hash: bytes, private_key: int) -> int:
    """Deterministic nonce (RFC 6979, HMAC-SHA256)."""
    x = private_key.to_bytes(32, "big")
    h1 = msg_hash
    v = b"\x01" * 32
    k = b"\x00" * 32
    k = hmac.new(k, v + b"\x00" + x + h1, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    k = hmac.new(k, v + b"\x01" + x + h1, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    while True:
        v = hmac.new(k, v, hashlib.sha256).digest()
        candidate = int.from_bytes(v, "big")
        if 1 <= candidate < N:
            return candidate
        k = hmac.new(k, v + b"\x00", hashlib.sha256).digest()
        v = hmac.new(k, v, hashlib.sha256).digest()


def sign(msg_hash: bytes, private_key: int) -> Tuple[int, int, int]:
    """Returns (r, s, y_parity) with low-s normalization
    (reference: src/crypto/ecdsa.zig:23-26)."""
    if not (1 <= private_key < N):
        raise SignatureError("private key out of range")
    z = int.from_bytes(msg_hash, "big") % N
    while True:
        k = _rfc6979_k(msg_hash, private_key)
        R = _point_mul(k, (GX, GY))
        assert R is not None
        r = R[0] % N
        if r == 0:
            msg_hash = hashlib.sha256(msg_hash).digest()
            continue
        s = _inv(k, N) * (z + r * private_key) % N
        if s == 0:
            msg_hash = hashlib.sha256(msg_hash).digest()
            continue
        y_parity = R[1] & 1
        if s > HALF_N:
            s = N - s
            y_parity ^= 1
        return (r, s, y_parity)


def pubkey_of(private_key: int) -> bytes:
    Q = _point_mul(private_key, (GX, GY))
    assert Q is not None
    return b"\x04" + Q[0].to_bytes(32, "big") + Q[1].to_bytes(32, "big")
