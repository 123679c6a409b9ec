"""secp256k1, only what signing the traffic needs: the group law in affine
coordinates, and ECDSA with a nonce fixed per key (one inversion and two
multiplications a signature; the keys sign nothing of value)."""

from __future__ import annotations

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
G = (
    0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
    0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8,
)


def add(a, b):
    """a + b on the curve; None is the point at infinity."""
    if a is None:
        return b
    if b is None:
        return a
    if a[0] == b[0]:
        if (a[1] + b[1]) % P == 0:
            return None
        lam = 3 * a[0] * a[0] * pow(2 * a[1], -1, P) % P
    else:
        lam = (b[1] - a[1]) * pow(b[0] - a[0], -1, P) % P
    x = (lam * lam - a[0] - b[0]) % P
    return x, (lam * (a[0] - x) - a[1]) % P


def mul(k: int, point=G):
    out = None
    while k:
        if k & 1:
            out = add(out, point)
        point = add(point, point)
        k >>= 1
    return out


def consecutive(first: int, count: int) -> list:
    """The points first*G, (first+1)*G, ...: one ladder, then additions."""
    out = [mul(first)]
    for _ in range(count - 1):
        out.append(add(out[-1], G))
    return out


def pubkey_bytes(point) -> bytes:
    return point[0].to_bytes(32, "big") + point[1].to_bytes(32, "big")


class Signer:
    """One key with its nonce point made once."""

    def __init__(self, key: int, nonce: int, nonce_point):
        self.key = key
        self.r = nonce_point[0] % N
        self.odd = nonce_point[1] & 1
        self.nonce_inv = pow(nonce, -1, N)
        if self.r == 0 or nonce_point[0] >= N:
            raise ValueError("unusable nonce")

    def sign(self, digest: bytes):
        """(r, s, recovery id) with s in the lower half, as EIP-2 wants."""
        z = int.from_bytes(digest, "big")
        s = self.nonce_inv * (z + self.r * self.key) % N
        if s == 0:
            raise ValueError("unusable signature")
        if s > N // 2:
            return self.r, N - s, self.odd ^ 1
        return self.r, s, self.odd
