"""Batched secp256k1 ecrecover on TPU via JAX.

The reference recovers one sender at a time through C libsecp256k1
(reference: src/crypto/ecdsa.zig:19-26, called per-tx from
src/signer/signer.zig:40-79). Here the whole recovery — point
decompression, r^-1 mod n, the double-scalar multiplication
Q = u1*G + u2*R (Shamir's trick), Jacobian->affine conversion, and
keccak256(pubkey) -> address — runs on device for a whole batch of
signatures at once (BASELINE.md config #4).

TPU-first design notes:
- u256 values are 16 x 16-bit limbs in uint32 lanes (a 16x16 product fits
  uint32; column sums stay < 2^21, so schoolbook multiply needs no u64).
- Reductions mod p and mod n use the "fold" identity 2^256 ≡ K (mod m)
  for m = 2^256 - K; both moduli are folds + one conditional subtract.
- Modular inverse / square root are fixed-exponent square-and-multiply
  `lax.scan`s over precomputed exponent bits (p-2, (p+1)/4, n-2).
- The 256-step Shamir ladder is a `lax.scan` whose body is one Jacobian
  double + one mixed add + one exceptional double, all branch-free via
  lane selects (identity tracked as Z == 0).
- Everything is fixed-shape; `recovery_id >= 2` (x = r + n, never emitted
  by Ethereum signers) falls back to the CPU backend.

Differential-tested bit-exactly against phant_tpu/crypto/secp256k1.py.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from phant_tpu.crypto.secp256k1 import GX, GY, N, P
from phant_tpu.utils.rungs import launches, note_launch, note_split
from phant_tpu.utils.trace import metrics

LIMBS = 16  # 16-bit limbs per u256
MASK16 = np.uint32(0xFFFF)

K_P = 2**256 - P  # 2^32 + 977
K_N = 2**256 - N


def _int_to_limbs_np(x: int, width: int = LIMBS) -> np.ndarray:
    return np.array([(x >> (16 * j)) & 0xFFFF for j in range(width)], dtype=np.uint32)


def _const_width(x: int) -> int:
    w = 1
    while x >> (16 * w):
        w += 1
    return w


def _bits_msb(x: int, nbits: int = 256) -> np.ndarray:
    return np.array([(x >> (nbits - 1 - i)) & 1 for i in range(nbits)], dtype=np.uint32)


class _ModSpec:
    """Modulus m = 2^256 - K with precomputed fold constant + limb forms."""

    def __init__(self, m: int, folds: int):
        self.m = m
        self.k = 2**256 - m
        self.k_limbs = _int_to_limbs_np(self.k, _const_width(self.k))
        self.m17 = _int_to_limbs_np(m, 17)
        self.folds = folds


P_SPEC = _ModSpec(P, folds=3)  # K_P < 2^33: 3 folds reach < 2m
N_SPEC = _ModSpec(N, folds=4)  # K_N < 2^129: 4 folds reach < 2m

_EXP_P_MINUS_2 = _bits_msb(P - 2)
_EXP_SQRT = _bits_msb((P + 1) // 4)
_EXP_N_MINUS_2 = _bits_msb(N - 2)

_G_X = _int_to_limbs_np(GX)
_G_Y = _int_to_limbs_np(GY)


# ---------------------------------------------------------------------------
# limb arithmetic (all shapes (B, w) uint32 with limbs < 2^16)
# ---------------------------------------------------------------------------


def _carry_unrolled(cols: jnp.ndarray, width: int) -> jnp.ndarray:
    """Propagate carries over `width` columns (statically unrolled so the
    whole thing fuses into one elementwise program; column values must stay
    < 2^31 so `col + carry` cannot overflow uint32)."""
    out = []
    carry = jnp.zeros(cols.shape[:-1], jnp.uint32)
    for i in range(width):
        t = cols[..., i] + carry
        out.append(t & MASK16)
        carry = t >> 16
    return jnp.stack(out, axis=-1), carry


def _pad_cols(x: jnp.ndarray, left: int, width: int) -> jnp.ndarray:
    """Place x's columns at offset `left` in a width-`width` row (static
    shift = concatenation, an elementwise-fusable op — never a scatter)."""
    right = width - left - x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1) + [(left, right)]
    return jnp.pad(x, pad)


def _mul_wide(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(B,16) x (B,16) -> (B,32) full 512-bit product.

    Schoolbook columns are accumulated with STATIC-shift pads + adds
    instead of `.at[].add` scatters: XLA lowers scatters to slow serialized
    updates on TPU, while pad+add fuses into the elementwise graph. Column
    sums stay < 2^21 (16 lo + 16 hi contributions of < 2^16), so uint32
    accumulation is exact."""
    cols = jnp.zeros(a.shape[:-1] + (33,), jnp.uint32)
    for i in range(LIMBS):
        prod = a[..., i : i + 1] * b  # < 2^32, exact in uint32
        cols = cols + _pad_cols(prod & MASK16, i, 33)
        cols = cols + _pad_cols(prod >> 16, i + 1, 33)
    limbs, carry = _carry_unrolled(cols, 32)
    return limbs  # product < 2^512 so the final carry is 0


def _mul_const(h: jnp.ndarray, k_limbs: np.ndarray) -> jnp.ndarray:
    """(B,w) * constant (k,) -> (B, w+k) exact product (pad+add columns,
    same rationale as _mul_wide)."""
    w = h.shape[-1]
    k = len(k_limbs)
    kk = jnp.asarray(k_limbs)
    width = w + k + 1
    cols = jnp.zeros(h.shape[:-1] + (width,), jnp.uint32)
    for i in range(w):
        prod = h[..., i : i + 1] * kk
        cols = cols + _pad_cols(prod & MASK16, i, width)
        cols = cols + _pad_cols(prod >> 16, i + 1, width)
    limbs, _ = _carry_unrolled(cols, w + k)
    return limbs


def _add_wide(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(B,wa) + (B,wb) -> (B, max+1)."""
    w = max(a.shape[-1], b.shape[-1])
    pa = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, w - a.shape[-1])])
    pb = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, w - b.shape[-1])])
    limbs, carry = _carry_unrolled(pa + pb, w)
    return jnp.concatenate([limbs, carry[..., None]], axis=-1)


def _sub_borrow(a: jnp.ndarray, b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """a - b limbwise; returns (difference, borrowed) with equal widths."""
    w = a.shape[-1]
    ai = a.astype(jnp.int32)
    bi = b.astype(jnp.int32)
    out = []
    borrow = jnp.zeros(a.shape[:-1], jnp.int32)
    for i in range(w):
        t = ai[..., i] - bi[..., i] - borrow
        out.append((t & 0xFFFF).astype(jnp.uint32))
        borrow = (t < 0).astype(jnp.int32)
    return jnp.stack(out, axis=-1), borrow > 0


def _cond_sub(a: jnp.ndarray, m_limbs: np.ndarray) -> jnp.ndarray:
    """a mod-subtract the constant m once if a >= m (same width)."""
    m = jnp.asarray(m_limbs)
    m = jnp.broadcast_to(m, a.shape)
    d, borrowed = _sub_borrow(a, m)
    return jnp.where(borrowed[..., None], a, d)


def _fold(x: jnp.ndarray, spec: _ModSpec) -> jnp.ndarray:
    """Reduce a wide value to (B,16) using 2^256 ≡ K (mod m)."""
    for _ in range(spec.folds):
        if x.shape[-1] <= LIMBS:
            break
        lo = x[..., :LIMBS]
        hi = x[..., LIMBS:]
        x = _add_wide(lo, _mul_const(hi, spec.k_limbs))
    # width is now <= 17 and value < 2m
    w = x.shape[-1]
    if w < 17:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 17 - w)])
    x = _cond_sub(x[..., :17], spec.m17)
    return x[..., :LIMBS]


def _mul_mod(a, b, spec: _ModSpec):
    return _fold(_mul_wide(a, b), spec)


def _add_mod(a, b, spec: _ModSpec):
    return _fold(_add_wide(a, b), spec)


def _sub_mod(a, b, spec: _ModSpec):
    d, borrowed = _sub_borrow(a, b)
    m = jnp.broadcast_to(jnp.asarray(_int_to_limbs_np(spec.m)), d.shape)
    limbs, _ = _carry_unrolled(d + m, LIMBS)
    return jnp.where(borrowed[..., None], limbs, d)


def _is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(a == 0, axis=-1)


def _eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(a == b, axis=-1)


def _lt_const(a: jnp.ndarray, m: int) -> jnp.ndarray:
    """a < m (for range checks against n)."""
    _, borrowed = _sub_borrow(a, jnp.broadcast_to(jnp.asarray(_int_to_limbs_np(m)), a.shape))
    return borrowed


def _pow_fixed(base: jnp.ndarray, exp_bits: np.ndarray, spec: _ModSpec) -> jnp.ndarray:
    """base^e for a fixed public exponent, square-and-multiply lax.scan."""
    base = jnp.asarray(base)
    # derive the initial accumulator from the input so it inherits the
    # input's varying manual axes under shard_map (a fresh constant would be
    # replicated and break the scan carry typing)
    acc0 = (base ^ base).at[..., 0].set(1)

    def body(acc, bit):
        acc = _mul_mod(acc, acc, spec)
        with_mul = _mul_mod(acc, base, spec)
        return jnp.where(bit.astype(bool), with_mul, acc), None

    acc, _ = jax.lax.scan(body, acc0, jnp.asarray(exp_bits))
    return acc


# ---------------------------------------------------------------------------
# point arithmetic (Jacobian; identity is Z == 0)
#
# Independent field multiplications are stacked along the batch axis into a
# single wider multiply (`_mul_many`) — same FLOPs, ~3x fewer HLO ops, which
# cuts XLA compile time of the 256-step ladder dramatically.
# ---------------------------------------------------------------------------


def _mul_many(pairs, spec: _ModSpec):
    """[(a1,b1),(a2,b2),...] -> [a1*b1, a2*b2, ...] via one stacked multiply."""
    if len(pairs) == 1:
        return [_mul_mod(pairs[0][0], pairs[0][1], spec)]
    a = jnp.concatenate([p[0] for p in pairs], axis=0)
    b = jnp.concatenate([p[1] for p in pairs], axis=0)
    out = _mul_mod(a, b, spec)
    B = pairs[0][0].shape[0]
    return [out[i * B : (i + 1) * B] for i in range(len(pairs))]


def _dbl2(A, YZ, C, XB2, F):
    """Assemble the doubling result from its precomputed products."""
    D = _sub_mod(_sub_mod(XB2, A, P_SPEC), C, P_SPEC)
    D = _add_mod(D, D, P_SPEC)  # 2((X+B)^2 - A - C)
    X3 = _sub_mod(_sub_mod(F, D, P_SPEC), D, P_SPEC)
    C8 = _add_mod(C, C, P_SPEC)
    C8 = _add_mod(C8, C8, P_SPEC)
    C8 = _add_mod(C8, C8, P_SPEC)
    Z3 = _add_mod(YZ, YZ, P_SPEC)
    return D, X3, C8, Z3


def _pt_dbl(X, Y, Z):
    """Jacobian doubling for y^2 = x^3 + 7 (a = 0); 7 muls in 3 stacked
    calls. Maps identity (Z=0) to identity and (x,0) to identity (Z'=2YZ)."""
    A, Bv, YZ = _mul_many([(X, X), (Y, Y), (Y, Z)], P_SPEC)
    XB = _add_mod(X, Bv, P_SPEC)
    E = _add_mod(_add_mod(A, A, P_SPEC), A, P_SPEC)  # 3A
    C, XB2, F = _mul_many([(Bv, Bv), (XB, XB), (E, E)], P_SPEC)
    D, X3, C8, Z3 = _dbl2(A, YZ, C, XB2, F)
    (EDX3,) = _mul_many([(E, _sub_mod(D, X3, P_SPEC))], P_SPEC)
    Y3 = _sub_mod(EDX3, C8, P_SPEC)
    return X3, Y3, Z3


def _select_pt(cond, a, b):
    """Componentwise (B,)-cond select between two Jacobian points."""
    c = cond[..., None]
    return tuple(jnp.where(c, x, y) for x, y in zip(a, b))


def _pt_add_mixed(X1, Y1, Z1, x2, y2):
    """Jacobian + affine with full exceptional-case handling:
    P identity -> (x2, y2, 1); equal points -> double; inverse -> identity.
    The exceptional double shares stacked multiplies with the add, so the
    whole thing is 18 muls in 6 stacked calls."""
    # interleaved schedule: [add] Z1Z1/U2/S2/H/R chain, [dbl] A/B/C/... chain
    Z1Z1, A, Bv, YZ = _mul_many([(Z1, Z1), (X1, X1), (Y1, Y1), (Y1, Z1)], P_SPEC)
    XB = _add_mod(X1, Bv, P_SPEC)
    E = _add_mod(_add_mod(A, A, P_SPEC), A, P_SPEC)
    U2, Z1c, C, XB2, F = _mul_many(
        [(x2, Z1Z1), (Z1, Z1Z1), (Bv, Bv), (XB, XB), (E, E)], P_SPEC
    )
    D, X3d, C8, Z3d = _dbl2(A, YZ, C, XB2, F)
    S2, EDX3 = _mul_many([(y2, Z1c), (E, _sub_mod(D, X3d, P_SPEC))], P_SPEC)
    Y3d = _sub_mod(EDX3, C8, P_SPEC)  # (X3d, Y3d, Z3d) = 2*(X1,Y1,Z1)
    H = _sub_mod(U2, X1, P_SPEC)
    Rr = _sub_mod(S2, Y1, P_SPEC)
    HH, RR, Z3 = _mul_many([(H, H), (Rr, Rr), (Z1, H)], P_SPEC)
    HHH, V = _mul_many([(H, HH), (X1, HH)], P_SPEC)
    X3 = _sub_mod(_sub_mod(RR, HHH, P_SPEC), _add_mod(V, V, P_SPEC), P_SPEC)
    Y1HHH, RrVX3 = _mul_many(
        [(Y1, HHH), (Rr, _sub_mod(V, X3, P_SPEC))], P_SPEC
    )
    Y3 = _sub_mod(RrVX3, Y1HHH, P_SPEC)

    p_inf = _is_zero(Z1)
    h_zero = _is_zero(H)
    r_zero = _is_zero(Rr)

    one = np.zeros(LIMBS, np.uint32)
    one[0] = 1
    one_l = jnp.broadcast_to(jnp.asarray(one), X1.shape)
    zero_l = jnp.zeros_like(X1)

    out = (X3, Y3, Z3)
    # equal points: the generic formula degenerates -> double instead
    out = _select_pt(h_zero & r_zero & ~p_inf, (X3d, Y3d, Z3d), out)
    # inverse points: identity
    out = _select_pt(h_zero & ~r_zero & ~p_inf, (one_l, one_l, zero_l), out)
    # P was identity: the affine operand
    out = _select_pt(p_inf, (x2, y2, one_l), out)
    return out


def _to_affine(X, Y, Z):
    """(x, y, is_infinity); inversion by Fermat since Z is public."""
    zi = _pow_fixed(Z, _EXP_P_MINUS_2, P_SPEC)
    zi2 = _mul_mod(zi, zi, P_SPEC)
    x = _mul_mod(X, zi2, P_SPEC)
    y = _mul_mod(Y, _mul_mod(zi, zi2, P_SPEC), P_SPEC)
    return x, y, _is_zero(Z)


def _bits_matrix(a: jnp.ndarray) -> jnp.ndarray:
    """(B,16) limbs -> (256, B) scalar bit per ladder step, msb first."""
    shifts = jnp.arange(16, dtype=jnp.uint32)
    bits = (a[:, :, None] >> shifts[None, None, :]) & 1  # (B, 16, 16)
    flat = bits.reshape(a.shape[0], LIMBS * 16)  # lsb-first
    return jnp.flip(flat, axis=1).T


# ---------------------------------------------------------------------------
# the fused kernel
# ---------------------------------------------------------------------------


def _be_words(v):
    """(B,16) limbs -> (B,8) LE u32 words of the big-endian 32 bytes.
    Consensus-critical: this is the byte layout keccak sees for the
    recovered pubkey."""
    sw = ((v & 0xFF) << 8) | (v >> 8)  # byteswap16 each limb
    hi = sw[:, ::-1]  # most significant limb first
    return hi[:, 0::2] | (hi[:, 1::2] << 16)


@jax.jit
def ecrecover_kernel(e, r, s, parity):
    """Batched ecrecover -> keccak digest of the recovered pubkey.

    Args:
      e: (B,16) uint32 limbs — message-hash scalar (any u256; reduced mod n).
      r, s: (B,16) uint32 limbs — signature fields.
      parity: (B,) uint32 — y-parity of R (recovery id 0/1).

    Returns:
      digest_words: (B, 8) uint32 — keccak256(pubkey_x || pubkey_y) as LE
        u32 words (address = bytes 12..31).
      valid: (B,) bool — r/s in range, x on curve, result not at infinity.
    """
    from phant_tpu.ops.keccak_jax import keccak256_chunked_auto

    B = r.shape[0]
    # varying-axes-safe zero (see _pow_fixed): shard_map scan carries must
    # not start from replicated constants
    zero16 = r ^ r

    # range checks (reference: src/crypto/ecdsa.zig:28-36, sans low-s which
    # is transaction policy, enforced by the signer layer)
    r_ok = ~_is_zero(r) & _lt_const(r, N)
    s_ok = ~_is_zero(s) & _lt_const(s, N)

    # decompress R = lift_x(r, parity): y = (r^3+7)^((p+1)/4)
    x = r  # r < n < p
    x2 = _mul_mod(x, x, P_SPEC)
    x3 = _mul_mod(x2, x, P_SPEC)
    seven = np.zeros(LIMBS, np.uint32)
    seven[0] = 7
    y_sq = _add_mod(x3, jnp.broadcast_to(jnp.asarray(seven), x.shape), P_SPEC)
    y = _pow_fixed(y_sq, _EXP_SQRT, P_SPEC)
    on_curve = _eq(_mul_mod(y, y, P_SPEC), y_sq)
    flip = (y[:, 0] & 1) != (parity & 1)
    y = jnp.where(flip[:, None], _sub_mod(zero16, y, P_SPEC), y)

    # scalars: u1 = -e/r, u2 = s/r (mod n)
    z = _fold(jnp.pad(e, ((0, 0), (0, 16))), N_SPEC)  # e mod n
    r_inv = _pow_fixed(_fold(jnp.pad(r, ((0, 0), (0, 16))), N_SPEC), _EXP_N_MINUS_2, N_SPEC)
    t = _mul_mod(z, r_inv, N_SPEC)
    u1 = jnp.where(_is_zero(t)[:, None], zero16, _sub_mod(zero16, t, N_SPEC))
    u2 = _mul_mod(s, r_inv, N_SPEC)

    # one-off affine G+R (for the Shamir table): full add of two affine pts
    gx = jnp.broadcast_to(jnp.asarray(_G_X), x.shape)
    gy = jnp.broadcast_to(jnp.asarray(_G_Y), x.shape)
    one = np.zeros(LIMBS, np.uint32)
    one[0] = 1
    one_l = jnp.broadcast_to(jnp.asarray(one), x.shape)
    grj = _pt_add_mixed(gx, gy, one_l, x, y)  # G (Z=1) + R
    gr_x, gr_y, gr_inf = _to_affine(*grj)
    # R == G: _pt_add_mixed handled it via its double branch, fine; R == -G
    # yields gr_inf and the ladder skips those adds below.

    # Shamir ladder over msb-first bit pairs
    bits_u1 = _bits_matrix(u1)  # (256, B)
    bits_u2 = _bits_matrix(u2)

    def step(S, bits):
        b1, b2 = bits
        b1 = b1.astype(bool)
        b2 = b2.astype(bool)
        S = _pt_dbl(*S)
        # table select: G / R / G+R
        tx = jnp.where(
            (b1 & b2)[:, None], gr_x, jnp.where(b1[:, None], gx, x)
        )
        ty = jnp.where(
            (b1 & b2)[:, None], gr_y, jnp.where(b1[:, None], gy, y)
        )
        added = _pt_add_mixed(S[0], S[1], S[2], tx, ty)
        skip = (~b1 & ~b2) | (b1 & b2 & gr_inf)
        S = _select_pt(skip, S, added)
        return S, None

    one_v = zero16.at[:, 0].set(1)  # varying-axes-safe identity point
    S0 = (one_v, one_v, zero16)
    Q, _ = jax.lax.scan(step, S0, (bits_u1, bits_u2))

    qx, qy, q_inf = _to_affine(*Q)
    valid = r_ok & s_ok & on_curve & ~q_inf

    words = jnp.zeros((B, 1, 34), jnp.uint32)
    words = words.at[:, 0, 0:8].set(_be_words(qx))
    words = words.at[:, 0, 8:16].set(_be_words(qy))
    words = words.at[:, 0, 16].set(jnp.uint32(0x00000001))  # keccak 0x01 pad
    words = words.at[:, 0, 33].set(jnp.uint32(0x80000000))  # final 0x80
    digest = keccak256_chunked_auto(words, jnp.ones((B,), jnp.int32), max_chunks=1)
    return digest, valid


# ---------------------------------------------------------------------------
# host API
# ---------------------------------------------------------------------------


def ints_to_limbs(xs: Sequence[int]) -> np.ndarray:
    out = np.zeros((len(xs), LIMBS), np.uint32)
    for i, v in enumerate(xs):
        for j in range(LIMBS):
            out[i, j] = (v >> (16 * j)) & 0xFFFF
    return out


def digest_words_to_addresses(words: np.ndarray) -> List[bytes]:
    """(B,8) LE u32 keccak words -> 20-byte addresses (digest bytes 12..31)."""
    arr = np.asarray(words, dtype="<u4")
    return [arr[i].tobytes()[12:32] for i in range(arr.shape[0])]


def ecrecover_batch_async(
    msg_hashes: Sequence[bytes],
    rs: Sequence[int],
    ss: Sequence[int],
    recovery_ids: Sequence[int],
):
    """Dispatch batched ecrecover and return a zero-argument `resolve()`
    callable that materializes the result list. The device computes while
    the host does other work between dispatch and resolve — the building
    block for cross-block pipelining (phant_tpu/blockchain/chain.py
    run_blocks prefetches block N+k's senders while block N executes on
    CPU). recovery_id >= 2 falls back to the CPU backend at dispatch time
    (x = r + n is never produced by Ethereum transactions)."""
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.crypto.secp256k1 import SignatureError, recover_pubkey

    B = len(msg_hashes)
    if B == 0:
        return lambda: []
    out: List[Optional[bytes]] = [None] * B
    device_idx = [i for i in range(B) if recovery_ids[i] in (0, 1)]
    for i in range(B):
        if recovery_ids[i] not in (0, 1):
            try:
                pub = recover_pubkey(msg_hashes[i], rs[i], ss[i], recovery_ids[i])
                out[i] = keccak256(pub[1:])[12:]
            except SignatureError:
                out[i] = None
    if not device_idx:
        return lambda: out
    # one ladder: Shamir's interleaved double-scalar multiplication
    return _dispatch_shamir(out, device_idx, msg_hashes, rs, ss, recovery_ids)


#: THE shape set of `ecrecover_kernel` on the served path, in signature
#: rows: ONE rung, a request of mainnet's shape (150-250 transactions; a
#: lone block's 225 signatures sat on 256 under the open power-of-two
#: buckets this replaces). A merged batch above it goes out as launches of
#: 256 (rows are independent), so the first request of any wave builds the
#: only shape there is and no later wave can meet another. Why one, and
#: why the first request's and not the boot's: a rung is 104-131 s of
#: backend compile on an empty cache and, cache warm, still 33 s of
#: tracing, lowering and loading (10 MB of StableHLO; my chip runs, PR 34:
#: three rungs at boot took a warm set-up from 125 s to 272 s), and seconds
#: in a server's constructor are seconds nobody can overlap with anything.
#: What one rung costs: a merged wave of four requests is four launches in
#: a row (4 x 24.5 ms; PERF.md section 6, PR 34), replay's 7,200
#: signatures are 29. Add a rung only with a cell that needs it and a way
#: to load it that does not trace it again (PERF.md section 7, w).
SIG_LADDER: Tuple[int, ...] = (256,)

#: Under the ladder, on the CPU only, where tests and dry runs recover a
#: handful of signatures and XLA-CPU takes seconds a launch at 256 rows.
#: An accelerator never runs these and a server's boot never builds them.
SIG_SMALL_RUNGS: Tuple[int, ...] = (32, 64, 128)


def sig_ladder() -> Tuple[int, ...]:
    """The rungs `ecrecover_kernel` may be launched on in this process."""
    if jax.default_backend() == "cpu":
        return SIG_SMALL_RUNGS + SIG_LADDER
    return SIG_LADDER


def pack_signatures(
    es: Sequence[int], rs: Sequence[int], ss: Sequence[int], parities: Sequence[int]
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, Tuple[int, ...]]:
    """(e, r, s, parity, rungs): the kernel's inputs for these signatures,
    padded with well-formed rows whose result is discarded (1, 1, 1, 0) to
    the sum of `rungs`, the launches they go out as (`rungs.launches`).
    Pure host work: the u256 -> limb encode."""
    rungs = tuple(launches(sig_ladder(), len(es)))
    pad = sum(rungs) - len(es)
    return (
        ints_to_limbs(list(es) + [1] * pad),
        ints_to_limbs(list(rs) + [1] * pad),
        ints_to_limbs(list(ss) + [1] * pad),
        np.array(list(parities) + [0] * pad, np.uint32),
        rungs,
    )


def launch_ecrecover(packed, n_real: int, device=None) -> list:
    """Upload and launch what `pack_signatures` made, one launch a rung,
    with no host sync: the unresolved (digest_words, valid) of each launch,
    in row order. `device` commits the inputs to one chip of a mesh (the
    compute follows them). Counted here, at dispatch: `sig.rows{kind=}`,
    the shapes run, and a batch that went out split."""
    e, r, s, par, rungs = packed

    def put(a):
        return jnp.asarray(a) if device is None else jax.device_put(a, device)

    outs, at = [], 0
    for rung in rungs:
        outs.append(
            ecrecover_kernel(*(put(a[at : at + rung]) for a in (e, r, s, par)))
        )
        note_launch("ecrecover", rung, device)
        at += rung
    metrics.count("sig.rows", n_real, kind="real")
    metrics.count("sig.rows", at - n_real, kind="pad")
    note_split("ecrecover", len(rungs))
    return outs


def read_ecrecover(outs: list) -> Tuple[np.ndarray, np.ndarray]:
    """The readback of `launch_ecrecover`'s outputs, launches joined in
    row order: (digest words, valid). The honest sync, and nothing else."""
    digests = np.concatenate([np.asarray(d) for d, _v in outs])  # phantlint: disable=HOSTSYNC — the caller's chosen sync point
    valid = np.concatenate([np.asarray(v) for _d, v in outs])  # phantlint: disable=HOSTSYNC — the caller's chosen sync point
    return digests, valid


def senders_of(
    digests: np.ndarray, valid: np.ndarray, n_real: int
) -> List[Optional[bytes]]:
    """The address of each of the first `n_real` rows `read_ecrecover`
    gave, None where the signature is invalid."""
    addrs = digest_words_to_addresses(digests[:n_real])
    return [a if ok else None for a, ok in zip(addrs, valid[:n_real].tolist())]


def _dispatch_shamir(out, device_idx, msg_hashes, rs, ss, recovery_ids):
    """Pack, pad to the ladder and launch the 256-step Shamir ladder."""
    packed = pack_signatures(
        [int.from_bytes(msg_hashes[i], "big") for i in device_idx],
        [rs[i] for i in device_idx],
        [ss[i] for i in device_idx],
        [recovery_ids[i] & 1 for i in device_idx],
    )
    outs = launch_ecrecover(packed, len(device_idx))

    def resolve() -> List[Optional[bytes]]:
        # resolve() IS the deliberate sync point of the async dispatch:
        # the caller chose when to materialize (cross-block pipelining)
        senders = senders_of(*read_ecrecover(outs), len(device_idx))
        for i, addr in zip(device_idx, senders):
            out[i] = addr
        return out

    return resolve


def ecrecover_batch(
    msg_hashes: Sequence[bytes],
    rs: Sequence[int],
    ss: Sequence[int],
    recovery_ids: Sequence[int],
) -> List[Optional[bytes]]:
    """Recover the Ethereum address for each signature on device; None for
    invalid signatures. Synchronous wrapper over ecrecover_batch_async."""
    return ecrecover_batch_async(msg_hashes, rs, ss, recovery_ids)()
