"""Differential tests: batched TPU ecrecover vs the CPU backend.

The CPU backend (phant_tpu/crypto/secp256k1.py) is the oracle, itself
checked against geth-generated vectors (reference: src/crypto/ecdsa.zig:38-49)
and real mainnet transactions (reference: src/signer/signer.zig:191-226).
"""

from __future__ import annotations

import numpy as np
import pytest

from phant_tpu.crypto.keccak import keccak256
from phant_tpu.crypto.secp256k1 import (
    GX,
    GY,
    N,
    P,
    SignatureError,
    pubkey_of,
    recover_pubkey,
    sign,
)
from phant_tpu.ops import secp256k1_jax as sj


def _cpu_address(msg_hash: bytes, r: int, s: int, recid: int):
    try:
        pub = recover_pubkey(msg_hash, r, s, recid)
    except SignatureError:
        return None
    return keccak256(pub[1:])[12:]


# ---------------------------------------------------------------------------
# limb arithmetic against Python ints


def test_limb_mul_mod():
    rng = np.random.default_rng(7)
    raw = [int.from_bytes(rng.bytes(32), "big") for _ in range(16)]
    for spec, m in ((sj.P_SPEC, P), (sj.N_SPEC, N)):
        # kernel precondition: operands already reduced mod m
        vals = [v % m for v in raw]
        a = sj.ints_to_limbs(vals[:8])
        b = sj.ints_to_limbs(vals[8:])
        got = np.asarray(sj._mul_mod(a, b, spec))
        for i in range(8):
            expected = vals[i] * vals[8 + i] % m
            have = sum(int(got[i, j]) << (16 * j) for j in range(16))
            assert have == expected, f"mul_mod wrong at {i} for m={hex(m)[:12]}"


def test_limb_add_sub_mod():
    rng = np.random.default_rng(8)
    vals = [int.from_bytes(rng.bytes(32), "big") % P for _ in range(8)]
    a = sj.ints_to_limbs(vals[:4])
    b = sj.ints_to_limbs(vals[4:])
    add = np.asarray(sj._add_mod(a, b, sj.P_SPEC))
    sub = np.asarray(sj._sub_mod(a, b, sj.P_SPEC))
    for i in range(4):
        have_add = sum(int(add[i, j]) << (16 * j) for j in range(16))
        have_sub = sum(int(sub[i, j]) << (16 * j) for j in range(16))
        assert have_add == (vals[i] + vals[4 + i]) % P
        assert have_sub == (vals[i] - vals[4 + i]) % P


def test_pow_fixed_is_inverse():
    rng = np.random.default_rng(9)
    vals = [int.from_bytes(rng.bytes(32), "big") % P for _ in range(4)]
    a = sj.ints_to_limbs(vals)
    inv = np.asarray(sj._pow_fixed(a, sj._EXP_P_MINUS_2, sj.P_SPEC))
    for i in range(4):
        have = sum(int(inv[i, j]) << (16 * j) for j in range(16))
        assert have == pow(vals[i], P - 2, P)


# ---------------------------------------------------------------------------
# full recovery, differential vs CPU


def test_ecrecover_batch_random_roundtrip():
    """Sign with random keys on CPU, recover on device, compare addresses."""
    rng = np.random.default_rng(1234)
    msgs, rs, ss, recids, expected = [], [], [], [], []
    for i in range(24):
        key = int.from_bytes(rng.bytes(32), "big") % N
        if key == 0:
            key = 1
        msg = keccak256(rng.bytes(40 + i))
        r, s, parity = sign(msg, key)
        msgs.append(msg)
        rs.append(r)
        ss.append(s)
        recids.append(parity)
        expected.append(keccak256(pubkey_of(key)[1:])[12:])
    got = sj.ecrecover_batch(msgs, rs, ss, recids)
    assert got == expected


def test_ecrecover_batch_matches_cpu_on_flipped_parity():
    """Wrong parity recovers a different-but-valid point: device must agree
    with CPU exactly, not just on happy paths."""
    rng = np.random.default_rng(5)
    key = 0xDEADBEEF1234567
    msg = keccak256(b"parity flip")
    r, s, parity = sign(msg, key)
    flipped = 1 - parity
    cpu = _cpu_address(msg, r, s, flipped)
    got = sj.ecrecover_batch([msg], [r], [s], [flipped])
    assert got == [cpu]


def test_ecrecover_batch_invalid_signatures():
    msg = keccak256(b"invalid cases")
    # r = 0, s = 0, r >= n, s >= n, x not on curve
    cases = [
        (0, 1, 0),
        (1, 0, 0),
        (N, 5, 0),
        (5, N, 0),
    ]
    # find an r whose x^3+7 is a non-residue (not on curve)
    x = 2
    while pow((pow(x, 3, P) + 7) % P, (P - 1) // 2, P) == 1:
        x += 1
    cases.append((x, 5, 0))
    msgs = [msg] * len(cases)
    rs = [c[0] for c in cases]
    ss = [c[1] for c in cases]
    recids = [c[2] for c in cases]
    got = sj.ecrecover_batch(msgs, rs, ss, recids)
    cpu = [_cpu_address(msg, r, s, v) for r, s, v in cases]
    assert got == cpu == [None] * len(cases)


def test_ecrecover_batch_recid_ge2_falls_back_to_cpu():
    """recovery_id 2/3 (x = r + n) is served by the CPU path."""
    rng = np.random.default_rng(11)
    key = 99991
    msg = keccak256(b"high recid")
    r, s, parity = sign(msg, key)
    got = sj.ecrecover_batch([msg], [r], [s], [parity + 2])
    cpu = _cpu_address(msg, r, s, parity + 2)
    assert got == [cpu]


def test_signer_batch_matches_scalar():
    """TxSigner.get_senders_batch on the tpu backend == per-tx get_sender."""
    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.signer.signer import TxSigner
    from phant_tpu.types.transaction import FeeMarketTx, LegacyTx

    signer = TxSigner(chain_id=1)
    txs = []
    for i, key in enumerate((1, 2, 0xDEADBEEF, N - 1)):
        legacy = LegacyTx(
            nonce=i, gas_price=10**9, gas_limit=21000,
            to=b"\x11" * 20, value=i, data=b"", v=0, r=0, s=0,
        )
        txs.append(signer.sign(legacy, key))
        typed = FeeMarketTx(
            chain_id_val=1, nonce=i, max_priority_fee_per_gas=1,
            max_fee_per_gas=10**9, gas_limit=21000, to=b"\x22" * 20,
            value=i, data=b"\x00" * i, access_list=(), y_parity=0, r=0, s=0,
        )
        txs.append(signer.sign(typed, key))
    expected = [signer.get_sender(tx) for tx in txs]
    set_crypto_backend("tpu")
    try:
        assert signer.get_senders_batch(txs) == expected
    finally:
        set_crypto_backend("cpu")
    # cpu path goes through the same API
    assert signer.get_senders_batch(txs) == expected


def test_ecrecover_sharded_matches_single():
    """dp-sharded ecrecover over an 8-device mesh == single-device kernel."""
    import jax.numpy as jnp

    from phant_tpu.parallel import ecrecover_sharded, make_mesh

    rng = np.random.default_rng(21)
    B = 32
    msgs, rs, ss, pars = [], [], [], []
    for i in range(B):
        key = int.from_bytes(rng.bytes(32), "big") % N or 1
        msg = keccak256(rng.bytes(16 + i))
        r, s, par = sign(msg, key)
        msgs.append(int.from_bytes(msg, "big"))
        rs.append(r)
        ss.append(s)
        pars.append(par)
    e = sj.ints_to_limbs(msgs)
    r_l = sj.ints_to_limbs(rs)
    s_l = sj.ints_to_limbs(ss)
    par_a = np.array(pars, np.uint32)

    single_d, single_v = sj.ecrecover_kernel(
        jnp.asarray(e), jnp.asarray(r_l), jnp.asarray(s_l), jnp.asarray(par_a)
    )
    mesh = make_mesh(8)
    shard_d, shard_v = ecrecover_sharded(mesh, e, r_l, s_l, par_a)
    assert (np.asarray(shard_v) == np.asarray(single_v)).all()
    assert (np.asarray(shard_d) == np.asarray(single_d)).all()


def test_adversarial_r_equals_gx_matches_cpu():
    """Signatures whose r is GX (attacker knows dlog of R, so R = +-G and
    the ladder's adds can meet equal or inverse operands): the public API
    must agree with the exact CPU recovery for every (z, s) tried."""
    rng = np.random.default_rng(11)
    msgs, rs, ss, recids = [], [], [], []
    for _ in range(32):
        msgs.append(rng.bytes(32))
        rs.append(GX)
        ss.append(int.from_bytes(rng.bytes(32), "big") % N or 1)
        recids.append(int(rng.integers(0, 2)))
    got = sj.ecrecover_batch(msgs, rs, ss, recids)
    for i in range(32):
        assert got[i] == _cpu_address(msgs[i], rs[i], ss[i], recids[i]), i


def test_ecrecover_eip155_canonical_vector():
    """The canonical EIP-155 example tx (chain id 1, nonce 9): known r/s
    constants, sender recovered on device must match the known address
    (same vector as tests/test_state_signer.py, reference:
    src/signer/signer.zig:191-226 uses equivalent etherscan vectors)."""
    from phant_tpu.signer.signer import signing_hash
    from phant_tpu.types.transaction import LegacyTx

    r = 0x28EF61340BD939BC2195FE537567866003E1A15D3C71FF63E1590620AA636276
    s = 0x67CBE9D8997F761AECB703304B3800CCF555C9F3DC64214B297FB1966A3B6D83
    tx = LegacyTx(
        nonce=9,
        gas_price=20 * 10**9,
        gas_limit=21000,
        to=bytes.fromhex("3535353535353535353535353535353535353535"),
        value=10**18,
        data=b"",
        v=37,
        r=r,
        s=s,
    )
    sighash = signing_hash(tx, chain_id=1)
    recid = 0  # v=37 -> parity 0 under EIP-155 chain id 1
    got = sj.ecrecover_batch([sighash], [r], [s], [recid])
    assert got == [bytes.fromhex("9d8a62f656a8d1615c1294fd71e9cfb3e4855a4f")]
