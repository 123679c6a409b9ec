"""Transaction signing-hash construction and sender recovery.

Equivalent surface to the reference TxSigner (reference:
src/signer/signer.zig:27-188): per-type signing payloads (pre/post EIP-155
legacy, EIP-2930/1559 typed with their 0x01/0x02 prefix), v/y_parity
normalization, r/s validation, and sender = keccak(pubkey[1:])[12:].
"""

from __future__ import annotations

from typing import Optional, Tuple

from phant_tpu import rlp
from phant_tpu.crypto import secp256k1
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.crypto.secp256k1 import SignatureError
from phant_tpu.types.transaction import (
    AccessListTx,
    BlobTx,
    FeeMarketTx,
    LegacyTx,
    SetCodeTx,
    Transaction,
    _encode_access_list,
)


def address_from_pubkey(pubkey65: bytes) -> bytes:
    """sender = keccak(uncompressed pubkey minus the 0x04 tag)[12:]
    (reference: src/signer/signer.zig:78)."""
    if len(pubkey65) != 65 or pubkey65[0] != 0x04:
        raise SignatureError("expected 65-byte uncompressed pubkey")
    return keccak256(pubkey65[1:])[12:]


def signing_hash(tx: Transaction, chain_id: int) -> bytes:
    """Hash the signature covers (reference: src/signer/signer.zig:81-188)."""
    if isinstance(tx, LegacyTx):
        base = [
            rlp.encode_uint(tx.nonce),
            rlp.encode_uint(tx.gas_price),
            rlp.encode_uint(tx.gas_limit),
            tx.to if tx.to is not None else b"",
            rlp.encode_uint(tx.value),
            tx.data,
        ]
        if tx.v in (27, 28):  # pre-EIP-155: six fields
            return keccak256(rlp.encode(base))
        # EIP-155: nine fields with chain_id, 0, 0
        base += [rlp.encode_uint(chain_id), b"", b""]
        return keccak256(rlp.encode(base))
    if isinstance(tx, AccessListTx):
        payload = [
            rlp.encode_uint(tx.chain_id_val),
            rlp.encode_uint(tx.nonce),
            rlp.encode_uint(tx.gas_price),
            rlp.encode_uint(tx.gas_limit),
            tx.to if tx.to is not None else b"",
            rlp.encode_uint(tx.value),
            tx.data,
            _encode_access_list(tx.access_list),
        ]
        return keccak256(b"\x01" + rlp.encode(payload))
    if isinstance(tx, FeeMarketTx):
        payload = [
            rlp.encode_uint(tx.chain_id_val),
            rlp.encode_uint(tx.nonce),
            rlp.encode_uint(tx.max_priority_fee_per_gas),
            rlp.encode_uint(tx.max_fee_per_gas),
            rlp.encode_uint(tx.gas_limit),
            tx.to if tx.to is not None else b"",
            rlp.encode_uint(tx.value),
            tx.data,
            _encode_access_list(tx.access_list),
        ]
        return keccak256(b"\x02" + rlp.encode(payload))
    if isinstance(tx, BlobTx):
        # EIP-4844: 0x03 ‖ rlp([..., max_fee_per_blob_gas, blob_hashes])
        payload = [
            rlp.encode_uint(tx.chain_id_val),
            rlp.encode_uint(tx.nonce),
            rlp.encode_uint(tx.max_priority_fee_per_gas),
            rlp.encode_uint(tx.max_fee_per_gas),
            rlp.encode_uint(tx.gas_limit),
            tx.to if tx.to is not None else b"",
            rlp.encode_uint(tx.value),
            tx.data,
            _encode_access_list(tx.access_list),
            rlp.encode_uint(tx.max_fee_per_blob_gas),
            [h for h in tx.blob_versioned_hashes],
        ]
        return keccak256(b"\x03" + rlp.encode(payload))
    if isinstance(tx, SetCodeTx):
        # EIP-7702: 0x04 ‖ rlp([..., access_list, authorization_list])
        payload = [
            rlp.encode_uint(tx.chain_id_val),
            rlp.encode_uint(tx.nonce),
            rlp.encode_uint(tx.max_priority_fee_per_gas),
            rlp.encode_uint(tx.max_fee_per_gas),
            rlp.encode_uint(tx.gas_limit),
            tx.to if tx.to is not None else b"",
            rlp.encode_uint(tx.value),
            tx.data,
            _encode_access_list(tx.access_list),
            [a.fields() for a in tx.authorization_list],
        ]
        return keccak256(b"\x04" + rlp.encode(payload))
    raise TypeError(f"unknown tx type {type(tx).__name__}")


AUTH_MAGIC = b"\x05"  # EIP-7702 authorization signing-domain separator


def authorization_signing_hash(auth) -> bytes:
    """keccak(0x05 ‖ rlp([chain_id, address, nonce])) — the message an
    EIP-7702 authority signs (EIP-7702; the MAGIC byte keeps it disjoint
    from every EIP-2718 tx type)."""
    return keccak256(
        AUTH_MAGIC
        + rlp.encode(
            [
                rlp.encode_uint(auth.chain_id),
                auth.address,
                rlp.encode_uint(auth.nonce),
            ]
        )
    )


def sign_authorization(
    chain_id: int, address: bytes, nonce: int, private_key: int
):
    """Test/tooling helper: a signed EIP-7702 authorization tuple."""
    from phant_tpu.types.transaction import Authorization

    unsigned = Authorization(
        chain_id=chain_id, address=address, nonce=nonce, y_parity=0, r=0, s=0
    )
    r, s, y_parity = secp256k1.sign(
        authorization_signing_hash(unsigned), private_key
    )
    return Authorization(
        chain_id=chain_id, address=address, nonce=nonce,
        y_parity=y_parity, r=r, s=s,
    )


def recover_authority(auth) -> Optional[bytes]:
    """The 20-byte authority that signed an EIP-7702 authorization tuple,
    or None when the signature is invalid. Validation per EIP-7702: low-s
    malleability and y_parity ∈ {0,1} (chain-id/nonce screening is the
    caller's per-tuple processing, chain.py)."""
    if auth.y_parity not in (0, 1):
        return None
    if not (0 < auth.r < secp256k1.N):
        return None
    if not (0 < auth.s <= secp256k1.N // 2):
        return None
    try:
        pub = secp256k1.recover_pubkey(
            authorization_signing_hash(auth), auth.r, auth.s, auth.y_parity
        )
    except SignatureError:
        return None
    return address_from_pubkey(pub)


def recovery_fields(tx: Transaction, chain_id: int) -> Tuple[int, int, int]:
    """(r, s, recovery_id), normalizing legacy v
    (reference: src/signer/signer.zig:45-75)."""
    if isinstance(tx, LegacyTx):
        v = tx.v
        if v in (27, 28):
            rec_id = v - 27
        else:
            derived = 35 + 2 * chain_id
            if v not in (derived, derived + 1):
                raise SignatureError(f"v {v} inconsistent with chain id {chain_id}")
            rec_id = v - derived
    else:
        if tx.y_parity not in (0, 1):
            raise SignatureError(f"bad y_parity {tx.y_parity}")
        if tx.chain_id_val != chain_id:
            raise SignatureError("tx chain id mismatch")
        rec_id = tx.y_parity
    return tx.r, tx.s, rec_id


def recover_rows_host(msgs, rs, ss, recids):
    """The host recovery route over raw signature rows: ONE fused native
    batch (recover + keccak + address in C, GIL released) when the
    toolchain is present, the scalar pure-Python path otherwise. Returns
    `(senders, backend)` with backend in ("native", "scalar"); None
    entries = unrecoverable. THE one definition shared by
    `TxSigner.recover_rows_async` and the serving sig engine's host
    route (ops/sig_engine.py), so the fallback semantics can never
    diverge from the oracle the lane is differential-tested against.
    Placeholder (invalid-signature) rows recover to garbage here; the
    caller's bad-mask discards them."""
    from phant_tpu.utils.native import load_native

    native = load_native()
    if native is not None:
        return native.ecrecover_batch(msgs, rs, ss, recids), "native"
    out = []
    for m, r, s, rid in zip(msgs, rs, ss, recids):
        try:
            pub = secp256k1.recover_pubkey(m, r, s, rid)
            out.append(address_from_pubkey(pub))
        except SignatureError:
            out.append(None)
    return out, "scalar"


class SigRows:
    """One transaction list's signature rows, built on the caller's own
    thread: per-tx `(signing_hash, r, s, recid)` plus the set of indices
    whose signatures failed static validation (`bad` — those rows carry a
    well-formed placeholder lane and their results are discarded, the
    `recover_senders_async` contract). This is the unit the serving sig
    lane merges across requests (ops/sig_engine.py): rows are pure host
    data, so K requests' rows concatenate into one device ecrecover
    dispatch with no per-request shape constraints."""

    __slots__ = ("msgs", "rs", "ss", "recids", "bad")

    def __init__(self, msgs, rs, ss, recids, bad):
        self.msgs = msgs
        self.rs = rs
        self.ss = ss
        self.recids = recids
        self.bad = bad  # frozenset of invalid-signature tx indices

    @property
    def n(self) -> int:
        return len(self.msgs)


class TxSigner:
    """Chain-id-aware sender recovery + test signing
    (reference: src/signer/signer.zig:20-79).

    `min_device_ecrecover` is the device-route batch floor, resolved ONCE
    at construction (env PHANT_TPU_MIN_ECRECOVER, default 64) — the r14
    bugfix: the old module helper re-read `os.environ` on every
    `recover_senders_async` call on the hot path. An explicit argument is
    the test/engine override and wins over the env."""

    def __init__(self, chain_id: int, min_device_ecrecover: Optional[int] = None):
        self.chain_id = chain_id
        if min_device_ecrecover is None:
            import os

            min_device_ecrecover = int(
                os.environ.get("PHANT_TPU_MIN_ECRECOVER", "64")
            )
        self._min_device = min_device_ecrecover

    def get_sender(self, tx: Transaction) -> bytes:
        r, s, rec_id = recovery_fields(tx, self.chain_id)
        secp256k1.validate_signature_fields(r, s)
        msg = signing_hash(tx, self.chain_id)
        pub = secp256k1.recover_pubkey(msg, r, s, rec_id)
        return address_from_pubkey(pub)

    def get_senders_batch(self, txs) -> list:
        """Recover every sender of a block's tx list in one batched device
        call when `--crypto_backend=tpu` and the batch is large enough to
        amortize dispatch latency, else through the fused native batch.
        Raises SignatureError if any signature is invalid — per-tx behavior
        matches `get_sender` exactly (differential-tested)."""
        out = self.recover_senders_async(txs)()
        bad = [i for i, a in enumerate(out) if a is None]
        if bad:
            raise SignatureError(f"unrecoverable signature at tx index {bad[0]}")
        return out

    def signature_rows(self, txs) -> SigRows:
        """The per-tx signature rows `(signing_hash, r, s, recid)` for a
        tx list — the host keccak-over-RLP work, shared by the local
        `recover_senders_async` path and the serving sig lane
        (ops/sig_engine.py), so the row semantics (invalid-signature
        placeholder lane included) can never diverge between them."""
        msgs, rs, ss, recids = [], [], [], []
        bad = set()
        for i, tx in enumerate(txs):
            try:
                r, s, rec_id = recovery_fields(tx, self.chain_id)
                secp256k1.validate_signature_fields(r, s)
            except SignatureError:
                bad.add(i)
                r, s, rec_id = 1, 1, 0  # placeholder lane; result discarded
                msgs.append(b"\x01" * 32)
            else:
                msgs.append(signing_hash(tx, self.chain_id))
            rs.append(r)
            ss.append(s)
            recids.append(rec_id)
        return SigRows(msgs, rs, ss, recids, frozenset(bad))

    def recover_senders_async(self, txs, force_cpu: bool = False):
        """Dispatch sender recovery and return `resolve() -> [address|None]`
        (None = invalid signature; the error is raised by whoever consumes
        the block, keeping prefetch failures attributed to the right block).

        Backend selection: the device kernel only wins when the batch
        amortizes transfer+dispatch latency, so batches below
        PHANT_TPU_MIN_ECRECOVER (default 64) take the fused native batch
        even on `--crypto_backend=tpu` — a single real block's ~8-200 txs
        must never pay the device round trip serially (round-2 lesson: the flag made
        replay 45x slower). Cross-block prefetch (chain.run_blocks)
        concatenates many blocks' txs to clear the floor, and the serving
        path's sig lane (ops/sig_engine.py — THE offload-gate story)
        merges CONCURRENT requests' rows to clear it under Engine API
        traffic where no single block can. `force_cpu`
        pins this call to the CPU path WITHOUT touching the process-global
        backend (the device-loss fallback must not race concurrent
        requests)."""
        if not txs:
            return lambda: []
        return self.recover_rows_async(
            self.signature_rows(txs), force_cpu=force_cpu
        )

    def recover_rows_async(self, rows: SigRows, force_cpu: bool = False):
        """`recover_senders_async` over PRE-BUILT signature rows — the
        serving sig lane's degrade path reuses the rows it already built
        instead of paying the signing-hash keccak pass twice
        (stateless.dispatch_sender_recovery). Same backend selection,
        same `resolve() -> [address|None]` contract."""
        from phant_tpu.backend import crypto_backend, jax_device_ok

        if rows.n == 0:
            return lambda: []
        tpu_ok = (
            not force_cpu and crypto_backend() == "tpu" and jax_device_ok()
        )
        use_tpu = tpu_ok and rows.n >= self._min_device
        if not use_tpu and tpu_ok:
            from phant_tpu.utils.native import load_native

            if load_native() is None:
                # no toolchain: the device kernel beats scalar Python
                # even below the floor (the floor only arbitrates
                # device vs the fused NATIVE batch)
                use_tpu = True

        msgs, rs, ss, recids, bad = rows.msgs, rows.rs, rows.ss, rows.recids, rows.bad

        if use_tpu:
            from phant_tpu.ops.secp256k1_jax import ecrecover_batch_async

            inner = ecrecover_batch_async(msgs, rs, ss, recids)
        else:
            # the shared host route: fused native batch, or scalar when
            # the toolchain is absent (recover_rows_host)
            done, _backend = recover_rows_host(msgs, rs, ss, recids)
            inner = lambda: done  # noqa: E731

        def resolve():
            out = inner()
            return [None if i in bad else a for i, a in enumerate(out)]

        return resolve

    def sign(self, tx: Transaction, private_key: int) -> Transaction:
        """Returns a copy of `tx` carrying the signature."""
        from dataclasses import replace

        msg = signing_hash(tx, self.chain_id)
        r, s, y_parity = secp256k1.sign(msg, private_key)
        if isinstance(tx, LegacyTx):
            v = 35 + 2 * self.chain_id + y_parity if tx.v not in (27, 28) else 27 + y_parity
            return replace(tx, v=v, r=r, s=s)
        return replace(tx, y_parity=y_parity, r=r, s=s)
