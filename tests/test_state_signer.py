"""StateDB journaling tests + signer/secp256k1 golden vectors."""

import pytest

from phant_tpu.crypto import secp256k1
from phant_tpu.crypto.secp256k1 import SignatureError
from phant_tpu.signer.signer import TxSigner, address_from_pubkey
from phant_tpu.state.statedb import StateDB
from phant_tpu.types.account import Account
from phant_tpu.types.receipt import Log
from phant_tpu.types.transaction import FeeMarketTx, LegacyTx

A1 = b"\x11" * 20
A2 = b"\x22" * 20


# --- StateDB --------------------------------------------------------------


def test_snapshot_revert_balances_storage():
    db = StateDB({A1: Account(balance=100)})
    db.start_tx()
    snap = db.snapshot()
    db.set_balance(A1, 40)
    db.set_storage(A1, 5, 123)
    db.set_nonce(A1, 7)
    db.create_account(A2)
    db.set_balance(A2, 1)
    assert db.get_balance(A1) == 40
    db.revert_to(snap)
    assert db.get_balance(A1) == 100
    assert db.get_storage(A1, 5) == 0
    assert db.get_nonce(A1) == 0
    assert not db.account_exists(A2)


def test_nested_snapshots():
    db = StateDB({A1: Account(balance=10)})
    db.start_tx()
    s1 = db.snapshot()
    db.set_balance(A1, 20)
    s2 = db.snapshot()
    db.set_balance(A1, 30)
    db.revert_to(s2)
    assert db.get_balance(A1) == 20
    db.revert_to(s1)
    assert db.get_balance(A1) == 10


def test_original_storage_eip2200():
    db = StateDB({A1: Account(storage={1: 5})})
    db.start_tx()
    assert db.get_original_storage(A1, 1) == 5
    db.set_storage(A1, 1, 7)
    db.set_storage(A1, 1, 9)
    assert db.get_original_storage(A1, 1) == 5
    assert db.get_storage(A1, 1) == 9
    # a revert does not disturb the tx-scope original
    snap = db.snapshot()
    db.set_storage(A1, 1, 11)
    db.revert_to(snap)
    assert db.get_original_storage(A1, 1) == 5
    assert db.get_storage(A1, 1) == 9
    # next tx resets originals
    db.start_tx()
    assert db.get_original_storage(A1, 1) == 9


def test_warm_sets_revert():
    db = StateDB()
    db.start_tx()
    snap = db.snapshot()
    assert db.access_address(A1) is False  # was cold
    assert db.access_address(A1) is True  # now warm
    assert db.access_storage_key(A1, 3) is False
    db.revert_to(snap)
    assert db.access_address(A1) is False  # re-cooled by revert
    assert db.access_storage_key(A1, 3) is False


def test_logs_and_refund_revert():
    db = StateDB()
    db.start_tx()
    db.add_refund(100)
    snap = db.snapshot()
    db.add_log(Log(A1, (), b"x"))
    db.add_refund(50)
    assert db.refund == 150 and len(db.logs) == 1
    db.revert_to(snap)
    assert db.refund == 100 and len(db.logs) == 0


def test_destroy_touched_empty():
    db = StateDB({A1: Account(), A2: Account(balance=1)})
    db.start_tx()
    db.touch(A1)
    db.touch(A2)
    db.destroy_touched_empty()
    assert not db.account_exists(A1)
    assert db.account_exists(A2)


def test_storage_zero_deletes_slot():
    db = StateDB({A1: Account(storage={1: 5})})
    db.start_tx()
    db.set_storage(A1, 1, 0)
    assert 1 not in db.accounts[A1].storage


# --- secp256k1 / signer ---------------------------------------------------

EIP155_KEY = 0x4646464646464646464646464646464646464646464646464646464646464646
EIP155_ADDR = bytes.fromhex("9d8a62f656a8d1615c1294fd71e9cfb3e4855a4f")


def _eip155_tx(v=0, r=0, s=0):
    return LegacyTx(
        nonce=9, gas_price=20 * 10**9, gas_limit=21000,
        to=bytes.fromhex("3535353535353535353535353535353535353535"),
        value=10**18, data=b"", v=v, r=r, s=s,
    )


def test_eip155_canonical_example():
    signer = TxSigner(chain_id=1)
    signed = signer.sign(_eip155_tx(), EIP155_KEY)
    assert signed.v == 37
    assert signed.r == 0x28EF61340BD939BC2195FE537567866003E1A15D3C71FF63E1590620AA636276
    assert signed.s == 0x67CBE9D8997F761AECB703304B3800CCF555C9F3DC64214B297FB1966A3B6D83
    assert signer.get_sender(signed) == EIP155_ADDR


def test_typed_tx_sign_recover_roundtrip():
    signer = TxSigner(chain_id=1)
    tx = FeeMarketTx(
        chain_id_val=1, nonce=3, max_priority_fee_per_gas=2, max_fee_per_gas=100,
        gas_limit=50000, to=b"\x42" * 20, value=5, data=b"\x01\x02",
        access_list=((b"\x43" * 20, (b"\x00" * 32,)),), y_parity=0, r=0, s=0,
    )
    for key in (1, 2, 0xDEADBEEF, secp256k1.N - 1):
        signed = signer.sign(tx, key)
        expect = address_from_pubkey(secp256k1.pubkey_of(key))
        assert signer.get_sender(signed) == expect


def test_pre_eip155_v27():
    signer = TxSigner(chain_id=1)
    tx = _eip155_tx(v=27)  # marks pre-155 signing scheme
    signed = signer.sign(tx, EIP155_KEY)
    assert signed.v in (27, 28)
    assert signer.get_sender(signed) == EIP155_ADDR


def test_signature_validation():
    with pytest.raises(SignatureError):
        secp256k1.validate_signature_fields(0, 1)
    with pytest.raises(SignatureError):
        secp256k1.validate_signature_fields(1, secp256k1.N)
    with pytest.raises(SignatureError):  # high-s rejected
        secp256k1.validate_signature_fields(1, secp256k1.HALF_N + 1)
    secp256k1.validate_signature_fields(1, secp256k1.HALF_N)


def test_wrong_chain_id_rejected():
    signer1 = TxSigner(chain_id=1)
    signed = signer1.sign(_eip155_tx(), EIP155_KEY)
    with pytest.raises(SignatureError):
        TxSigner(chain_id=5).get_sender(signed)


def test_recover_rejects_garbage():
    with pytest.raises(SignatureError):
        secp256k1.recover_pubkey(b"\x00" * 32, 1, 1, 7)
    # a random r that is not an x-coordinate of a curve point for parity 0
    bad_r = 5  # x=5: x^3+7=132; sqrt exists? validated by exception-or-recover
    try:
        secp256k1.recover_pubkey(b"\x11" * 32, bad_r, 1, 0)
    except SignatureError:
        pass  # acceptable: not on curve


def test_incremental_state_root_matches_rebuild():
    """StateDB.state_root keeps a retained trie synced via a dirty set; it
    must equal a from-scratch rebuild across mutations, deletions, and
    journal rollbacks."""
    import numpy as np

    from phant_tpu.state.root import state_root as rebuild_root
    from phant_tpu.state.statedb import StateDB
    from phant_tpu.types.account import Account

    rng = np.random.default_rng(21)
    db = StateDB(
        {rng.bytes(20): Account(balance=int(rng.integers(1, 10**12)))
         for _ in range(50)}
    )
    addrs = list(db.accounts)
    assert db.state_root() == rebuild_root(db.accounts)

    db.begin_block()
    for i in range(30):
        a = addrs[int(rng.integers(0, len(addrs)))]
        db.add_balance(a, 7)
        db.set_storage(a, int(rng.integers(0, 5)), int(rng.integers(0, 3)))
    new_addr = rng.bytes(20)
    db.set_balance(new_addr, 123)
    db.delete_account(addrs[0])
    assert db.state_root() == rebuild_root(db.accounts)

    # rollback must bring the incremental root back too
    db.begin_block()
    before = db.state_root()
    db.set_balance(addrs[1], 999)
    db.delete_account(addrs[2])
    db.set_storage(addrs[3], 1, 42)
    db.rollback_block()
    assert db.state_root() == before == rebuild_root(db.accounts)


def test_incremental_root_survives_rollback_after_state_root():
    """Code-review r3 repro: state_root() mid-block syncs the retained trie
    to a post-state that the block's rollback then rejects; the rollback
    must re-mark reverted addresses dirty or every later root is wrong."""
    import numpy as np

    from phant_tpu.state.root import state_root as rebuild_root
    from phant_tpu.state.statedb import StateDB
    from phant_tpu.types.account import Account

    rng = np.random.default_rng(33)
    db = StateDB(
        {rng.bytes(20): Account(balance=int(rng.integers(1, 10**12)),
                                storage={1: 5, 2: 9})
         for _ in range(20)}
    )
    addrs = list(db.accounts)
    good = db.state_root()

    db.begin_block()
    db.set_balance(addrs[0], 777)
    db.set_storage(addrs[1], 2, 0)   # storage deletion
    db.set_storage(addrs[1], 7, 123)
    db.delete_account(addrs[2])
    bad = db.state_root()            # syncs the retained trie mid-block
    assert bad != good
    db.rollback_block()              # block rejected (e.g. root mismatch)
    assert db.state_root() == good == rebuild_root(db.accounts)


@pytest.mark.parametrize("seed", [43, 44])
def test_a_rejected_blocks_root_leaves_no_reference_behind(walk_counts, seed):
    """The rejected-block path on a trie that keeps each node's reference:
    `state_root()` syncs the retained tries to the block's post-state (new
    nodes made, old ones freed), `revert_to(0)` rejects it, and the next
    `state_root()` must be the pre-block root and a full rebuild's: a
    reference that outlived its node, or answered for a new node at a dead
    one's address, is a wrong root here. Accepted blocks in between; and
    every root hashes at most the nodes it encoded."""
    import gc

    import numpy as np

    from phant_tpu.state.root import state_root as rebuild_root
    from phant_tpu.state.statedb import StateDB
    from phant_tpu.types.account import Account

    rng = np.random.default_rng(seed)
    db = StateDB(
        {
            rng.bytes(20): Account(
                balance=int(rng.integers(1, 10**12)),
                storage={s: s + 1 for s in range(int(rng.integers(0, 4)))},
            )
            for _ in range(600)
        }
    )
    assert db.state_root() == rebuild_root(db.accounts)

    for block in range(6):
        addrs = list(db.accounts)
        good = db.state_root()
        db.begin_block()
        for _ in range(40):
            a = addrs[int(rng.integers(0, len(addrs)))]
            db.add_balance(a, 3)
            db.set_storage(a, int(rng.integers(0, 6)), int(rng.integers(0, 3)))
        for _ in range(25):  # new leaves split old ones: new nodes, freed nodes
            db.set_balance(rng.bytes(20), int(rng.integers(1, 10**9)))
        for i in rng.choice(len(addrs), size=25, replace=False):
            db.delete_account(addrs[int(i)])  # branches collapse
        before = walk_counts()
        post = db.state_root()
        encoded, hashed = (b - a for a, b in zip(before, walk_counts()))
        assert 0 < hashed <= encoded, (block, encoded, hashed)
        assert post != good
        if block % 2:
            assert post == rebuild_root(db.accounts)
            continue  # accepted
        db.revert_to(0)
        gc.collect()
        before = walk_counts()
        again = db.state_root()
        encoded, hashed = (b - a for a, b in zip(before, walk_counts()))
        assert 0 < hashed <= encoded, (block, encoded, hashed)
        assert again == good == rebuild_root(db.accounts), block


def test_delete_and_recreate_of_an_account_with_storage(node_encoder):
    """An account with a retained storage trie is deleted and made again in
    one block, with a root taken at every step and the block rejected at
    the end: the storage trie's entries go with the trie, the account's
    leaf is re-encoded with each storage root, and nothing of the dead
    account is read afterwards."""
    import numpy as np

    from phant_tpu.state.root import state_root as rebuild_root
    from phant_tpu.state.statedb import StateDB
    from phant_tpu.types.account import Account

    rng = np.random.default_rng(45)
    accounts = {rng.bytes(20): Account(balance=int(rng.integers(1, 10**9))) for _ in range(80)}
    big = rng.bytes(20)
    accounts[big] = Account(code=b"\xfe", storage={i: i + 1 for i in range(150)})
    db = StateDB(accounts)
    good = db.state_root()
    assert good == rebuild_root(db.accounts)

    db.begin_block()
    db.set_storage(big, 3, 99)
    assert db.state_root() == rebuild_root(db.accounts)
    db.delete_account(big)
    assert db.state_root() == rebuild_root(db.accounts)
    db.create_account(big)
    db.set_balance(big, 5)
    for slot in range(0, 40, 3):
        db.set_storage(big, slot, 1000 + slot)
    assert db.state_root() == rebuild_root(db.accounts)
    db.set_storage(big, 3, 0)
    assert db.state_root() == rebuild_root(db.accounts)
    db.rollback_block()
    assert db.state_root() == good == rebuild_root(db.accounts)


def test_incremental_storage_root_heavy_account():
    """Per-account retained storage tries: repeated single-slot writes to a
    large contract must stay correct across roots, deletion, recreation."""
    import numpy as np

    from phant_tpu.state.root import state_root as rebuild_root
    from phant_tpu.state.statedb import StateDB
    from phant_tpu.types.account import Account

    rng = np.random.default_rng(34)
    big = rng.bytes(20)
    db = StateDB({big: Account(code=b"\xfe", storage={i: i + 1 for i in range(200)})})
    db.state_root()
    db.begin_block()
    for step in range(12):
        db.set_storage(big, int(rng.integers(0, 250)), int(rng.integers(0, 3)))
        assert db.state_root() == rebuild_root(db.accounts), step
    # delete + recreate resets storage entirely (object-identity guard)
    db.delete_account(big)
    db.create_account(big)
    db.set_storage(big, 5, 42)
    assert db.state_root() == rebuild_root(db.accounts)
