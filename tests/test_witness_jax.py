"""Differential tests: fused device witness pipeline vs the CPU oracle
(phant_tpu/mpt/proof.py + CPU keccak)."""

import jax.numpy as jnp
import numpy as np
import pytest

from phant_tpu import rlp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import Trie
from phant_tpu.mpt.proof import generate_proof, verify_witness
from phant_tpu.ops.witness_jax import (
    WITNESS_MAX_CHUNKS as MAX_CHUNKS,
    _pack_rows_np,
    _ref_words_from_rows,
    node_row_features,
    pack_node_rows,
    pack_witness_blob,
    pack_witness_fused,
    roots_to_words,
    witness_digests,
    witness_node_features,
    witness_verify_fused,
)


def _trie_with_proofs(n_keys=64, touched=8, seed=3):
    rng = np.random.default_rng(seed)
    trie = Trie()
    keys = []
    for _ in range(n_keys):
        key = keccak256(rng.bytes(20))
        trie.put(key, rlp.encode(rng.bytes(40)))
        keys.append(key)
    root = trie.root_hash()
    idx = rng.choice(n_keys, size=touched, replace=False)
    nodes: dict = {}
    entries = []
    for i in idx:
        for n in generate_proof(trie, keys[i]):
            nodes[n] = None
        entries.append((keys[i], trie.get(keys[i])))
    return root, entries, list(nodes.keys())


def test_witness_digests_match_cpu():
    rng = np.random.default_rng(0)
    payloads = [rng.bytes(int(rng.integers(1, MAX_CHUNKS * 136))) for _ in range(33)]
    blob, meta = pack_witness_blob([payloads], MAX_CHUNKS)
    got = np.asarray(
        witness_digests(
            jnp.asarray(blob),
            jnp.asarray(meta[0]),
            jnp.asarray(meta[1]),
            max_chunks=MAX_CHUNKS,
        )
    )
    exp = np.stack([np.frombuffer(keccak256(p), "<u4") for p in payloads])
    assert (got[: len(payloads)] == exp).all()


def test_witness_verify_fused_blocks():
    blocks = [_trie_with_proofs(seed=s) for s in range(4)]
    # CPU oracle agrees these witnesses are complete
    for root, entries, nodes in blocks:
        assert verify_witness(root, entries, nodes)

    node_lists = [nodes for _r, _e, nodes in blocks]
    roots = roots_to_words([r for r, _e, _n in blocks])
    blob, meta16 = pack_witness_fused(node_lists, MAX_CHUNKS)
    ok = np.asarray(
        witness_verify_fused(
            jnp.asarray(blob),
            jnp.asarray(meta16),
            jnp.asarray(roots),
            max_chunks=MAX_CHUNKS,
            n_blocks=len(blocks),
        )
    )
    assert ok.all()

    # corrupt one block's root -> only that block fails
    bad = roots.copy()
    bad[2] ^= 0xFF
    ok = np.asarray(
        witness_verify_fused(
            jnp.asarray(blob),
            jnp.asarray(meta16),
            jnp.asarray(bad),
            max_chunks=MAX_CHUNKS,
            n_blocks=len(blocks),
        )
    )
    assert list(ok) == [True, True, False, True]


def test_pack_witness_blob_layout():
    rng = np.random.default_rng(1)
    nl = [
        [rng.bytes(int(rng.integers(32, 577))) for _ in range(int(rng.integers(1, 9)))]
        for _ in range(7)
    ]
    blob, meta = pack_witness_blob(nl, MAX_CHUNKS)
    flat = [n for nodes in nl for n in nodes]
    offsets, lens, block_id = meta
    for i, n in enumerate(flat):
        assert blob[offsets[i] : offsets[i] + lens[i]].tobytes() == n
    exp_bid = [b for b, nodes in enumerate(nl) for _ in nodes]
    assert list(block_id[: len(flat)]) == exp_bid
    assert (lens[len(flat) :] == 0).all()
    # oversized node rejected
    try:
        pack_witness_blob([[b"x" * (MAX_CHUNKS * 136)]], MAX_CHUNKS)
        raise AssertionError("oversized node accepted")
    except ValueError:
        pass


# ---------------------------------------------------------------------------
# the row form (PR 31): rows laid out on the host, refs sliced as rows
# ---------------------------------------------------------------------------

def _h(rng):
    return rng.bytes(32)


def _account_leaf(rng, path=b"\x20" + b"\x11" * 30):
    acct = rlp.encode([b"\x05", rng.bytes(7), _h(rng), _h(rng)])
    return rlp.encode([path, acct])


def _row_form_cases():
    """name -> nodes; each batch is hashed and parsed in both forms."""
    rng = np.random.default_rng(31)
    full_branch = rlp.encode([_h(rng) for _ in range(16)] + [b""])
    sparse_branch = rlp.encode(
        [_h(rng) if k in (0, 7, 15) else b"" for k in range(16)] + [b"val"]
    )
    # a child under 32 bytes is embedded as a LIST: never a ref, and the
    # slots after it still land where their bytes are
    embedded = rlp.encode(
        [[b"\x35", b"ab"], _h(rng)] + [b""] * 13 + [_h(rng), b""]
    )
    big = bytearray(rng.bytes(679))  # the largest row: 5 chunks, pad in the last byte
    big[0] = 0xB9
    big[1:3] = (676).to_bytes(2, "big")
    return {
        "branch": [full_branch, sparse_branch],
        "extension": [
            rlp.encode([b"\x00\xab\xcd", _h(rng)]),
            rlp.encode([b"\x1a", _h(rng)]),
            rlp.encode([b"\x00\xab", [b"\x35", b"ab"]]),  # embedded child: no ref
        ],
        "account_leaf": [
            _account_leaf(rng),
            _account_leaf(rng, b"\x3a" + b"\x22" * 12),
            # a 32-byte VALUE is not an account: ref-less
            rlp.encode([b"\x20" + b"\x01" * 20, _h(rng)]),
        ],
        "storage_leaf": [
            rlp.encode([b"\x20" + b"\x33" * 31, rlp.encode(rng.bytes(9))]),
            rlp.encode([b"\x3f" + b"\x33" * 5, b"\x07"]),
        ],
        "embedded_child": [embedded, full_branch],
        "malformed_rlp": [
            b"\xc2\x81",  # list whose item runs past its end
            b"\xf9\xff\xff" + rng.bytes(40),  # length past the node
            b"\xa0" + _h(rng),  # a string, not a list
            rlp.encode([_h(rng)] * 18),  # 18 items
            rlp.encode([b"", _h(rng)]),  # empty hex-prefix path
            full_branch + b"\x00",  # trailing byte
            rng.bytes(100),
        ],
        "node_679_bytes": [bytes(big), rng.bytes(679), full_branch],
        "zero_length_pad_rows": [b"", full_branch, b"", _account_leaf(rng)],
    }


_ROW_CASES = _row_form_cases()


@pytest.mark.parametrize("case", sorted(_ROW_CASES))
def test_row_form_features_equal_blob_form(case):
    """What the resident update computes from rows the host laid out is what
    the blob form computes from rows the device gathered: digests, ref words
    and liveness, pad rows included; and the digests are keccak's."""
    import jax

    nodes = _ROW_CASES[case]
    rows = 8
    blob, meta = pack_witness_blob([nodes], MAX_CHUNKS, pad_nodes_to=rows)
    want_d, want_r, want_l = jax.jit(
        witness_node_features, static_argnames="max_chunks"
    )(jnp.asarray(blob), jnp.asarray(meta[0]), jnp.asarray(meta[1]), max_chunks=MAX_CHUNKS)
    words, lens = pack_node_rows(nodes, MAX_CHUNKS, pad_rows_to=rows)
    assert words.shape == (rows, MAX_CHUNKS * 34) and (lens == meta[1]).all()
    got_d, got_r, got_l = jax.jit(node_row_features, static_argnames="max_chunks")(
        jnp.asarray(words), jnp.asarray(lens), max_chunks=MAX_CHUNKS
    )
    assert (np.asarray(got_d) == np.asarray(want_d)).all()
    assert (np.asarray(got_l) == np.asarray(want_l)).all()
    assert (np.asarray(got_r) == np.asarray(want_r)).all()
    exp = np.stack([np.frombuffer(keccak256(n), "<u4") for n in nodes])
    assert (np.asarray(got_d)[: len(nodes)] == exp).all()
    live = np.asarray(got_l)
    assert not live[len(nodes) :].any()
    assert not np.asarray(got_r)[~live].any()  # a dead slot reads zero
    if case == "malformed_rlp":
        assert not live.any()
    if case == "branch":
        assert live[0, :16].all() and not live[0, 16]
        assert live[1].nonzero()[0].tolist() == [0, 7, 15]


def _ref_words_bytewise(rows_u8, ref_pos):
    """The form `_ref_words_from_rows` had until PR 31, in numpy: 32 single
    bytes a slot at clip(ref_pos), dead slots reading whatever lies there."""
    idx = np.clip(ref_pos, 0, rows_u8.shape[1] - 33)[:, :, None] + np.arange(32)
    b = np.take_along_axis(rows_u8[:, None, :], idx, axis=2)
    return b.reshape(*ref_pos.shape, 8, 4).copy().view("<u4")[..., 0]


@pytest.mark.parametrize("max_chunks,seed", [(5, 0), (5, 1), (1, 2), (3, 3)])
def test_ref_words_as_rows_equal_the_byte_gather(max_chunks, seed):
    """The barrel shifter against the byte gather it replaced, at random ref
    positions of every byte alignment, dead slots masked."""
    import jax

    rng = np.random.default_rng(seed)
    B, row = 64, max_chunks * 136
    rows_u8 = rng.integers(0, 256, size=(B, row), dtype=np.uint8)
    ref_pos = rng.integers(0, row - 32, size=(B, 17)).astype(np.int32)
    ref_pos[:, :4] = np.array([0, 1, row - 33, row - 34])  # the edges
    ref_pos[rng.random((B, 17)) < 0.3] = -1
    want = _ref_words_bytewise(rows_u8, ref_pos) * (ref_pos >= 0)[:, :, None]
    got = jax.jit(_ref_words_from_rows)(
        jnp.asarray(rows_u8.view("<u4")), jnp.asarray(ref_pos)
    )
    assert (np.asarray(got) == want).all()


def test_native_packer_and_its_numpy_twin_give_the_same_rows():
    from phant_tpu.utils.native import load_native

    native = load_native()
    if native is None:
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(5)
    nodes = [rng.bytes(int(n)) for n in rng.integers(0, 680, 97)] + [b"", rng.bytes(679)]
    a = native.pack_rows(nodes, 128, 680)
    b = _pack_rows_np(nodes, 128, 680)
    assert a.dtype == b.dtype == np.uint8 and (a == b).all()
    for i, n in enumerate(nodes):
        assert a[i, : len(n)].tobytes() == n and not a[i, len(n) :].any()
    assert not a[len(nodes) :].any()
    for pack in (native.pack_rows, _pack_rows_np):
        with pytest.raises(ValueError):
            pack([rng.bytes(680)], 1, 680)  # no free byte for the keccak pad
    with pytest.raises(ValueError):
        pack_node_rows(nodes, MAX_CHUNKS, pad_rows_to=64)
