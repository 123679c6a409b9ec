"""Differential tests for the fused witness kernel (on-device RLP ref
extraction, phant_tpu/ops/witness_jax.py witness_verify_fused): verdicts
must match the explicit-refs device kernel AND the host BFS
(phant_tpu/mpt/proof.py verify_witness_linked) on real witnesses, corrupted
witnesses, and adversarial node bytes."""

import jax.numpy as jnp
import numpy as np
import pytest

from phant_tpu import rlp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import Trie
from phant_tpu.mpt.proof import generate_proof, verify_witness_linked
from phant_tpu.ops.witness_jax import (
    WITNESS_MAX_CHUNKS,
    pack_witness,
    pack_witness_fused,
    roots_to_words,
    scan_refs_py,
    witness_verify_fused,
    witness_verify_linked,
)


def _fused(node_lists, roots):
    blob, meta16 = pack_witness_fused(node_lists, WITNESS_MAX_CHUNKS)
    out = witness_verify_fused(
        jnp.asarray(blob),
        jnp.asarray(meta16),
        jnp.asarray(roots_to_words(roots)),
        max_chunks=WITNESS_MAX_CHUNKS,
        n_blocks=len(roots),
    )
    return np.asarray(out)


def _linked(node_lists, roots):
    blob, meta, ref_meta = pack_witness(node_lists, WITNESS_MAX_CHUNKS)
    out = witness_verify_linked(
        jnp.asarray(blob),
        jnp.asarray(meta),
        jnp.asarray(ref_meta),
        jnp.asarray(roots_to_words(roots)),
        max_chunks=WITNESS_MAX_CHUNKS,
        n_blocks=len(roots),
    )
    return np.asarray(out)


def _account_world(rng, n_accounts=120, n_storage=24):
    """State trie whose leaves commit real storage subtrees (the witness
    links account leaf -> storage root -> storage nodes)."""
    storage = Trie()
    for _ in range(n_storage):
        storage.put(
            keccak256(rng.bytes(32)),
            rlp.encode(rlp.encode_uint(int.from_bytes(rng.bytes(25), "big") + 1)),
        )
    sroot = storage.root_hash()
    trie = Trie()
    keys = []
    for i in range(n_accounts):
        key = keccak256(rng.bytes(20))
        leaf = rlp.encode(
            [
                rlp.encode_uint(int(rng.integers(0, 1000))),
                rlp.encode_uint(int(rng.integers(0, 10**18))),
                sroot if i % 3 == 0 else rng.bytes(32),
                rng.bytes(32),
            ]
        )
        trie.put(key, leaf)
        keys.append(key)
    return trie, storage, keys


def _witnesses(rng, trie, storage, keys, n_blocks=6, per_block=8):
    node_lists, roots = [], []
    skeys = [
        k
        for k in (keccak256(rng.bytes(32)) for _ in range(4))
    ]
    for _ in range(n_blocks):
        idx = rng.choice(len(keys), size=per_block, replace=False)
        nodes: dict = {}
        for i in idx:
            for enc in generate_proof(trie, keys[i]):
                nodes[enc] = None
        # storage subtree nodes ride along for accounts committing sroot
        for sk in skeys:
            for enc in generate_proof(storage, sk):
                nodes[enc] = None
        node_lists.append(list(nodes))
        roots.append(trie.root_hash())
    return node_lists, roots


def test_fused_matches_linked_and_host():
    rng = np.random.default_rng(5)
    trie, storage, keys = _account_world(rng)
    node_lists, roots = _witnesses(rng, trie, storage, keys)
    fused = _fused(node_lists, roots)
    linked = _linked(node_lists, roots)
    host = [verify_witness_linked(r, n) for r, n in zip(roots, node_lists)]
    assert fused.tolist() == linked.tolist() == host
    assert all(host)  # the generated witnesses are genuinely valid


def test_fused_rejects_broken_linkage():
    rng = np.random.default_rng(7)
    trie, storage, keys = _account_world(rng)
    node_lists, roots = _witnesses(rng, trie, storage, keys)
    # drop the largest (inner) node of block 2: subtree no longer connected
    victim = max(range(len(node_lists[2])), key=lambda i: len(node_lists[2][i]))
    node_lists[2] = [n for i, n in enumerate(node_lists[2]) if i != victim]
    fused = _fused(node_lists, roots)
    host = [verify_witness_linked(r, n) for r, n in zip(roots, node_lists)]
    assert fused.tolist() == host
    assert not fused[2] and fused[0] and fused[1]


def test_fused_rejects_wrong_root():
    rng = np.random.default_rng(9)
    trie, storage, keys = _account_world(rng)
    node_lists, roots = _witnesses(rng, trie, storage, keys, n_blocks=3)
    roots[1] = bytes(32)
    fused = _fused(node_lists, roots)
    assert fused.tolist() == [True, False, True]


def test_fused_device_refs_match_host_scanner():
    """The on-device RLP parser must find exactly the refs the host/native
    scanner finds, node for node."""
    import jax

    from phant_tpu.ops.witness_jax import (
        _extract_ref_positions,
        _gather_node_rows,
        _row_words,
    )

    rng = np.random.default_rng(11)
    trie, storage, keys = _account_world(rng)
    node_lists, _roots = _witnesses(rng, trie, storage, keys, n_blocks=2)
    nodes = [n for nl in node_lists for n in nl]
    blob = np.frombuffer(
        b"".join(nodes) + b"\x00" * (WITNESS_MAX_CHUNKS * 136), np.uint8
    )
    lens = np.fromiter((len(n) for n in nodes), np.int64, len(nodes))
    offsets = np.zeros(len(nodes), np.int64)
    offsets[1:] = np.cumsum(lens[:-1])
    want_off, want_node = scan_refs_py(blob.tobytes(), offsets, lens)
    want = {(int(n), int(o)) for n, o in zip(want_node, want_off)}

    data = _row_words(
        _gather_node_rows(
            jnp.asarray(blob),
            jnp.asarray(offsets.astype(np.int32)),
            jnp.asarray(lens.astype(np.int32)),
            WITNESS_MAX_CHUNKS * 136,
        )
    )
    ref_pos = np.asarray(
        jax.jit(_extract_ref_positions)(data, jnp.asarray(lens.astype(np.int32)))
    )
    got = {
        (i, int(offsets[i] + ref_pos[i, k]))
        for i in range(len(nodes))
        for k in range(17)
        if ref_pos[i, k] >= 0
    }
    assert got == want


def test_fused_garbage_nodes_fail_closed():
    """Arbitrary bytes in the witness must never verify (the device parser
    marks malformed nodes ref-less; the host packer raises instead — both
    reject)."""
    rng = np.random.default_rng(13)
    trie, storage, keys = _account_world(rng, n_accounts=40)
    node_lists, roots = _witnesses(rng, trie, storage, keys, n_blocks=2, per_block=4)
    garbage = [bytes(rng.integers(0, 256, size=int(s), dtype=np.uint8)) for s in (1, 33, 100, 679)]
    node_lists[1] = node_lists[1] + garbage
    fused = _fused(node_lists, roots)
    assert fused[0] and not fused[1]


def test_fused_empty_blocks():
    # a block with no nodes cannot contain its root
    fused = _fused([[], [rlp.encode([b"\x20", b"v" * 40])]], [bytes(32), bytes(32)])
    assert fused.tolist() == [False, False]
