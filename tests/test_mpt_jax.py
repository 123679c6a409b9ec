"""Differential tests: device MPT root (phant_tpu/ops/mpt_jax.py) vs the
host recursion (phant_tpu/mpt/mpt.py) — bit-exact on every trie shape,
including the embedded-node fallback and the backend dispatch used by the
block path (reference scope: src/mpt/mpt.zig:38-119)."""

import numpy as np
import pytest

from phant_tpu import rlp
from phant_tpu.backend import set_crypto_backend
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import Trie, ordered_trie_root, trie_root_hash
from phant_tpu.ops.mpt_jax import build_hash_plan, trie_root_device


def _account_leaf(rng) -> bytes:
    """~70B leaf value shaped like an account: keeps node encodings >= 32B."""
    return rlp.encode(
        [
            rlp.encode_uint(int(rng.integers(0, 1000))),
            rlp.encode_uint(int(rng.integers(0, 10**18))),
            rng.bytes(32),
            rng.bytes(32),
        ]
    )


@pytest.mark.parametrize("n", [1, 2, 3, 5, 17, 100])
def test_device_root_matches_host(n):
    """Random secure-trie shapes, incl. non-power-of-two level populations
    (regression: digest rows are pow2-padded per level; child references
    must use padded positions)."""
    rng = np.random.default_rng(n)
    trie = Trie()
    for _ in range(n):
        trie.put(keccak256(rng.bytes(20)), _account_leaf(rng))
    assert trie_root_device(trie) == trie.root_hash()


def test_device_root_deep_extension():
    """Keys sharing long prefixes force extension nodes and deep levels."""
    rng = np.random.default_rng(42)
    trie = Trie()
    base = bytearray(keccak256(b"base"))
    for i in range(8):
        key = bytes(base[:-1]) + bytes([i * 16 + 7])
        trie.put(key, _account_leaf(rng))
    trie.put(keccak256(b"elsewhere"), _account_leaf(rng))
    assert trie_root_device(trie) == trie.root_hash()


def test_embedded_node_trie_falls_back():
    """Small values produce <32B leaf encodings; the plan refuses and the
    device path must return the host root."""
    trie = Trie()
    for i in range(4):
        trie.put(bytes([i]) * 4, rlp.encode_uint(i + 1))
    assert build_hash_plan(trie) is None
    assert trie_root_device(trie) == trie.root_hash()


def test_empty_and_single():
    from phant_tpu.mpt.mpt import EMPTY_TRIE_ROOT

    assert trie_root_device(Trie()) == EMPTY_TRIE_ROOT
    rng = np.random.default_rng(0)
    t = Trie()
    t.put(keccak256(b"solo"), _account_leaf(rng))
    assert trie_root_device(t) == t.root_hash()


def test_branch_value_node():
    """A key that is a strict prefix of another puts a value on a branch."""
    rng = np.random.default_rng(9)
    t = Trie()
    long_key = keccak256(b"x")
    t.put(long_key, _account_leaf(rng))
    # shorter key = prefix of long_key's nibble path
    t.put(long_key[:16], _account_leaf(rng))
    assert trie_root_device(t) == t.root_hash()


def test_backend_dispatch_ordered_root():
    """ordered_trie_root must agree across crypto backends (the tx/receipt/
    withdrawal roots the block path recomputes, reference:
    src/blockchain/blockchain.zig:200-203)."""
    rng = np.random.default_rng(3)
    values = [rng.bytes(int(rng.integers(40, 200))) for _ in range(30)]
    cpu = ordered_trie_root(values)
    set_crypto_backend("tpu")
    try:
        tpu = ordered_trie_root(values)
    finally:
        set_crypto_backend("cpu")
    assert cpu == tpu


def test_backend_dispatch_state_root():
    """state_root through the dispatcher (phant_tpu/state/root.py)."""
    from phant_tpu.state.root import state_root
    from phant_tpu.types.account import Account

    rng = np.random.default_rng(5)
    accounts = {}
    for _ in range(20):
        addr = rng.bytes(20)
        accounts[addr] = Account(
            nonce=int(rng.integers(0, 100)),
            balance=int(rng.integers(0, 10**18)),
            storage={int(rng.integers(0, 50)): int.from_bytes(rng.bytes(25), "big") + 1},
        )
    cpu = state_root(accounts)
    set_crypto_backend("tpu")
    try:
        tpu = state_root(accounts)
    finally:
        set_crypto_backend("cpu")
    assert cpu == tpu


def test_trie_root_hash_dispatch():
    rng = np.random.default_rng(11)
    t = Trie()
    for _ in range(12):
        t.put(keccak256(rng.bytes(20)), _account_leaf(rng))
    set_crypto_backend("tpu")
    try:
        assert trie_root_hash(t) == t.root_hash()
    finally:
        set_crypto_backend("cpu")


def test_plan_cache_invalidated_on_mutation():
    """trie_root_device caches the HashPlan per mutation epoch; a put or
    delete must invalidate it (stale plans would silently hash old bytes)."""
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.ops.mpt_jax import trie_root_device

    trie = Trie()
    for i in range(40):
        trie.put(keccak256(bytes([i])), b"v" * 40)
    r1 = trie_root_device(trie)
    assert r1 == trie.root_hash()
    assert trie._device_plan is not None
    trie.put(keccak256(bytes([100])), b"w" * 40)
    r2 = trie_root_device(trie)
    assert r2 == trie.root_hash() and r2 != r1
    trie.delete(keccak256(bytes([100])))
    r3 = trie_root_device(trie)
    assert r3 == trie.root_hash() == r1


def test_batched_roots_match_host():
    """K same-structure plans (value-mutated blobs) in one dispatch must
    reproduce the host executor's root for every blob — the replay shape
    that amortizes the device round trip over a span of blocks."""
    import copy

    from phant_tpu.ops.mpt_jax import execute_plan_host, trie_roots_device_batched

    rng = np.random.default_rng(5)
    trie = Trie()
    for _ in range(64):
        trie.put(keccak256(rng.bytes(20)), _account_leaf(rng))
    plan = build_hash_plan(trie)
    assert plan is not None

    leaf_off, leaf_ln, _hp, _hc = plan.levels[0]
    plans = []
    for _k in range(4):
        p = copy.copy(plan)
        p.blob = plan.blob.copy()
        p.device_args = None
        for i in np.nonzero(leaf_ln)[0][:3]:
            off = int(leaf_off[int(i)])
            p.blob[off + 40 : off + 48] = np.frombuffer(rng.bytes(8), np.uint8)
        plans.append(p)
    got = trie_roots_device_batched(plans)
    want = [execute_plan_host(p) for p in plans]
    assert got == want
    assert len(set(got)) == len(got)  # mutations actually changed the roots


def test_batched_roots_reject_mismatched_structure():
    from phant_tpu.ops.mpt_jax import trie_roots_device_batched

    rng = np.random.default_rng(6)
    t1, t2 = Trie(), Trie()
    for _ in range(8):
        t1.put(keccak256(rng.bytes(20)), _account_leaf(rng))
    for _ in range(16):
        t2.put(keccak256(rng.bytes(20)), _account_leaf(rng))
    p1, p2 = build_hash_plan(t1), build_hash_plan(t2)
    with pytest.raises(ValueError):
        trie_roots_device_batched([p1, p2])


# --- templates: the extension's node encoder against the Python one ---------


def _template_cases():
    from phant_tpu.ops.mpt_jax import _HOLE, _ValueHole

    digest = bytes(range(32))
    embedded = [b"\x20", [b"\x31", b"\x05"]]  # a child under 32 bytes, two deep
    return {
        "no-holes-leaf": [b"\x20\x12", b"v" * 70],
        "no-holes-branch-of-constants": [digest] * 16 + [b""],
        "one-hole-extension": [b"\x00\x12", _HOLE],
        "one-hole-first-of-branch": [_HOLE] + [b""] * 16,
        "one-hole-last-child": [b""] * 15 + [_HOLE, b""],
        "17-holes": [_HOLE] * 17,
        "16-holes-and-a-value": [_HOLE] * 16 + [b"\x07" * 40],
        "holes-between-constants": [digest, _HOLE, b"", embedded, _HOLE] + [b""] * 12,
        "value-hole": [b"\x20" + b"\x11" * 31, _ValueHole(b"\xf8\x44\x01\x80\xa0", b"\xa0" + digest)],
        "value-hole-bare": [b"\x31", _ValueHole(b"", b"")],
        "value-hole-long-string": [b"\x31", _ValueHole(b"p" * 200, b"s" * 100)],
        "value-hole-and-hole": [_ValueHole(b"ab", b"cd"), _HOLE],
        "value-byte-0x7f": [b"\x31", b"\x7f"],
        "value-byte-0x80": [b"\x31", b"\x80"],
        "payload-55B": [b"a" * 54],
        "payload-56B": [b"a" * 55],
        "payload-255B": [b"a" * 253],
        "payload-256B": [b"a" * 253, b""],
        "payload-70000B": [b"\x31", b"a" * 70_000, _HOLE],
        "empty": [],
    }


_TEMPLATE_CASES = _template_cases()


@pytest.mark.parametrize("case", sorted(_TEMPLATE_CASES))
def test_encode_template_matches_the_python_encoder(node_encoder, case):
    """The encoding AND every hole's offset; each offset points at 32 zero
    bytes, which filled in give the node's own RLP."""
    from phant_tpu.ops.mpt_jax import (
        _HOLE,
        _ValueHole,
        _encode_template,
        _encode_template_python,
    )

    items = _TEMPLATE_CASES[case]
    template, holes = _encode_template(items)
    assert (template, list(holes)) == _encode_template_python(items)
    n_holes = sum(it is _HOLE or isinstance(it, _ValueHole) for it in items)
    assert len(holes) == n_holes and list(holes) == sorted(holes)
    # each hole filled in with a mark of its own gives the node's own RLP
    filled = bytearray(template)
    want = []
    offsets = iter(holes)
    for it in items:
        mark = bytes([len(want) + 1]) * 32
        if it is _HOLE:
            want.append(mark)
        elif isinstance(it, _ValueHole):
            want.append(it.prefix + mark + it.suffix)
        else:
            want.append(it)
            continue
        at = next(offsets)
        assert template[at : at + 32] == b"\x00" * 32
        filled[at : at + 32] = mark
    assert bytes(filled) == rlp.encode_python(want)


@pytest.mark.parametrize(
    "items",
    [
        [b"\x31", None],
        [b"\x31", "text"],
        [b"\x31", 1.5],
        [b"\x31", [b"a", None]],
    ],
    ids=["none", "str", "float", "nested-none"],
)
def test_a_malformed_template_item_is_a_type_error(node_encoder, items):
    from phant_tpu.ops.mpt_jax import _encode_template

    with pytest.raises(TypeError):
        _encode_template(items)


def test_a_hole_below_the_top_list_is_a_type_error(node_encoder):
    """Holes are children of the node itself; an embedded child holds none
    (its subtree is unplannable), under either encoder."""
    from phant_tpu.ops.mpt_jax import _HOLE, _ValueHole, _encode_template

    for nested in ([b"\x31", [_HOLE]], [b"\x31", [_ValueHole(b"a", b"b")]]):
        with pytest.raises(TypeError):
            _encode_template(nested)


def test_the_builder_counts_its_nodes_under_the_encoder_it_used(node_encoder):
    from phant_tpu.utils.trace import metrics

    rng = np.random.default_rng(5)
    trie = Trie()
    for _ in range(40):
        trie.put(keccak256(rng.bytes(8)), _account_leaf(rng))

    def counted(impl):
        return metrics.snapshot()["counters"].get(f'mpt.node_encodings{{impl="{impl}"}}', 0)

    other = "python" if node_encoder == "native" else "native"
    before = counted(node_encoder), counted(other)
    plan = build_hash_plan(trie)
    assert counted(node_encoder) - before[0] == plan.n_nodes
    assert counted(other) == before[1]
