"""MPT tests: golden roots from official fixtures, proofs, hex-prefix codec."""

import os
import random
from pathlib import Path

import pytest

from phant_tpu import rlp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import (
    EMPTY_TRIE_ROOT,
    Trie,
    bytes_to_nibbles,
    decode_hex_prefix,
    encode_hex_prefix,
    ordered_trie_root,
    trie_root,
)
from phant_tpu.mpt.proof import ProofError, generate_proof, verify_proof, verify_witness
from phant_tpu.spec.fixtures import walk_fixtures
from phant_tpu.state.root import state_root
from phant_tpu.types.block import Block
from phant_tpu.utils.hexutils import hex_to_bytes

FIXTURES = Path(__file__).parent / "fixtures"


def test_empty_trie_root_constant():
    assert EMPTY_TRIE_ROOT.hex() == (
        "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421"
    )
    assert ordered_trie_root([]) == EMPTY_TRIE_ROOT


def test_hex_prefix_roundtrip():
    for nibbles, is_leaf in [
        ((), False), ((), True), ((1,), False), ((1,), True),
        ((0, 1, 2), True), ((15, 0, 15, 0), False), (tuple(range(16)), True),
    ]:
        enc = encode_hex_prefix(nibbles, is_leaf)
        assert decode_hex_prefix(enc) == (nibbles, is_leaf)


def test_hex_prefix_vectors():
    # Yellow paper appendix C examples
    assert encode_hex_prefix((1, 2, 3, 4, 5), False) == bytes.fromhex("112345")
    assert encode_hex_prefix((0, 1, 2, 3, 4, 5), False) == bytes.fromhex("00012345")
    assert encode_hex_prefix((15, 1, 12, 11, 8), True) == bytes.fromhex("3f1cb8")
    assert encode_hex_prefix((0, 15, 1, 12, 11, 8), True) == bytes.fromhex("200f1cb8")


def test_single_leaf_root():
    key, value = b"\x01\x23", b"hello world, this value is >= 32 bytes!!"
    expect = keccak256(rlp.encode([encode_hex_prefix(bytes_to_nibbles(key), True), value]))
    assert trie_root([(key, value)]) == expect


def test_insert_order_independence():
    rng = random.Random(42)
    pairs = [(os.urandom(rng.randint(1, 32)), os.urandom(rng.randint(1, 64)))
             for _ in range(200)]
    # dedupe keys (later wins); use dict semantics for both orders
    d = dict(pairs)
    items = list(d.items())
    shuffled = items[:]
    rng.shuffle(shuffled)
    assert trie_root(items) == trie_root(shuffled)


def test_get_returns_inserted():
    trie = Trie()
    d = {os.urandom(8): os.urandom(40) for _ in range(50)}
    for k, v in d.items():
        trie.put(k, v)
    for k, v in d.items():
        assert trie.get(k) == v
    assert trie.get(b"\x00" * 8) is None or b"\x00" * 8 in d


# --- golden roots from the official execution-spec-tests fixtures ---------


@pytest.mark.parametrize("check", ["genesis_hash", "state_root", "block_roots"])
def test_fixture_golden(check):
    n = 0
    for path, fx in walk_fixtures(FIXTURES):
        n += 1
        genesis = Block.decode(fx.genesis_rlp)
        if check == "genesis_hash":
            assert genesis.header.hash() == hex_to_bytes(fx.genesis_header_json["hash"])
        elif check == "state_root":
            assert state_root(fx.pre) == hex_to_bytes(
                fx.genesis_header_json["stateRoot"]
            ), f"{path.name}:{fx.name}"
        else:
            for fb in fx.blocks:
                if fb.expect_exception:
                    continue
                block = Block.decode(fb.rlp)
                assert ordered_trie_root(
                    [tx.encode() for tx in block.transactions]
                ) == block.header.transactions_root
                if block.withdrawals is not None:
                    assert ordered_trie_root(
                        [w.encode() for w in block.withdrawals]
                    ) == block.header.withdrawals_root
    assert n >= 80  # 20 files, multiple forks/tests per file


# --- proofs ---------------------------------------------------------------


def _random_trie(n, seed=7):
    rng = random.Random(seed)
    trie = Trie()
    d = {}
    for _ in range(n):
        k = bytes(rng.randrange(256) for _ in range(rng.randint(1, 16)))
        v = bytes(rng.randrange(256) for _ in range(rng.randint(1, 80)))
        d[k] = v
    for k, v in d.items():
        trie.put(k, v)
    return trie, d


def test_proof_roundtrip():
    trie, d = _random_trie(150)
    root = trie.root_hash()
    for k, v in list(d.items())[:30]:
        proof = generate_proof(trie, k)
        assert verify_proof(root, k, proof) == v


def test_absence_proof():
    trie, d = _random_trie(50)
    root = trie.root_hash()
    missing = b"\xff" * 20
    assert missing not in d
    proof = generate_proof(trie, missing)
    assert verify_proof(root, missing, proof) is None


def test_tampered_proof_fails():
    trie, d = _random_trie(80)
    root = trie.root_hash()
    k, v = next(iter(d.items()))
    proof = generate_proof(trie, k)
    # flip one byte of one node: either the walk breaks (ProofError) or the
    # value comes out wrong — it must never silently verify.
    bad = bytearray(proof[0])
    bad[-1] ^= 0x01
    tampered = [bytes(bad)] + list(proof[1:])
    try:
        got = verify_proof(root, k, tampered)
        assert got != v
    except ProofError:
        pass


def test_witness_multi_key():
    trie, d = _random_trie(100)
    root = trie.root_hash()
    keys = list(d.keys())[:10] + [b"\xfe" * 10]
    nodes = []
    for k in keys:
        nodes.extend(generate_proof(trie, k))
    entries = [(k, d.get(k)) for k in keys]
    assert verify_witness(root, entries, nodes)
    wrong = [(keys[0], b"not the value")] + entries[1:]
    assert not verify_witness(root, wrong, nodes)


# --- deletion + node collapse (round-3: EIP-158/selfdestruct/storage-zeroing
# need real delete semantics; the reference is insert-only, mpt.zig:47-119) --


def _rebuild_root(d: dict) -> bytes:
    t = Trie()
    for k, v in d.items():
        t.put(k, v)
    return t.root_hash()


def test_delete_to_empty():
    t = Trie()
    t.put(b"k", b"v")
    t.delete(b"k")
    assert t.root_hash() == EMPTY_TRIE_ROOT
    t.delete(b"missing")  # no-op on empty
    assert t.root_hash() == EMPTY_TRIE_ROOT


def test_delete_missing_key_is_noop():
    t = Trie()
    t.put(b"abc", b"1")
    t.put(b"abd", b"2")
    before = t.root_hash()
    t.delete(b"zzz")
    t.delete(b"ab")  # prefix of existing keys, not itself present
    assert t.root_hash() == before


def test_delete_collapses_branch_to_leaf():
    # two keys diverge at the last nibble -> branch; deleting one must fold
    # the branch back into a single leaf identical to a fresh insert
    t = Trie()
    t.put(b"a1", b"one")
    t.put(b"a2", b"two")
    t.delete(b"a2")
    assert t.root_hash() == _rebuild_root({b"a1": b"one"})


def test_delete_merges_extension_chain():
    # shared prefix -> extension + branch; removing one side must merge the
    # extension with the surviving subtree
    d = {b"abcdef01": b"x", b"abcdef02": b"y", b"abcdXYZ9": b"z"}
    t = Trie()
    for k, v in d.items():
        t.put(k, v)
    t.delete(b"abcdXYZ9")
    del d[b"abcdXYZ9"]
    assert t.root_hash() == _rebuild_root(d)
    t.delete(b"abcdef01")
    del d[b"abcdef01"]
    assert t.root_hash() == _rebuild_root(d)


def test_delete_branch_value_only():
    # a key that terminates AT a branch (its value slot), plus two children
    t = Trie()
    keys = {bytes([0x12]): b"at-branch", bytes([0x12, 0x30]): b"c1", bytes([0x12, 0x45]): b"c2"}
    for k, v in keys.items():
        t.put(k, v)
    t.delete(bytes([0x12]))
    del keys[bytes([0x12])]
    assert t.root_hash() == _rebuild_root(keys)
    # now deleting one child folds the branch away entirely
    t.delete(bytes([0x12, 0x45]))
    del keys[bytes([0x12, 0x45])]
    assert t.root_hash() == _rebuild_root(keys)


def test_put_empty_value_deletes():
    t = Trie()
    t.put(b"k1", b"v1")
    t.put(b"k2", b"v2")
    t.put(b"k2", b"")
    assert t.root_hash() == _rebuild_root({b"k1": b"v1"})


def test_delete_fuzz_against_rebuild():
    rng = random.Random(42)
    d: dict = {}
    t = Trie()
    for step in range(600):
        if d and rng.random() < 0.45:
            k = rng.choice(list(d))
            t.delete(k)
            del d[k]
        else:
            k = rng.randbytes(rng.choice([1, 2, 3, 8, 20, 32]))
            v = rng.randbytes(rng.randint(1, 40))
            t.put(k, v)
            d[k] = v
        if step % 7 == 0:  # frequent roots: the per-path enc cache must stay coherent
            assert t.root_hash() == _rebuild_root(d), f"divergence at step {step}"
    assert t.root_hash() == _rebuild_root(d)
    for k in list(d):
        t.delete(k)
    assert t.root_hash() == EMPTY_TRIE_ROOT


# --- the node encoder: extension against oracle -----------------------------
#
# `Trie.node_encoding` runs in the extension (native/pyext.cc
# `encode_subtree`) or, without it, in `_node_encoding_python`. Both are
# held to the oracle below, which shares nothing with either but
# `rlp.encode_python`; "python" masks the extension out the way a machine
# without a compiler would have it.


from phant_tpu.stateless import HashNode as _Edge  # an unwitnessed subtree
from phant_tpu.stateless import PartialTrie


def _witness_trie() -> Trie:
    """An empty trie over a witness: the kind whose walk meets edges."""
    return PartialTrie(EMPTY_TRIE_ROOT, {})


def _oracle(node, path_enc=encode_hex_prefix, embed_below=32):
    """(structure, encoding) by the yellow paper, appendix D."""
    from phant_tpu.mpt.mpt import BranchNode, ExtensionNode, LeafNode

    def ref(child):
        if isinstance(child, _Edge):
            return child.digest
        structure, encoded = _oracle(child, path_enc, embed_below)
        return structure if len(encoded) < embed_below else keccak256(encoded)

    if isinstance(node, LeafNode):
        structure = [path_enc(node.path, True), node.value]
    elif isinstance(node, ExtensionNode):
        structure = [path_enc(node.path, False), ref(node.child)]
    else:
        assert isinstance(node, BranchNode)
        structure = [b"" if c is None else ref(c) for c in node.children]
        structure.append(b"" if node.value is None else node.value)
    return structure, rlp.encode_python(structure)


def _oracle_entry(node, path_enc=encode_hex_prefix, embed_below=32):
    """The memo's entry of a node: its oracle, and the reference its parent
    holds of it."""
    structure, encoded = _oracle(node, path_enc, embed_below)
    ref = structure if len(encoded) < embed_below else keccak256(encoded)
    return structure, encoded, ref


def _leaf(path, size, fill=0xAB):
    from phant_tpu.mpt.mpt import LeafNode

    return LeafNode(tuple(path), bytes([fill]) * size)


def _branch(children: dict, value=None):
    from phant_tpu.mpt.mpt import BranchNode

    node = BranchNode()
    for i, child in children.items():
        node.children[i] = child
    node.value = value
    return node


def _node_cases():
    from phant_tpu.mpt.mpt import ExtensionNode, LeafNode

    rng = random.Random(35)
    cases = {}
    # a value's own RLP: one byte below and at 0x80, the short and long
    # string headers, a length of three bytes
    for size in (0, 55, 56, 255, 256, 70_000):
        cases[f"leaf-{size}B"] = _leaf((1, 2, 3), size)
    cases["leaf-byte-0x7f"] = LeafNode((4,), b"\x7f")
    cases["leaf-byte-0x80"] = LeafNode((4,), b"\x80")
    cases["leaf-empty-path"] = _leaf((), 40)
    cases["leaf-64-nibbles"] = _leaf([rng.randrange(16) for _ in range(64)], 70)
    for k in range(17):
        slots = rng.sample(range(16), k)
        cases[f"branch-{k}-children"] = _branch(
            {i: _leaf((i, 7), 33 + i, fill=i) for i in slots}
        )
    cases["branch-value"] = _branch({3: _leaf((1,), 40)}, value=b"\x01" * 9)
    cases["branch-all-edges"] = _branch(
        {i: _Edge(bytes([i]) * 32) for i in range(16)}
    )
    cases["extension-over-branch"] = ExtensionNode(
        (0xA, 0xB, 0xC), _branch({0: _leaf((5,), 50), 9: _leaf((6, 6), 50)})
    )
    cases["extension-over-edge"] = ExtensionNode((1,), _Edge(b"\x11" * 32))
    # encodings under 32 bytes stay inside their parents, two deep: tiny
    # leaves in a small branch in an extension in a branch
    small = _branch({1: _leaf((2,), 1, fill=5), 2: _leaf((), 2, fill=9)})
    assert len(_oracle(small)[1]) < 32
    cases["embedded-two-deep"] = _branch(
        {0: ExtensionNode((7,), small), 5: _leaf((3,), 3), 8: _leaf((1,), 60)}
    )
    cases["embedded-leaf-in-extension-branch"] = ExtensionNode(
        (1, 2), _branch({4: _leaf((9,), 1, fill=0x80), 6: _leaf((), 1, fill=0x7F)})
    )
    return cases


_NODE_CASES = _node_cases()


@pytest.mark.parametrize("case", sorted(_NODE_CASES))
def test_node_encoding_matches_the_oracle(node_encoder, case):
    """Structure, encoding AND reference, of the node and of everything
    under it that the walk left in the memo."""
    from phant_tpu.mpt.mpt import BranchNode, ExtensionNode

    node = _NODE_CASES[case]
    trie = _witness_trie()
    assert trie.node_encoding(node) == _oracle_entry(node)
    assert trie._enc_cache[id(node)] == _oracle_entry(node)
    stack = [node]
    while stack:
        at = stack.pop()
        if isinstance(at, _Edge):
            assert id(at) not in trie._enc_cache
            continue
        assert trie._enc_cache[id(at)] == _oracle_entry(at), type(at).__name__
        if isinstance(at, ExtensionNode):
            stack.append(at.child)
        elif isinstance(at, BranchNode):
            stack.extend(c for c in at.children if c is not None)
    # the memo answers the second call with the same objects
    assert trie.node_encoding(node) is trie._enc_cache[id(node)]


@pytest.mark.parametrize("seed", range(6))
def test_random_tries_root_and_memo_match_the_oracle(node_encoder, seed):
    """Tries as `put` and `delete` build them, short values among them so
    that embedded nodes occur where they occur in the wild."""
    rng = random.Random(seed)
    trie = Trie()
    keys = [bytes(rng.randrange(256) for _ in range(rng.choice((1, 2, 32)))) for _ in range(120)]
    for key in keys:
        trie.put(key, bytes(rng.randrange(256) for _ in range(rng.choice((1, 3, 33, 70)))))
    for key in keys[::7]:
        trie.delete(key)
    root = trie.root_hash()
    assert root == keccak256(_oracle(trie.root)[1])
    assert trie._enc_cache[id(trie.root)] == _oracle_entry(trie.root)
    # a put evicts its path alone; the walk fills in what is missing
    held = len(trie._enc_cache)
    trie.put(keys[1], b"\x05" * 40)
    assert 0 < held - len(trie._enc_cache) < held
    assert trie.root_hash() == keccak256(_oracle(trie.root)[1])


def test_binary_scheme_hashes_every_child(node_encoder):
    """The binary scheme's hooks (bit-prefix paths through the callback,
    `_embed_below` 0) give the oracle's bytes under both encoders."""
    from phant_tpu.commitment.binary import BinaryTrie, encode_bit_prefix

    trie = BinaryTrie()
    for i in range(40):
        trie.put(keccak256(bytes([i])), bytes([i + 1]) * (1 + i % 5))
    want = _oracle_entry(trie.root, path_enc=encode_bit_prefix, embed_below=0)
    assert trie.node_encoding(trie.root) == want
    assert trie.root_hash() == keccak256(want[1]) == want[2]


# --- the memo keeps each node's reference ------------------------------------
#
# An entry is (structure, encoding, reference): a dirty branch reads what it
# holds of a clean child from the child's entry and hashes nothing twice.
# One entry, so whatever drops an encoding drops the reference with it; the
# cases below are the ways a reference could outlive its encoding.


def _scheme(name):
    """(trie class, the oracle's hooks) of a commitment scheme."""
    if name == "hexary":
        return Trie, {}
    from phant_tpu.commitment.binary import BinaryTrie, encode_bit_prefix

    return BinaryTrie, {"path_enc": encode_bit_prefix, "embed_below": 0}


def _assert_memo_is_the_oracles(trie, hooks):
    """Every node under the root has its entry, and each entry is what the
    oracle computes from the structure as it stands NOW."""
    from phant_tpu.mpt.mpt import BranchNode, ExtensionNode

    stack, nodes = [trie.root], 0
    while stack:
        at = stack.pop()
        nodes += 1
        assert trie._enc_cache[id(at)] == _oracle_entry(at, **hooks), type(at).__name__
        if isinstance(at, ExtensionNode):
            stack.append(at.child)
        elif isinstance(at, BranchNode):
            stack.extend(c for c in at.children if c is not None)
    # and nothing else: an entry of a node that left the trie is a
    # reference waiting for its id to be used again
    assert len(trie._enc_cache) == nodes


@pytest.mark.parametrize("scheme", ["hexary", "binary"])
@pytest.mark.parametrize("seed", range(3))
def test_a_retained_trie_roots_like_a_fresh_build_after_every_round(
    walk_counts, scheme, seed
):
    """Rounds of random puts, overwrites and deletes on ONE trie that keeps
    its memo: after every round its root is a fresh build's, every entry of
    the memo is the oracle's, and the round hashed no node twice."""
    cls, hooks = _scheme(scheme)
    rng = random.Random(4300 + seed)
    held: dict = {}
    trie = cls()

    def value():
        return rng.randbytes(rng.choice((1, 3, 33, 70)))

    for round_no in range(8):
        for _ in range(rng.randint(1, 40)):
            roll = rng.random()
            if held and roll < 0.3:
                key = rng.choice(list(held))
                trie.delete(key)
                del held[key]
            elif held and roll < 0.55:
                key = rng.choice(list(held))
                held[key] = value()
                trie.put(key, held[key])
            else:
                key = rng.randbytes(rng.choice((1, 2, 32)))
                held[key] = value()
                trie.put(key, held[key])
        before = walk_counts()
        root = trie.root_hash()
        encoded, hashed = (b - a for a, b in zip(before, walk_counts()))
        fresh = cls()
        for key, val in held.items():
            fresh.put(key, val)
        assert root == fresh.root_hash(), f"round {round_no}"
        if trie.root is None:
            continue
        assert root == keccak256(_oracle(trie.root, **hooks)[1])
        _assert_memo_is_the_oracles(trie, hooks)
        assert 0 < encoded and hashed <= encoded, (round_no, encoded, hashed)
    # a root with nothing dirty encodes nothing and hashes nothing
    before = walk_counts()
    assert trie.root_hash() == root
    assert walk_counts() == before


@pytest.mark.parametrize("scheme", ["hexary", "binary"])
def test_a_root_after_k_updates_hashes_each_dirty_node_once(walk_counts, scheme):
    """`mpt.ref_hashes` of a root is at most its `mpt.node_encodings`: the
    dirty nodes are hashed, their clean siblings are read. (With the
    reference left out of the memo's entry a root after 20 updates of 4,000
    keys hashed seven to nine times the nodes it encoded.)"""
    cls, _hooks = _scheme(scheme)
    rng = random.Random(43)
    keys = [keccak256(i.to_bytes(4, "big")) for i in range(4000)]
    trie = cls()
    for key in keys:
        trie.put(key, rng.randbytes(70))
    before = walk_counts()
    trie.root_hash()
    encoded, hashed = (b - a for a, b in zip(before, walk_counts()))
    # the first root hashes every node once (all of them 32 bytes or more)
    assert encoded == hashed == len(trie._enc_cache)
    for key in rng.sample(keys, 20):
        trie.put(key, rng.randbytes(70))
    before = walk_counts()
    trie.root_hash()
    encoded, hashed = (b - a for a, b in zip(before, walk_counts()))
    assert 20 < encoded < 400
    assert 0 < hashed <= encoded


def test_a_root_that_encodes_under_32_bytes_is_hashed_never_embedded(node_encoder):
    """A short root's reference is its structure, as any short node's; the
    trie's root is keccak256 of its encoding all the same, before the trie
    grows past 32 bytes, after, and when it has shrunk again."""
    trie = Trie()
    trie.put(b"\x01", b"a")
    structure, encoded, ref = trie.node_encoding(trie.root)
    assert len(encoded) < 32 and ref is structure
    assert trie.root_hash() == keccak256(encoded) == _rebuild_root({b"\x01": b"a"})
    assert trie.root_hash() == keccak256(encoded)  # from the memo, the same
    trie.put(b"\x02", b"b" * 40)
    assert len(trie.node_encoding(trie.root)[1]) >= 32
    assert trie.root_hash() == _rebuild_root({b"\x01": b"a", b"\x02": b"b" * 40})
    assert trie.root_hash() == trie._enc_cache[id(trie.root)][2]
    trie.delete(b"\x02")
    assert trie.root_hash() == keccak256(encoded)
    _assert_memo_is_the_oracles(trie, {})


def test_an_embedded_child_beside_hashed_ones_stays_a_structure(walk_counts):
    """A branch with a short child (held as its structure) among long ones
    (held as digests): a write to one long sibling re-encodes the branch
    from the others' entries, the embedded one's too; then the short child
    grows past 32 bytes and shrinks back, and its reference follows."""
    from phant_tpu.mpt.mpt import BranchNode

    held = {bytes([0x10 * i, 0x11]): bytes([i]) * 40 for i in range(1, 6)}
    held[b"\x00\x01"] = b"s"  # the short one: [hex-prefix, b"s"] in 5 bytes
    trie = Trie()
    for key, val in held.items():
        trie.put(key, val)
    assert trie.root_hash() == _rebuild_root(held)
    assert isinstance(trie.root, BranchNode)
    short = trie.root.children[0]
    assert trie._enc_cache[id(short)][2] is trie._enc_cache[id(short)][0]
    assert isinstance(trie._enc_cache[id(trie.root.children[1])][2], bytes)

    before = walk_counts()
    held[b"\x10\x11"] = b"\xee" * 50
    trie.put(b"\x10\x11", held[b"\x10\x11"])
    assert id(short) in trie._enc_cache  # off the dirty path
    root = trie.root_hash()
    encoded, hashed = (b - a for a, b in zip(before, walk_counts()))
    assert (encoded, hashed) == (2, 2)  # the leaf and the root, no sibling
    assert root == _rebuild_root(held)
    _assert_memo_is_the_oracles(trie, {})

    for val in (b"L" * 60, b"s"):
        held[b"\x00\x01"] = val
        trie.put(b"\x00\x01", val)
        assert trie.root_hash() == _rebuild_root(held)
        _assert_memo_is_the_oracles(trie, {})
    now = trie._enc_cache[id(trie.root.children[0])]
    assert now[2] is now[0] and len(now[1]) < 32


def test_a_freed_nodes_id_never_answers_for_its_successor(node_encoder):
    """Deleted subtrees are freed and CPython hands their addresses to the
    next nodes made: rounds of delete-everything-under-a-prefix and put it
    back with other values must never read a dead node's reference."""
    import gc

    rng = random.Random(7)
    held = {rng.randbytes(4): rng.randbytes(40) for _ in range(300)}
    trie = Trie()
    for key, val in held.items():
        trie.put(key, val)
    trie.root_hash()
    for round_no in range(6):
        gone = [key for key in held if key[0] % 4 == round_no % 4]
        for key in gone:
            trie.delete(key)
            del held[key]
        trie.root_hash()  # entries of what is left, some beside freed nodes
        gc.collect()
        for key in gone:
            held[key] = rng.randbytes(40)
            trie.put(key, held[key])
        assert trie.root_hash() == _rebuild_root(held), round_no
        _assert_memo_is_the_oracles(trie, {})


def test_a_memo_entry_of_another_form_is_refused(node_encoder):
    """An entry without its reference (the form until PR 43) is an error,
    never a read past the tuple's end."""
    node = _leaf((1, 2), 40)
    trie = Trie()
    root = _branch({3: node})
    trie._enc_cache[id(node)] = _oracle(node)
    with pytest.raises((TypeError, IndexError)):
        trie.node_encoding(root)
    assert id(root) not in trie._enc_cache


@pytest.mark.parametrize(
    "bad", [None, "text", 1.5, object()], ids=["none", "str", "float", "object"]
)
def test_a_malformed_item_is_a_type_error(node_encoder, bad):
    """As `rlp.encode` has it, and never a crash of the interpreter: as a
    leaf's value, as a branch's value and as a child."""
    from phant_tpu.mpt.mpt import LeafNode

    with pytest.raises(TypeError):
        rlp.encode_python([b"", bad])
    nodes = [LeafNode((1,), bad)]
    if bad is not None:  # a branch's None is an empty slot, or no value
        nodes += [_branch({2: _leaf((1,), 40)}, value=bad), _branch({2: bad})]
    for node in nodes:
        trie = Trie()
        with pytest.raises((TypeError, AttributeError)):
            trie.node_encoding(node)
        assert id(node) not in trie._enc_cache


def test_an_int_is_its_minimal_big_endian_bytes(node_encoder):
    """`rlp.encode` takes an int for its bytes (0 is the empty string), and
    so does the extension: no trie node holds one, but the two agree."""
    from phant_tpu.mpt.mpt import LeafNode

    for value in (0, 1, 0x7F, 0x80, 1024, 2**64 - 1, 2**64, 2**255 + 7, True):
        node = LeafNode((1,), value)
        assert Trie().node_encoding(node)[1] == rlp.encode_python(
            [encode_hex_prefix((1,), True), value]
        )
    with pytest.raises(ValueError):
        Trie().node_encoding(LeafNode((1,), -1))


@pytest.mark.parametrize("length", [0, 1, 2, 3, 31, 32, 63, 64, 65])
def test_native_hex_prefix_matches(length):
    from phant_tpu.utils.native import load_engine_ext

    ext = load_engine_ext()
    if ext is None:
        pytest.skip("no extension on this machine")
    rng = random.Random(length)
    path = tuple(rng.randrange(16) for _ in range(length))
    for is_leaf in (True, False):
        assert ext.hex_prefix(path, is_leaf) == encode_hex_prefix(path, is_leaf)
        assert decode_hex_prefix(ext.hex_prefix(list(path), is_leaf)) == (path, is_leaf)
    with pytest.raises(ValueError):
        ext.hex_prefix(path + (16,), True)
    with pytest.raises(TypeError):
        ext.hex_prefix(path + (None,), True)


_RLP_ITEMS = {
    "empty-string": b"",
    "byte-0x00": b"\x00",
    "byte-0x7f": b"\x7f",
    "byte-0x80": b"\x80",
    "55B": b"a" * 55,
    "56B": b"a" * 56,
    "255B": b"a" * 255,
    "256B": b"a" * 256,
    "70000B": b"a" * 70_000,
    "empty-list": [],
    "tuple": (b"cat", (b"dog",)),
    "nested": [[], [[]], [[], [[]]]],
    "list-55B-payload": [b"a" * 54],
    "list-56B-payload": [b"a" * 55],
    "list-70000B-payload": [b"a" * 35_000, [b"b" * 35_000]],
    "bytearray-memoryview": [bytearray(b"ab"), memoryview(b"\x01")],
    "ints": [0, 1, 127, 128, 2**64 - 1, 2**64, 2**256 - 1, True],
    "a-transaction": [9, 20 * 10**9, 21000, b"\x35" * 20, 10**18, b"", 37, 2**255, 2**254],
}


@pytest.mark.parametrize("case", sorted(_RLP_ITEMS))
def test_rlp_encode_rides_the_same_writer(node_encoder, case):
    """`rlp.encode` is the extension's `rlp_encode` where the process has
    it: the same bytes as the Python encoder, for every shape it takes."""
    from phant_tpu.utils.native import load_engine_ext

    item = _RLP_ITEMS[case]
    want = rlp.encode_python(item)
    assert rlp.encode(item) == want
    ext = load_engine_ext()
    if node_encoder == "native":
        assert ext.rlp_encode(item) == want
    else:
        assert ext is None
    if not isinstance(item, bytes):
        assert rlp.decode(want) == rlp.decode(rlp.encode_python(rlp.decode(want)))
