"""WitnessEngine: differential + memoization + corruption tests.

The engine must agree bit-for-bit with the two existing verifiers —
mpt/proof.verify_witness_linked (host BFS) and
ops/witness_jax.witness_verify_fused (device kernel) — on valid witnesses
and on every corruption class, while hashing each unique node only once.
"""

import numpy as np
import pytest

from phant_tpu import rlp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import Trie
from phant_tpu.mpt.proof import generate_proof, verify_witness_linked
from phant_tpu.ops.witness_engine import WitnessEngine


def _build_trie(n=256, seed=5):
    rng = np.random.default_rng(seed)
    trie = Trie()
    keys = []
    for _ in range(n):
        k = keccak256(rng.bytes(20))
        trie.put(k, rlp.encode([rlp.encode_uint(1), rng.bytes(8)]))
        keys.append(k)
    return trie, keys, trie.root_hash()


def _witness(trie, keys, picks, rng):
    idx = rng.choice(len(keys), size=picks, replace=False)
    nodes = {}
    for i in idx:
        for n in generate_proof(trie, keys[i]):
            nodes[n] = None
    return list(nodes.keys())


@pytest.fixture(params=["ext", "ctypes", "python"], autouse=True)
def engine_core(request, monkeypatch):
    """Run every test in this module against ALL engine cores: the C++
    one (native/engine.cc) behind its two drivers — the CPython extension
    (native/pyext.cc) and the ctypes+numpy fallback — and the pure-Python
    twin they must match."""
    monkeypatch.setenv(
        "PHANT_ENGINE_NATIVE", "0" if request.param == "python" else "1"
    )
    monkeypatch.setenv(
        "PHANT_ENGINE_EXT", "1" if request.param == "ext" else "0"
    )
    if request.param == "ext":
        from phant_tpu.utils.native import load_engine_ext

        if load_engine_ext() is None:
            pytest.skip("engine extension unavailable")
    elif request.param == "ctypes":
        from phant_tpu.utils.native import load_native

        lib = load_native()
        if lib is None or not lib.has_engine:
            pytest.skip("native engine core unavailable")
    return request.param


@pytest.fixture()
def setup():
    trie, keys, root = _build_trie()
    rng = np.random.default_rng(9)
    witnesses = [(root, _witness(trie, keys, 8, rng)) for _ in range(12)]
    return trie, keys, root, witnesses


def test_valid_batch_verifies(setup):
    _trie, _keys, _root, witnesses = setup
    eng = WitnessEngine()
    out = eng.verify_batch(witnesses)
    assert out.all()
    # differential: host BFS agrees on every block
    for root, nodes in witnesses:
        assert verify_witness_linked(root, nodes)


def test_memoization_hashes_each_unique_node_once(setup):
    _trie, _keys, _root, witnesses = setup
    eng = WitnessEngine()
    eng.verify_batch(witnesses)
    unique = {n for _r, nodes in witnesses for n in nodes}
    assert eng.stats["hashed"] == len(unique)
    before = eng.stats["hashed"]
    out = eng.verify_batch(witnesses)  # fully cached second pass
    assert out.all()
    assert eng.stats["hashed"] == before


def test_corruptions_rejected(setup):
    _trie, _keys, root, witnesses = setup
    eng = WitnessEngine()
    nodes = list(witnesses[0][1])

    # wrong root
    assert not eng.verify(b"\x00" * 32, nodes)
    # missing root node (drop the node that hashes to the root)
    no_root = [n for n in nodes if keccak256(n) != root]
    assert not eng.verify(root, no_root)
    # unlinked extra node (a foreign node nothing references)
    foreign = rlp.encode([b"\x20\x99", b"zzz"])
    assert not eng.verify(root, nodes + [foreign])
    # a flipped byte inside a node breaks the parent->child link
    victim = max(nodes, key=len)
    flipped = bytes([victim[0]]) + bytes([victim[1] ^ 1]) + victim[2:]
    broken = [flipped if n == victim else n for n in nodes]
    assert not eng.verify(root, broken)
    # empty witness
    assert not eng.verify(root, [])
    # the valid witness still verifies after all that interning
    assert eng.verify(root, nodes)
    # differential: the host BFS agrees on every corruption verdict
    assert not verify_witness_linked(b"\x00" * 32, nodes)
    assert not verify_witness_linked(root, no_root)
    assert not verify_witness_linked(root, nodes + [foreign])
    assert not verify_witness_linked(root, broken)


def test_late_binding_child_arrives_in_later_batch(setup):
    _trie, _keys, root, witnesses = setup
    eng = WitnessEngine()
    nodes = list(witnesses[0][1])
    assert len(nodes) >= 2
    # first: intern only the root node (a trivially-valid one-node witness;
    # its child refs stay pending)
    root_node = next(n for n in nodes if keccak256(n) == root)
    assert eng.verify(root, [root_node])
    # later: the full witness arrives; the CACHED root node's child refids
    # (interned at its insert) must match the newly interned children's own
    # refids or linkage breaks
    assert eng.verify(root, nodes)
    hashed = eng.stats["hashed"]
    assert hashed == len(set(nodes))  # root node not re-hashed


def test_eviction_keeps_correctness(setup):
    _trie, _keys, root, witnesses = setup
    unique = {n for _r, nodes in witnesses for n in nodes}
    eng = WitnessEngine(max_nodes=max(4, len(unique) // 3))
    for root_, nodes in witnesses:
        assert eng.verify(root_, nodes)
    assert eng.stats["evictions"] >= 1
    # post-eviction verification still sound
    assert eng.verify(root, list(witnesses[0][1]))
    assert not eng.verify(b"\x11" * 32, list(witnesses[0][1]))


def test_differential_vs_device_kernel(setup):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from phant_tpu.ops.witness_jax import (
        WITNESS_MAX_CHUNKS,
        pack_witness_fused,
        roots_to_words,
        witness_verify_fused,
    )

    _trie, _keys, root, witnesses = setup
    cases = list(witnesses[:4])
    # add corruption cases to the batch
    nodes0 = list(witnesses[0][1])
    foreign = rlp.encode([b"\x20\x99", b"zzz"])
    cases.append((root, nodes0 + [foreign]))
    cases.append((b"\x00" * 32, nodes0))

    eng = WitnessEngine()
    got = eng.verify_batch(cases)

    blob, meta16 = pack_witness_fused([n for _r, n in cases], WITNESS_MAX_CHUNKS)
    out = witness_verify_fused(
        jnp.asarray(blob),
        jnp.asarray(meta16),
        jnp.asarray(roots_to_words([r for r, _n in cases])),
        max_chunks=WITNESS_MAX_CHUNKS,
        n_blocks=len(cases),
    )
    want = np.asarray(out)
    assert (got == want).all(), (got, want)
    assert list(got) == [True, True, True, True, False, False]


def test_cpu_backend_never_initializes_a_jax_device(setup):
    """The adaptive offload gate probes the device link — which must never
    happen on the pure-CPU path (a device call that never returns would
    hang a run that never asked for a device). Runs in-process: conftest pins JAX_PLATFORMS=cpu,
    so backend init here is cheap but still detectable."""
    import subprocess
    import sys

    code = (
        "from phant_tpu.ops.witness_engine import WitnessEngine\n"
        "eng = WitnessEngine()\n"
        "eng._hash_batch([b'abc' * 50] * 100)\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, xb._backends\n"
        "print('ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-1500:]


def test_storage_subtree_linked_through_account_leaf():
    rng = np.random.default_rng(13)
    storage = Trie()
    skeys = []
    for _ in range(64):
        sk = keccak256(rng.bytes(32))
        storage.put(sk, rlp.encode(rlp.encode_uint(7)))
        skeys.append(sk)
    sroot = storage.root_hash()

    trie = Trie()
    akeys = []
    for i in range(128):
        k = keccak256(rng.bytes(20))
        leaf = rlp.encode(
            [
                rlp.encode_uint(1),
                rlp.encode_uint(10**18),
                sroot if i % 2 == 0 else rng.bytes(32),
                rng.bytes(32),
            ]
        )
        trie.put(k, leaf)
        akeys.append(k)
    root = trie.root_hash()

    # find an account whose leaf commits sroot
    nodes = {}
    anchor = None
    for i in range(0, 128, 2):
        proof = generate_proof(trie, akeys[i])
        if sroot in proof[-1]:
            anchor = i
            break
    assert anchor is not None
    for n in generate_proof(trie, akeys[anchor]):
        nodes[n] = None
    for sk in skeys[:8]:
        for n in generate_proof(storage, sk):
            nodes[n] = None

    eng = WitnessEngine()
    assert eng.verify(root, list(nodes.keys()))
    assert verify_witness_linked(root, list(nodes.keys()))
    # without the anchoring account leaf, the storage nodes are unlinked
    unanchored = [n for n in nodes if sroot not in n or len(n) < 32]
    if len(unanchored) < len(nodes):
        assert not eng.verify(root, unanchored)


def test_oversized_node_routes_to_native_not_wrong_digest(monkeypatch):
    """A node >= the device kernel's absorb capacity (680B) must never get
    a silently wrong device digest (ADVICE r3 medium): the batch routes to
    the native hasher and the verdict matches the linked reference
    verifier. Witnesses are untrusted Engine-API input."""
    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.crypto.keccak import RATE, keccak256
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.ops.witness_jax import WITNESS_MAX_CHUNKS

    big = b"\xfa" * (WITNESS_MAX_CHUNKS * RATE + 40)  # over capacity
    root = keccak256(big)
    monkeypatch.setenv("PHANT_LINK_MBPS", "100000")  # make offload "pay"
    monkeypatch.setenv("PHANT_LINK_RTT_MS", "0.01")
    set_crypto_backend("tpu")
    try:
        eng = WitnessEngine(device_batch_floor=1)
        assert eng.verify(root, [big])
        assert eng.stats.get("device_batches", 0) == 0  # routed native
    finally:
        set_crypto_backend("cpu")
    # and the device path itself refuses rather than mis-hashing
    import pytest as _pytest

    with _pytest.raises(ValueError):
        WitnessEngine._hash_batch_device([big])


def test_eviction_does_not_inflate_hit_stats():
    """intern() discards its scan pass on eviction; the hits counted in
    that pass must be rolled back (ADVICE r3: stats drive the
    phant_witnessEngineStats RPC's hit_rate)."""
    from phant_tpu.ops.witness_engine import WitnessEngine

    eng = WitnessEngine(max_nodes=4)
    a = [b"\x01" * 40, b"\x02" * 40, b"\x03" * 40]
    eng.intern(a)
    assert eng.stats["hits"] == 0
    # second call: 3 hits counted, then 2 novel nodes overflow max_nodes=4
    # -> eviction discards the pass; re-intern of the 5 sees 0 hits
    eng.intern(a + [b"\x04" * 40, b"\x05" * 40])
    assert eng.stats["evictions"] == 1
    assert eng.stats["hits"] == 0


def test_native_vs_python_core_differential(engine_core, monkeypatch):
    """The C++ core (native/engine.cc) and the Python engine must return
    identical verdict arrays and hashed/hit counters on a gauntlet of
    adversarial batches: duplicate nodes, zero-length and malformed RLP
    nodes, deep-embedded ref inflation (>17 refs), unknown roots,
    cross-batch memoization and eviction. This is the soundness contract
    of swapping the core."""
    if engine_core == "python":
        pytest.skip("constructs both cores itself (native param vs python)")
    from phant_tpu.utils.native import load_native

    lib = load_native()
    if lib is None or not lib.has_engine:
        pytest.skip("native engine core unavailable")

    trie, keys, root = _build_trie(n=128, seed=21)
    rng = np.random.default_rng(77)
    batches = []
    for _ in range(6):
        wit = [(root, _witness(trie, keys, 6, rng)) for _ in range(5)]
        batches.append(wit)

    # adversarial extras
    nodes0 = list(batches[0][0][1])
    malformed = b"\xc3\x01"  # list header longer than payload
    not_a_list = b"\x85hello"
    # branch with an embedded list that nests 20 x 32-byte strings (ref
    # inflation attempt past the 17-slot cap)
    from phant_tpu import rlp as _rlp
    deep = _rlp.encode([_rlp.encode([rng.bytes(32) for _ in range(20)])] + [b""] * 15 + [b"v"])
    dup = nodes0[0]
    batches.append(
        [
            (root, nodes0 + [malformed]),
            (root, nodes0 + [not_a_list]),
            (root, nodes0 + [deep]),
            (root, nodes0 + [b""]),        # zero-length node bytes
            (root, [dup, dup] + nodes0),
            (b"\x07" * 32, nodes0),       # unknown root digest
            (root, []),                    # empty witness
            (root, [dup]) if keccak256(dup) != root else (root, nodes0),
        ]
    )

    monkeypatch.setenv("PHANT_ENGINE_NATIVE", "1")
    eng_n = WitnessEngine(max_nodes=200)  # small cap: exercise eviction
    assert eng_n._core is not None or eng_n._ext_core is not None
    monkeypatch.setenv("PHANT_ENGINE_NATIVE", "0")
    eng_p = WitnessEngine(max_nodes=200)
    assert eng_p._core is None and eng_p._ext_core is None

    for wit in batches:
        out_n = eng_n.verify_batch(wit)
        out_p = eng_p.verify_batch(wit)
        assert (out_n == out_p).all(), (out_n, out_p)
    assert eng_n.stats["hashed"] == eng_p.stats["hashed"]
    assert eng_n.stats["hits"] == eng_p.stats["hits"]
    assert eng_n.stats["evictions"] == eng_p.stats["evictions"]
    sn, sp = eng_n.stats_snapshot(), eng_p.stats_snapshot()
    assert sn["interned_nodes"] == sp["interned_nodes"]
    assert sn["interned_digests"] == sp["interned_digests"]


# ---------------------------------------------------------------------------
# pipelined two-phase API (begin_batch / resolve_batch) — PR 5
# ---------------------------------------------------------------------------


def test_two_phase_matches_verify_batch(setup):
    """begin/resolve over outstanding batches is byte-identical to
    verify_batch over the same witnesses, on every core (autouse core
    fixture), including bad witnesses and interleaved classic calls."""
    _trie, _keys, root, witnesses = setup
    bad = list(witnesses)
    bad[3] = (bad[3][0], bad[3][1] + [rlp.encode([b"\x20\x99", b"zzz"])])
    bad[7] = (b"\x00" * 32, bad[7][1])

    oracle = WitnessEngine()
    want = oracle.verify_batch(bad)

    eng = WitnessEngine()
    h1 = eng.begin_batch(bad[:4])
    h2 = eng.begin_batch(bad[4:8])   # two handles in flight
    v1 = eng.resolve_batch(h1)
    mid = eng.verify_batch(bad[8:10])  # classic call interleaves freely
    h3 = eng.begin_batch(bad[10:])
    v2 = eng.resolve_batch(h2)
    v3 = eng.resolve_batch(h3)
    got = np.concatenate([v1, v2, np.asarray(want[8:10]), v3])
    assert (np.concatenate([v1, v2]) == want[:8]).all()
    assert (mid == want[8:10]).all()
    assert (v3 == want[10:]).all()
    assert got.shape == want.shape


def test_two_phase_any_resolve_order_and_double_resolve(setup):
    """Handles resolve in ANY order (several schedulers may share one
    engine, each FIFO only over its own handles): out-of-order resolves
    produce correct verdicts, double-resolve still raises."""
    _trie, _keys, _root, witnesses = setup
    eng = WitnessEngine()
    ha = eng.begin_batch(witnesses[:2])
    hb = eng.begin_batch(witnesses[2:4])
    assert eng.resolve_batch(hb).all()  # resolved BEFORE ha
    assert eng.resolve_batch(ha).all()
    assert eng._inflight == 0
    with pytest.raises(RuntimeError, match="already resolved"):
        eng.resolve_batch(ha)
    # out-of-order with overlapping novel sets: the commit membership
    # re-check dedups regardless of which batch lands first
    h1 = eng.begin_batch(witnesses[4:8])
    h2 = eng.begin_batch(witnesses[4:8])
    assert eng.resolve_batch(h2).all()
    assert eng.resolve_batch(h1).all()
    hashed = eng.stats["hashed"]
    assert eng.verify_batch(witnesses[4:8]).all()
    assert eng.stats["hashed"] == hashed


def test_two_phase_cross_batch_duplicate_novels(setup):
    """A node novel in two outstanding batches commits once logically:
    verdicts stay correct and a later classic pass is fully cached."""
    _trie, _keys, _root, witnesses = setup
    eng = WitnessEngine()
    h1 = eng.begin_batch(witnesses[:4])
    h2 = eng.begin_batch(witnesses[:4])  # same novels, both in flight
    assert eng.resolve_batch(h1).all()
    assert eng.resolve_batch(h2).all()
    hashed = eng.stats["hashed"]
    assert eng.verify_batch(witnesses[:4]).all()
    assert eng.stats["hashed"] == hashed  # everything already interned


def test_two_phase_defers_eviction_while_inflight(setup):
    """A generation flush must never run under an outstanding handle: the
    over-cap begin defers it, and the next begin with an empty pipeline
    flushes. Correctness holds throughout."""
    _trie, _keys, root, witnesses = setup
    # cap sized so h0+h1 fit EXACTLY; h2 overflows via synthetic nodes
    cap = len({n for _r, nodes in witnesses[:9] for n in nodes})
    eng = WitnessEngine(max_nodes=cap)
    h0 = eng.begin_batch(witnesses[:6])
    assert eng.resolve_batch(h0).all()
    h1 = eng.begin_batch(witnesses[6:9])  # fills to the cap, no eviction
    # h2 crosses the cap WHILE h1 is in flight: 256 foreign (unlinked)
    # nodes guarantee the overflow; the flush must be DEFERRED — h1's
    # scanned rows point into the current generation
    extra = [rlp.encode([bytes([0x20, i % 250, i // 250]), b"v" * 40]) for i in range(256)]
    h2 = eng.begin_batch(
        [(root, list(witnesses[9][1]) + extra)] + witnesses[10:]
    )
    assert eng._evict_pending, "over-cap begin under an in-flight handle must defer"
    assert eng.stats["evictions"] == 0
    assert eng.resolve_batch(h1).all()
    v2 = eng.resolve_batch(h2)
    assert not v2[0] and v2[1:].all()  # unlinked extras fail only block 0
    # the drain at h2's resolve ran the deferred flush (pinned in detail
    # by test_deferred_eviction_runs_at_resolve_drain); the re-interned
    # generation still verifies
    h3 = eng.begin_batch(witnesses[:3])
    assert eng.resolve_batch(h3).all()
    assert eng.stats["evictions"] == 1
    assert not eng._evict_pending


def test_two_phase_stats_match_classic(setup):
    """hits/hashed accounting through begin/resolve equals the classic
    verify_batch accounting over the same batch sequence."""
    _trie, _keys, _root, witnesses = setup
    classic = WitnessEngine()
    for i in range(0, len(witnesses), 4):
        assert classic.verify_batch(witnesses[i : i + 4]).all()
    piped = WitnessEngine()
    handles = [
        piped.begin_batch(witnesses[i : i + 4])
        for i in range(0, len(witnesses), 4)
    ]
    for h in handles:
        assert piped.resolve_batch(h).all()
    # sequential pipelining (resolve after all begins) re-hashes novels
    # shared across in-flight batches; with disjoint-enough batches the
    # totals still agree exactly when each batch was begun after the
    # previous resolved — pin THAT equivalence:
    piped2 = WitnessEngine()
    for i in range(0, len(witnesses), 4):
        h = piped2.begin_batch(witnesses[i : i + 4])
        assert piped2.resolve_batch(h).all()
    assert piped2.stats["hashed"] == classic.stats["hashed"]
    assert piped2.stats["hits"] == classic.stats["hits"]


def test_failed_resolve_abandons_and_does_not_wedge(setup):
    """A readback/hash failure in resolve_batch must release the handle:
    later handles stay resolvable, the in-flight count returns to zero,
    and deferred evictions can still run (a wedged count would defer
    generation flushes forever on the process-shared engine)."""
    _trie, _keys, _root, witnesses = setup

    calls = {"n": 0}

    def flaky_hasher(nodes):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("device lost mid-readback")
        return [keccak256(n) for n in nodes]

    eng = WitnessEngine(hasher=flaky_hasher)
    h1 = eng.begin_batch(witnesses[:4])
    h2 = eng.begin_batch(witnesses[4:8])
    with pytest.raises(RuntimeError, match="device lost"):
        eng.resolve_batch(h1)
    assert h1.resolved  # released, not wedged
    assert eng.resolve_batch(h2).all()
    assert eng._inflight == 0
    with pytest.raises(RuntimeError, match="already resolved"):
        eng.resolve_batch(h1)
    # explicit abandonment (the scheduler's _die path) is idempotent and
    # works in any order
    h3 = eng.begin_batch(witnesses[:2])
    h4 = eng.begin_batch(witnesses[2:4])
    eng.abandon_batch(h4)
    eng.abandon_batch(h4)
    assert eng.resolve_batch(h3).all()
    assert eng._inflight == 0
    # the engine still verifies (and can still evict) afterwards
    assert eng.verify_batch(witnesses).all()


def test_deferred_eviction_runs_at_resolve_drain(setup):
    """The starvation fix: under continuous pipelined load the in-flight
    count may never be zero at a BEGIN (the executor packs N+1 while N
    resolves), so the deferred flush must fire the moment the pipeline
    drains AT RESOLVE TIME — waiting for some later begin could defer it
    forever and grow the tables without bound."""
    _trie, _keys, root, witnesses = setup
    cap = len({n for _r, nodes in witnesses[:9] for n in nodes})
    # tiered_evict=False: this test pins the flush-at-drain TIMING and
    # asserts the FLAT flush's empty fresh generation; the tiered
    # flush's pinned retention is pinned by tests/test_witness_stream.py
    eng = WitnessEngine(max_nodes=cap, tiered_evict=False)
    h0 = eng.begin_batch(witnesses[:6])
    assert eng.resolve_batch(h0).all()
    h1 = eng.begin_batch(witnesses[6:9])  # fills to the cap exactly
    extra = [
        rlp.encode([bytes([0x20, i % 250, i // 250]), b"v" * 40])
        for i in range(256)
    ]
    h2 = eng.begin_batch(
        [(root, list(witnesses[9][1]) + extra)] + witnesses[10:]
    )
    assert eng._evict_pending and eng.stats["evictions"] == 0
    assert eng.resolve_batch(h1).all()
    # pipeline still occupied by h2: flush stays deferred
    assert eng._evict_pending and eng.stats["evictions"] == 0
    v2 = eng.resolve_batch(h2)  # drain -> the deferred flush fires HERE
    assert not v2[0] and v2[1:].all()
    assert eng.stats["evictions"] == 1
    assert not eng._evict_pending
    assert eng.stats_snapshot()["interned_nodes"] == 0  # fresh generation
    # and the engine still verifies afterwards
    assert eng.verify_batch(witnesses[:4]).all()
    # threaded smoke: a producer keeping the pipe busy while a consumer
    # resolves must stay correct and leak nothing (no end-state size
    # assertion: generation contents depend on flush/arrival interleaving)
    import queue as _queue
    import threading as _t

    q: "_queue.Queue" = _queue.Queue(maxsize=2)
    results = []

    def resolver():
        while True:
            h = q.get()
            if h is None:
                return
            results.append(bool(eng.resolve_batch(h).all()))

    t = _t.Thread(target=resolver)
    t.start()
    try:
        for _round in range(4):
            for i in range(0, 12, 3):
                q.put(eng.begin_batch(witnesses[i : i + 3]))
    finally:
        q.put(None)
        t.join(60)
    assert all(results) and len(results) == 16
    assert eng._inflight == 0


def test_intern_overflow_flushes_python_twin_not_core(setup, engine_core):
    """The public intern() fills the PYTHON tables even on a C-core
    engine; its deferred overflow flush (pipeline busy) must clear those
    dicts at the drain — never the warm memoized core cache."""
    _trie, _keys, _root, witnesses = setup
    all_nodes = [n for _r, nodes in witnesses for n in nodes]
    unique = list(dict.fromkeys(all_nodes))
    cap = max(4, len(unique) // 2)
    eng = WitnessEngine(max_nodes=cap)
    assert eng.verify_batch(witnesses[:6]).all()  # warm the verify tables
    core_nodes_before = eng.stats_snapshot()["interned_nodes"]
    h = eng.begin_batch(witnesses[:2])  # pipeline busy
    eng.intern(unique[:cap])            # fills the twin to the cap
    eng.intern(unique)                  # crosses it -> deferred py flush
    assert eng._evict_pending_py
    assert eng.resolve_batch(h).all()   # drain runs the deferred flush
    assert not eng._evict_pending_py
    assert len(eng._row_of_bytes) == 0  # twin flushed
    if engine_core != "python":
        # ...but the warm core cache SURVIVED (on a pure-python engine the
        # twin IS the verify table, so there is nothing to preserve)
        assert eng.stats_snapshot()["interned_nodes"] >= core_nodes_before
        assert eng.verify_batch(witnesses[:6]).all()
