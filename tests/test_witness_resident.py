"""Device-resident intern table (ops/witness_resident.py).

Pins the PR's tentpole contract on the XLA-CPU proxy (PHANT_RESIDENT=1 +
PHANT_ALLOW_JAX_CPU=1 — the same route a real accelerator takes, minus
the chip): resident verdicts are byte-identical to the host route across
all three engine cores and scheduler pipeline depths 1/2 (corrupt
witnesses included), the steady state uploads ZERO novel bytes, the
device-side open-addressed index agrees with the authoritative host map,
generation flushes stay consistent under in-flight handles, mesh lanes
keep independent resident tables, `reset()` releases the device arrays,
and an abandoned handle leaves the table consistent.
"""

import numpy as np
import pytest

from phant_tpu import rlp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.ops.witness_engine import WitnessEngine
from phant_tpu.utils.trace import metrics

from _witnesses import build_witnesses


@pytest.fixture(autouse=True)
def resident_env(monkeypatch):
    """The resident route on the CPU box: jax-cpu allowed, crypto
    backend tpu for the duration, residency forced. The host ORACLE
    engines below stay on the native path regardless (the offload cost
    model reports the XLA-CPU 'device' as a loss, and resident=False
    pins them off the resident route)."""
    from phant_tpu.backend import set_crypto_backend

    monkeypatch.setenv("PHANT_ALLOW_JAX_CPU", "1")
    monkeypatch.setenv("PHANT_RESIDENT", "1")
    set_crypto_backend("tpu")
    yield
    set_crypto_backend("cpu")


@pytest.fixture(params=["ext", "ctypes", "python"])
def engine_core(request, monkeypatch):
    """Every differential test runs against ALL three engine cores —
    the resident route commits the HOST tables from device digests, so
    each core's commit path must stay byte-identical."""
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


def _with_corruptions(root, wits):
    """The witness set plus every corruption class (expected verdicts
    come from the host oracle, so the classes just need coverage)."""
    out = list(wits)
    nodes = list(wits[0][1])
    out.append((b"\x00" * 32, nodes))  # wrong root
    out.append((root, [n for n in nodes if keccak256(n) != root]))  # no root node
    out.append((root, nodes + [rlp.encode([b"\x20\x99", b"zzz"])]))  # unlinked
    victim = max(nodes, key=len)
    flipped = bytes([victim[0]]) + bytes([victim[1] ^ 1]) + victim[2:]
    out.append((root, [flipped if n == victim else n for n in nodes]))  # broken link
    out.append((root, []))  # empty witness
    return out


def _host_oracle(wits):
    from phant_tpu.backend import set_crypto_backend

    set_crypto_backend("cpu")
    try:
        return np.asarray(WitnessEngine(resident=False).verify_batch(wits))
    finally:
        set_crypto_backend("tpu")


# ---------------------------------------------------------------------------
# differential byte-identity, all cores
# ---------------------------------------------------------------------------


def test_resident_matches_host_all_cores(engine_core):
    root, wits = build_witnesses()
    batch = _with_corruptions(root, wits)
    want = _host_oracle(batch)
    eng = WitnessEngine(resident=True, resident_cap=4096)
    got = np.asarray(eng.verify_batch(batch))
    assert (got == want).all(), (engine_core, got, want)
    # the resident route actually engaged, and it IS the device route
    st = eng.stats_snapshot()
    assert st.get("resident_batches", 0) >= 1
    assert "resident" in st and st["resident"]["uploaded_nodes"] > 0
    # steady state: a second pass uploads NOTHING and stays identical
    up0 = st["resident"]["uploaded_nodes"]
    got2 = np.asarray(eng.verify_batch(batch))
    st2 = eng.stats_snapshot()["resident"]
    assert (got2 == want).all()
    assert st2["uploaded_nodes"] == up0, "steady state re-uploaded bytes"


def test_resident_through_scheduler_depths(engine_core):
    """The serving path: resident engine behind the continuous-batching
    scheduler at pipeline depths 1 AND 2 — verdict multiset identical to
    the host oracle, corrupt witness included."""
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    root, wits = build_witnesses(n_blocks=12)
    batch = list(wits)
    batch[3] = (b"\x11" * 32, batch[3][1])  # corrupt: must stay False
    want = _host_oracle(batch)
    for depth in (1, 2):
        eng = WitnessEngine(resident=True, resident_cap=4096)
        with VerificationScheduler(
            engine=eng,
            config=SchedulerConfig(
                max_batch=4, max_wait_ms=5.0, queue_depth=4096,
                pipeline_depth=depth,
            ),
        ) as s:
            got = s.verify_many(batch)
        assert (np.asarray(got) == want).all(), (engine_core, depth)
        assert eng.stats_snapshot().get("resident_batches", 0) >= 1
        eng.reset()


# ---------------------------------------------------------------------------
# the device-side index (the on-device scan)
# ---------------------------------------------------------------------------


def _node_fps(nodes):
    from phant_tpu.utils.native import load_native

    native = load_native()
    if native is not None:
        digs = list(native.keccak256_batch_fast(nodes))
    else:
        digs = [keccak256(n) for n in nodes]
    return np.stack([np.frombuffer(d[:8], "<u4") for d in digs])


def test_device_index_agrees_with_host_map():
    root, wits = build_witnesses()
    eng = WitnessEngine(resident=True, resident_cap=4096)
    assert np.asarray(eng.verify_batch(wits)).all()
    table = eng.resident_table()
    assert table is not None
    all_nodes = [n for _r, ns in wits for n in ns]
    rows_host = table.host_rows_of(all_nodes)
    assert (rows_host >= 0).all()
    rows_dev = table.device_lookup(_node_fps(all_nodes))
    assert (rows_dev == rows_host).all()
    # absent fingerprints miss (-1): the verdict path treats a miss as
    # a FAILING node, never a silent pass
    absent = np.frombuffer(keccak256(b"never-interned")[:8], "<u4")
    assert table.device_lookup(absent.reshape(1, 2))[0] == -1
    assert table.stats_snapshot()["index_dropped"] == 0


def test_index_insert_lookup_unit():
    """Pure kernel unit: N random fingerprints insert (zero drops at
    load factor 0.5) and every one resolves; absent keys miss."""
    import jax.numpy as jnp

    from phant_tpu.ops.keccak_jax import (
        INDEX_EMPTY,
        index_insert,
        index_lookup,
    )

    rng = np.random.default_rng(7)
    cap = 256
    n = 128
    fps = rng.integers(0, 2**32, size=(cap, 2), dtype=np.uint32)
    index = jnp.full((2 * cap,), INDEX_EMPTY, jnp.int32)
    slots = jnp.arange(cap, dtype=jnp.int32)
    live = jnp.arange(cap) < n
    index, dropped = index_insert(index, jnp.asarray(fps), slots, live)
    assert int(dropped) == 0
    got = np.asarray(index_lookup(index, jnp.asarray(fps), jnp.asarray(fps)))
    assert (got[:n] == np.arange(n)).all()
    # rows past n were never inserted; their keys must miss (their fps
    # ARE in the fps store, so this exercises the bucket probe, not the
    # row verify)
    assert (got[n:] == -1).all()


# ---------------------------------------------------------------------------
# generations: flush under in-flight handles, reset, abandon
# ---------------------------------------------------------------------------


def test_resident_generation_flush_under_inflight(engine_core):
    """An over-cap begin with a handle in flight DEFERS the host flush;
    when the pipeline drains, host AND resident tables flush together
    (one generation), and verification after the flush is still
    byte-identical with the uploads starting over."""
    root, wits = build_witnesses(n_blocks=8, picks=3)
    u_first = {n for _r, ns in wits[:4] for n in ns}
    u_all = {n for _r, ns in wits for n in ns}
    assert len(u_all) - len(u_first) >= 2, "fixture lost its novel tail"
    # the committed first half fits; the second half's novels cross it
    eng = WitnessEngine(
        resident=True, max_nodes=len(u_first) + 1, resident_cap=4096
    )
    want = _host_oracle(wits)
    assert (np.asarray(eng.verify_batch(wits[:4])) == want[:4]).all()
    h1 = eng.begin_batch(wits[:4])  # fully cached, held in flight
    h2 = eng.begin_batch(wits[4:])  # crosses max_nodes: flush must DEFER
    table = eng.resident_table()
    gen0 = table.generation
    assert table.generation == gen0  # nothing flushed while in flight
    v2 = eng.resolve_batch(h2)
    v1 = eng.resolve_batch(h1)
    assert (np.asarray(v1) == want[:4]).all()
    assert (np.asarray(v2) == want[4:]).all()
    # the deferred host generation flush ran at pipeline drain and took
    # the resident generation with it
    assert eng.stats["evictions"] >= 1
    assert table.generation > gen0
    assert table.stats_snapshot()["flushes"] >= 1
    # next batch rebuilds residency from scratch, verdicts identical
    up0 = table.stats_snapshot()["uploaded_nodes"]
    got = np.asarray(eng.verify_batch(wits))
    assert (got == want).all()
    assert table.stats_snapshot()["uploaded_nodes"] > up0


def test_reset_releases_resident_table():
    root, wits = build_witnesses()
    eng = WitnessEngine(resident=True, resident_cap=4096)
    assert np.asarray(eng.verify_batch(wits)).all()
    table = eng.resident_table()
    assert table is not None and table.rows() > 0
    eng.reset()
    assert eng.resident_table() is None  # device arrays released
    assert table._arrays is None
    # verification rebuilds a fresh table and stays correct
    assert np.asarray(eng.verify_batch(wits)).all()
    t2 = eng.resident_table()
    assert t2 is not None and t2 is not table and t2.rows() > 0


def test_reset_refuses_inflight():
    root, wits = build_witnesses(n_blocks=4)
    eng = WitnessEngine(resident=True, resident_cap=4096)
    h = eng.begin_batch(wits)
    with pytest.raises(RuntimeError):
        eng.reset()
    eng.abandon_batch(h)
    eng.reset()  # idle now: fine


def test_abandon_keeps_resident_consistent(engine_core):
    """A dispatched-then-abandoned resident handle: the enqueued update
    stands (rows resident), the host core never committed — the next
    batch re-reports those nodes as novel, the prune skips the
    re-upload, and verdicts stay byte-identical."""
    root, wits = build_witnesses(n_blocks=6)
    want = _host_oracle(wits)
    eng = WitnessEngine(resident=True, resident_cap=4096)
    h = eng.begin_batch(wits)
    assert h.resident is not None
    eng.abandon_batch(h)
    table = eng.resident_table()
    up0 = table.stats_snapshot()["uploaded_nodes"]
    assert up0 > 0
    got = np.asarray(eng.verify_batch(wits))
    assert (got == want).all()
    st = table.stats_snapshot()
    assert st["uploaded_nodes"] == up0, "abandoned rows were re-uploaded"
    assert st["pruned_nodes"] > 0  # the host prune did the work


# ---------------------------------------------------------------------------
# mesh: independent per-lane tables
# ---------------------------------------------------------------------------


def test_mesh_lanes_keep_independent_resident_tables():
    """Two device-pinned lane engines: each owns its OWN resident table
    (rows only for what IT verified; the other lane's nodes are not
    resident there) — the per-chip intern-table identity the mesh
    affinity routing preserves."""
    from phant_tpu.serving.mesh_exec import MeshExecutorPool

    _root_a, wits_a = build_witnesses(seed=5)
    _root_b, wits_b = build_witnesses(seed=17)
    pool = MeshExecutorPool(2, prewarm=False)
    try:
        e0, e1 = pool.engines()
        assert np.asarray(e0.verify_batch(wits_a)).all()
        assert np.asarray(e1.verify_batch(wits_b)).all()
        t0, t1 = e0.resident_table(), e1.resident_table()
        assert t0 is not None and t1 is not None and t0 is not t1
        nodes_a = [n for _r, ns in wits_a for n in ns]
        nodes_b = [n for _r, ns in wits_b for n in ns]
        assert (t0.host_rows_of(nodes_a) >= 0).all()
        assert (t1.host_rows_of(nodes_b) >= 0).all()
        # lane 1 never saw lane 0's witnesses (and vice versa)
        assert (t1.host_rows_of(nodes_a) == -1).all()
        assert (t0.host_rows_of(nodes_b) == -1).all()
    finally:
        pool.shutdown(10.0)


# ---------------------------------------------------------------------------
# cache_hit_rate vs trie_depth histogram
# ---------------------------------------------------------------------------


def test_depth_histogram_skew(monkeypatch):
    """Replayed fixture span with cross-block reuse: the per-depth
    hit/miss families land in the registry, and the hit rate is
    DEPTH-SKEWED — top-of-trie depths (0-1) hit strictly better than
    the leaf-most depths, the 2408.14217 reuse model the resident
    eviction policy assumes."""
    from phant_tpu.backend import set_crypto_backend

    set_crypto_backend("cpu")  # host route: the histogram is route-blind
    monkeypatch.setenv("PHANT_RESIDENT", "0")
    root, wits = build_witnesses(n_blocks=24, picks=3, trie_n=256)
    eng = WitnessEngine(resident=False, depth_hist=True)
    snap0 = metrics.snapshot()["counters"]
    # replay: every block verified twice (consecutive-span overlap is
    # already heavy; the second pass is the steady state)
    assert np.asarray(eng.verify_batch(wits)).all()
    assert np.asarray(eng.verify_batch(wits)).all()
    snap1 = metrics.snapshot()["counters"]

    def delta(fam, d):
        key = f'{fam}{{depth="{d}"}}'
        return snap1.get(key, 0) - snap0.get(key, 0)

    def hit_rate(d):
        h = delta("witness_engine.depth_hits", d)
        m = delta("witness_engine.depth_misses", d)
        return (h / (h + m)) if (h + m) else None

    shallow = [r for r in (hit_rate("0"), hit_rate("1")) if r is not None]
    assert shallow, "no shallow-depth samples recorded"
    # the root is shared by EVERY block: all but its first occurrence hit
    assert hit_rate("0") > 0.9
    # depth 1 (the 16 branch children) is still heavily reused — its
    # unique-node count is tiny against its occurrence count
    assert min(shallow) > 0.75
    deep_labels = [d for d in ("3", "4", "5", "6", "7+") if hit_rate(d) is not None]
    if deep_labels:  # trie depth depends on the fixture shape
        deepest = hit_rate(deep_labels[-1])
        assert deepest <= min(shallow), (
            f"reuse not depth-skewed: deep {deepest} vs shallow {shallow}"
        )


def test_depth_histogram_memo_overflow(monkeypatch):
    """Memo overflow clears and RE-SCANS the batch: hit nodes whose memo
    entries were just evicted re-enter as fresh (their occurrences count
    as misses, like an engine generation flush) instead of KeyError-ing
    the BFS — a crash here would fail live verification traffic, not
    just the histogram (review finding)."""
    from phant_tpu.backend import set_crypto_backend

    set_crypto_backend("cpu")
    monkeypatch.setenv("PHANT_RESIDENT", "0")
    root, wits = build_witnesses(n_blocks=12, picks=3)
    eng = WitnessEngine(resident=False, depth_hist=True)
    eng._depth._max = 8  # force an overflow clear on every batch
    assert np.asarray(eng.verify_batch(wits)).all()
    assert np.asarray(eng.verify_batch(wits)).all()  # used to KeyError


# ---------------------------------------------------------------------------
# the row form (PR 31): the table against the old program's semantics
# ---------------------------------------------------------------------------


def _ref_slots_py(node: bytes):
    """The 17 ref slots of a node as the update program has always filled
    them (slots 0..15 a branch's hashed children, slot 16 an extension's
    child or an account leaf's storage root; anything malformed ref-less),
    from a plain RLP decode: the reference the device parser is held to."""
    slots = [None] * 17
    try:
        items = rlp.decode(node)
    except Exception:
        return slots
    if not isinstance(items, list):
        return slots
    if len(items) == 17:
        for k in range(16):
            if isinstance(items[k], bytes) and len(items[k]) == 32:
                slots[k] = items[k]
    elif len(items) == 2 and isinstance(items[0], bytes) and items[0]:
        path, val = items
        if not path[0] & 0x20:  # extension
            if isinstance(val, bytes) and len(val) == 32:
                slots[16] = val
        elif isinstance(val, bytes) and len(val) != 32:
            try:
                acct = rlp.decode(val)
            except Exception:
                return slots
            if (
                isinstance(acct, list)
                and len(acct) == 4
                and all(isinstance(a, bytes) for a in acct)
                and len(acct[2]) == 32
                and len(acct[3]) == 32
            ):
                slots[16] = acct[2]
    return slots


def _index_insert_fixed_rounds(index, fps, slots, live, probes):
    """`keccak_jax.index_insert` as it was until PR 31, in numpy: a fixed
    count of rounds, each a read, a scatter-min and a read."""
    from phant_tpu.ops.keccak_jax import INDEX_EMPTY

    def mix(d0, d1):
        h = d0 ^ (d1 * np.uint32(0x9E3779B9))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
        return h ^ (h >> np.uint32(16))

    index = index.copy()
    h = mix(fps[:, 0], fps[:, 1])
    pending = live.copy()
    for rnd in range(probes):
        pos = ((h + np.uint32(rnd)) & np.uint32(len(index) - 1)).astype(np.int64)
        want = pending & (index[pos] >= INDEX_EMPTY)
        np.minimum.at(index, pos[want], slots[want])
        pending &= ~(want & (index[pos] == slots))
    return index, int(pending.sum())


class _OldTable:
    """A table built by the old update program's semantics, on the host."""

    def __init__(self, cap):
        from phant_tpu.ops.keccak_jax import INDEX_EMPTY

        self.cap = cap
        self.digests = np.zeros((cap, 8), np.uint32)
        self.refs = np.zeros((cap, 17, 8), np.uint32)
        self.live = np.zeros((cap, 17), bool)
        self.index = np.full(4 * cap, INDEX_EMPTY, np.int32)
        self.n = 0
        self.dropped = 0

    def update(self, nodes):
        from phant_tpu.ops.keccak_jax import INDEX_PROBES

        base = self.n
        for j, nb in enumerate(nodes):
            self.digests[base + j] = np.frombuffer(keccak256(nb), "<u4")
            for k, ref in enumerate(_ref_slots_py(nb)):
                if ref is not None:
                    self.refs[base + j, k] = np.frombuffer(ref, "<u4")
                    self.live[base + j, k] = True
        self.n += len(nodes)
        sl = np.arange(base, self.n, dtype=np.int32)
        self.index, dropped = _index_insert_fixed_rounds(
            self.index, self.digests[sl, :2], sl, np.ones(len(sl), bool), INDEX_PROBES
        )
        self.dropped += dropped


def _assert_table_equals(table, old):
    import jax

    from phant_tpu.ops.witness_resident import _rows_at

    digests, refs, tail, index, fps = table.arrays()
    assert table.rows() == old.n and digests.shape[0] == old.cap
    n = old.n
    assert (np.asarray(digests)[:n] == old.digests[:n]).all()
    assert (np.asarray(fps)[:n] == old.digests[:n, :2]).all()
    r17, live = jax.jit(_rows_at)(refs, tail, np.arange(n, dtype=np.int32))
    assert (np.asarray(live) == old.live[:n]).all()
    assert (np.asarray(r17) == old.refs[:n]).all()  # dead slots zero on both
    assert (np.asarray(index) == old.index).all()
    got = table.device_lookup(old.digests[:n, :2].copy())
    assert (got == np.arange(n)).all()


def test_table_after_updates_and_tiered_flush_equals_the_old_programs():
    """Two updates and a `flush_retaining`: digests, ref words, liveness,
    fingerprints, the index's every bucket and `device_lookup` are what the
    old program (byte gathers, (cap, 17, 8) refs, 32 fixed probe rounds)
    left for the same batches."""
    from phant_tpu.ops.witness_resident import ResidentTable

    rng = np.random.default_rng(9)
    _root, wits = build_witnesses(n_blocks=6, picks=6, trie_n=256)
    extra = [
        rlp.encode([b"\x00\xab", rng.bytes(32)]),  # extension
        rlp.encode(  # account leaf
            [b"\x20" + rng.bytes(20), rlp.encode([b"\x01", b"\x02", rng.bytes(32), rng.bytes(32)])]
        ),
        rng.bytes(64),  # garbage: hashed, ref-less
    ]
    first = list(dict.fromkeys(n for _r, ns in wits[:3] for n in ns))
    second = [
        n for n in dict.fromkeys([n for _r, ns in wits[3:] for n in ns] + extra)
        if n not in set(first)
    ]
    table = ResidentTable(max_cap=1024, start_cap=1024)
    old = _OldTable(1024)
    for batch in (first, second):
        h = table.dispatch([(b"\x00" * 32, batch)], [])
        assert h is not None and h.uploaded_nodes == len(batch)
        h.resolve()
        old.update(batch)
        _assert_table_equals(table, old)
    keep = first[::2] + second[:5]
    table.flush_retaining(keep)
    old = _OldTable(1024)
    old.update(keep)
    _assert_table_equals(table, old)
    assert table.stats_snapshot()["index_dropped"] == old.dropped == 0


def _gauge(name):
    return int(metrics.snapshot()["gauges"].get(name, 0))


def _counter(name, **labels):
    snap = metrics.snapshot()["counters"]
    key = name + "{" + ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
    return int(snap.get(key, 0))


def test_same_row_count_different_byte_totals_run_one_update_program():
    """The blob form keyed the program on the blob's padded length; the row
    form has rows only: 1,000 bytes or 20,000 in a rung of rows are one program."""
    from phant_tpu.ops.witness_resident import ResidentTable

    rng = np.random.default_rng(3)
    # a row space no other test of this process uses, so the shape is new
    table = ResidentTable(max_cap=2048, start_cap=2048)
    small = [rng.bytes(20) for _ in range(40)]
    large = [rng.bytes(500) for _ in range(40)]
    shapes = 'lanes.program_shapes{program="update"}'
    before = _gauge(shapes)
    built = table._update_fn._cache_size()
    table.dispatch([(b"\x00" * 32, small)], []).resolve()
    assert _gauge(shapes) == before + 1
    table.dispatch([(b"\x00" * 32, large)], []).resolve()
    assert _gauge(shapes) == before + 1
    assert table._update_fn._cache_size() == built + 1


def test_update_counters_say_what_the_row_form_uploads():
    from phant_tpu.ops.witness_resident import ResidentTable

    rng = np.random.default_rng(4)
    nodes = [rng.bytes(int(n)) for n in rng.integers(1, 600, 37)]
    table = ResidentTable(max_cap=1024, start_cap=1024)
    was = {
        (n, k): _counter(n, kind=k)
        for n, k in [
            ("witness_resident.update_rows", "real"),
            ("witness_resident.update_rows", "pad"),
            ("witness_resident.update_bytes", "payload"),
            ("witness_resident.update_bytes", "pad"),
        ]
    }
    table.dispatch([(b"\x00" * 32, nodes)], []).resolve()
    grew = {key: _counter(key[0], kind=key[1]) - v for key, v in was.items()}
    payload = sum(map(len, nodes))
    assert grew[("witness_resident.update_rows", "real")] == 37
    assert grew[("witness_resident.update_rows", "pad")] == 2048 - 37
    assert grew[("witness_resident.update_bytes", "payload")] == payload
    assert grew[("witness_resident.update_bytes", "pad")] == 2048 * 680 - payload
    st = table.stats_snapshot()
    assert st["uploaded_nodes"] == 37 and st["uploaded_bytes"] == payload
