"""Batched post-state-root recomputation (PR 11): the differential suite.

The batched device path (stateless.WitnessStateDB.post_root_plan ->
serving root lane -> ops/root_engine.py merged dispatch) must be
BYTE-IDENTICAL to the host `state_root()` oracle for every mutation class
— account create / update / EIP-158 delete / selfdestruct-recreate /
storage-trie collapse — on all three witness-engine cores at pipeline
depths 1 AND 2, with embedded-node fallback exercised per trie and a
poisoned root dispatch failing only in-flight requests with -32052 plus a
stage-named crash record. The repeated-state_root idempotency bugfix
(memoized write-backs: a second call hashes ZERO nodes) is pinned here
too.
"""

from __future__ import annotations

import os
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from phant_tpu import rlp
from phant_tpu.backend import set_crypto_backend
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import EMPTY_TRIE_ROOT, Trie
from phant_tpu.mpt.proof import generate_proof
from phant_tpu.state.root import account_leaf
from phant_tpu.stateless import WitnessStateDB
from phant_tpu.types.account import Account


@pytest.fixture(params=["ext", "ctypes", "python"])
def engine_core(request, monkeypatch):
    """The three witness-engine cores: the root lane must coexist with
    each (the serving pipeline interleaves witness and root batches)."""
    monkeypatch.setenv(
        "PHANT_ENGINE_NATIVE", "0" if request.param == "python" else "1"
    )
    monkeypatch.setenv(
        "PHANT_ENGINE_EXT", "1" if request.param == "ext" else "0"
    )
    return request.param


@pytest.fixture
def forced_device(monkeypatch):
    """Force the root lane + device route on the XLA-CPU proxy."""
    monkeypatch.setenv("PHANT_ALLOW_JAX_CPU", "1")
    monkeypatch.setenv("PHANT_BATCHED_ROOT", "1")
    set_crypto_backend("tpu")
    yield
    set_crypto_backend("cpu")


# ---------------------------------------------------------------------------
# builders: witness-backed states with full-coverage witnesses
# ---------------------------------------------------------------------------

N_ACCOUNTS = 24
STORED = (5, 6, 7)  # addresses byte-patterns with storage


def _addr(i: int) -> bytes:
    return bytes([i]) * 20


def _pre_accounts(seed: int) -> dict:
    accounts = {}
    for i in range(1, N_ACCOUNTS):
        storage = (
            {j: j + seed + 1 for j in range(1, 9)} if i in STORED else {}
        )
        accounts[_addr(i)] = Account(
            nonce=i % 3, balance=i * 10**15 + seed, storage=storage
        )
    return accounts


def _full_witness(accounts, extra_keys=()) -> tuple:
    """Pre-state root + witness covering EVERY account path and every
    storage slot (plus absence proofs for `extra_keys` addresses), so any
    mutation class stays inside the witnessed region."""
    trie = Trie()
    for a, acct in accounts.items():
        trie.put(keccak256(a), account_leaf(acct))
    nodes: dict = {}
    for a in list(accounts) + list(extra_keys):
        for enc in generate_proof(trie, keccak256(a)):
            nodes[enc] = None
    for a, acct in accounts.items():
        if not acct.storage:
            continue
        st = Trie()
        for s, v in acct.storage.items():
            st.put(
                keccak256(s.to_bytes(32, "big")), rlp.encode(rlp.encode_uint(v))
            )
        for s in acct.storage:
            for enc in generate_proof(st, keccak256(s.to_bytes(32, "big"))):
                nodes[enc] = None
    return trie.root_hash(), list(nodes)


NEW_ADDR = b"\xee" * 20


def mut_update(db):
    db.set_storage(_addr(5), 1, 4242)
    db.set_storage(_addr(6), 3, 777)
    db.get_balance(_addr(7))
    db.accounts[_addr(7)].balance += 11


def mut_create(db):
    db.get_balance(NEW_ADDR)  # witnessed absence
    db.accounts[NEW_ADDR] = Account(balance=123)
    db.set_storage(NEW_ADDR, 9, 99)


def mut_delete(db):
    # EIP-158-style removal of a touched pre-existing account
    db.get_balance(_addr(3))
    del db.accounts[_addr(3)]


def mut_selfdestruct_recreate(db):
    db.get_storage(_addr(6), 1)
    fresh = Account(balance=1)  # new identity: storage restarts EMPTY
    db.accounts[_addr(6)] = fresh
    db.set_storage(_addr(6), 2, 5)


def mut_storage_collapse(db):
    # zero enough slots that the storage trie collapses branches; leave
    # one survivor so the trie stays non-empty
    for s in range(2, 9):
        db.set_storage(_addr(5), s, 0)
    # and empty another account's storage entirely (root -> EMPTY)
    for s in range(1, 9):
        db.set_storage(_addr(7), s, 0)


MUTATIONS = (
    mut_update,
    mut_create,
    mut_delete,
    mut_selfdestruct_recreate,
    mut_storage_collapse,
)


def _state(seed: int, mutate) -> WitnessStateDB:
    accounts = _pre_accounts(seed)
    root, nodes = _full_witness(accounts, extra_keys=[NEW_ADDR])
    db = WitnessStateDB(root, nodes, [])
    mutate(db)
    return db


def _request_set(seeds=range(len(MUTATIONS))) -> tuple:
    """(host oracle roots, PostRootPlans, states) — twin states per seed:
    one walks the host oracle, one takes the plan path."""
    hosts, prps, dbs = [], [], []
    for i, seed in enumerate(seeds):
        mutate = MUTATIONS[i % len(MUTATIONS)]
        hosts.append(_state(seed, mutate).state_root())
        db = _state(seed, mutate)
        prp = db.post_root_plan()
        assert prp is not None, f"seed {seed} unexpectedly unplannable"
        prps.append(prp)
        dbs.append(db)
    return hosts, prps, dbs


# ---------------------------------------------------------------------------
# engine-level identity (forced device, XLA-CPU proxy)
# ---------------------------------------------------------------------------


def test_mutation_classes_device_identity(forced_device):
    """Every mutation class, merged into ONE forced-device dispatch, is
    byte-identical to the host oracle."""
    from phant_tpu.ops.root_engine import RootEngine

    hosts, prps, dbs = _request_set()
    eng = RootEngine(device_floor=0)
    outs = eng.root_many([p.plan for p in prps])
    assert eng.stats["device_batches"] == 1
    for prp, db, out, want in zip(prps, dbs, outs, hosts):
        assert db.apply_post_root(prp, out) == want
        # the memo answers the follow-up host walk with the same root
        assert db.state_root() == want


def test_host_route_identity():
    """The offload-gated host route (cpu backend) returns the same
    digests through the same engine protocol."""
    from phant_tpu.ops.root_engine import RootEngine

    hosts, prps, dbs = _request_set()
    eng = RootEngine()
    outs = eng.root_many([p.plan for p in prps])
    assert eng.stats["host_batches"] == 1
    for prp, db, out, want in zip(prps, dbs, outs, hosts):
        assert db.apply_post_root(prp, out) == want


def test_prefetch_merge_consumed(forced_device):
    """An identity-matched prefetch merge is consumed by begin_batch; a
    mismatched plans list is dropped stale (released, not leaked)."""
    from phant_tpu.ops.root_engine import RootEngine

    hosts, prps, dbs = _request_set()
    eng = RootEngine(device_floor=0)
    plans = [p.plan for p in prps]
    pf = eng.prefetch_batch(plans)
    assert pf.merged is not None
    h = eng.begin_batch(plans, prefetch=pf)
    assert pf.merged is None  # ownership moved
    outs = eng.resolve_batch(h)
    for prp, db, out, want in zip(prps, dbs, outs, hosts):
        assert db.apply_post_root(prp, out) == want
    # stale: a different list object is released whole
    hosts2, prps2, _dbs2 = _request_set(seeds=(7,))
    pf2 = eng.prefetch_batch([p.plan for p in prps2])
    h2 = eng.begin_batch([prps2[0].plan], prefetch=pf2)  # different list
    assert pf2.lease is None  # released back to the pool
    eng.resolve_batch(h2)


def test_abandoned_handle_releases_lease(forced_device):
    """abandon_batch on an undispatched handle returns the merge lease;
    on a dispatched one the lease is (boundedly) stranded — either way
    the handle is dead and a second abandon is a no-op."""
    from phant_tpu.ops.root_engine import RootEngine

    _hosts, prps, _dbs = _request_set(seeds=(1,))
    eng = RootEngine(device_floor=0)
    h = eng.begin_batch([prps[0].plan])
    eng.abandon_batch(h)
    eng.abandon_batch(h)  # idempotent
    assert h.resolved
    with pytest.raises(RuntimeError):
        eng.resolve_batch(h)


# ---------------------------------------------------------------------------
# embedded-node / fallback paths
# ---------------------------------------------------------------------------


def test_embedded_node_trie_is_unplannable():
    """The PlanBuilder rejects (with clean rollback) tries containing
    embedded (<32 B) nodes — short-key tries like tx/receipt tries."""
    from phant_tpu.ops.mpt_jax import PlanBuilder, build_hash_plan

    t = Trie()
    for i in range(4):
        t.put(rlp.encode(rlp.encode_uint(i)), rlp.encode_uint(i + 1))
    assert build_hash_plan(t) is None
    b = PlanBuilder()
    assert b.try_subtree(t.root) is None
    assert not b.entries and not b.too_small  # rolled back clean


def test_storage_subtree_fallback_per_trie(monkeypatch):
    """A storage trie the builder rejects falls back ALONE: its root is
    host-hashed into the leaf as a constant, the rest of the request
    still plans — and when the ACCOUNT trie is rejected too, the whole
    request repairs back to the host walk. Identity holds either way."""
    import phant_tpu.ops.mpt_jax as mj

    real = mj.PlanBuilder

    def make_failing(n_fail):
        class Failing(real):
            _fails = n_fail

            def try_subtree(self, node):
                if Failing._fails > 0:
                    Failing._fails -= 1
                    # the embedded-node contract: None with the builder
                    # rolled back untouched
                    return None
                return super().try_subtree(node)

        return Failing

    want = _state(3, mut_update).state_root()

    # first try_subtree (a storage trie) fails -> constant-root fallback
    db = _state(3, mut_update)
    monkeypatch.setattr(mj, "PlanBuilder", make_failing(1))
    prp = db.post_root_plan()
    assert prp is not None
    from phant_tpu.ops.mpt_jax import execute_plan_outputs_host

    assert db.apply_post_root(prp, execute_plan_outputs_host(prp.plan)) == want

    # every try_subtree fails -> full repair, host walk answers
    db2 = _state(3, mut_update)
    monkeypatch.setattr(mj, "PlanBuilder", make_failing(99))
    assert db2.post_root_plan() is None
    assert db2.state_root() == want


def test_unplannable_states_return_none():
    """Nothing dirty -> no plan (the memo answers); a poisoned trie
    raises identically on both paths."""
    db = _state(0, lambda d: d.get_balance(_addr(5)))  # read-only touch
    assert db.post_root_plan() is None
    want = db.state_root()
    assert db.state_root() == want


# ---------------------------------------------------------------------------
# the idempotency bugfix (satellite): call-it-twice counters
# ---------------------------------------------------------------------------


def test_repeated_state_root_hashes_zero_nodes(monkeypatch):
    """The r11 bugfix pin: `_storage_root_of` used to rebuild `changed`
    and re-put every changed slot on EVERY state_root() call. Now the
    write-backs memoize: the second call performs zero keccaks and zero
    trie mutations, and a write in between invalidates the memo."""
    import phant_tpu.mpt.mpt as mpt_mod

    db = _state(1, mut_update)
    r1 = db.state_root()
    calls = {"n": 0}
    real = mpt_mod.keccak256

    def counting(data):
        calls["n"] += 1
        return real(data)

    monkeypatch.setattr(mpt_mod, "keccak256", counting)
    epoch0 = db._trie._epoch
    assert db.state_root() == r1
    assert calls["n"] == 0, "second state_root() hashed nodes"
    assert db._trie._epoch == epoch0, "second state_root() mutated the trie"
    monkeypatch.setattr(mpt_mod, "keccak256", real)
    # a write in between invalidates the memo and changes the root
    db.set_storage(_addr(5), 2, 31337)
    r2 = db.state_root()
    assert r2 != r1
    # and the plan path fills the same memo (see
    # test_mutation_classes_device_identity for the device twin)


# ---------------------------------------------------------------------------
# the serving root lane: differential across cores x depths, coalescing,
# crash semantics, mesh, end-to-end server
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2])
def test_sched_root_lane_differential(engine_core, depth, forced_device):
    """Batched-vs-host byte identity through the scheduler at both
    pipeline depths on every witness-engine core, with witness traffic
    interleaved on the same scheduler (the lanes must coexist)."""
    from phant_tpu.ops.root_engine import RootEngine
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    hosts, prps, dbs = _request_set()
    # a couple of witness jobs ride along (native-routed: device floor
    # untouched so the witness engine stays on the host hasher)
    wit_root, wit_nodes = _full_witness(_pre_accounts(0))
    with VerificationScheduler(
        engine=WitnessEngine(),
        config=SchedulerConfig(
            max_batch=16,
            max_wait_ms=20.0,
            pipeline_depth=depth,
            root_engine_factory=lambda: RootEngine(device_floor=0),
        ),
    ) as s:
        wfuts = [s.submit_witness(wit_root, wit_nodes) for _ in range(3)]
        outs = s.root_many([p.plan for p in prps])
        assert all(f.result(timeout=30) for f in wfuts)
        st = s.stats_snapshot()
    assert st["root_batches"] >= 1
    assert st["root_requests"] == len(prps)
    for prp, db, out, want in zip(prps, dbs, outs, hosts):
        assert db.apply_post_root(prp, out) == want


def test_root_jobs_coalesce_and_meta(forced_device):
    """Same-depth plans coalesce into one dispatch; root_traced returns
    the joinable batch record (backend, batch_id, queue_wait_ms)."""
    import threading

    from phant_tpu.ops.root_engine import RootEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    hosts, prps, dbs = _request_set(seeds=(0, 10, 20))
    depths = {len(p.plan.levels) for p in prps}
    with VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8,
            max_wait_ms=200.0,
            root_engine_factory=lambda: RootEngine(device_floor=0),
        ),
    ) as s:
        results = [None] * len(prps)

        def one(i):
            # no deadline: a cold XLA compile on the proxy can exceed the
            # default 30s (the test pins coalescing, not latency)
            results[i] = s.root_traced(prps[i].plan, deadline_s=float("inf"))

        threads = [
            threading.Thread(target=one, args=(i,)) for i in range(len(prps))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        st = s.stats_snapshot()
    metas = []
    for prp, db, (out, meta), want in zip(prps, dbs, results, hosts):
        assert db.apply_post_root(prp, out) == want
        assert meta is not None and meta["backend"] == "device"
        assert meta["lane"] == "root" and "queue_wait_ms" in meta
        metas.append(meta)
    if len(depths) == 1:
        # all three shared one level-shape bucket: they must coalesce
        assert st["root_coalesced"] >= 2
        assert len({m["batch_id"] for m in metas}) == 1


def test_poisoned_root_dispatch_crash(engine_core):
    """A poisoned root dispatch fails ONLY in-flight requests with
    -32052 and leaves a stage-named crash record; earlier results keep
    their digests."""
    from phant_tpu.obs.flight import flight
    from phant_tpu.ops.root_engine import RootEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        SchedulerDown,
        VerificationScheduler,
    )

    class _Poisoned(RootEngine):
        armed = False

        def begin_batch(self, plans, prefetch=None):
            if _Poisoned.armed:
                raise RuntimeError("test-induced root dispatch crash")
            return super().begin_batch(plans, prefetch=prefetch)

    _Poisoned.armed = False
    hosts, prps, dbs = _request_set()
    s = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8,
            max_wait_ms=5.0,
            pipeline_depth=2,
            root_engine_factory=_Poisoned,
        ),
    )
    try:
        first = [s.submit_root(prps[0].plan), s.submit_root(prps[1].plan)]
        got = [f.result(timeout=60) for f in first]
        assert all(got)
        _Poisoned.armed = True
        second = [s.submit_root(p.plan) for p in prps[2:]]
        for f in second:
            with pytest.raises(SchedulerDown) as ei:
                f.result(timeout=60)
            assert ei.value.code == -32052
        # already-resolved digests survive
        assert [f.result(timeout=1) for f in first] == got
    finally:
        s.shutdown()
    crashes = [
        r
        for r in flight.records()
        if r.get("kind") == "sched.executor_crash"
    ]
    assert crashes, "no crash record"
    assert crashes[-1]["stage"] in ("pack", "dispatch", "prefetch")


def test_root_lane_mesh_dispatch(forced_device):
    """Mesh mode: root batches route to a device lane (device-tagged
    record) and resolve byte-identical through the lane's own pinned
    RootEngine."""
    from phant_tpu.ops.root_engine import RootEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    hosts, prps, dbs = _request_set(seeds=(0, 1))
    with VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8,
            max_wait_ms=20.0,
            pipeline_depth=2,
            mesh_devices=2,
            root_engine_factory=lambda: RootEngine(device_floor=0),
        ),
    ) as s:
        out0, meta0 = s.root_traced(prps[0].plan)
        out1, meta1 = s.root_traced(prps[1].plan)
        st = s.stats_snapshot()
    assert dbs[0].apply_post_root(prps[0], out0) == hosts[0]
    assert dbs[1].apply_post_root(prps[1], out1) == hosts[1]
    assert meta0 is not None and meta0.get("device") is not None
    assert st["mesh_batches"] >= 1 and st["root_batches"] >= 1


def test_expired_root_jobs_shed_without_execution():
    """A root job whose deadline passes while queued sheds with -32051
    (the witness lane's deadline semantics, inherited wholesale)."""
    from phant_tpu.serving.scheduler import (
        DeadlineExpired,
        SchedulerConfig,
        VerificationScheduler,
    )

    _hosts, prps, _dbs = _request_set(seeds=(0,))

    class _Slow:
        def verify_batch(self, w):
            time.sleep(0.3)
            import numpy as np

            return np.ones(len(w), bool)

    wit_root, wit_nodes = _full_witness(_pre_accounts(0))
    s = VerificationScheduler(
        engine=_Slow(),
        config=SchedulerConfig(max_batch=4, max_wait_ms=1.0, pipeline_depth=1),
    )
    try:
        # a slow witness batch occupies the executor while the root job's
        # deadline expires in the queue
        s.submit_witness(wit_root, wit_nodes)
        f = s.submit_root(prps[0].plan, deadline_s=0.05)
        with pytest.raises(DeadlineExpired):
            f.result(timeout=30)
    finally:
        s.shutdown()


def test_memo_invalidated_on_plan_abort(monkeypatch):
    """Review regression pin: post_root_plan's ABORT paths apply trie
    mutations before bailing out — the post-root memo must die the
    moment a mutation lands, or the follow-up state_root() would return
    the stale pre-mutation root."""
    import phant_tpu.ops.mpt_jax as mj

    db = _state(4, mut_update)
    r1 = db.state_root()  # memo set
    # new mutations after the memo
    db.get_balance(_addr(4))
    del db.accounts[_addr(4)]
    db.set_storage(_addr(5), 3, 777)

    class _AlwaysFail(mj.PlanBuilder):
        def try_subtree(self, node):
            return None

    monkeypatch.setattr(mj, "PlanBuilder", _AlwaysFail)
    assert db.post_root_plan() is None  # aborted AFTER applying mutations
    monkeypatch.undo()
    r2 = db.state_root()
    assert r2 != r1, "stale post-root memo survived an aborted plan"
    # and the fresh root matches an untouched twin oracle
    twin = _state(4, mut_update)
    twin.get_balance(_addr(4))
    del twin.accounts[_addr(4)]
    twin.set_storage(_addr(5), 3, 777)
    assert r2 == twin.state_root()


def test_lone_request_guard_skips_plan(monkeypatch):
    """The offload gate may never regress a single request: with no root
    work queued to coalesce with and a witness payload the link model
    rejects, compute_post_root keeps the host walk WITHOUT even building
    a plan. Forcing the lane (PHANT_BATCHED_ROOT=1) bypasses the guard."""
    import phant_tpu.backend as backend
    from phant_tpu import serving
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )
    from phant_tpu.stateless import WitnessStateDB, compute_post_root

    monkeypatch.setenv("PHANT_ALLOW_JAX_CPU", "1")
    monkeypatch.setenv("PHANT_BATCHED_ROOT", "auto")
    set_crypto_backend("tpu")
    monkeypatch.setattr(backend, "device_offload_pays", lambda n: False)
    calls = {"n": 0}
    orig = WitnessStateDB.post_root_plan

    def counting(self):
        calls["n"] += 1
        return orig(self)

    monkeypatch.setattr(WitnessStateDB, "post_root_plan", counting)
    want = _state(2, mut_update).state_root()
    s = VerificationScheduler(
        config=SchedulerConfig(max_batch=8, max_wait_ms=5.0)
    )
    serving.install(s)
    try:
        db = _state(2, mut_update)
        assert compute_post_root(db) == want
        assert calls["n"] == 0, "lone request paid plan construction"
        # forcing the lane engages the plan path on the same state shape
        monkeypatch.setenv("PHANT_BATCHED_ROOT", "1")
        db2 = _state(2, mut_update)
        assert compute_post_root(db2) == want
        assert calls["n"] == 1
    finally:
        serving.uninstall(s)
        s.shutdown()
        set_crypto_backend("cpu")


def test_execute_stateless_routes_post_root_through_scheduler(monkeypatch):
    """End-to-end: with PHANT_BATCHED_ROOT=1 a real
    engine_executeStatelessPayloadV1 computes its post root through the
    active scheduler's root lane (host backend here — the lane itself is
    backend-agnostic) and the reply root is unchanged."""
    from test_serving import _post, _stateless_request

    from phant_tpu.engine_api.server import EngineAPIServer
    from phant_tpu.serving import SchedulerConfig

    monkeypatch.setenv("PHANT_BATCHED_ROOT", "1")
    chain, rpc, want_root = _stateless_request()
    server = EngineAPIServer(
        chain,
        host="127.0.0.1",
        port=0,
        sched_config=SchedulerConfig(max_batch=8, max_wait_ms=10.0),
    )
    server.serve_in_background()
    try:
        base = f"http://127.0.0.1:{server.port}"
        code, body = _post(base, rpc)
        assert code == 200 and body["result"]["status"] == "VALID", body
        assert body["result"]["stateRoot"] == want_root
        st = server.scheduler.stats_snapshot()
        # the post root rode the root lane (a no-op-dirtiness payload
        # would return plan=None and keep the host walk — this fixture
        # mutates state, so a plan must have been submitted)
        assert st["root_batches"] >= 1, st
    finally:
        server.shutdown()


@pytest.mark.parametrize("op", ["enqueue", "sync"])
def test_device_host_seconds_grows_on_the_root_lane(forced_device, op):
    """The host's time at the device, measured where it is spent (PR 26):
    `enqueue` around the merged plan's upload and launch, `sync` around
    the out-row readback."""
    from phant_tpu.ops.root_engine import RootEngine
    from phant_tpu.utils.trace import metrics

    key = f'device.host_seconds{{lane="root",op="{op}"}}'
    before = metrics.snapshot()["histograms"].get(key, {"count": 0, "sum": 0.0})
    hosts, prps, dbs = _request_set(seeds=range(1))
    (out,) = RootEngine(device_floor=0).root_many([prps[0].plan])
    assert dbs[0].apply_post_root(prps[0], out) == hosts[0]
    after = metrics.snapshot()["histograms"][key]
    assert after["count"] == before["count"] + 1
    assert after["sum"] > before["sum"]


# ---------------------------------------------------------------------------
# PR 35: one native node encoder under the walk and under the plan builder.
# The plan that comes out is the parent's, byte for byte.
# ---------------------------------------------------------------------------


def _parent_encode_template(items):
    """`ops/mpt_jax._encode_template` of commit 47c2d1e, verbatim but for
    `rlp.encode`, which is `rlp.encode_python` here: the oracle shares no
    encoder with what it judges."""
    from phant_tpu.ops.mpt_jax import _HOLE, _ValueHole, _list_header, str_header

    payload = bytearray()
    holes = []
    for it in items:
        if it is _HOLE:
            payload.append(0xA0)  # RLP string header for 32 bytes
            holes.append(len(payload))
            payload += b"\x00" * 32
        elif isinstance(it, _ValueHole):
            total = len(it.prefix) + 32 + len(it.suffix)
            payload += str_header(total)
            payload += it.prefix
            holes.append(len(payload))
            payload += b"\x00" * 32
            payload += it.suffix
        else:
            payload += rlp.encode_python(it)
    header = _list_header(len(payload))
    return bytes(header) + bytes(payload), [h + len(header) for h in holes]


def _parent_builder(cls):
    """`cls` (a scheme's PlanBuilder) with the `visit` and `finish` of
    commit 47c2d1e, verbatim: one template a call, one numpy element an
    assignment."""
    import numpy as np

    from phant_tpu.crypto.keccak import RATE
    from phant_tpu.mpt.mpt import ExtensionNode, LeafNode
    from phant_tpu.ops.mpt_jax import (
        _HOLE,
        MPT_MAX_CHUNKS,
        HashPlan,
        _pow2,
        _ValueHole,
    )

    _encode_template = _parent_encode_template

    class ParentBuilder(cls):
        def __init__(self):
            super().__init__()
            self.__dict__.pop("_path_enc", None)  # the scheme's Python one

        def visit(self, node):
            dg = getattr(node, "digest", None)
            if dg is not None:
                return None, 0, dg
            nid = id(node)
            if nid in self._index:
                gi = self._index[nid]
                return gi, self.entries[gi][0], None
            if isinstance(node, LeafNode):
                vh = self.value_holes.get(nid)
                if vh is not None:
                    prefix, suffix, child_gi, child_level = vh
                    template, holes = _encode_template(
                        [self._path_enc(node.path, True), _ValueHole(prefix, suffix)]
                    )
                    level = child_level + 1
                    hole_refs = [(holes[0], child_gi)]
                else:
                    template, _holes = _encode_template(
                        [self._path_enc(node.path, True), node.value]
                    )
                    level = 0
                    hole_refs = []
            elif isinstance(node, ExtensionNode):
                ci, clvl, cdg = self.visit(node.child)
                if cdg is not None:
                    template, _holes = _encode_template(
                        [self._path_enc(node.path, False), cdg]
                    )
                    level = 0
                    hole_refs = []
                else:
                    template, holes = _encode_template(
                        [self._path_enc(node.path, False), _HOLE]
                    )
                    level = clvl + 1
                    hole_refs = [(holes[0], ci)]
            else:  # BranchNode
                items = []
                child_order = []
                level = -1
                for child in node.children:
                    if child is None:
                        items.append(b"")
                        continue
                    ci, clvl, cdg = self.visit(child)
                    if cdg is not None:
                        items.append(cdg)  # constant 32-byte digest ref
                    else:
                        items.append(_HOLE)
                        child_order.append(ci)
                        level = max(level, clvl)
                items.append(node.value if node.value is not None else b"")
                template, holes = _encode_template(items)
                level += 1  # -1 (all-constant children) -> level 0
                hole_refs = list(zip(holes, child_order))
            if len(template) < self._min_template:
                self.too_small = True
            if len(template) > MPT_MAX_CHUNKS * RATE - 1:
                self.too_small = True  # oversized node: CPU path
            gi = len(self.entries)
            self.entries.append((level, template, hole_refs))
            self._index[nid] = gi
            self._order.append(nid)
            return gi, level, None

        def finish(self, root_gi, out_gis=()):
            if self.too_small or not self.entries:
                return None
            entries = self.entries
            n = len(entries)
            offsets = np.zeros(n, np.int64)
            pos = 0
            for gi, (_lvl, template, _holes) in enumerate(entries):
                offsets[gi] = pos
                pos += len(template)
            blob = np.zeros(_pow2(pos + MPT_MAX_CHUNKS * RATE), np.uint8)
            for gi, (_lvl, template, _holes) in enumerate(entries):
                blob[offsets[gi] : offsets[gi] + len(template)] = np.frombuffer(
                    template, np.uint8
                )

            max_level = max(lvl for lvl, _t, _h in entries)
            levels = []
            remap = np.zeros(n, np.int64)
            next_global = 0
            scratch = len(blob) - 32  # scatter target for hole padding rows
            for lvl in range(max_level + 1):
                idxs = [gi for gi in range(n) if entries[gi][0] == lvl]
                for k, gi in enumerate(idxs):
                    remap[gi] = next_global + k
                npad = _pow2(len(idxs))
                off = np.zeros(npad, np.int32)
                ln = np.zeros(npad, np.int32)
                for k, gi in enumerate(idxs):
                    off[k] = offsets[gi]
                    ln[k] = len(entries[gi][1])
                hp = []
                hc = []
                for gi in idxs:
                    for hole_off, child_gi in entries[gi][2]:
                        hp.append(int(offsets[gi]) + hole_off)
                        hc.append(int(remap[child_gi]))
                hpad = _pow2(len(hp)) if hp else 1
                hole_pos = np.full(hpad, scratch, np.int32)
                hole_child = np.zeros(hpad, np.int32)
                hole_pos[: len(hp)] = hp
                hole_child[: len(hc)] = hc
                levels.append((off, ln, hole_pos, hole_child))
                next_global += npad
            top_real = [gi for gi in range(n) if entries[gi][0] == max_level]
            assert top_real == [root_gi]
            out_rows = None
            if out_gis:
                out_rows = np.asarray([int(remap[g]) for g in out_gis], np.int32)
            return HashPlan(
                blob=blob,
                levels=levels,
                n_nodes=n,
                root_pos=int(remap[root_gi]),
                out_rows=out_rows,
                used=pos,
            )

    return ParentBuilder


def _assert_same_plan(got, want) -> None:
    """Everything `merge_plans`, the rungs and the device program read."""
    import numpy as np

    assert got.used == want.used and got.n_nodes == want.n_nodes
    assert got.root_pos == want.root_pos
    assert got.blob.shape == want.blob.shape and got.blob.dtype == want.blob.dtype
    assert bytes(got.blob[: got.used]) == bytes(want.blob[: want.used])
    assert not got.blob[got.used :].any()
    assert len(got.levels) == len(want.levels)
    for lvl, (mine, theirs) in enumerate(zip(got.levels, want.levels)):
        for name, a, b in zip(("off", "ln", "hole_pos", "hole_child"), mine, theirs):
            assert a.dtype == b.dtype == np.int32, (lvl, name)
            assert np.array_equal(a, b), (lvl, name)
    assert (got.out_rows is None) == (want.out_rows is None)
    if want.out_rows is not None:
        assert got.out_rows.dtype == want.out_rows.dtype == np.int32
        assert np.array_equal(got.out_rows, want.out_rows)


def _scheme_state(scheme, seed: int, mutate) -> WitnessStateDB:
    root, nodes, codes = scheme.witness_of_state(_pre_accounts(seed))
    db = WitnessStateDB(root, nodes, codes, scheme=scheme)
    mutate(db)
    return db


@pytest.mark.parametrize("mutate", MUTATIONS, ids=lambda m: m.__name__)
@pytest.mark.parametrize("scheme_name", ["mpt", "binary"])
def test_hash_plan_is_the_parents_byte_for_byte(
    node_encoder, scheme_name, mutate, monkeypatch
):
    """Twin states, one planned by the program and one by the parent's
    builder: blob up to `used`, every level's four arrays, `out_rows`,
    `root_pos`. Then the plan, run on the host, gives the walk's root."""
    from phant_tpu.commitment import get_scheme
    from phant_tpu.ops.mpt_jax import execute_plan_outputs_host

    scheme = get_scheme(scheme_name)
    seed = MUTATIONS.index(mutate)
    want_root = _scheme_state(scheme, seed, mutate).state_root()
    db = _scheme_state(scheme, seed, mutate)
    prp = db.post_root_plan()
    assert prp is not None
    parent_cls = _parent_builder(type(scheme.plan_builder()))
    monkeypatch.setattr(scheme, "plan_builder", parent_cls)
    parent_prp = _scheme_state(scheme, seed, mutate).post_root_plan()
    _assert_same_plan(prp.plan, parent_prp.plan)
    assert [p.gi for p in prp.patches] == [p.gi for p in parent_prp.patches]
    assert db.apply_post_root(prp, execute_plan_outputs_host(prp.plan)) == want_root


def _serve_reference_block(request_json, post_root):
    """The request through the Engine API's handler with `post_root` in
    `compute_post_root`'s place; returns the reply."""
    import time

    import phant_tpu.stateless as stateless
    from phant_tpu.__main__ import make_genesis_parent_header
    from phant_tpu.blockchain.chain import Blockchain
    from phant_tpu.blockchain.fork import fork_for
    from phant_tpu.config import ChainConfig
    from phant_tpu.crypto import kzg
    from phant_tpu.engine_api import handle_request
    from phant_tpu.state.statedb import StateDB

    config = ChainConfig.from_chain_id(1)
    state = StateDB({})
    public = kzg.public_network()
    chain = Blockchain(
        chain_id=1,
        state=state,
        parent_header=make_genesis_parent_header(),
        fork=fork_for(config, state, 0, int(time.time())),
        config=config,
    )
    real = stateless.compute_post_root
    stateless.compute_post_root = post_root
    try:
        code, reply = handle_request(chain, request_json)
    finally:
        stateless.compute_post_root = real
        # a mainnet chain names its network for the whole process
        kzg.set_public_network(public)
    assert code == 200, reply
    return reply


def test_reference_block_walk_plan_and_reference_agree(
    node_encoder, reference_block, monkeypatch
):
    """On a block of the benchmark's chain: `state_root()`,
    `post_root_plan()` + `apply_post_root` and the reference's own root are
    one root; the plan is the parent's; a repeated `state_root()` encodes
    and hashes nothing."""
    from phant_tpu.commitment import get_scheme
    from phant_tpu.ops.mpt_jax import execute_plan_outputs_host
    from phant_tpu.utils.trace import metrics

    block, request_json = reference_block
    seen = {}

    def encoded() -> int:
        counters = metrics.snapshot()["counters"]
        return sum(v for k, v in counters.items() if k.startswith("mpt.node_encodings"))

    def walk(state):
        root = state.state_root()
        before = encoded()
        assert state.state_root() == root  # the memo
        seen["encoded_by_repeat"] = encoded() - before
        return root

    def plan(state):
        prp = state.post_root_plan()
        assert prp is not None and prp.patches  # contract calls: storage holes
        seen["plan"] = prp.plan
        root = state.apply_post_root(prp, execute_plan_outputs_host(prp.plan))
        assert state.state_root() == root  # tries left canonical
        return root

    def parent_plan(state):
        seen["parent_plan"] = state.post_root_plan().plan
        return state.state_root()

    for post_root in (walk, plan):
        reply = _serve_reference_block(request_json, post_root)
        assert reply["result"]["status"] == "VALID", reply
        assert bytes.fromhex(reply["result"]["stateRoot"][2:]) == block.header.state_root
    assert seen["encoded_by_repeat"] == 0
    scheme = get_scheme("mpt")
    monkeypatch.setattr(
        scheme, "plan_builder", _parent_builder(type(scheme.plan_builder()))
    )
    _serve_reference_block(request_json, parent_plan)
    _assert_same_plan(seen["plan"], seen["parent_plan"])
    assert seen["plan"].n_nodes > 100


def test_a_served_request_counts_native_encodings_only():
    """`mpt.node_encodings{impl=}` on a machine with the extension: a
    stateless request through the server (transactions root in the front
    end, receipts root in execution, the post-root's walk) grows `native`
    and leaves `python` where it was. A silent fallback cannot hide."""
    from test_serving import _post, _stateless_request

    from phant_tpu.engine_api.server import EngineAPIServer
    from phant_tpu.serving import SchedulerConfig
    from phant_tpu.utils.native import load_engine_ext
    from phant_tpu.utils.trace import metrics

    if load_engine_ext() is None:
        pytest.skip("no extension on this machine")

    def counted(impl: str) -> int:
        return metrics.snapshot()["counters"].get(
            f'mpt.node_encodings{{impl="{impl}"}}', 0
        )

    chain, rpc, want_root = _stateless_request()
    server = EngineAPIServer(
        chain,
        host="127.0.0.1",
        port=0,
        sched_config=SchedulerConfig(max_batch=8, max_wait_ms=10.0),
    )
    server.serve_in_background()
    try:
        before = counted("native"), counted("python")
        base = f"http://127.0.0.1:{server.port}"
        code, body = _post(base, rpc)
        assert code == 200 and body["result"]["status"] == "VALID", body
        assert body["result"]["stateRoot"] == want_root
        assert counted("native") > before[0]
        assert counted("python") == before[1]
        import urllib.request

        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert 'phant_mpt_node_encodings_total{impl="native"}' in text
    finally:
        server.shutdown()
