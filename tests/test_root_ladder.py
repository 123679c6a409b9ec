"""The served root program's shapes come from a ladder (PR 28).

`ops/mpt_jax.merge_plans` lays a batch's HashPlans out in strips of
STRIP_ROWS on a rung of PLAN_LADDER, and `_hash_plan_outputs` is keyed on the
rung alone: a block's own sizes never reach the jit cache (PERF.md section
7, fault 0a, closed). Pinned here: the strips' digests against both host
oracles over random dirty tries and merged sets; that the shapes stop
growing over a chain of blocks; and that the resident intern table is born
at its cap where an accelerator holds it, and small on the CPU (fault 0b).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from phant_tpu import rlp
from phant_tpu.backend import set_crypto_backend
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import Trie
from phant_tpu.mpt.proof import generate_proof
from phant_tpu.state.root import account_leaf
from phant_tpu.stateless import WitnessStateDB
from phant_tpu.types.account import Account
from phant_tpu.utils.trace import metrics


@pytest.fixture
def forced_device(monkeypatch):
    """`with forced_device():` the device route on the XLA-CPU proxy. The
    states and the host walk's roots are made outside it, on the cpu
    backend: under the tpu backend the suite's conftest sends every trie
    root to `trie_root_device`, a program a trie shape."""
    import contextlib

    monkeypatch.setenv("PHANT_ALLOW_JAX_CPU", "1")

    @contextlib.contextmanager
    def forced():
        set_crypto_backend("tpu")
        try:
            yield
        finally:
            set_crypto_backend("cpu")

    return forced


def _slot_key(slot: int) -> bytes:
    return keccak256(slot.to_bytes(32, "big"))


def _dirty_state(seed: int, n_accounts: int, n_touched: int) -> tuple:
    """(host walk's post root, the twin state that will take the plan
    path): a pre-state of `n_accounts` random accounts, every 24th with
    storage; a witness of the touched accounts' paths, their slots and one
    absent address; then balances moved, slots written and zeroed, one
    account made and one nonce raised, all drawn from `seed` (removals:
    test_post_root.py's mutation classes)."""
    rng = np.random.default_rng([seed, 0x1ADD])
    accounts = {}
    for i in range(n_accounts):
        storage = {}
        if i % 24 == 0:
            storage = {int(s): int(s) + seed + 1 for s in rng.integers(1, 1 << 30, 6)}
        accounts[rng.bytes(20)] = Account(
            nonce=i % 3, balance=int(rng.integers(1, 1 << 60)), storage=storage
        )
    addrs = list(accounts)
    touched = [addrs[int(i)] for i in rng.choice(n_accounts, n_touched, replace=False)]
    absent = rng.bytes(20)
    trie = Trie()
    for a, acct in accounts.items():
        trie.put(keccak256(a), account_leaf(acct))
    nodes: dict = {}
    for a in touched + [absent]:
        nodes.update(dict.fromkeys(generate_proof(trie, keccak256(a))))
    for a in touched:
        if not accounts[a].storage:
            continue
        st = Trie()
        for s, v in accounts[a].storage.items():
            st.put(_slot_key(s), rlp.encode(rlp.encode_uint(v)))
        for s in accounts[a].storage:
            nodes.update(dict.fromkeys(generate_proof(st, _slot_key(s))))
    root = trie.root_hash()
    draws = rng.integers(1, 1 << 20, len(touched))

    def mutate(db: WitnessStateDB) -> WitnessStateDB:
        for k, a in enumerate(touched[1:]):
            db.get_balance(a)
            db.accounts[a].balance += int(draws[k])
            slots = list(accounts[a].storage)
            if slots:
                db.set_storage(a, slots[0], int(draws[k]))
                db.set_storage(a, slots[1], 0)
        db.get_balance(absent)
        db.accounts[absent] = Account(balance=int(draws[-1]))
        db.set_storage(absent, 7, 77)
        db.get_balance(touched[0])
        db.accounts[touched[0]].nonce += 1
        return db

    want = mutate(WitnessStateDB(root, list(nodes), [])).state_root()
    return want, mutate(WitnessStateDB(root, list(nodes), []))


def _sizes(seed: int) -> tuple:
    """(accounts, touched) of case `seed`: most the size of a test, a few
    wide enough that a level spans several strips."""
    rng = np.random.default_rng([seed, 0x512E])
    n = int(rng.integers(300, 900)) if seed % 5 == 4 else int(rng.integers(20, 200))
    return n, int(rng.integers(4, min(300, max(5, n // 2))))


@pytest.mark.parametrize("seed", range(20))
def test_strip_digests_match_both_oracles(forced_device, seed):
    """A merged set of 1-4 random dirty plans through the served program:
    every out row equals the plan's host execution, and the post root the
    host walk's, byte for byte."""
    from phant_tpu.ops.mpt_jax import STRIP_ROWS, execute_plan_outputs_host, merge_plans
    from phant_tpu.ops.root_engine import RootEngine

    k = 1 + seed % 4
    wants, dbs, prps = [], [], []
    for j in range(k):
        want, db = _dirty_state(100 * seed + j, *_sizes(seed + 31 * j))
        prp = db.post_root_plan()
        assert prp is not None
        wants.append(want)
        dbs.append(db)
        prps.append(prp)
    plans = [p.plan for p in prps]
    merged, outs = merge_plans(plans)
    assert merged is not None and merged.n_nodes == sum(p.n_nodes for p in plans)
    assert merged.rung == 0  # one program serves all twenty cases
    assert merged.n_steps * STRIP_ROWS >= merged.n_nodes
    assert [len(o) for o in outs] == [len(p.out_rows) for p in plans]
    eng = RootEngine(device_floor=0)
    with forced_device():
        got = eng.root_many(plans)
    assert eng.stats["device_batches"] == 1
    for plan, prp, db, out, want in zip(plans, prps, dbs, got, wants):
        assert [bytes(d) for d in out] == execute_plan_outputs_host(plan)
        assert db.apply_post_root(prp, out) == want


def test_dense_levels_are_cut_by_their_holes(forced_device):
    """A full trie's upper levels are branches of 16 children: 128 of them
    hold 2,048 holes, so those strips close on STRIP_HOLES and the program
    still gives the host walk's root."""
    from phant_tpu.ops.mpt_jax import (
        STRIP_HOLES,
        STRIP_ROWS,
        build_hash_plan,
        merge_plans,
    )
    from phant_tpu.ops.root_engine import RootEngine

    trie = Trie()
    for i in range(3000):
        trie.put(keccak256(i.to_bytes(4, "big")), b"\x01" * 70)
    plan = build_hash_plan(trie)
    merged, _outs = merge_plans([plan])
    per_strip = (merged.hole_pos != len(merged.blob) - 32).sum(axis=1)
    assert per_strip.max() <= STRIP_HOLES
    assert merged.n_steps > -(-plan.n_nodes // STRIP_ROWS)  # some strips closed early
    want = trie.root_hash()
    with forced_device():
        ((root,),) = RootEngine(device_floor=0).root_many([plan])
    assert bytes(root) == want


def test_plan_shapes_stop_growing_over_a_chain(forced_device):
    """14 blocks of differing sizes through the forced device route: the
    served program is built for the first and for no later one, and never
    for more shapes than the ladder has rungs."""
    from phant_tpu.ops.mpt_jax import PLAN_LADDER, _hash_plan_outputs
    from phant_tpu.ops.root_engine import RootEngine, note_plan_shape

    def shapes() -> int:
        return int(metrics.snapshot()["gauges"]["root.plan_shapes"])

    note_plan_shape()
    eng = RootEngine(device_floor=0)
    seen, built = [], []
    for block in range(14):
        want, db = _dirty_state(7000 + block, *_sizes(block))
        prp = db.post_root_plan()
        with forced_device():
            (out,) = eng.root_many([prp.plan])
        assert db.apply_post_root(prp, out) == want
        seen.append(shapes())
        built.append(_hash_plan_outputs._cache_size())
    assert seen[0] >= 1
    assert seen[1:] == seen[:-1], seen  # no block after the first added a shape
    assert built[1:] == built[:-1], built  # nor built a program
    assert seen[-1] <= len(PLAN_LADDER) and built[-1] <= len(PLAN_LADDER)
    counters = metrics.snapshot()["counters"]
    assert counters['root.plan_rung{rung="0"}'] >= 14
    assert counters['root.plan_rows{kind="real"}'] > 0
    assert counters['root.plan_rows{kind="pad"}'] > 0


def test_prewarm_builds_the_program_a_dispatch_then_finds(forced_device, monkeypatch):
    """What a server does at start on an accelerator, here on the ladder's
    first rung: after it, a real plan on that rung builds nothing."""
    import phant_tpu.ops.mpt_jax as mpt_jax
    from phant_tpu.ops.root_engine import RootEngine, prewarm_ladder

    monkeypatch.setattr(mpt_jax, "PLAN_LADDER", mpt_jax.PLAN_LADDER[:1])
    rungs, seconds = prewarm_ladder()
    assert rungs == 1 and seconds > 0
    assert metrics.snapshot()["gauges"]["root.prewarm_seconds"] == seconds
    built = mpt_jax._hash_plan_outputs._cache_size()
    want, db = _dirty_state(4242, 80, 30)
    prp = db.post_root_plan()
    with forced_device():
        (out,) = RootEngine(device_floor=0).root_many([prp.plan])
    assert db.apply_post_root(prp, out) == want
    assert mpt_jax._hash_plan_outputs._cache_size() == built


def test_batch_over_the_top_rung_is_hashed_on_the_host(forced_device, monkeypatch):
    """Above the ladder there is no program to build: the engine walks the
    plans on the host and says so."""
    import phant_tpu.ops.mpt_jax as mpt_jax
    from phant_tpu.ops.root_engine import RootEngine

    want, db = _dirty_state(1, 60, 20)
    prp = db.post_root_plan()
    monkeypatch.setattr(mpt_jax, "PLAN_LADDER", (mpt_jax.Rung(1, 1 << 20, 64),))
    before = metrics.snapshot()["counters"].get('root.plan_rung{rung="over"}', 0)
    eng = RootEngine(device_floor=0)
    with forced_device():
        (out,) = eng.root_many([prp.plan])
    assert eng.stats["host_batches"] == 1 and eng.stats["device_batches"] == 0
    assert db.apply_post_root(prp, out) == want
    assert metrics.snapshot()["counters"]['root.plan_rung{rung="over"}'] == before + 1


@pytest.mark.parametrize("accelerator", [False, True])
def test_resident_table_start(monkeypatch, accelerator):
    """On the CPU the table starts at 2^10 rows and doubles; where an
    accelerator holds it, it is born at its cap and never regrown, so its
    five programs are built for one row space."""
    import jax

    from phant_tpu.ops.witness_resident import ResidentTable

    monkeypatch.delenv("PHANT_RESIDENT_START_CAP", raising=False)
    with monkeypatch.context() as m:
        if accelerator:
            m.setattr(jax, "default_backend", lambda: "tpu")
        table = ResidentTable(max_cap=1 << 12)
    grown = []
    sound = table._grow_locked

    def spy(need):
        had = table._arrays is not None
        sound(need)
        if had:
            grown.append(need)

    monkeypatch.setattr(table, "_grow_locked", spy)
    with table._lock:
        table._grow_locked(10)
    assert table.stats_snapshot()["cap"] == (1 << 12 if accelerator else 1 << 10)
    with table._lock:
        if table._n_rows + 3000 > table._cap:
            table._grow_locked(3000)
    st = table.stats_snapshot()
    assert st["cap"] == 1 << 12
    assert (st["grows"], len(grown)) == ((0, 0) if accelerator else (1, 1))


def test_plan_finds_a_leaf_that_a_later_put_split():
    """An account with dirty storage takes a storage-root hole in its leaf;
    a NEW account put after it whose path ends at that leaf splits it, and
    the trie then holds another leaf object for the first. The plan looks
    its leaves up after every put (found by the random cases above: the
    hole was wired to the object the split had discarded, and the plan's
    root was not the host walk's)."""
    from phant_tpu.ops.mpt_jax import execute_plan_outputs_host

    first = b"\x01" * 20
    nibble = keccak256(first)[0] >> 4
    others = [
        bytes([i]) * 20
        for i in range(2, 60)
        if keccak256(bytes([i]) * 20)[0] >> 4 != nibble
    ][:6]
    # the new account sorts after `first` and shares its first nibble only
    new = next(
        a
        for a in (bytes([0xF0, i]) + b"\x00" * 18 for i in range(256))
        if keccak256(a)[0] >> 4 == nibble and keccak256(a)[1] != keccak256(first)[1]
    )
    accounts = {a: Account(balance=10**18, storage={1: 5, 2: 6}) for a in [first] + others}
    trie = Trie()
    for a, acct in accounts.items():
        trie.put(keccak256(a), account_leaf(acct))
    nodes: dict = {}
    for a in (first, new):
        nodes.update(dict.fromkeys(generate_proof(trie, keccak256(a))))
    st = Trie()
    for s, v in accounts[first].storage.items():
        st.put(_slot_key(s), rlp.encode(rlp.encode_uint(v)))
    for s in accounts[first].storage:
        nodes.update(dict.fromkeys(generate_proof(st, _slot_key(s))))
    root = trie.root_hash()

    def mutate(db):
        db.set_storage(first, 1, 4242)
        db.get_balance(new)
        db.accounts[new] = Account(balance=7)
        return db

    want = mutate(WitnessStateDB(root, list(nodes), [])).state_root()
    db = mutate(WitnessStateDB(root, list(nodes), []))
    prp = db.post_root_plan()
    assert prp is not None and len(prp.patches) == 1
    assert db.apply_post_root(prp, execute_plan_outputs_host(prp.plan)) == want
