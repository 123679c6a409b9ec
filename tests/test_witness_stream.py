"""Streaming witness ingestion (PR 9): the 4-stage serving pipeline's
prefetch stage + depth-tiered generational eviction.

Pins the tentpole contracts:

  * eviction-policy differential — a depth-skewed replay span through an
    over-cap engine under flat-flush vs depth-tiered eviction is verdict
    BYTE-IDENTICAL on all three cores, the tiered engine's steady-state
    hit rate is strictly higher, the shallow pinned set survives >= 2
    generation flushes, and the device-resident table's open-addressed
    index stays consistent with the host map after a pinned re-commit;
  * scheduler differential — concurrent traffic at pipeline depths 1/2
    with prefetch on/off is verdict byte-identical across all three
    cores, and a poisoned prefetch stage fails ONLY in-flight work with
    -32052 and a `prefetch`-stage-named crash record;
  * the stateless request path decodes each witness exactly once
    (`stateless.witness_nodes_decoded` counter — the satellite bugfix);
  * the mesh-mode SIGINT e2e: `python -m phant_tpu --sched-mesh 2
    --sched-mesh-dispatch megabatch` exits rc 0 within a deadline even
    with an inherited SIGINT=SIG_IGN disposition (the PR 8 e2e hang:
    CPython honors inherited SIG_IGN by never installing the
    KeyboardInterrupt handler, so a server launched as a shell
    background job ignored ^C forever).
"""

import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from phant_tpu import rlp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import Trie
from phant_tpu.mpt.proof import generate_proof
from phant_tpu.ops.witness_engine import WitnessEngine
from phant_tpu.serving.scheduler import (
    SchedulerConfig,
    SchedulerDown,
    VerificationScheduler,
)
from phant_tpu.utils.trace import metrics


@pytest.fixture(params=["ext", "ctypes", "python"])
def engine_core(request, monkeypatch):
    """Differential tests run against ALL three engine cores: the tiered
    flush re-commits pins through each core's own scan/commit protocol,
    so every one must stay byte-identical to the flat policy."""
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


# ---------------------------------------------------------------------------
# workload: a depth-skewed replay span (the PR 8 histogram shape)
# ---------------------------------------------------------------------------


def _skew_span(n_blocks=36, picks=4, trie_n=512, seed=5):
    """A span over one STATIC trie with rotating account picks: shallow
    nodes (root + top branches) repeat across every block while the
    leaf-ward paths churn — exactly the reuse skew 2408.14217 predicts
    and the PR 8 depth histogram measured. Returns (root, witnesses)."""
    rng = np.random.default_rng(seed)
    trie = Trie()
    keys = []
    for _ in range(trie_n):
        k = keccak256(rng.bytes(20))
        trie.put(k, rlp.encode([rlp.encode_uint(1), rng.bytes(8)]))
        keys.append(k)
    root = trie.root_hash()
    r = np.random.default_rng(seed + 4)
    wits = []
    for _ in range(n_blocks):
        idx = r.choice(len(keys), size=picks, replace=False)
        nodes = {}
        for i in idx:
            for n in generate_proof(trie, keys[int(i)]):
                nodes[n] = None
        wits.append((root, list(nodes.keys())))
    return root, wits


def _junk_witnesses(n, seed=0):
    """`n` single-leaf witnesses of fresh random nodes: novel filler that
    pushes an over-cap engine into a generation flush on demand."""
    rng = np.random.default_rng(1000 + seed)
    out = []
    for _ in range(n):
        node = rlp.encode([b"\x20" + rng.bytes(8), rng.bytes(16)])
        out.append((keccak256(node), [node]))
    return out


def _replay(eng, wits, chunk=3):
    """Verify the span in small chunks (one serving batch per chunk) so
    over-cap flushes fire MID-SPAN, and return the verdicts."""
    out = []
    for i in range(0, len(wits), chunk):
        out.extend(np.asarray(eng.verify_batch(wits[i : i + chunk])).tolist())
    return out


def _hit_rate_over(eng, wits, chunk=3):
    h0, m0 = eng.stats["hits"], eng.stats["hashed"]
    verdicts = _replay(eng, wits, chunk)
    dh = eng.stats["hits"] - h0
    dm = eng.stats["hashed"] - m0
    return verdicts, dh / max(1, dh + dm)


# ---------------------------------------------------------------------------
# eviction-policy differential (all three cores)
# ---------------------------------------------------------------------------


def test_tiered_vs_flat_eviction_differential(engine_core):
    """The satellite's core claim: same span, same cap, flat vs tiered —
    verdicts byte-identical, steady-state hit rate strictly higher for
    tiered, and the shallow pinned set survives >= 2 flushes."""
    root, wits = _skew_span()
    uniq = len({n for _r, ns in wits for n in ns})
    cap = max(48, uniq // 4)
    flat = WitnessEngine(max_nodes=cap, tiered_evict=False)
    tier = WitnessEngine(
        max_nodes=cap, tiered_evict=True, pin_depth=2, pin_budget=cap // 2
    )

    # cold replay: flushes fire mid-span for both policies
    vf = _replay(flat, wits)
    vt = _replay(tier, wits)
    assert vf == vt, "tiered eviction changed a verdict"
    assert all(vt), "depth-skew span must verify"
    assert flat.stats["evictions"] >= 2, flat.stats
    assert tier.stats["evictions"] >= 2, tier.stats
    # the tiered flush actually TIERED: pins were retained, and the
    # evictions metric's tier label says so
    assert tier.stats.get("evictions_deep", 0) >= 2, tier.stats
    assert tier.stats.get("pinned_retained", 0) > 0, tier.stats
    assert flat.stats.get("evictions_full", 0) >= 2, flat.stats
    snap = tier.stats_snapshot()
    assert snap["tiered_evict"] is True and snap["pinned_rows"] > 0
    assert "0" in snap["pinned_per_depth"], snap["pinned_per_depth"]

    # steady state: replay the span again — the pinned shallow tier
    # turns into hits the flat policy keeps re-hashing
    vf2, rate_flat = _hit_rate_over(flat, wits)
    vt2, rate_tier = _hit_rate_over(tier, wits)
    assert vf2 == vt2 and all(vt2)
    assert rate_tier > rate_flat, (
        f"tiered steady-state hit rate {rate_tier:.3f} not above "
        f"flat {rate_flat:.3f}"
    )

    # shallow-pinned survival, functionally: force one MORE flush with
    # novel filler (small enough batches that the tiered flush keeps
    # room for pins), then probe the root node — tiered still has it
    # interned (zero new hashes), flat just dropped it
    root_node = next(
        n for _r, ns in wits for n in ns if keccak256(n) == root
    )
    for k, eng in enumerate((flat, tier)):
        ev0 = eng.stats["evictions"]
        for attempt in range(8):
            junk = _junk_witnesses(cap // 2, seed=k * 100 + attempt)
            _replay(eng, junk, chunk=cap // 2)
            if eng.stats["evictions"] > ev0:
                break
        assert eng.stats["evictions"] > ev0, "filler did not force a flush"
    probe = [(root, [root_node])]
    m0 = tier.stats["hashed"]
    tier.verify_batch(probe)
    assert tier.stats["hashed"] == m0, "pinned root was re-hashed"
    m0 = flat.stats["hashed"]
    flat.verify_batch(probe)
    assert flat.stats["hashed"] == m0 + 1, "flat flush kept the root?"


def test_tiered_eviction_with_corruptions(engine_core):
    """Verdict identity holds through flushes with every corruption class
    in the span (the tiered re-commit must not resurrect stale rows into
    a wrong verdict)."""
    root, wits = _skew_span(n_blocks=18)
    nodes = list(wits[0][1])
    bad = [
        (b"\x00" * 32, nodes),  # wrong root
        (root, [n for n in nodes if keccak256(n) != root]),  # no root node
        (root, nodes + [rlp.encode([b"\x20\x99", b"zzz"])]),  # unlinked
        (root, []),  # empty witness
    ]
    victim = max(nodes, key=len)
    flipped = bytes([victim[0]]) + bytes([victim[1] ^ 1]) + victim[2:]
    bad.append((root, [flipped if n == victim else n for n in nodes]))
    span = wits[:9] + bad + wits[9:]
    uniq = len({n for _r, ns in span for n in ns})
    cap = max(48, uniq // 3)
    want = [bool(v) for v in WitnessEngine().verify_batch(span)]
    assert not all(want) and any(want)  # the corruptions actually fail
    flat = WitnessEngine(max_nodes=cap, tiered_evict=False)
    tier = WitnessEngine(max_nodes=cap, tiered_evict=True)
    vf = _replay(flat, span) + _replay(flat, span)
    vt = _replay(tier, span) + _replay(tier, span)
    assert vf == vt == want + want


def test_pin_budget_respects_incoming_batch():
    """A single over-cap batch degrades to the flat flush (pins must
    never crowd out live traffic): room = max_nodes - incoming_novel."""
    root, wits = _skew_span(n_blocks=12)
    uniq = len({n for _r, ns in wits for n in ns})
    eng = WitnessEngine(max_nodes=uniq - 1, tiered_evict=True)
    assert np.asarray(eng.verify_batch(wits)).all()
    # one batch carrying MORE novels than the whole cap: the flush it
    # triggers has no room for pins and must go tier="full"
    junk = _junk_witnesses(uniq + 8)
    assert np.asarray(eng.verify_batch(junk)).all()
    assert eng.stats.get("evictions_full", 0) >= 1, eng.stats


def test_stale_pins_age_out_when_the_trie_churns():
    """The pinned set must not saturate with dead nodes: when traffic
    moves wholly from trie A to trie B (state-root churn — the real
    workload), flushes whose generation never served an A root PRUNE
    A's pins, freeing the budget for B's shallow tier. Without the
    flush-time liveness prune the budget froze on the first
    generations' nodes forever."""
    root_a, wits_a = _skew_span(seed=11)
    root_b, wits_b = _skew_span(seed=77)
    uniq = len({n for _r, ns in wits_a + wits_b for n in ns})
    eng = WitnessEngine(
        max_nodes=max(48, uniq // 5), tiered_evict=True, pin_budget=uniq
    )
    assert all(_replay(eng, wits_a))
    assert eng.stats.get("pinned_retained", 0) > 0, eng.stats
    # traffic churns: only B from here on. Once BOTH liveness windows
    # (recent + previous generation) are A-root-free — 3 flushes after
    # the switch at the latest — A's pins (its root node included) must
    # have aged out
    ev0 = eng.stats["evictions"]
    for _ in range(10):
        assert all(_replay(eng, wits_b))
        if eng.stats["evictions"] >= ev0 + 3:
            break
    assert eng.stats["evictions"] >= ev0 + 3, eng.stats
    pinned_digests = {
        dg for _nb, (_d, dg) in eng._pin._pinned.items()
    }
    assert root_a not in pinned_digests, "dead trie's root still pinned"
    assert root_b in pinned_digests, "live trie's root not pinned"


def test_tiered_flush_keeps_resident_index_consistent(monkeypatch):
    """After a depth-tiered flush, the device-resident table re-commits
    the same pinned set the host retained: row ids agree between the
    authoritative host map and the device's open-addressed index, and
    verdicts stay correct (XLA-CPU proxy route, PHANT_RESIDENT=1)."""
    from test_witness_resident import _node_fps

    from phant_tpu.backend import set_crypto_backend

    monkeypatch.setenv("PHANT_ALLOW_JAX_CPU", "1")
    monkeypatch.setenv("PHANT_RESIDENT", "1")
    set_crypto_backend("tpu")
    try:
        root, wits = _skew_span(n_blocks=24, trie_n=256)
        uniq = len({n for _r, ns in wits for n in ns})
        cap = max(48, uniq // 3)
        eng = WitnessEngine(
            max_nodes=cap, resident=True, resident_cap=4096,
            tiered_evict=True, pin_budget=cap // 2,
        )
        assert all(_replay(eng, wits))
        assert eng.stats.get("evictions_deep", 0) >= 1, eng.stats
        table = eng.resident_table()
        assert table is not None
        assert table.stats_snapshot().get("retained_rows", 0) > 0
        # every node the host currently knows must resolve to the SAME
        # row through the device index; absent keys must miss
        live = [
            n for _r, ns in wits for n in ns
            if (table.host_rows_of([n]) >= 0).all()
        ]
        assert live, "no live rows after the tiered flush"
        rows_host = table.host_rows_of(live)
        rows_dev = table.device_lookup(_node_fps(live))
        assert (rows_dev == rows_host).all(), (
            "device index disagrees with the host map after a pinned "
            "re-commit"
        )
        absent = np.frombuffer(keccak256(b"never-interned")[:8], "<u4")
        assert table.device_lookup(absent.reshape(1, 2))[0] == -1
        # and the engine still VERIFIES correctly through the rebuilt
        # generation (a broken index would fail valid blocks)
        assert np.asarray(eng.verify_batch(wits[:6])).all()
        assert not eng.verify(b"\x00" * 32, list(wits[0][1]))
    finally:
        set_crypto_backend("cpu")


# ---------------------------------------------------------------------------
# scheduler differential: depths 1/2 x prefetch on/off, all cores
# ---------------------------------------------------------------------------


def test_scheduler_prefetch_differential(engine_core):
    """The acceptance criterion: concurrent traffic at pipeline depths
    1/2 with prefetch on/off is verdict byte-identical across all three
    cores — and the 4th stage actually RAN when enabled."""
    root, wits = _skew_span(n_blocks=24)
    direct = [bool(v) for v in WitnessEngine().verify_batch(wits)]
    for depth in (1, 2):
        for prefetch in (False, True):
            eng = WitnessEngine()
            with VerificationScheduler(
                engine=eng,
                config=SchedulerConfig(
                    max_batch=4, max_wait_ms=5.0, queue_depth=4096,
                    pipeline_depth=depth, prefetch=prefetch,
                ),
            ) as s:
                got = s.verify_many(wits)
                st = s.stats_snapshot()
                state = s.state()
            assert [bool(v) for v in got] == direct, (
                engine_core, depth, prefetch,
            )
            if depth >= 2 and prefetch:
                assert st["prefetched_batches"] >= 1, st
                assert state["prefetch"] is True
            else:
                # depth 1 has no pipeline to hide the decode under;
                # --sched-prefetch 0 pins the 3-stage behavior
                assert st["prefetched_batches"] == 0, (depth, prefetch, st)
                assert state["prefetch"] is False


def test_prefetch_plan_hit_metrics():
    """Consumed plans land in the witness_engine.prefetch_plan_{hits,
    stale} counters, and the prefetch phase timer records the decode."""
    metrics.reset()
    root, wits = _skew_span(n_blocks=16)
    with VerificationScheduler(
        engine=WitnessEngine(),
        config=SchedulerConfig(
            max_batch=4, max_wait_ms=5.0, queue_depth=4096,
            pipeline_depth=2, prefetch=True,
        ),
    ) as s:
        assert all(s.verify_many(wits))
    snap = metrics.snapshot()
    hits = snap["counters"].get("witness_engine.prefetch_plan_hits", 0)
    stale = snap["counters"].get("witness_engine.prefetch_plan_stale", 0)
    assert hits + stale >= 1, snap["counters"]
    assert snap["timers"].get("witness_engine.prefetch", {}).get("count", 0) >= 1
    assert snap["counters"].get("sched.prefetch_batches", 0) >= 1


def test_advisory_set_is_lazy_without_prefetch_consumer(monkeypatch):
    """The pre-scan's advisory byte set duplicates up to max_nodes of
    node bytes — an engine with no prefetch consumer (depth-1 scheduler,
    --sched-prefetch 0, offline verify_batch) must never populate it.
    First prefetch_batch activates it; from then on every core's commits
    maintain it, and the python core additionally seeds it from its
    committed table at activation (the C cores hold bytes natively, so
    they warm from commits only)."""
    root, wits = _skew_span(n_blocks=8)
    eng = WitnessEngine()
    assert all(np.asarray(eng.verify_batch(wits)))
    assert not eng._seen_advisory, (
        f"advisory set held {len(eng._seen_advisory)} nodes with no "
        "prefetch consumer"
    )
    plan = eng.prefetch_batch(wits)
    plan.release()
    # post-activation commits maintain the set on the default core
    junk = _junk_witnesses(6, seed=77)
    assert all(np.asarray(eng.verify_batch(junk)))
    assert eng._seen_advisory, "post-activation commit did not warm the set"
    plan2 = eng.prefetch_batch(junk)
    plan2.release()
    assert not plan2.novel, "warmed pre-scan re-reported committed nodes"

    # python core: activation itself seeds from the committed table, so
    # an already-interned span pre-scans as fully known with no warm-up
    monkeypatch.setenv("PHANT_ENGINE_NATIVE", "0")
    monkeypatch.setenv("PHANT_ENGINE_EXT", "0")
    peng = WitnessEngine()
    assert peng._core is None and peng._ext_core is None
    assert all(np.asarray(peng.verify_batch(wits)))
    assert not peng._seen_advisory
    pplan = peng.prefetch_batch(wits)
    pplan.release()
    assert peng._seen_advisory, "activation did not seed from the table"
    assert not pplan.novel, "seeded pre-scan re-reported committed nodes"


def test_prefetch_through_mesh_lanes():
    """Mesh lanes run the prefetch stage per lane (the decode hides
    under the lane's OWN previous dispatch/resolve): verdicts identical,
    and lane batch records carry prefetch_ms."""
    from phant_tpu.obs.flight import flight

    root, wits = _skew_span(n_blocks=24)
    direct = [bool(v) for v in WitnessEngine().verify_batch(wits)]
    with VerificationScheduler(
        config=SchedulerConfig(
            max_batch=4, max_wait_ms=5.0, queue_depth=4096,
            pipeline_depth=2, prefetch=True, mesh_devices=2,
        ),
    ) as s:
        got = s.verify_many(wits)
        snap = s.stats_snapshot()
    assert [bool(v) for v in got] == direct
    recs = [
        r for r in flight.records()
        if r.get("kind") == "sched.batch_done" and "prefetch_ms" in r
    ]
    assert recs, "no mesh batch record carried prefetch_ms"
    # the stats RPC answers "did the 4th stage run" in mesh mode too: the
    # per-lane count folds into the scheduler's top-level stat (the
    # scheduler's own worker is off when a pool routes)
    assert snap["prefetched_batches"] >= 1, snap
    assert snap["mesh"]["prefetched_batches"] >= 1, snap["mesh"]


class _PoisonedPrefetchEngine:
    """Healthy until ARMED, then the prefetch pre-scan dies — the
    4th-stage crash drill. Arming after the healthy futures complete
    keeps the test immune to batch assembly."""

    def __init__(self):
        self.eng = WitnessEngine()
        self.armed = False

    def prefetch_batch(self, witnesses):
        if self.armed:
            raise RuntimeError("prefetch stage poisoned")
        return self.eng.prefetch_batch(witnesses)

    def begin_batch(self, witnesses, prefetch=None):
        return self.eng.begin_batch(witnesses, prefetch=prefetch)

    def resolve_batch(self, h):
        return self.eng.resolve_batch(h)

    def abandon_batch(self, h):
        self.eng.abandon_batch(h)

    def verify_batch(self, witnesses):
        return self.eng.verify_batch(witnesses)

    def stats_snapshot(self):
        return self.eng.stats_snapshot()


def test_poisoned_prefetch_fails_only_inflight():
    """The acceptance crash contract: a prefetch-stage crash fails ONLY
    in-flight work with -32052, the crash flight record names the
    `prefetch` stage, already-resolved verdicts survive, and no engine
    lease leaks."""
    from phant_tpu.obs.flight import flight

    root, wits = _skew_span(n_blocks=8)
    eng = _PoisonedPrefetchEngine()
    s = VerificationScheduler(
        engine=eng,
        config=SchedulerConfig(
            max_batch=4, max_wait_ms=5.0, pipeline_depth=2, prefetch=True,
        ),
    )
    try:
        first = [s.submit_witness(*w) for w in wits[:4]]
        assert all(f.result(timeout=30) for f in first)
        eng.armed = True
        second = [s.submit_witness(*w) for w in wits[4:]]
        for f in second:
            with pytest.raises(SchedulerDown) as ei:
                f.result(timeout=30)
            assert ei.value.code == -32052
        assert all(f.result(timeout=1) for f in first)  # verdicts survive
        assert s.state()["executor_alive"] is False
        crash = [
            r for r in flight.records()
            if r.get("kind") == "sched.executor_crash"
        ][-1]
        assert crash.get("stage") == "prefetch", crash
        assert "prefetch stage poisoned" in crash.get("error", "")
    finally:
        s.shutdown()
    assert eng.eng._inflight == 0
    assert eng.eng.verify_batch(wits[:2]).all()  # engine still serves


class _PoisonedBeginEngine:
    """prefetch_batch produces a REAL plan, then begin_batch dies —
    the plan's staging leases must still make it back to the pool."""

    def __init__(self):
        self.eng = WitnessEngine()

    def prefetch_batch(self, witnesses):
        return self.eng.prefetch_batch(witnesses)

    def begin_batch(self, witnesses, prefetch=None):
        raise RuntimeError("begin poisoned")

    def resolve_batch(self, h):
        return self.eng.resolve_batch(h)

    def abandon_batch(self, h):
        self.eng.abandon_batch(h)

    def verify_batch(self, witnesses):
        return self.eng.verify_batch(witnesses)

    def stats_snapshot(self):
        return self.eng.stats_snapshot()


class _BlockingPrefetchEngine:
    """prefetch_batch parks on an event so a test can run _die while the
    worker is mid-pre-scan (the orphaned-plan race)."""

    def __init__(self):
        self.eng = WitnessEngine()
        self.entered = threading.Event()
        self.go = threading.Event()

    def prefetch_batch(self, witnesses):
        self.entered.set()
        assert self.go.wait(10), "test never released the prefetch gate"
        return self.eng.prefetch_batch(witnesses)

    def begin_batch(self, witnesses, prefetch=None):
        return self.eng.begin_batch(witnesses, prefetch=prefetch)

    def resolve_batch(self, h):
        return self.eng.resolve_batch(h)

    def abandon_batch(self, h):
        self.eng.abandon_batch(h)

    def verify_batch(self, witnesses):
        return self.eng.verify_batch(witnesses)

    def stats_snapshot(self):
        return self.eng.stats_snapshot()


def test_crash_paths_release_prefetch_plans(monkeypatch):
    """_die's lease-release contract holds on BOTH plan-leak windows: a
    batch whose plan the executor already picked up when pack crashed
    (popped from _prefetch_pending, invisible to _die), and a plan that
    finishes computing only AFTER _die orphaned its item. Either leak
    would silently drain the shared engine's staging pool."""
    from phant_tpu.ops import witness_engine as we

    released = []
    orig_release = we.PrefetchPlan.release

    def spy(self):
        released.append(self)
        orig_release(self)

    monkeypatch.setattr(we.PrefetchPlan, "release", spy)
    root, wits = _skew_span(n_blocks=4)

    # window 1: begin_batch raises with a consumed-by-nobody plan in hand
    s = VerificationScheduler(
        engine=_PoisonedBeginEngine(),
        config=SchedulerConfig(
            max_batch=4, max_wait_ms=5.0, pipeline_depth=2, prefetch=True,
        ),
    )
    try:
        futs = [s.submit_witness(*w) for w in wits]
        for f in futs:
            with pytest.raises(SchedulerDown):
                f.result(timeout=30)
        deadline = time.monotonic() + 5
        while not released and time.monotonic() < deadline:
            time.sleep(0.01)
        assert released, "pack-crash path never released the plan"
    finally:
        s.shutdown()

    # window 2: _die runs while the worker is INSIDE prefetch_batch —
    # the item is orphaned with plan=None, so the worker itself must
    # release the plan it went on to finish
    released.clear()
    eng = _BlockingPrefetchEngine()
    s = VerificationScheduler(
        engine=eng,
        config=SchedulerConfig(
            max_batch=4, max_wait_ms=5.0, pipeline_depth=2, prefetch=True,
        ),
    )
    try:
        futs = [s.submit_witness(*w) for w in wits]
        assert eng.entered.wait(10), "prefetch worker never picked up"
        s._die(RuntimeError("induced mid-prefetch death"), [])
        eng.go.set()
        for f in futs:
            with pytest.raises(SchedulerDown):
                f.result(timeout=30)
        deadline = time.monotonic() + 5
        while not released and time.monotonic() < deadline:
            time.sleep(0.01)
        assert released, "orphaned plan was never released by the worker"
    finally:
        s.shutdown()


def test_cli_prefetch_flag():
    from phant_tpu.__main__ import build_parser

    args = build_parser().parse_args([])
    assert args.sched_prefetch is None  # env/on default applies
    args = build_parser().parse_args(["--sched-prefetch", "0"])
    assert args.sched_prefetch == 0
    assert SchedulerConfig(prefetch=False).prefetch is False


# ---------------------------------------------------------------------------
# stateless request path: each witness decodes exactly once
# ---------------------------------------------------------------------------


def test_stateless_decodes_witness_exactly_once():
    """The satellite bugfix pinned by its counter: one execute_stateless
    call builds the digest map ONCE — `stateless.witness_nodes_decoded`
    grows by exactly len(nodes), not 2x (the old WitnessStateDB re-parse
    of what the request path already decoded)."""
    from test_stateless import (
        CHAIN_ID,
        _build_block,
        _pre_accounts,
        _transfer_tx,
        _witness_for,
    )

    from phant_tpu.stateless import execute_stateless
    from test_stateless import COINBASE, RECIPIENT

    sender, accounts = _pre_accounts()
    parent, block, post_root, _full = _build_block(accounts, [_transfer_tx()])
    pre_root, nodes = _witness_for(accounts, [sender, RECIPIENT, COINBASE])
    snap0 = metrics.snapshot()["counters"].get(
        "stateless.witness_nodes_decoded", 0
    )
    _result, computed_root = execute_stateless(
        CHAIN_ID, parent, block, pre_root, nodes, []
    )
    assert computed_root == post_root
    snap1 = metrics.snapshot()["counters"].get(
        "stateless.witness_nodes_decoded", 0
    )
    assert snap1 - snap0 == len(nodes), (
        f"witness decoded {((snap1 - snap0) / max(1, len(nodes))):.1f}x "
        f"(want exactly 1x: {len(nodes)} nodes)"
    )


# ---------------------------------------------------------------------------
# mesh-mode SIGINT e2e (the PR 8 shutdown-hang satellite)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sigint_mesh_e2e_exits_clean():
    """`python -m phant_tpu --sched-mesh 2 --sched-mesh-dispatch
    megabatch` under the EXACT hang conditions (SIGINT inherited as
    SIG_IGN, the shell-background-job disposition): the server must
    drain and exit rc 0 within the deadline after one SIGINT."""
    port = 18651 + (os.getpid() % 500)
    env = dict(os.environ)
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.abspath(os.path.join("build", "jax_cache_pytest")),
    )
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "phant_tpu",
            "-p", str(port),
            "--sched-mesh", "2",
            "--sched-mesh-dispatch", "megabatch",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        # reproduce the bug's trigger: CPython honors an inherited
        # SIG_IGN by skipping its KeyboardInterrupt handler install
        preexec_fn=lambda: signal.signal(signal.SIGINT, signal.SIG_IGN),
    )
    try:
        deadline = time.monotonic() + 90
        up = False
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                break
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=1
                ) as r:
                    up = r.status == 200
                    break
            except Exception:
                time.sleep(0.25)
        assert up, (
            f"server never came up (rc={proc.poll()}): "
            f"{proc.stdout.read().decode(errors='replace')[-2000:]}"
        )
        proc.send_signal(signal.SIGINT)
        rc = proc.wait(timeout=45)
        assert rc == 0, (
            f"SIGINT shutdown hang/regression: rc={rc}: "
            f"{proc.stdout.read().decode(errors='replace')[-2000:]}"
        )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
