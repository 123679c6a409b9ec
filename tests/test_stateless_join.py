"""The verdict is joined where execution first needs it (PR 29): with a
scheduler installed `execute_stateless` admits the witness, waits for its
batch's launch, decodes while the verdict is computed and joins before
`chain.run_block`; without one the inline order stays as it was."""

from __future__ import annotations

import threading
import time

import pytest

from phant_tpu import stateless
from phant_tpu.blockchain.chain import Blockchain
from phant_tpu.obs import critpath
from phant_tpu.ops.witness_engine import WitnessEngine
from phant_tpu.serving import (
    DeadlineExpired,
    SchedulerConfig,
    SchedulerDown,
    VerificationScheduler,
    install,
    uninstall,
)
from phant_tpu.stateless import StatelessError, execute_stateless
from phant_tpu.utils import trace
from phant_tpu.utils.trace import add_span_sink, metrics, remove_span_sink

from test_stateless import (
    CHAIN_ID,
    COINBASE,
    RECIPIENT,
    _build_block,
    _pre_accounts,
    _transfer_tx,
    _witness_for,
)


def _request():
    sender, accounts = _pre_accounts()
    parent, block, post_root, _full = _build_block(accounts, [_transfer_tx()])
    pre_root, nodes = _witness_for(accounts, [sender, RECIPIENT, COINBASE])
    return parent, block, post_root, pre_root, nodes


class _GatedEngine:
    """A real WitnessEngine whose resolve_batch (and the depth-1
    verify_batch) stands on `gate`: the verdict cannot arrive before the
    test opens it."""

    def __init__(self, error: BaseException = None):
        self.eng = WitnessEngine()
        self.gate = threading.Event()
        self.begun = threading.Event()
        self.error = error

    def verify_batch(self, w):
        self.begun.set()
        self.gate.wait(30)
        if self.error is not None:
            raise self.error
        return self.eng.verify_batch(w)

    def begin_batch(self, w):
        handle = self.eng.begin_batch(w)
        self.begun.set()
        return handle

    def resolve_batch(self, h):
        self.gate.wait(30)
        if self.error is not None:
            self.eng.abandon_batch(h)
            raise self.error
        return self.eng.resolve_batch(h)

    def abandon_batch(self, h):
        self.eng.abandon_batch(h)


@pytest.fixture
def scheduled():
    """install(scheduler over engine) for the test's threads; every
    scheduler made is shut down afterwards."""
    made = []

    def make(engine=None, **cfg):
        cfg.setdefault("max_batch", 4)
        cfg.setdefault("max_wait_ms", 1.0)
        s = VerificationScheduler(
            engine=engine or WitnessEngine(), config=SchedulerConfig(**cfg)
        )
        made.append(s)
        install(s)
        return s

    yield make
    for s in made:
        uninstall(s)
        s.shutdown()


def _in_thread(fn):
    box = {}

    def run():
        try:
            box["result"] = fn()
        except BaseException as e:  # handed to the test's thread
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box


def _counter(name: str, **labels) -> int:
    return metrics.snapshot()["counters"].get(trace._labels_key(name, labels), 0)


def _hist_count(name: str) -> int:
    return metrics.snapshot()["histograms"].get(name, {"count": 0})["count"]


# ---------------------------------------------------------------------------
# (a) run_block never starts before the verdict
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2])
def test_run_block_is_not_entered_before_the_verdict_is_done(depth, scheduled, monkeypatch):
    parent, block, post_root, pre_root, nodes = _request()
    engine = _GatedEngine()
    scheduled(engine, pipeline_depth=depth)
    order = []
    decoded = threading.Event()
    sound_db, sound_run = stateless.witness_node_db, Blockchain.run_block

    def node_db(ns):
        out = sound_db(ns)
        order.append("decode")
        decoded.set()
        return out

    def run_block(self, *a, **kw):
        order.append("run_block")
        return sound_run(self, *a, **kw)

    monkeypatch.setattr(stateless, "witness_node_db", node_db)
    monkeypatch.setattr(Blockchain, "run_block", run_block)
    t, box = _in_thread(
        lambda: execute_stateless(CHAIN_ID, parent, block, pre_root, nodes, [])
    )
    assert engine.begun.wait(30)
    if depth == 2:
        # launched, verdict withheld: the handler decodes under the wait
        assert decoded.wait(30)
    time.sleep(0.1)
    assert "run_block" not in order and t.is_alive()
    order.append("verdict")
    engine.gate.set()
    t.join(30)
    assert not t.is_alive() and "error" not in box, box
    assert box["result"][1] == post_root
    want = ["decode", "verdict", "run_block"] if depth == 2 else ["verdict", "decode", "run_block"]
    assert order == want


def test_a_false_verdict_keeps_run_block_out(scheduled, monkeypatch):
    parent, block, _post, pre_root, nodes = _request()
    scheduled(pipeline_depth=2)
    entered = []
    monkeypatch.setattr(Blockchain, "run_block", lambda self, *a, **kw: entered.append(1))
    victim = max(range(len(nodes)), key=lambda i: len(nodes[i]))
    bad = [n for i, n in enumerate(nodes) if i != victim]
    with pytest.raises(StatelessError, match="witness rejected"):
        execute_stateless(CHAIN_ID, parent, block, pre_root, bad, [])
    assert not entered


# ---------------------------------------------------------------------------
# (b) a failure of the early decode never speaks before the verdict
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2])
def test_altered_witness_whose_decode_raises_still_answers_witness_rejected(depth, scheduled):
    parent, block, _post, pre_root, nodes = _request()
    scheduled(pipeline_depth=depth)
    # one byte of the ROOT node: its digest moves, so the decode finds no
    # root node (StatelessError "witness is missing the root node") AND
    # the linked check fails; the caller must hear the verdict
    from phant_tpu.crypto.keccak import keccak256

    (root_at,) = [i for i, n in enumerate(nodes) if keccak256(n) == pre_root]
    bad = list(nodes)
    bad[root_at] = bad[root_at][:-1] + bytes([bad[root_at][-1] ^ 1])
    with pytest.raises(StatelessError, match="missing the root node"):
        stateless.WitnessStateDB(pre_root, bad, [])
    before = _counter("stateless.errors", kind="StatelessError")
    with pytest.raises(StatelessError, match="witness rejected: not a subtree"):
        execute_stateless(CHAIN_ID, parent, block, pre_root, bad, [])
    assert _counter("stateless.errors", kind="StatelessError") == before + 1


@pytest.mark.parametrize("depth", [1, 2])
def test_decode_error_under_a_true_verdict_is_raised_as_itself(depth, scheduled):
    parent, block, _post, pre_root, nodes = _request()
    s = scheduled(pipeline_depth=depth)

    def fork_factory(_state):
        raise KeyError("fork boom")

    with pytest.raises(KeyError, match="fork boom"):
        execute_stateless(
            CHAIN_ID, parent, block, pre_root, nodes, [], fork_factory=fork_factory
        )
    # the verdict was joined (and True) before the decode's error spoke
    assert s.stats_snapshot()["batches"] == 1
    assert s.inflight_state() is None


# ---------------------------------------------------------------------------
# (c) a SchedulerError at the join propagates; nothing stays in flight
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2])
def test_scheduler_death_at_the_join_propagates_and_leaves_nothing_in_flight(depth, scheduled):
    parent, block, _post, pre_root, nodes = _request()
    engine = _GatedEngine(error=RuntimeError("readback died"))
    s = scheduled(engine, pipeline_depth=depth)
    t, box = _in_thread(
        lambda: execute_stateless(CHAIN_ID, parent, block, pre_root, nodes, [])
    )
    assert engine.begun.wait(30)
    engine.gate.set()
    t.join(30)
    assert not t.is_alive()
    assert isinstance(box.get("error"), SchedulerDown), box
    assert s.inflight_state() is None
    assert engine.eng.stats_snapshot().get("inflight", 0) == 0


def test_a_job_shed_before_any_launch_releases_the_waiting_handler(scheduled):
    """Expiry while queued completes the future with no launch: the
    handler waits for "launched or done", so it hears DeadlineExpired
    instead of waiting for a launch that will not come."""
    parent, block, _post, pre_root, nodes = _request()
    s = scheduled(pipeline_depth=2, deadline_ms=40.0)
    gate = threading.Event()
    s.submit_serial(gate.wait)  # hold the executor past the deadline
    time.sleep(0.05)
    t, box = _in_thread(
        lambda: execute_stateless(CHAIN_ID, parent, block, pre_root, nodes, [])
    )
    time.sleep(0.15)
    gate.set()
    t.join(30)
    assert not t.is_alive()
    assert isinstance(box.get("error"), DeadlineExpired), box
    assert s.inflight_state() is None


def test_launch_signal_is_set_at_launch_and_by_every_completion(scheduled):
    parent, block, _post, pre_root, nodes = _request()
    engine = _GatedEngine()
    s = scheduled(engine, pipeline_depth=2)
    pending = s.witness_async(pre_root, nodes)
    pending.wait_launched()  # returns with the verdict still withheld
    assert pending.done_ns is None
    engine.gate.set()
    assert pending.join()[0] is True
    assert pending.done_ns is not None
    # a dead scheduler releases whoever waits on a job it fails
    dead = _GatedEngine(error=RuntimeError("boom"))
    s2 = scheduled(dead, pipeline_depth=1)
    p2 = s2.witness_async(pre_root, nodes)
    dead.gate.set()
    p2.wait_launched()
    with pytest.raises(SchedulerDown):
        p2.join()


# ---------------------------------------------------------------------------
# (e) the mechanism's counters; (f) without a scheduler nothing moved
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("state", ["waited", "ready"])
def test_verdict_joins_and_decode_hidden_seconds_move(state, scheduled, monkeypatch):
    parent, block, post_root, pre_root, nodes = _request()
    engine = _GatedEngine()
    scheduled(engine, pipeline_depth=2)
    decoded = threading.Event()
    sound_chain_init = Blockchain.__init__

    def chain_init(self, *a, **kw):  # the decode phase's last step
        sound_chain_init(self, *a, **kw)
        decoded.set()
        if state == "ready":
            engine.gate.set()
            time.sleep(0.05)  # the verdict arrives inside the decode

    monkeypatch.setattr(Blockchain, "__init__", chain_init)
    joins = _counter("stateless.verdict_joins", state=state)
    other = _counter("stateless.verdict_joins", state="ready" if state == "waited" else "waited")
    hidden = metrics.snapshot()["histograms"].get(
        "stateless.decode_hidden_seconds", {"count": 0, "sum": 0.0}
    )
    t, box = _in_thread(
        lambda: execute_stateless(CHAIN_ID, parent, block, pre_root, nodes, [])
    )
    assert decoded.wait(30)
    time.sleep(0.05)
    engine.gate.set()
    t.join(30)
    assert box.get("result", (None, None))[1] == post_root, box
    assert _counter("stateless.verdict_joins", state=state) == joins + 1
    assert _counter(
        "stateless.verdict_joins", state="ready" if state == "waited" else "waited"
    ) == other
    after = metrics.snapshot()["histograms"]["stateless.decode_hidden_seconds"]
    assert after["count"] == hidden["count"] + 1
    assert after["sum"] > hidden["sum"]  # some of the decode ran before the verdict


def test_without_a_scheduler_phase_order_and_counters_are_as_before():
    parent, block, post_root, pre_root, nodes = _request()
    records = []
    add_span_sink(records.append)
    joins = {st: _counter("stateless.verdict_joins", state=st) for st in ("ready", "waited")}
    hidden = _hist_count("stateless.decode_hidden_seconds")
    decoded = _counter("stateless.witness_nodes_decoded")
    verified = _counter("stateless.blocks_verified")
    try:
        _result, root = execute_stateless(CHAIN_ID, parent, block, pre_root, nodes, [])
    finally:
        remove_span_sink(records.append)
    assert root == post_root
    (rec,) = [r for r in records if r["span"] == "verify_block"]
    assert [iv[0] for iv in rec["intervals"] if iv[0].startswith("stateless.")] == [
        "stateless.witness_verify",
        "stateless.witness_decode",
        "stateless.execute",
        "stateless.post_root",
    ]
    assert "stages" not in rec and "batch_id" not in rec
    assert {st: _counter("stateless.verdict_joins", state=st) for st in joins} == joins
    assert _hist_count("stateless.decode_hidden_seconds") == hidden
    assert _counter("stateless.witness_nodes_decoded") == decoded + len(nodes)
    assert _counter("stateless.blocks_verified") == verified + 1
    # and the whole wait is `dispatch`, as before: no batch record to cut it
    breakdown, _un, _wall = critpath.attribute(rec)
    assert breakdown["dispatch"] == pytest.approx(
        rec["phases"]["stateless.witness_verify"]["total_ms"], abs=1e-3
    )
    assert not {"queue_wait", "prefetch", "pack", "resolve"} & set(breakdown)


def test_with_a_scheduler_the_decode_lies_between_the_two_waits(scheduled):
    parent, block, post_root, pre_root, nodes = _request()
    scheduled(pipeline_depth=2)
    records = []
    add_span_sink(records.append)
    try:
        _result, root = execute_stateless(CHAIN_ID, parent, block, pre_root, nodes, [])
    finally:
        remove_span_sink(records.append)
    assert root == post_root
    (rec,) = [r for r in records if r["span"] == "verify_block"]
    assert [iv[0] for iv in rec["intervals"] if iv[0].startswith("stateless.")] == [
        "stateless.witness_verify",
        "stateless.witness_decode",
        "stateless.witness_verify",
        "stateless.execute",
        "stateless.post_root",
    ]
    # the batch record is folded into the span at the join
    assert rec["batch_id"] >= 1 and set(rec["stages"]) == {"prefetch", "pack", "resolve"}
    breakdown, unattributed, wall = critpath.attribute(rec)
    waits = sum(b - a for n, a, b in rec["intervals"] if n == "stateless.witness_verify")
    tiled = sum(breakdown.get(p, 0.0) for p in ("queue_wait", "prefetch", "pack", "dispatch", "resolve"))
    assert tiled == pytest.approx(waits / 1e6, abs=1e-6)
    assert sum(breakdown.values()) + unattributed == pytest.approx(wall, abs=1e-2)


def test_many_handlers_each_join_their_own_verdict_under_a_short_switch_interval(scheduled):
    """More handler threads than cores through one scheduler, the
    interpreter's switch interval shortened: every request decodes under
    the wait and joins ITS verdict (a good and a broken witness mixed),
    no waiter is left behind, and the joins are counted once each."""
    import sys

    parent, block, post_root, pre_root, nodes = _request()
    victim = max(range(len(nodes)), key=lambda i: len(nodes[i]))
    bad = [n for i, n in enumerate(nodes) if i != victim]
    s = scheduled(pipeline_depth=2, max_batch=8)
    joins = sum(_counter("stateless.verdict_joins", state=st) for st in ("ready", "waited"))
    n_threads, per_thread = 24, 6
    outcomes = [[] for _ in range(n_threads)]

    def work(k):
        for i in range(per_thread):
            good = (k + i) % 3 != 0
            try:
                _r, root = execute_stateless(
                    CHAIN_ID, parent, block, pre_root, nodes if good else bad, []
                )
                outcomes[k].append((good, root == post_root))
            except StatelessError as e:
                outcomes[k].append((good, "witness rejected" in str(e) and None))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,), daemon=True) for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    flat = [o for per in outcomes for o in per]
    assert len(flat) == n_threads * per_thread
    # a good witness came back VALID with the right root, a broken one was
    # rejected by its verdict: nobody read a neighbour's
    assert all(got is True for good, got in flat if good)
    assert all(got is None for good, got in flat if not good)
    after = sum(_counter("stateless.verdict_joins", state=st) for st in ("ready", "waited"))
    assert after == joins + n_threads * per_thread
    assert s.inflight_state() is None
