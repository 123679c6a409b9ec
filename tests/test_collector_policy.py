"""The collector's policy while a server is up (PR 27, serving/collector.py):
survivors of a full collection are tenured, a deep collection runs when
nobody waits, and the last server's shutdown hands the heap back. CPU only,
no jax program is built."""

from __future__ import annotations

import gc
import time
import urllib.request
import weakref

import pytest

from phant_tpu.engine_api.server import EngineAPIServer, MetricsServer
from phant_tpu.serving import collector
from phant_tpu.utils import trace
from phant_tpu.utils.trace import metrics, span

from test_engine_api import _fresh_chain, _serve


def _counter(name: str) -> int:
    return metrics.snapshot()["counters"].get(name, 0)


def _hist_count(generation: str) -> int:
    key = trace._labels_key("runtime.gc_pause_seconds", {"generation": generation})
    return metrics.snapshot()["histograms"].get(key, {"count": 0})["count"]


class _Node:
    """A container the collector tracks, to build cycles from."""

    def __init__(self):
        self.other = None


def _tenured_cycle():
    """A reference cycle that was reachable when a full collection tenured
    it and is garbage now: only a deep collection can free it."""
    a, b = _Node(), _Node()
    a.other, b.other = b, a
    gc.collect()
    return weakref.ref(a)


@pytest.fixture
def policy():
    """An installed policy, and the process as it was found afterwards."""
    thresholds = gc.get_threshold()
    collector.install()
    try:
        assert gc.get_threshold() == collector.YOUNG_THRESHOLDS
        yield trace.gc_policy
    finally:
        collector.uninstall()
        assert trace.gc_policy is None
        assert gc.get_freeze_count() == 0
        assert gc.get_threshold() == thresholds
        assert trace._on_gc not in gc.callbacks


@pytest.fixture
def due(monkeypatch):
    """Nobody has waited long enough, and the interval has passed."""
    monkeypatch.setattr(collector, "IDLE_S", 0.0)
    monkeypatch.setattr(collector, "DEEP_INTERVAL_S", 0.0)


def test_survivors_of_a_full_collection_are_tenured_and_counted(policy):
    long_lived = [[i] for i in range(50_000)]
    tenures = _counter("runtime.gc_tenures")
    gc.collect()
    assert gc.get_freeze_count() >= len(long_lived)
    assert _counter("runtime.gc_tenures") == tenures + 1
    # and the gauge follows the tenure (near, not equal: tenured objects
    # still die by reference count)
    gauge = metrics.snapshot()["gauges"]["runtime.gc_tenured_objects"]
    assert abs(gauge - gc.get_freeze_count()) < 1_000
    # under load it is counted at most once per interval; when idle, at once
    more = [[i] for i in range(1_000)]
    gc.collect()
    assert metrics.snapshot()["gauges"]["runtime.gc_tenured_objects"] == gauge
    policy.flush(idle=True)
    assert metrics.snapshot()["gauges"]["runtime.gc_tenured_objects"] >= gauge + len(more)
    # the young generations tenure nothing
    frozen = gc.get_freeze_count()
    gc.collect(0)
    gc.collect(1)
    assert gc.get_freeze_count() == frozen
    assert _counter("runtime.gc_tenures") == tenures + 2


def test_second_full_collection_walks_only_what_came_since(policy):
    long_lived = [[i] for i in range(300_000)]
    t0 = time.perf_counter()
    gc.collect()
    first = time.perf_counter() - t0
    # what a collection can still reach: nothing of the long-lived list
    assert len(gc.get_objects()) < len(long_lived) // 10
    t0 = time.perf_counter()
    gc.collect()
    second = time.perf_counter() - t0
    assert second < first / 4, (first, second)
    assert len(long_lived) == 300_000


def test_without_a_policy_nothing_is_tenured():
    frozen = gc.get_freeze_count()
    trace.watch_gc()
    try:
        gc.collect()
    finally:
        trace.unwatch_gc()
    # the interpreter itself moves its immortal objects there, no more
    assert gc.get_freeze_count() < frozen + 10_000
    gc.unfreeze()


def test_two_servers_share_one_policy_and_the_last_shutdown_undoes_it():
    thresholds = gc.get_threshold()
    gc.unfreeze()
    first = EngineAPIServer(_fresh_chain(), host="127.0.0.1", port=0)
    _serve(first)
    the_policy = trace.gc_policy
    second = EngineAPIServer(_fresh_chain(), host="127.0.0.1", port=0)
    _serve(second)
    try:
        assert the_policy is not None and trace.gc_policy is the_policy
        assert gc.callbacks.count(trace._on_gc) == 1
        gc.collect()
        assert gc.get_freeze_count() > 0
    finally:
        first.shutdown()
    try:
        # one server is still up: still in force
        assert trace.gc_policy is the_policy
        assert gc.callbacks.count(trace._on_gc) == 1
        assert gc.get_freeze_count() > 0
    finally:
        second.shutdown()
    assert trace.gc_policy is None
    assert gc.get_freeze_count() == 0
    assert gc.get_threshold() == thresholds
    assert trace._on_gc not in gc.callbacks
    # and a full collection tenures nothing of ours any more
    tenures = _counter("runtime.gc_tenures")
    gc.collect()
    assert _counter("runtime.gc_tenures") == tenures


def test_uninstall_without_install_is_nothing():
    collector.uninstall()
    assert trace.gc_policy is None


def test_deep_collection_frees_a_tenured_cycle(policy, due):
    ref = _tenured_cycle()
    gc.collect()  # no collection looks at it again
    assert ref() is not None
    deep, tenures = _counter("runtime.gc_deep_collections"), _counter("runtime.gc_tenures")
    labelled_deep, labelled_2 = _hist_count("deep"), _hist_count("2")
    collector.idle_tick()
    assert ref() is None
    assert _counter("runtime.gc_deep_collections") == deep + 1
    # what was left is tenured again, and the pause is labelled for what it was
    assert _counter("runtime.gc_tenures") == tenures + 1
    assert gc.get_freeze_count() > 0
    assert _hist_count("deep") == labelled_deep + 1
    assert _hist_count("2") == labelled_2
    # (tenured objects still die by reference count, and any collection moves the
    # interpreter's immortal objects there: near, not equal)
    gauge = metrics.snapshot()["gauges"]["runtime.gc_tenured_objects"]
    assert abs(gauge - gc.get_freeze_count()) < 1_000


def test_deep_collection_waits_while_a_request_span_is_open(policy, due):
    ref = _tenured_cycle()
    deep = _counter("runtime.gc_deep_collections")
    with span("request", frame=True):
        assert trace.idle_seconds() is None
        collector.idle_tick()
        with span("verify_block"):
            collector.idle_tick()
    assert ref() is not None
    assert _counter("runtime.gc_deep_collections") == deep
    assert trace.idle_seconds() is not None
    collector.idle_tick()
    assert ref() is None


def test_deep_collection_waits_until_nobody_has_been_seen_for_a_while(policy, monkeypatch):
    monkeypatch.setattr(collector, "DEEP_INTERVAL_S", 0.0)
    monkeypatch.setattr(collector, "IDLE_S", 3600.0)
    ref = _tenured_cycle()
    with span("verify_block"):
        pass  # a request has just ended
    collector.idle_tick()
    assert ref() is not None
    monkeypatch.setattr(collector, "IDLE_S", 0.0)
    collector.idle_tick()
    assert ref() is None


@pytest.mark.parametrize("held_back_by", ["interval", "growth"])
def test_deep_collection_respects_its_interval_and_its_share(policy, monkeypatch, held_back_by):
    monkeypatch.setattr(collector, "IDLE_S", 0.0)
    monkeypatch.setattr(collector, "DEEP_INTERVAL_S", 0.0)
    gc.collect()
    collector.idle_tick()  # the first: everything is growth over nothing
    deep = _counter("runtime.gc_deep_collections")
    assert deep >= 1
    ref = _tenured_cycle()
    if held_back_by == "interval":
        monkeypatch.setattr(collector, "DEEP_INTERVAL_S", 3600.0)
        grown = [[i] for i in range(int(gc.get_freeze_count() * collector.DEEP_GROWTH) + 1000)]
    else:
        grown = []  # two objects are no twentieth of the tenured heap
    gc.collect()
    collector.idle_tick()
    assert _counter("runtime.gc_deep_collections") == deep
    assert ref() is not None
    gauge = metrics.snapshot()["gauges"]["runtime.gc_tenured_objects"]
    assert abs(gauge - gc.get_freeze_count()) < 1_000
    del grown


def test_full_collections_are_still_timed_and_still_intervals_of_open_spans(policy):
    before = _hist_count("2")
    with span("verify_block") as sp:
        gc.collect()
        gc.collect()
    assert [iv[0] for iv in sp.intervals if iv[0] == "gc"] == ["gc", "gc"]
    assert _hist_count("2") == before + 2


def test_the_accept_loop_ticks_the_policy(monkeypatch):
    ticks = []
    monkeypatch.setattr(collector, "idle_tick", lambda: ticks.append(1))
    srv = MetricsServer(host="127.0.0.1", port=0)
    _serve(srv)
    try:
        urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/healthz", timeout=10).read()
        give_up = time.monotonic() + 10
        while not ticks and time.monotonic() < give_up:
            time.sleep(0.005)
    finally:
        srv.shutdown()
    assert ticks
