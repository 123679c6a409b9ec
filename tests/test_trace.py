"""Tracing/metrics subsystem tests (SURVEY §5 aux-subsystem slot)."""

from __future__ import annotations

import threading
import time

from phant_tpu.utils.trace import (
    Histogram,
    Metrics,
    jax_profile,
    metrics,
    scoped_logger,
    span,
)


def test_phase_timing_and_counters():
    m = Metrics()
    m.count("payloads")
    m.count("payloads", 2)
    with m.phase("work"):
        time.sleep(0.01)
    with m.phase("work"):
        pass
    snap = m.snapshot()
    assert snap["counters"]["payloads"] == 3
    t = snap["timers"]["work"]
    assert t["count"] == 2
    assert t["total_s"] >= 0.01
    assert t["min_s"] <= t["mean_s"] <= t["max_s"]
    report = m.report()
    assert "payloads" in report and "work" in report
    m.reset()
    assert m.snapshot() == {
        "counters": {},
        "timers": {},
        "gauges": {},
        "histograms": {},
    }


def test_phase_records_on_exception():
    m = Metrics()
    try:
        with m.phase("boom"):
            raise ValueError("x")
    except ValueError:
        pass
    assert m.snapshot()["timers"]["boom"]["count"] == 1


def test_metrics_thread_safety():
    m = Metrics()

    def worker():
        for _ in range(500):
            m.count("n")
            m.observe("t", 0.001)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = m.snapshot()
    assert snap["counters"]["n"] == 4000
    assert snap["timers"]["t"]["count"] == 4000


def test_jax_profile_noop_and_scoped_logger():
    with jax_profile(None):  # must be a cheap no-op without a logdir
        pass
    assert scoped_logger("vm").name == "phant_tpu.vm"


def test_engine_api_emits_metrics():
    from phant_tpu.engine_api import handle_request

    metrics.reset()
    handle_request(None, {"id": 1, "method": "engine_bogusMethod"})
    handle_request(None, {"id": 2, "method": "engine_getPayloadV2"})
    snap = metrics.snapshot()
    # untrusted method strings share one bucket (bounded cardinality);
    # known methods label one shared family
    assert snap["counters"]["engine_api.unknown_method"] == 1
    assert snap["counters"]['engine_api.requests{method="engine_getPayloadV2"}'] == 1


# ---------------------------------------------------------------------------
# histograms / gauges / labels / exposition (PR 1 observability surface)


def test_histogram_bucket_edges():
    h = Histogram(buckets=(0.01, 0.1, 1.0))
    h.add(0.01)  # exactly ON an upper bound lands IN that bucket (le semantics)
    h.add(0.010001)  # just over -> next bucket
    h.add(0.5)
    h.add(2.0)  # above the last bound -> +Inf slot
    assert h.counts == [1, 1, 1, 1]
    assert h.count == 4
    assert abs(h.sum - 2.520001) < 1e-9


def test_metrics_histogram_and_gauge():
    m = Metrics()
    m.observe_hist("req.seconds", 0.003, buckets=(0.001, 0.01))
    m.observe_hist("req.seconds", 0.5, buckets=(0.001, 0.01))
    m.gauge_set("inflight", 3)
    m.gauge_add("inflight", -1)
    snap = m.snapshot()
    assert snap["histograms"]["req.seconds"]["counts"] == [0, 1, 1]
    assert snap["histograms"]["req.seconds"]["count"] == 2
    assert snap["gauges"]["inflight"] == 2


def test_labeled_counters():
    m = Metrics()
    m.count("keccak.batches", backend="tpu")
    m.count("keccak.batches", 2, backend="tpu")
    m.count("keccak.batches", backend="cpu")
    m.count("keccak.batches")  # unlabeled series of the same family
    snap = m.snapshot()
    assert snap["counters"]['keccak.batches{backend="tpu"}'] == 3
    assert snap["counters"]['keccak.batches{backend="cpu"}'] == 1
    assert snap["counters"]["keccak.batches"] == 1
    # label rendering is order-insensitive (sorted label names)
    m.count("x", a="1", b="2")
    m.count("x", b="2", a="1")
    assert m.snapshot()["counters"]['x{a="1",b="2"}'] == 2


def test_a_phase_hands_its_attributes_to_its_annotation(monkeypatch):
    """`phase(name, rung=...)`: where ANNOTATIONS names the phase and a
    profiler runs, the event carries the attributes (a lane's dispatch
    says its rung); the timer is observed either way."""
    from phant_tpu.utils import trace

    seen = []

    class FakeAnnotation:
        def __init__(self, name, **attrs):
            seen.append((name, attrs))

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            seen.append("ended")

    monkeypatch.setattr(trace, "_trace_me", FakeAnnotation)
    name = next(iter(trace.ANNOTATIONS))
    m = Metrics()
    with m.phase(name, rung=4096):
        pass
    with m.phase("not.annotated", rung=1):
        pass
    assert seen[0][0] == trace.ANNOTATIONS[name] and seen[0][1]["rung"] == 4096
    assert seen[1:] == ["ended"]
    assert m.snapshot()["timers"][name]["count"] == 1


def test_prometheus_text_parses_back():
    """The exposition must be machine-parseable standard text format:
    parse it back line by line and recover the recorded values."""
    import re

    m = Metrics()
    m.count("engine_api.requests", 5, method="engine_newPayloadV2")
    m.gauge_set("engine_api.inflight", 1)
    m.observe_hist("engine_api.request_seconds", 0.004, buckets=(0.001, 0.01))
    m.observe("stateless.execute", 0.25)
    m.observe("stateless.execute", 0.75)
    text = m.prometheus_text()
    sample_re = re.compile(r"^([a-zA-Z_][a-zA-Z0-9_]*)(\{(.*)\})? (\S+)$")
    samples = {}
    types = {}
    helps = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            helps.add(line.split()[2])
            continue
        if line.startswith("# TYPE "):
            _, _, fam, mtype = line.split()
            types[fam] = mtype
            continue
        mt = sample_re.match(line)
        assert mt, f"unparseable exposition line: {line!r}"
        samples[(mt.group(1), mt.group(3) or "")] = float(mt.group(4))
    assert types["phant_engine_api_requests_total"] == "counter"
    assert samples[("phant_engine_api_requests_total", 'method="engine_newPayloadV2"')] == 5
    assert types["phant_engine_api_inflight"] == "gauge"
    assert samples[("phant_engine_api_inflight", "")] == 1
    assert types["phant_engine_api_request_seconds"] == "histogram"
    # cumulative buckets: 0.004 is <= 0.01 and <= +Inf but not <= 0.001
    assert samples[("phant_engine_api_request_seconds_bucket", 'le="0.001"')] == 0
    assert samples[("phant_engine_api_request_seconds_bucket", 'le="0.01"')] == 1
    assert samples[("phant_engine_api_request_seconds_bucket", 'le="+Inf"')] == 1
    assert samples[("phant_engine_api_request_seconds_count", "")] == 1
    assert types["phant_stateless_execute_seconds"] == "summary"
    assert samples[("phant_stateless_execute_seconds_sum", "")] == 1.0
    assert samples[("phant_stateless_execute_seconds_count", "")] == 2
    # every family in the shipped METRIC_HELP catalog got a help line
    assert "phant_engine_api_requests_total" in helps
    # metric names are clean phant_[a-z0-9_]+ families
    for fam in types:
        assert re.fullmatch(r"phant_[a-z0-9_]+", fam), fam


def test_snapshot_is_deep_copy():
    """snapshot() must deep-copy stats under the lock: mutating the live
    registry afterwards must not change an already-taken snapshot (the
    exposition path must never read torn values)."""
    m = Metrics()
    m.observe("t", 0.5)
    m.observe_hist("h", 0.5, buckets=(1.0,))
    snap = m.snapshot()
    m.observe("t", 10.0)
    m.observe_hist("h", 10.0)
    assert snap["timers"]["t"]["count"] == 1
    assert snap["timers"]["t"]["total_s"] == 0.5
    assert snap["histograms"]["h"]["counts"] == [1, 0]
    # and list fields are not aliased into the registry
    snap["histograms"]["h"]["counts"][0] = 99
    assert m.snapshot()["histograms"]["h"]["counts"][0] == 1


def test_span_nesting_and_log_line(caplog):
    """Spans stack per thread; nested spans fold into the parent and the
    TOP-LEVEL span emits exactly one structured-JSON log line carrying the
    nested phase timings."""
    import json as _json
    import logging as _logging

    with caplog.at_level(_logging.INFO, logger="phant_tpu.span"):
        with span("verify_block", block=7) as sp:
            with metrics.phase("stateless.execute"):
                time.sleep(0.002)
            with span("inner", part="post_root"):
                with metrics.phase("stateless.post_root"):
                    pass
    records = [r for r in caplog.records if r.name == "phant_tpu.span"]
    assert len(records) == 1  # one line per top-level span, not per child
    d = _json.loads(records[0].message)
    assert d["span"] == "verify_block" and d["block"] == 7
    assert d["phases"]["stateless.execute"]["count"] == 1
    assert d["phases"]["stateless.execute"]["total_ms"] >= 2
    (child,) = d["children"]
    assert child["span"] == "inner" and child["part"] == "post_root"
    # the nested phase attached to the INNERMOST open span
    assert child["phases"]["stateless.post_root"]["count"] == 1
    assert "stateless.post_root" not in d["phases"]
    # the span object handed to the with-body is the live span
    assert sp.duration_s > 0


def test_span_threads_do_not_interfere():
    """Per-thread span stacks: phases recorded on one thread must not leak
    into a span open on another."""
    got = {}

    def worker():
        with span("other_thread") as sp:
            with metrics.phase("worker.phase"):
                pass
        got["phases"] = dict(sp.phases)

    with span("main_thread") as main_sp:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert "worker.phase" in got["phases"]
    assert "worker.phase" not in main_sp.phases
