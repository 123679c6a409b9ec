"""Tracing/metrics subsystem tests (SURVEY §5 aux-subsystem slot)."""

from __future__ import annotations

import os
import threading
import time
from pathlib import Path

import pytest

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


# ---------------------------------------------------------------------------
# the second clock (PR 38): thread-CPU seconds beside wall seconds
# ---------------------------------------------------------------------------


#: what the machine gave this thread's last piece of work: the share of a
#: core a spin got by its OWN readings of the two clocks (1.0 on an idle
#: machine, less beside five other xdist workers), so that a split is
#: judged against that and not against a whole core
_gave = threading.local()


def _sleep(seconds: float) -> None:
    time.sleep(seconds)
    _gave.share = 1.0


def _spin(seconds: float) -> None:
    t0, c0 = time.monotonic(), time.thread_time()
    end = t0 + seconds
    while time.monotonic() < end:
        pass
    _gave.share = (time.thread_time() - c0) / (time.monotonic() - t0)


def _split_of_a_phase(work) -> tuple:
    with span("verify_block") as sp:
        with metrics.phase("stateless.sig_rows"):
            work(0.05)
    st = sp.to_dict()["phases"]["stateless.sig_rows"]
    return st["total_ms"], st["cpu_ms"]


def _split_of_a_mark(work) -> tuple:
    with span("request", frame=True) as sp:
        sp.mark("json")
        work(0.05)
        sp.mark("reply")
    st = sp.to_dict()["phases"]["json"]
    assert [iv[0] for iv in sp.intervals] == ["json", "reply"]  # triples, as they were
    return st["total_ms"], st["cpu_ms"]


def _split_of_a_lane_stage(work) -> tuple:
    from phant_tpu.utils.trace import fold_stages, lane_stage

    stages: dict = {}
    with lane_stage(stages, "pack", ["t1"], 7):
        work(0.05)
    record: dict = {}
    fold_stages(record, stages)
    t0, t1 = record["stages"]["pack"]
    assert set(record["stages"]) == {"pack"}  # [start, end] pairs alone, as critpath reads them
    return (t1 - t0) / 1e6, record["stage_cpu_ms"]["pack"]


def _hist_sum(key: str) -> float:
    return metrics.snapshot()["histograms"].get(key, {"sum": 0.0})["sum"]


def _split_of_a_device_visit(work) -> tuple:
    from phant_tpu.utils.trace import device_host

    wall = 'device.host_seconds{lane="sig",op="sync"}'
    cpu = 'device.host_cpu_seconds{lane="sig",op="sync"}'
    w0, c0 = _hist_sum(wall), _hist_sum(cpu)
    with device_host("sig", "sync"):
        work(0.05)
    return (_hist_sum(wall) - w0) * 1e3, (_hist_sum(cpu) - c0) * 1e3


def _split_of_a_stage_timer(work) -> tuple:
    label = '{lane="root",stage="pack"}'
    names = ("lanes.stage_seconds", "lanes.stage_cpu_seconds", "lanes.stage_offcpu_seconds")
    before = [_hist_sum(n + label) for n in names]
    with metrics.phase("witness_engine.root_pack"):
        work(0.05)
    wall, cpu, off = (_hist_sum(n + label) - b for n, b in zip(names, before))
    assert abs(cpu + off - wall) < 1e-9  # the wait is the wall less the CPU
    return wall * 1e3, cpu * 1e3


@pytest.mark.parametrize(
    "split",
    [_split_of_a_phase, _split_of_a_mark, _split_of_a_lane_stage, _split_of_a_device_visit, _split_of_a_stage_timer],
)
@pytest.mark.parametrize("work,low,high", [(_sleep, 0.0, 0.10), (_spin, 0.80, 1.001)])
def test_every_timed_place_reads_the_threads_cpu_beside_the_wall(split, work, low, high):
    """A phase that sleeps ran almost no CPU; one that spins reads at least
    `low` of the CPU the spin itself read on the same thread's clock, the
    share of a core the machine gave it (`_gave`), whatever that share was;
    and CPU is read inside the wall readings, so it does not pass the wall
    (a mark's two clocks are read one after the other at each end: by
    microseconds at most). Up to five goes: a thread can lose its core
    between a phase's reading and the spin's own."""
    for _attempt in range(5):
        wall_ms, cpu_ms = split(work)
        assert 50.0 <= wall_ms < 5000.0 and 0.0 <= cpu_ms <= wall_ms + 0.05
        if low * _gave.share <= cpu_ms / wall_ms <= high:
            return
    raise AssertionError(f"{split.__name__}/{work.__name__}: cpu {cpu_ms} of wall {wall_ms}, given {_gave.share}")


def test_two_threads_on_one_lock_wait_one_threads_wall_between_them():
    """Two threads that spin in Python share the interpreter lock: they
    never run at once, so the CPU the two phases read sums to at most one
    thread's wall and their off-CPU seconds (wall less CPU) to at least
    about one. What `handler_lock_wait_ms` rests on. From below the CPU is
    judged against what the machine gave the PROCESS meanwhile
    (`time.process_time`, all its threads): the two phases account for
    most of it, be it a whole core or, beside other workers, half of one."""
    for _attempt in range(5):
        out: list = []
        gate = threading.Barrier(2)

        def worker():
            gate.wait()
            out.append(_split_of_a_phase(lambda _s: _spin(0.3)))

        ts = [threading.Thread(target=worker) for _ in range(2)]
        p0 = time.process_time()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        wall = sum(w for w, _c in out) / 2
        cpu = sum(c for _w, c in out)
        off = sum(w - c for w, c in out)
        given = min((time.process_time() - p0) * 1e3 / wall, 1.0)
        assert all(0.0 <= c <= w for w, c in out)
        if 0.7 * given <= cpu / wall <= 1.15 and 0.7 <= off / wall <= 2.0 - 0.7 * given:
            return
    raise AssertionError(f"two spinning threads read {out} where the process got {given} of a core")


def test_observe_split_books_a_ticks_excess_against_the_next_observations():
    """On the chip's host the thread's CPU clock steps by 10 ms: a reading
    is 0 or a whole tick whatever the phase's width. Booked observation by
    observation the CPU never passes the wall and the two families sum to
    it; what a tick holds beyond its phase is carried to the series' next
    observations, so the sums stay true to the clock. A fine clock's
    readings are booked as they are; series do not share a carry."""
    m = Metrics()
    names = ("lanes.stage_cpu_seconds", "lanes.stage_offcpu_seconds")
    for cpu in (0.010, 0.0, 0.0, 0.0, 0.0, 0.0, 0.010):  # phases of 2 ms
        m.observe_split(*names, 0.002, cpu, lane="sig", stage="pack")
    for cpu in (0.0015, 0.0005):  # a fine clock, another series
        m.observe_split(*names, 0.002, cpu, lane="root", stage="pack")
    h = m.snapshot()["histograms"]
    sums = lambda label: tuple(h[n + label][k] for n in names for k in ("count", "sum"))  # noqa: E731
    # the first tick covers five phases, the sixth ran none, the second tick is still being booked
    assert sums('{lane="sig",stage="pack"}') == pytest.approx((7, 0.012, 7, 0.002))
    assert sums('{lane="root",stage="pack"}') == pytest.approx((2, 0.002, 2, 0.002))
    m.reset()
    m.observe_split(*names, 0.002, 0.0, lane="sig", stage="pack")
    assert m.snapshot()["histograms"][names[0] + '{lane="sig",stage="pack"}']["sum"] == 0.0  # reset drops the carry


def test_a_span_keeps_cpu_by_name_and_its_intervals_stay_triples():
    """`phases[name]["cpu_ms"]` beside `total_ms`, summed over a name's
    intervals; what is observed without a CPU reading (an `observe`, the
    collector's `gc`) has none; nested spans carry theirs."""
    with span("verify_block") as sp:
        for _ in range(2):
            with metrics.phase("stateless.witness_verify"):
                _spin(0.01)
        metrics.observe("sched.prefetch_wait", 0.001)
        sp.intervals.append(("gc", sp.start_ns, sp.start_ns + 5))
        with span("inner") as inner:
            with metrics.phase("stateless.execute"):
                _spin(0.01)
    d = sp.to_dict()
    assert all(len(iv) == 3 for iv in d["intervals"])
    wv = d["phases"]["stateless.witness_verify"]
    assert wv["count"] == 2 and 10.0 <= wv["cpu_ms"] <= wv["total_ms"]
    assert "cpu_ms" not in d["phases"]["sched.prefetch_wait"] and "cpu_ms" not in d["phases"]["gc"]
    assert d["children"][0]["phases"]["stateless.execute"]["cpu_ms"] > 5.0
    assert inner.cpu_ns["stateless.execute"] <= 1e6 * d["children"][0]["phases"]["stateless.execute"]["total_ms"] + 1e3


NEW_FAMILIES = {
    "critpath.phase_cpu_seconds": "histogram",
    "critpath.phase_offcpu_seconds": "histogram",
    "engine_api.phase_cpu_seconds": "histogram",
    "engine_api.phase_offcpu_seconds": "histogram",
    "lanes.stage_seconds": "histogram",
    "lanes.stage_cpu_seconds": "histogram",
    "lanes.stage_offcpu_seconds": "histogram",
    "device.host_cpu_seconds": "histogram",
    "runtime.process_cpu_seconds": "gauge",
    "native.unlocked_seconds": "gauge",
    "native.lock_retake_seconds": "gauge",
    "native.keccak_calls": "gauge",
}


def test_prometheus_text_carries_every_new_family_with_its_help():
    """One served stateless request through the real server, then the
    exposition: each family of the second clock is there under its help
    line, and the catalog gate (phantlint METRICNAME) is green with them."""
    from phant_tpu.analysis import Analyzer, default_rules
    from phant_tpu.engine_api.server import EngineAPIServer
    from phant_tpu.utils.trace import METRIC_HELP, device_host, prometheus_name

    from test_serving import _post, _stateless_request

    chain, rpc, _root = _stateless_request()
    server = EngineAPIServer(chain, host="127.0.0.1", port=0)
    server.serve_in_background()
    try:
        code, body = _post(f"http://127.0.0.1:{server.port}", rpc)
        assert code == 200 and body["result"]["status"] == "VALID", body
    finally:
        server.shutdown()
    with device_host("witness", "enqueue"):  # the cpu backend visits no device
        pass
    text = metrics.prometheus_text()
    for name, kind in NEW_FAMILIES.items():
        family = prometheus_name(name)
        assert f"# HELP {family} {METRIC_HELP[name]}" in text, name
        assert f"# TYPE {family} {kind}" in text, name
    assert 'phant_critpath_phase_cpu_seconds_count{phase="evm"}' in text
    assert 'phant_engine_api_phase_offcpu_seconds_count{phase="reply"}' in text
    assert 'phant_lanes_stage_cpu_seconds_count{lane="witness",stage="pack"}' in text
    assert 'phant_native_lock_retake_seconds{site="scan"}' in text
    user, system = (
        float(text.split('\nphant_runtime_process_cpu_seconds{mode="%s"} ' % mode)[1].split()[0])
        for mode in ("user", "system")
    )
    assert 0.0 < user and 0.0 <= system and user + system <= time.process_time() + 0.011
    repo = Path(__file__).resolve().parents[1]
    result = Analyzer(
        [repo / "phant_tpu"], default_rules(["METRICNAME"]), baseline=repo / "scripts/phantlint_baseline.json"
    ).run()
    assert not result.new, [f.render() for f in result.new]


def test_the_extensions_lock_clocks_grow_across_a_begin_batch():
    """The one place the program measures a wait for the lock: the
    extension's scan gives the lock away, and both clocks of that site grow
    across a `begin_batch` (the retake at least by a clock read)."""
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.utils import native

    from test_obs import _witness_set

    if native.load_engine_ext() is None:
        pytest.skip("no toolchain: the extension cannot be built here")
    eng = WitnessEngine()
    if eng._ext_core is None:
        pytest.skip("the witness engine runs without the extension's driver here")
    before = native.lock_clocks()
    handle = eng.begin_batch(_witness_set(4))
    assert eng.resolve_batch(handle).all()
    after = native.lock_clocks()
    assert set(after) == set(native.LOCK_SITES)
    assert after["scan"][0] > before["scan"][0] and after["scan"][1] > before["scan"][1]
    assert all(after[s][k] >= before[s][k] for s in native.LOCK_SITES for k in (0, 1))
    grown = sum(after[s][0] - before[s][0] for s in native.LOCK_SITES)
    assert 0.0 < grown < 5.0


def test_without_the_extension_the_lock_clocks_read_zero():
    """A process that never loaded the extension (no toolchain,
    PHANT_NO_NATIVE) exports the two families at 0, site by site, and
    builds nothing to do so."""
    import subprocess
    import sys

    code = (
        "from phant_tpu.utils import native\n"
        "from phant_tpu.utils.trace import metrics\n"
        "assert native.lock_clocks() == dict.fromkeys(native.LOCK_SITES, (0.0, 0.0))\n"
        "text = metrics.prometheus_text()\n"
        "assert native._ext_mod is None\n"
        "for site in native.LOCK_SITES:\n"
        "    assert 'phant_native_unlocked_seconds{site=\"%s\"} 0.0' % site in text\n"
        "    assert 'phant_native_lock_retake_seconds{site=\"%s\"} 0.0' % site in text\n"
        "assert native.keccak_calls() == {'held': 0, 'released': 0}\n"
        "assert 'phant_native_keccak_calls{lock=\"held\"} 0\\n' in text\n"
        "print('zero')\n"
    )
    env = {**os.environ, "PHANT_NO_NATIVE": "1", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=str(Path(__file__).resolve().parents[1]), timeout=120,
    )  # fmt: skip
    assert proc.returncode == 0 and proc.stdout.strip() == "zero", proc.stderr
