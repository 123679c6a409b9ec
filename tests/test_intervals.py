"""Measured intervals (PR 26): spans with ids, a start and an end on one
clock, phases as child intervals, the front end's marks, the lanes' stages
in the batch records, device waits, compiles and the collector timed inside
the program, and all of it in the profiler's trace without importing jax
where the cpu backend runs without it."""

from __future__ import annotations

import gc
import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from phant_tpu.backend import set_crypto_backend
from phant_tpu.engine_api.server import FRONTEND_PHASES, EngineAPIServer
from phant_tpu.obs import critpath, timeline
from phant_tpu.serving import SchedulerConfig, collector
from phant_tpu.utils import trace
from phant_tpu.utils.trace import (
    ANNOTATIONS,
    add_span_sink,
    metrics,
    remove_span_sink,
    span,
    trace_context,
)

from test_engine_api import _serve
from test_serving import _post, _stateless_request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the handler thread's phases of a request, in the order they run
HANDLER_PHASES = (
    "stateless.sig_rows",
    "stateless.witness_verify",
    "stateless.witness_decode",
    "stateless.execute",
    "stateless.post_root",
)


def _hist(name: str, **labels) -> dict:
    key = trace._labels_key(name, labels)
    return metrics.snapshot()["histograms"].get(key, {"count": 0, "sum": 0.0})


# ---------------------------------------------------------------------------
# spans and intervals (pure)
# ---------------------------------------------------------------------------


def _nested_record() -> dict:
    got = []
    add_span_sink(got.append)
    try:
        with trace_context("abc"), span("verify_block", block=3) as sp:
            for name in HANDLER_PHASES:
                with metrics.phase(name):
                    time.sleep(0.001)
            with span("inner"):
                with metrics.phase("stateless.post_root_plan"):
                    time.sleep(0.001)
            metrics.observe("sched.sig_wait", 0.0005)
            assert sp.span_id > 0
    finally:
        remove_span_sink(got.append)
    return got[-1]


def test_span_has_an_id_a_start_and_an_end_on_the_span_clock():
    before = trace.clock_ns()
    rec = _nested_record()
    after = trace.clock_ns()
    assert before <= rec["start_ns"] < rec["end_ns"] <= after
    assert rec["duration_ms"] == pytest.approx((rec["end_ns"] - rec["start_ns"]) / 1e6, abs=1e-3)
    (child,) = rec["children"]
    assert child["parent_id"] == rec["span_id"] and child["span_id"] > rec["span_id"]
    assert "parent_id" not in rec  # a top-level span under no frame
    assert rec["trace_id"] == "abc" and child["trace_id"] == "abc"


@pytest.mark.parametrize("phase", HANDLER_PHASES + ("sched.sig_wait",))
def test_interval_nests_inside_its_span(phase):
    rec = _nested_record()
    mine = [iv for iv in rec["intervals"] if iv[0] == phase]
    assert len(mine) == 1
    _name, t0, t1 = mine[0]
    assert rec["start_ns"] <= t0 <= t1 <= rec["end_ns"]


def test_child_intervals_of_one_thread_do_not_overlap():
    rec = _nested_record()
    timed = sorted(iv[1:] for iv in rec["intervals"] if iv[0] in HANDLER_PHASES)
    assert len(timed) == len(HANDLER_PHASES)
    assert all(a[1] <= b[0] for a, b in zip(timed, timed[1:]))
    # the child span lies after the last of them, inside its parent
    (child,) = rec["children"]
    assert timed[-1][1] <= child["start_ns"] <= child["end_ns"] <= rec["end_ns"]
    (plan,) = child["intervals"]
    assert child["start_ns"] <= plan[1] <= plan[2] <= child["end_ns"]


def test_phases_totals_equal_the_sum_of_intervals():
    got = []
    add_span_sink(got.append)
    try:
        with span("verify_block"):
            for _ in range(3):
                with metrics.phase("stateless.execute"):
                    time.sleep(0.0005)
            with metrics.phase("stateless.post_root"):
                pass
    finally:
        remove_span_sink(got.append)
    rec = got[-1]
    for name, st in rec["phases"].items():
        mine = [iv for iv in rec["intervals"] if iv[0] == name]
        assert st["count"] == len(mine)
        assert st["total_ms"] == pytest.approx(sum(b - a for _n, a, b in mine) / 1e6, abs=1e-3)
    assert rec["phases"]["stateless.execute"]["count"] == 3


def test_every_interval_belongs_to_a_record_that_carries_the_trace_id():
    rec = _nested_record()
    (child,) = rec["children"]
    for r in (rec, child):
        assert r["trace_id"] == "abc" and r["span_id"]
        assert all(isinstance(a, int) and a <= b for _n, a, b in r["intervals"])


#: a record as the program reported it before this PR (PERF.md's cell, one
#: request), and the 12 numbers critpath made of it
RECORDED = {
    "span": "verify_block", "block": 41, "nodes": 1498, "codes": 1,
    "trace_id": "5b1c0e7d9a3f2c44", "duration_ms": 201.337,
    "batch_id": 97, "batch_size": 1, "backend": "device", "stage": "resolve",
    "queue_wait_ms": 5.412, "prefetch_ms": 21.07, "pack_ms": 31.208, "resolve_ms": 49.113,
    "sig_batch_id": 96, "sig_queue_wait_ms": 5.2, "root_queue_wait_ms": 0.0,
    "phases": {
        "stateless.sig_rows": {"count": 1, "total_ms": 9.871},
        "stateless.witness_verify": {"count": 1, "total_ms": 131.604},
        "stateless.witness_decode": {"count": 1, "total_ms": 6.23},
        "sched.sig_wait": {"count": 1, "total_ms": 0.402},
        "stateless.execute": {"count": 1, "total_ms": 48.9},
        "stateless.post_root": {"count": 1, "total_ms": 4.09},
    },
}
RECORDED_BREAKDOWN = {
    "sig_rows": 9.871, "queue_wait": 5.412, "prefetch": 21.07, "pack": 31.208,
    "dispatch": 24.801, "resolve": 49.113, "witness_decode": 6.23, "sig_wait": 0.402,
    "evm": 48.498, "post_root": 4.09,
}


@pytest.mark.parametrize("with_new_keys", [False, True])
def test_critpath_attribute_reads_a_recorded_record_as_before(with_new_keys):
    rec = dict(RECORDED)
    if with_new_keys:  # what a span record gained: none of it moves a phase
        rec.update(
            span_id=7, parent_id=6, start_ns=10**12, end_ns=10**12 + 201_337_000,
            intervals=[["stateless.execute", 10**12 + 5, 10**12 + 48_900_005], ["gc", 1, 2]],
            stages={"prefetch": [1, 2], "pack": [3, 4], "resolve": [5, 6]},
            compile_ms=3.0,
        )
        rec["phases"] = {**rec["phases"], "gc": {"count": 1, "total_ms": 400.0}}
    breakdown, unattributed, wall = critpath.attribute(rec)
    assert wall == 201.337
    assert breakdown == pytest.approx(RECORDED_BREAKDOWN)
    assert unattributed == pytest.approx(201.337 - sum(RECORDED_BREAKDOWN.values()))


def test_frame_span_reports_its_child_as_top_level_with_a_parent_id():
    got = []
    add_span_sink(got.append)
    try:
        with trace_context("r1"), span("request", frame=True) as req:
            req.resume = "reply"
            req.mark("read")
            req.mark("json")
            req.mark("decode")
            with span("verify_block"):
                time.sleep(0.001)
            time.sleep(0.0005)
    finally:
        remove_span_sink(got.append)
    child, frame = got[-2], got[-1]
    assert child["span"] == "verify_block" and frame["span"] == "request"
    assert child["parent_id"] == frame["span_id"]
    assert "children" not in frame  # reported on its own, not folded in
    names = [iv[0] for iv in frame["intervals"]]
    assert names == ["read", "json", "decode", "reply"]
    # the marks are contiguous, stop where the child opens and resume as
    # `reply` where it closes
    ivs = frame["intervals"]
    assert ivs[0][2] == ivs[1][1] and ivs[1][2] == ivs[2][1]
    assert ivs[2][2] <= child["start_ns"] and child["end_ns"] <= ivs[3][1]
    assert ivs[3][2] <= frame["end_ns"]


def test_frame_span_without_a_child_is_not_reported():
    got = []
    add_span_sink(got.append)
    try:
        with span("request", frame=True) as req:
            req.mark("read")
    finally:
        remove_span_sink(got.append)
    assert got == []
    assert [iv[0] for iv in req.intervals] == ["read"]


# ---------------------------------------------------------------------------
# the front end, the lanes' stages and the timeline, over HTTP
# ---------------------------------------------------------------------------


@pytest.fixture
def served(monkeypatch):
    """A server with its default lanes, one sink for every span record."""
    monkeypatch.setenv("PHANT_TIMELINE_SAMPLE_N", "1")
    records: list = []
    add_span_sink(records.append)
    chain, rpc, want_root = _stateless_request()
    server = EngineAPIServer(
        chain, host="127.0.0.1", port=0,
        sched_config=SchedulerConfig(max_batch=4, max_wait_ms=1.0),
    )
    _serve(server)
    try:
        yield f"http://127.0.0.1:{server.port}", rpc, want_root, records
    finally:
        remove_span_sink(records.append)
        server.shutdown()


def _await(cond, seconds: float = 10.0):
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert cond()


def test_front_end_phases_tile_the_request_less_verify_block(served):
    base, rpc, want_root, records = served
    metrics.reset()
    n = 6
    for _ in range(n):
        code, body = _post(base, rpc)
        assert code == 200 and body["result"]["stateRoot"] == want_root
    # request_seconds is observed after the reply is written
    _await(lambda: _hist("engine_api.request_seconds")["count"] == n)
    front = sum(_hist("engine_api.phase_seconds", phase=p)["sum"] for p in FRONTEND_PHASES)
    for p in FRONTEND_PHASES:
        assert _hist("engine_api.phase_seconds", phase=p)["count"] == n, p
    whole = _hist("engine_api.request_seconds")["sum"]
    inside = _hist("critpath.wall_seconds")["sum"]
    assert front == pytest.approx(whole - inside, rel=0.02)
    # and record by record: verify_block's parent is the request's frame
    frames = {r["span_id"]: r for r in records if r["span"] == "request"}
    blocks = [r for r in records if r["span"] == "verify_block"]
    assert len(frames) == n and len(blocks) == n
    for b in blocks:
        f = frames[b["parent_id"]]
        assert f["trace_id"] == b["trace_id"]
        assert f["start_ns"] <= b["start_ns"] <= b["end_ns"] <= f["end_ns"]
        assert [iv[0] for iv in f["intervals"] if iv[0] in FRONTEND_PHASES] == list(FRONTEND_PHASES)


@pytest.mark.parametrize("stage", ["prefetch", "pack", "resolve"])
def test_batch_record_carries_the_stages_start_and_end(served, stage):
    base, rpc, _root, records = served
    assert _post(base, rpc)[0] == 200
    rec = [r for r in records if r["span"] == "verify_block"][-1]
    t0, t1 = rec["stages"][stage]
    # the handler's two waits (PR 29): to the launch, then, after the
    # decode, to the verdict
    to_launch, to_verdict = [iv for iv in rec["intervals"] if iv[0] == "stateless.witness_verify"]
    (decode,) = [iv for iv in rec["intervals"] if iv[0] == "stateless.witness_decode"]
    assert to_launch[2] <= decode[1] <= decode[2] <= to_verdict[1]
    # on the span's clock, between admission and the join; the launch
    # signal comes after pack, so the first wait holds prefetch and pack
    assert to_launch[1] <= t0 <= t1 <= to_verdict[2]
    if stage != "resolve":
        assert t1 <= to_launch[2]
    assert (t1 - t0) / 1e6 == pytest.approx(rec[f"{stage}_ms"], abs=1.0)
    order = [rec["stages"][s] for s in ("prefetch", "pack", "resolve")]
    assert all(a[1] <= b[0] for a, b in zip(order, order[1:]))


def test_timeline_lays_phase_slices_at_their_measured_offsets(served):
    base, rpc, _root, records = served
    timeline.reset()
    assert _post(base, rpc)[0] == 200
    rec = [r for r in records if r["span"] == "verify_block"][-1]
    with urllib.request.urlopen(base + "/debug/timeline?window=60", timeout=30) as resp:
        export = json.loads(resp.read())
    (block,) = [e for e in export["traceEvents"] if e.get("name") == "verify_block"]
    slices = {e["name"]: e for e in export["traceEvents"] if e.get("cat") == "phase"}
    assert set(slices) <= set(critpath.PHASES)
    for raw, label in (("stateless.witness_decode", "witness_decode"), ("stateless.execute", "evm")):
        (iv,) = [iv for iv in rec["intervals"] if iv[0] == raw]
        want_off = (iv[1] - rec["start_ns"]) // 1000
        assert slices[label]["ts"] - block["ts"] == want_off
        assert slices[label]["dur"] == max((iv[2] - iv[1]) // 1000, 1)
    # the witness wait is cut at the lane's own clock readings
    pack = rec["stages"]["pack"]
    assert slices["pack"]["ts"] - block["ts"] == (pack[0] - rec["start_ns"]) // 1000
    assert slices["queue_wait"]["ts"] <= slices["pack"]["ts"]
    # resolve has a slice only where the handler WAITED under it: what ran
    # while it decoded is hidden time, in no phase
    if "resolve" in slices:
        assert slices["pack"]["ts"] <= slices["resolve"]["ts"]


# ---------------------------------------------------------------------------
# device waits, compiles, the collector
# ---------------------------------------------------------------------------


@pytest.fixture
def tpu_backend(monkeypatch):
    """The tpu crypto backend on the CPU platform, as the suite's [tpu]
    cases run it."""
    monkeypatch.setenv("PHANT_ALLOW_JAX_CPU", "1")
    monkeypatch.setenv("PHANT_BATCHED_ROOT", "1")
    set_crypto_backend("tpu")
    yield
    set_crypto_backend("cpu")


def _run_lane(lane: str) -> None:
    if lane == "witness":
        from phant_tpu.ops.witness_engine import WitnessEngine
        from _witnesses import build_witnesses

        _root, wits = build_witnesses(n_blocks=2, picks=2, trie_n=32)
        assert all(WitnessEngine(resident=True, resident_cap=1024).verify_batch(wits))
    elif lane == "keccak":
        from phant_tpu.ops.keccak_jax import keccak256_batch_jax

        assert len(keccak256_batch_jax([b"ab", b"cd" * 40])) == 2


@pytest.mark.parametrize("op", ["enqueue", "sync"])
@pytest.mark.parametrize("site", ["witness", "keccak"])
def test_device_host_seconds_grows_on_the_witness_lane(tpu_backend, site, op):
    """The resident table's route and the classic keccak route. The sig and
    root lanes' cases stand in test_sender_lane.py and test_post_root.py,
    beside the tests that already build those lanes' programs."""
    before = _hist("device.host_seconds", lane="witness", op=op)
    _run_lane(site)
    after = _hist("device.host_seconds", lane="witness", op=op)
    assert after["count"] > before["count"]
    assert after["sum"] > before["sum"]


@pytest.mark.parametrize("thread", ["serving", "other"])
def test_jit_compiles_counts_by_thread(thread):
    import jax
    import jax.numpy as jnp

    from phant_tpu.serving import deadline

    deadline.start_compile_clock()
    # a mesh pool's boot prewarm is a daemon thread that goes on compiling
    # after the test that made the pool (tests/test_post_root.py,
    # test_serving_mesh.py): where one of those files ran before this one in
    # the same worker, its compiles are not this test's "other" thread's
    for left in threading.enumerate():
        if left.name == "phant-mesh-prewarm":
            left.join(300)
    key = trace._labels_key("jit.compiles", {"thread": thread})
    other = trace._labels_key(
        "jit.compiles", {"thread": "other" if thread == "serving" else "serving"}
    )
    seen = {}

    def work():
        if thread == "serving":
            deadline.serving_thread()
        c0 = metrics.snapshot()["counters"]
        with span("verify_block") as sp:
            # a shape and a constant no other test compiles
            jax.jit(lambda x: x * 3.25 + (7 if thread == "serving" else 9))(
                jnp.ones((3, 5 if thread == "serving" else 7))
            ).block_until_ready()
        c1 = metrics.snapshot()["counters"]
        seen["mine"] = c1.get(key, 0) - c0.get(key, 0)
        seen["theirs"] = c1.get(other, 0) - c0.get(other, 0)
        seen["intervals"] = [iv for iv in sp.intervals if iv[0] == "compile"]
        seen["span"] = (sp.start_ns, sp.end_ns)

    t = threading.Thread(target=work)
    t.start()
    t.join(120)
    assert not t.is_alive()
    assert seen["mine"] >= 1 and seen["theirs"] == 0, [th.name for th in threading.enumerate()]
    # the request that stood behind the compile says so
    assert seen["intervals"]
    for _n, t0, t1 in seen["intervals"]:
        assert seen["span"][0] <= t0 <= t1 <= seen["span"][1]
    if thread == "serving":
        assert metrics.snapshot()["gauges"]["jit.serving_compile_seconds"] > 0


def test_lane_batch_that_stood_behind_a_compile_says_so():
    stages: dict = {}
    with trace.lane_stage(stages, "pack", ["t1", None, "t2"], 5):
        assert trace.current_trace_id() == "t1|t2"
        trace.note_compile(0.25)
    assert trace.current_trace_id() is None
    record: dict = {}
    trace.fold_stages(record, stages)
    assert record["compile_ms"] == 250.0
    assert list(record["stages"]) == ["pack"]
    t0, t1 = record["stages"]["pack"]
    assert t0 <= t1


def test_gc_pause_histogram_and_gc_interval_of_an_open_span():
    trace.watch_gc()
    try:
        before2 = _hist("runtime.gc_pause_seconds", generation="2")["count"]
        before0 = _hist("runtime.gc_pause_seconds", generation="0")["count"]
        with span("verify_block") as sp:
            t0 = trace.clock_ns()
            gc.collect()  # a full collection
            gc.collect(0)
            t1 = trace.clock_ns()
        ours = [iv for iv in sp.intervals if iv[0] == "gc"]
        assert len(ours) == 1  # the full one only
        assert t0 <= ours[0][1] <= ours[0][2] <= t1
        assert sp.phases["gc"][0] == 1
        assert _hist("runtime.gc_pause_seconds", generation="2")["count"] == before2 + 1
        assert _hist("runtime.gc_pause_seconds", generation="0")["count"] == before0 + 1
    finally:
        trace.unwatch_gc()
    # uninstalled with the last watcher: nothing is timed any more
    n = _hist("runtime.gc_pause_seconds", generation="2")["count"]
    gc.collect()
    assert _hist("runtime.gc_pause_seconds", generation="2")["count"] == n
    assert trace._on_gc not in gc.callbacks


@pytest.mark.parametrize("with_policy", [False, True])
def test_gc_callback_takes_no_lock_the_collecting_thread_may_hold(with_policy):
    """A collection can start under an allocation made while the thread
    holds the registry's lock: the callback must not want that lock, nor,
    where the tenure policy is installed (PR 27), the policy's or the
    install count's."""
    if not with_policy:
        trace.watch_gc()
        try:
            with metrics._lock:
                gc.collect()
        finally:
            trace.unwatch_gc()
        return
    collector.install()
    try:
        policy = trace.gc_policy
        tenures = policy.tenures
        with metrics._lock, collector._lock, policy._lock:
            gc.collect()
        assert policy.tenures == tenures + 1
    finally:
        collector.uninstall()
    assert gc.get_freeze_count() == 0


# ---------------------------------------------------------------------------
# the profiler's trace
# ---------------------------------------------------------------------------


def test_annotation_vocabulary_is_closed_and_named():
    assert all(v.startswith("phant/") for v in ANNOTATIONS.values())
    assert len(set(ANNOTATIONS.values())) == len(ANNOTATIONS)
    # every phase the table names is a phase the registry documents
    assert set(ANNOTATIONS) <= set(trace.METRIC_HELP)
    for lane in ("witness", "sig", "root"):
        for stage in ("prefetch", "pack", "dispatch", "resolve"):
            assert f"phant/{lane}.{stage}" in ANNOTATIONS.values()


def test_annotate_is_nothing_with_no_profiler_running():
    assert trace.annotate("phant/evm") is None
    assert trace.annotate(None) is None


def test_cpu_backend_serves_without_importing_jax():
    """Where the cpu crypto backend runs without jax today it still does:
    a request through spans, phases, marks and the collector's callback in
    a process of its own, and `jax` is not in sys.modules afterwards."""
    code = """
import gc, sys
sys.path.insert(0, {root!r})
from phant_tpu.utils import trace
from phant_tpu.utils.trace import metrics, span, trace_context
trace.watch_gc()
with trace_context(), span("request", frame=True) as req:
    req.resume = "reply"
    req.mark("read")
    with span("verify_block"):
        with metrics.phase("stateless.execute"):
            gc.collect()
        with trace.lane_stage({{}}, "pack", ["a"], 1), metrics.phase("witness_engine.pack"):
            pass
        with trace.device_host("witness", "sync"):
            pass
        trace.note_compile(0.1)
trace.unwatch_gc()
assert metrics.snapshot()["histograms"]['runtime.gc_pause_seconds{{generation="2"}}']["count"] >= 1
assert "jax" not in sys.modules, sorted(m for m in sys.modules if m.startswith("jax"))
print("ok")
""".format(root=ROOT)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120, env=env
    )
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "ok"
