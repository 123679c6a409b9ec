"""Unified timeline export (PR 16): the acceptance suite.

Covers the tentpole surfaces end to end: Chrome-trace schema validity
of `export()`'s output (every event well-formed by `ph` type, flow
begin/end ids pairing in order), a depth-2 real-HTTP run where one
request's span track provably links to its witness + root + sig batch
tracks via flow ids, tail-sampling determinism (an SLO violator is
ALWAYS kept, the uniform sampler is injected-RNG pinned, the drop
counters reconcile exactly with offered load), bounded memory under
overflow (oldest kept entry evicted, `reason=ring_full` counted), and
`GET /debug/timeline` routing on BOTH servers incl. the bad-window 400
— plus the satellite surfaces: the near-budget `/debug/slow` tier and
the `--flight-ring` config/resize path.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import phant_tpu.obs.flight
from phant_tpu.engine_api.server import EngineAPIServer, MetricsServer
from phant_tpu.obs import critpath, timeline

# the package re-exports the RECORDER INSTANCE under the same name as the
# submodule (obs.flight), so grab the module itself for refresh/resize
flight_mod = sys.modules["phant_tpu.obs.flight"]
from phant_tpu.serving import SchedulerConfig
from phant_tpu.utils.trace import metrics

from test_serving import _post, _stateless_request


@pytest.fixture(autouse=True)
def _fresh_timeline():
    """Every test starts from a clean, enabled recorder with the default
    config; teardown restores the defaults (the module is process-global
    state shared across the suite)."""
    timeline.refresh_from_env()
    timeline.reset()
    timeline.configure(
        enabled=True, sample_n=16, ring=1024, dirpath="", keep=8,
        rng=random.Random(),
    )
    critpath.refresh_from_env()
    critpath.configure(enabled=True)
    yield
    timeline.configure(
        enabled=True, sample_n=16, ring=1024, dirpath="", keep=8,
        rng=random.Random(),
    )
    timeline.reset()
    critpath.configure(
        enabled=True, budget_ms=0.0, phase_budgets_ms={},
        near_pct=0.0, near_sample_n=8, near_rng=random.Random(),
    )


def _get_json(base: str, path: str):
    try:
        with urllib.request.urlopen(base + path, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _span_record(
    trace_id: str,
    dur_ms: float = 5.0,
    error: str | None = None,
    phases: dict | None = None,
    **attrs,
):
    """A minimal top-level verify_block span record as trace.span() would
    hand the sinks (totals, not offsets)."""
    rec = {
        "span": "verify_block",
        "duration_ms": dur_ms,
        "trace_id": trace_id,
        "block": 1,
        "phases": phases
        or {"stateless.witness_verify": {"count": 1, "total_ms": dur_ms / 2}},
    }
    if error:
        rec["error"] = error
    rec.update(attrs)
    return rec


def _validate_chrome_trace(payload: dict):
    """Schema validity: every event well-formed by ph type; flow s/f ids
    pair 1:1 with the `s` strictly before its `f`. Returns the events."""
    assert isinstance(payload["traceEvents"], list)
    assert payload["displayTimeUnit"] == "ms"
    s_events: dict = {}
    f_events: dict = {}
    for ev in payload["traceEvents"]:
        assert ev["ph"] in ("M", "X", "s", "f", "i"), ev
        assert isinstance(ev["pid"], int) and ev["pid"] >= 1, ev
        assert isinstance(ev["tid"], int), ev
        assert isinstance(ev["ts"], int) and ev["ts"] >= 0, ev
        assert isinstance(ev["name"], str) and ev["name"], ev
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], int) and ev["dur"] >= 1, ev
        if ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name"), ev
            assert ev["args"]["name"], ev
        if ev["ph"] == "s":
            assert ev["id"] not in s_events, f"duplicate flow start {ev}"
            s_events[ev["id"]] = ev
        if ev["ph"] == "f":
            assert ev["bp"] == "e", ev  # bind to enclosing slice
            assert ev["id"] not in f_events, f"duplicate flow finish {ev}"
            f_events[ev["id"]] = ev
        if ev["ph"] == "i":
            assert ev["s"] in ("t", "p", "g"), ev
    assert set(s_events) == set(f_events), "unpaired flow events"
    for fid, s_ev in s_events.items():
        assert s_ev["ts"] < f_events[fid]["ts"], f"flow {fid} out of order"
    return payload["traceEvents"]


# ---------------------------------------------------------------------------
# schema validity + flow pairing (offline, synthetic records)
# ---------------------------------------------------------------------------


def test_export_schema_valid_and_flows_pair():
    timeline.configure(sample_n=1)  # keep everything
    # two requests served by the same witness batch, one by a root batch
    timeline.on_span(_span_record("req-a", batch_id=7, root_batch_id=9))
    timeline.on_span(_span_record("req-b", batch_id=7))
    timeline.record_batch(
        {"batch_id": 7, "device": "0", "batch_size": 2, "backend": "fused",
         "pack_ms": 0.4, "prefetch_ms": 0.2, "resolve_ms": 0.3},
        lane="witness", duration_ms=3.0, trace_ids=["req-a", "req-b"],
    )
    timeline.record_batch(
        {"batch_id": 9, "device": "0", "batch_size": 1},
        lane="root", duration_ms=1.0, trace_ids=["req-a"],
    )
    payload = timeline.export(60.0)
    events = _validate_chrome_trace(payload)
    flows = {e["id"] for e in events if e["ph"] == "s"}
    assert flows == {"witness:7:req-a", "witness:7:req-b", "root:9:req-a"}
    # the batch's stage sub-slices never escape the batch interval
    batch = next(
        e for e in events
        if e["ph"] == "X" and e["name"] == "witness batch"
    )
    for st in (e for e in events if e.get("cat") == "stage"):
        if st["tid"] != batch["tid"]:
            continue
        assert st["ts"] >= batch["ts"]
        assert st["ts"] + st["dur"] <= batch["ts"] + batch["dur"]
    # no host-clock bracket is drawn under a device's name any more (PR
    # 26): what the chip is busy under is read from the profiler's trace
    assert not any(
        e["ph"] == "M" and e["args"]["name"] == "devices" for e in events
    )
    assert payload["metadata"]["kept"] == {"sample": 2}


def test_flow_start_only_for_batches_inside_window():
    """A request whose serving batch fell outside the window must NOT
    emit a dangling `s` — pairing is guaranteed at export time."""
    timeline.configure(sample_n=1)
    timeline.on_span(_span_record("lonely", batch_id=42))
    payload = timeline.export(60.0)  # batch 42 was never recorded
    events = _validate_chrome_trace(payload)
    assert not [e for e in events if e["ph"] in ("s", "f")]
    # the request slice itself IS there
    assert any(
        e["ph"] == "X" and e.get("args", {}).get("trace_id") == "lonely"
        for e in events
    )


def test_profile_capture_emits_clock_sync():
    timeline.configure(sample_n=1)
    t1 = time.time()
    timeline.record_profile("/tmp/prof-x", t1 - 0.5, t1)
    payload = timeline.export(60.0)
    events = _validate_chrome_trace(payload)
    names = [e["name"] for e in events if e["ph"] == "i"]
    assert names == ["capture_start", "capture_end"]
    assert payload["metadata"]["clock_sync"] == [
        {"path": "/tmp/prof-x", "start_us": int((t1 - 0.5) * 1e6),
         "end_us": int(t1 * 1e6)}
    ]


# ---------------------------------------------------------------------------
# tail-sampling: determinism + reconciliation
# ---------------------------------------------------------------------------


def test_tail_sampling_deterministic_and_reconciles():
    """The uniform sampler is RNG-pinned; an SLO violator and a crashed
    request are kept regardless of the sampler; kept + sampled_out
    reconciles EXACTLY with offered load."""
    n = 4
    timeline.configure(sample_n=n, rng=random.Random(0xBEEF))
    critpath.configure(budget_ms=100.0)
    twin = random.Random(0xBEEF)
    offered = 0
    expect_sample = 0
    for i in range(40):
        timeline.on_span(_span_record(f"u{i}", dur_ms=1.0))
        offered += 1
        if twin.randrange(n) == 0:
            expect_sample += 1
    # the violator (wall > budget) is kept WITHOUT consuming the sampler
    timeline.on_span(_span_record("slow", dur_ms=250.0))
    # the crash is kept even though it also blew the budget: error wins
    timeline.on_span(_span_record("boom", dur_ms=300.0, error="RuntimeError"))
    offered += 2
    st = timeline.stats()
    assert st["kept"].get("sample", 0) == expect_sample
    assert st["kept"].get("slo", 0) == 1
    assert st["kept"].get("error", 0) == 1
    kept_total = sum(st["kept"].values())
    assert kept_total + st["dropped"].get("sampled_out", 0) == offered
    # the kept entries carry their reason (the export shows it)
    events = timeline.export(60.0)["traceEvents"]
    by_trace = {
        e["args"]["trace_id"]: e["args"]
        for e in events
        if e["ph"] == "X" and e.get("cat") == "request"
    }
    assert by_trace["slow"]["reason"] == "slo"
    assert by_trace["boom"]["reason"] == "error"
    assert by_trace["boom"]["error"] == "RuntimeError"


def test_sample_n_zero_keeps_nothing_uniform():
    timeline.configure(sample_n=0)
    for i in range(10):
        timeline.on_span(_span_record(f"z{i}"))
    st = timeline.stats()
    assert st["kept"] == {}
    assert st["dropped"] == {"sampled_out": 10}


def test_p99_exemplar_kept_once_thresholds_warm():
    """With the uniform sampler OFF, a phase outlier is still kept once
    the rolling per-phase histogram has enough samples to trust a p99."""
    timeline.configure(sample_n=0)
    # warm the evm histogram past _P99_MIN_COUNT and through a recache
    for i in range(100):
        timeline.on_span(_span_record(
            f"w{i}", dur_ms=1.2,
            phases={"stateless.execute": {"count": 1, "total_ms": 1.0}},
        ))
    timeline.on_span(_span_record(
        "outlier", dur_ms=60.0,
        phases={"stateless.execute": {"count": 1, "total_ms": 55.0}},
    ))
    st = timeline.stats()
    assert st["kept"].get("p99", 0) >= 1
    events = timeline.export(60.0)["traceEvents"]
    out = next(
        e for e in events
        if e["ph"] == "X" and e.get("args", {}).get("trace_id") == "outlier"
    )
    assert out["args"]["reason"] == "p99"


def test_disabled_recorder_is_a_no_op():
    timeline.configure(enabled=False, sample_n=1)
    timeline.on_span(_span_record("off"))
    timeline.record_batch({"batch_id": 1}, lane="witness", duration_ms=1.0,
                          trace_ids=["off"])
    assert not timeline.enabled()
    assert timeline.stats() == {"kept": {}, "dropped": {}}
    timeline.configure(enabled=True)
    assert timeline.export(60.0)["metadata"]["requests"] == 0


# ---------------------------------------------------------------------------
# bounded memory under overflow
# ---------------------------------------------------------------------------


def test_ring_overflow_evicts_oldest_and_counts_ring_full():
    timeline.configure(sample_n=1, ring=8)
    assert timeline.capacity() == 8
    for i in range(50):
        timeline.on_span(_span_record(f"t{i}"))
    st = timeline.stats()
    assert st["kept"] == {"sample": 50}
    assert st["dropped"] == {"ring_full": 42}
    payload = timeline.export(3600.0)
    traces = sorted(
        e["args"]["trace_id"]
        for e in payload["traceEvents"]
        if e["ph"] == "X" and e.get("cat") == "request"
    )
    # exactly the NEWEST 8 survive; the oldest 42 were evicted
    assert traces == sorted(f"t{i}" for i in range(42, 50))
    # the drop counters rode to the metrics family too
    counters = metrics.snapshot()["counters"]
    assert counters.get('obs.timeline_kept{reason="sample"}', 0) >= 50
    assert counters.get('obs.timeline_dropped{reason="ring_full"}', 0) >= 42


def test_spool_rotates_and_keeps_newest(tmp_path):
    timeline.configure(sample_n=1, dirpath=str(tmp_path), keep=2)
    timeline.on_span(_span_record("sp"))
    for _ in range(4):
        timeline.export(60.0)
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 2, files
    for f in files:
        with open(tmp_path / f) as fh:
            _validate_chrome_trace(json.load(fh))


# ---------------------------------------------------------------------------
# the REAL serving path: depth 2, all three lanes, flow linkage over HTTP
# ---------------------------------------------------------------------------


def test_request_links_to_all_three_lane_batches_over_http(monkeypatch):
    """The tentpole acceptance: real engine_executeStatelessPayloadV1
    traffic with the witness + batched-root + batched-sig lanes engaged;
    `GET /debug/timeline` must return valid Chrome-trace JSON in which
    at least one request's span connects by flow events to the witness,
    root, AND sig batches that served it — with handler-thread, lane,
    and device tracks all present."""
    monkeypatch.setenv("PHANT_BATCHED_ROOT", "1")
    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    chain, rpc, want_root = _stateless_request()
    server = EngineAPIServer(
        chain,
        host="127.0.0.1",
        port=0,
        sched_config=SchedulerConfig(
            max_batch=8, max_wait_ms=5.0, pipeline_depth=2
        ),
    )
    # AFTER construction (which re-resolves the memoized config from the
    # env): keep every request so the flow-linkage assert is deterministic
    timeline.configure(sample_n=1)
    timeline.reset()
    server.serve_in_background()
    try:
        base = f"http://127.0.0.1:{server.port}"
        with ThreadPoolExecutor(max_workers=4) as pool:
            for code, body in pool.map(lambda _i: _post(base, rpc), range(8)):
                assert code == 200 and body["result"]["status"] == "VALID", body
                assert body["result"]["stateRoot"] == want_root
        st = server.scheduler.stats_snapshot()
        assert st["root_batches"] >= 1 and st["sig_batches"] >= 1, st
        status, payload = _get_json(base, "/debug/timeline?window=60")
    finally:
        server.shutdown()
    assert status == 200
    events = _validate_chrome_trace(payload)
    # all three track families are named
    proc_names = {
        e["args"]["name"]
        for e in events
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert {"requests", "lanes"} <= proc_names, proc_names
    # at least one request flows to a batch on EVERY lane
    f_ids = {e["id"] for e in events if e["ph"] == "f"}
    linked = {}
    for e in events:
        if e["ph"] != "s":
            continue
        lane, _bid, trace_id = e["id"].split(":", 2)
        assert e["id"] in f_ids  # _validate checked pairing; be explicit
        linked.setdefault(trace_id, set()).add(lane)
    assert any(
        lanes >= {"witness", "root", "sig"} for lanes in linked.values()
    ), f"no request linked to all three lanes: {linked}"
    # the lane tracks carry per-lane thread names
    lane_tracks = {
        e["args"]["name"]
        for e in events
        if e["ph"] == "M" and e["name"] == "thread_name" and e["pid"] == 2
    }
    assert any("witness lane" in n for n in lane_tracks), lane_tracks
    assert any("root lane" in n for n in lane_tracks), lane_tracks
    assert any("sig lane" in n for n in lane_tracks), lane_tracks
    assert payload["metadata"]["requests"] >= 8


# ---------------------------------------------------------------------------
# /debug/timeline routing: BOTH servers, bad-window 400, healthz echo
# ---------------------------------------------------------------------------


def test_timeline_endpoint_on_both_servers_and_bad_window():
    timeline.configure(sample_n=1)
    timeline.on_span(_span_record("routed"))
    chain, _rpc, _root = _stateless_request()
    server = EngineAPIServer(chain, host="127.0.0.1", port=0)
    server.serve_in_background()
    try:
        base = f"http://127.0.0.1:{server.port}"
        status, payload = _get_json(base, "/debug/timeline?window=5")
        assert status == 200
        _validate_chrome_trace(payload)
        # default window when the param is absent
        status, _payload = _get_json(base, "/debug/timeline")
        assert status == 200
        for bad in ("abc", "-1", "0", "inf", "nan"):
            status, body = _get_json(base, f"/debug/timeline?window={bad}")
            assert status == 400, (bad, body)
            assert "window" in body["error"]
        # /healthz echoes every debug-ring capacity
        status, health = _get_json(base, "/healthz")
        assert status == 200
        assert health["debug_rings"] == {
            "flight": flight_mod.flight.capacity,
            "slow": critpath.slow.capacity,
            "timeline": timeline.capacity(),
        }
    finally:
        server.shutdown()
    srv = MetricsServer(host="127.0.0.1", port=0)
    srv.serve_in_background()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        status, payload = _get_json(base, "/debug/timeline?window=5")
        assert status == 200
        _validate_chrome_trace(payload)
        status, _body = _get_json(base, "/debug/timeline?window=oops")
        assert status == 400
    finally:
        srv.shutdown()


def test_cli_env_flags_take_effect_at_server_construction(monkeypatch):
    """The --timeline-* / --flight-ring flags land in the env before the
    server is built; construction must re-resolve the memoized configs
    (the env-read-per-event anti-pattern stays dead — a LATER env change
    without a refresh is invisible)."""
    monkeypatch.setenv("PHANT_TIMELINE_SAMPLE_N", "3")
    monkeypatch.setenv("PHANT_TIMELINE_RING", "77")
    monkeypatch.setenv("PHANT_FLIGHT_RING", "99")
    chain, _rpc, _root = _stateless_request()
    server = EngineAPIServer(chain, host="127.0.0.1", port=0)
    server.serve_in_background()  # shutdown() joins the serve loop
    try:
        assert timeline.capacity() == 77
        assert flight_mod.flight.capacity == 99
        # a later env write WITHOUT a refresh changes nothing
        monkeypatch.setenv("PHANT_TIMELINE_RING", "5")
        assert timeline.capacity() == 77
    finally:
        server.shutdown()
        monkeypatch.delenv("PHANT_TIMELINE_SAMPLE_N")
        monkeypatch.delenv("PHANT_TIMELINE_RING")
        monkeypatch.delenv("PHANT_FLIGHT_RING")
        flight_mod.refresh_from_env()


# ---------------------------------------------------------------------------
# satellite: the near-budget /debug/slow tier
# ---------------------------------------------------------------------------


def test_near_budget_tier_sampled_capture():
    critpath.configure(
        budget_ms=100.0, near_pct=20.0, near_sample_n=1,
        near_rng=random.Random(7),
    )
    critpath.slow.clear()
    # inside the near window (> 80ms, <= 100ms): captured, trigger=near,
    # over_ms NEGATIVE (the remaining headroom)
    critpath.rollup(_span_record("near-1", dur_ms=90.0))
    recs = critpath.slow.records()
    assert recs and recs[-1]["trigger"] == "near"
    assert recs[-1]["over_ms"] == pytest.approx(-10.0)
    counters = metrics.snapshot()["counters"]
    assert counters.get('obs.slow_captures{trigger="near"}', 0) >= 1
    # a true violator still reads trigger=wall (the tiers don't collide)
    critpath.rollup(_span_record("over-1", dur_ms=150.0))
    assert critpath.slow.records()[-1]["trigger"] == "wall"
    # below the near window: nothing captured
    critpath.slow.clear()
    critpath.rollup(_span_record("fast-1", dur_ms=10.0))
    assert critpath.slow.records() == []
    # near_sample_n=0 disables the tier even inside the window
    critpath.configure(near_sample_n=0)
    critpath.rollup(_span_record("near-2", dur_ms=95.0))
    assert critpath.slow.records() == []


def test_near_budget_sampler_pinned():
    n = 3
    critpath.configure(
        budget_ms=100.0, near_pct=50.0, near_sample_n=n,
        near_rng=random.Random(0xCAFE),
    )
    critpath.slow.clear()
    twin = random.Random(0xCAFE)
    expect = 0
    for i in range(30):
        critpath.rollup(_span_record(f"n{i}", dur_ms=75.0))
        if twin.randrange(n) == 0:
            expect += 1
    got = [r for r in critpath.slow.records() if r["trigger"] == "near"]
    assert len(got) == expect


# ---------------------------------------------------------------------------
# satellite: --flight-ring config + resize
# ---------------------------------------------------------------------------


def test_flight_ring_resize_keeps_newest():
    fr = flight_mod.FlightRecorder(capacity=4)
    for i in range(6):
        fr.record("t.event", seq_no=i)
    assert [r["seq_no"] for r in fr.records()] == [2, 3, 4, 5]
    fr.resize(2)
    assert fr.capacity == 2
    assert [r["seq_no"] for r in fr.records()] == [4, 5]
    fr.resize(8)  # growing keeps what survived
    assert fr.capacity == 8
    assert [r["seq_no"] for r in fr.records()] == [4, 5]
    fr.record("t.event", seq_no=6)
    assert len(fr.records()) == 3


def test_flight_ring_env_refresh(monkeypatch):
    old = flight_mod.flight.capacity
    try:
        monkeypatch.setenv("PHANT_FLIGHT_RING", "4096")
        flight_mod.refresh_from_env()
        assert flight_mod.flight.capacity == 4096
        # the legacy name still works when the new one is absent
        monkeypatch.delenv("PHANT_FLIGHT_RING")
        monkeypatch.setenv("PHANT_FLIGHT_CAPACITY", "512")
        flight_mod.refresh_from_env()
        assert flight_mod.flight.capacity == 512
        # garbage falls back to the default instead of crashing
        monkeypatch.setenv("PHANT_FLIGHT_CAPACITY", "banana")
        flight_mod.refresh_from_env()
        assert flight_mod.flight.capacity == 2048
    finally:
        monkeypatch.delenv("PHANT_FLIGHT_RING", raising=False)
        monkeypatch.delenv("PHANT_FLIGHT_CAPACITY", raising=False)
        flight_mod.flight.resize(old)
