"""Critical-path latency attribution (PR 15): the acceptance suite.

Covers the tentpole surfaces end to end: the rollup's tiling math (pure
unit), the >= 95% wall-clock coverage assert on the REAL serving path —
depths 1 AND 2, all three engine lanes (witness + root + sig) engaged
through a live EngineAPIServer — the derived p50/p99 quantile gauges in the
exposition (front-door histogram included), `POST /debug/profile`'s
single-flight guard + artifact-on-disk contract, and `/debug/slow`
exemplar capture under an induced slow request.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from phant_tpu.engine_api.server import EngineAPIServer, MetricsServer
from phant_tpu.obs import critpath, profiler
from phant_tpu.ops.witness_engine import WitnessEngine
from phant_tpu.serving import SchedulerConfig, VerificationScheduler
from phant_tpu.utils.trace import (
    REQUEST_SECONDS_BUCKETS,
    Metrics,
    histogram_quantile,
    metrics,
    span,
    trace_context,
)

from test_obs import _witness_set
from test_serving import _post, _stateless_request


@pytest.fixture(autouse=True)
def _fresh_attribution(monkeypatch):
    """Every test starts from the default-on attribution config and its
    own coverage window; the memoized config is restored from the (test-
    scoped) env afterwards."""
    critpath.refresh_from_env()
    critpath.configure(enabled=True)
    critpath.reset_totals()
    yield
    # deterministic teardown (monkeypatched env may still be live here):
    # back to enabled, no budgets
    critpath.configure(enabled=True, budget_ms=0.0, phase_budgets_ms={})


def _get_json(base: str, path: str):
    with urllib.request.urlopen(base + path, timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def _post_raw(base: str, path: str, timeout: float = 60.0):
    req = urllib.request.Request(base + path, data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ---------------------------------------------------------------------------
# the tiling math (pure unit)
# ---------------------------------------------------------------------------


def test_attribute_tiles_wall_clock_exactly():
    """The sub-tilings must sum exactly to their parent phases, the
    remainder labels must absorb what the batch records did not name,
    and the residual is wall minus the top-level phases."""
    record = {
        "span": "verify_block",
        "duration_ms": 100.0,
        "queue_wait_ms": 5.0,
        "prefetch_ms": 2.0,
        "pack_ms": 3.0,
        "resolve_ms": 10.0,
        "root_queue_wait_ms": 4.0,
        "phases": {
            "stateless.sig_rows": {"count": 1, "total_ms": 1.0},
            "stateless.witness_verify": {"count": 1, "total_ms": 40.0},
            "stateless.witness_decode": {"count": 1, "total_ms": 8.0},
            "stateless.execute": {"count": 1, "total_ms": 30.0},
            "sched.sig_wait": {"count": 1, "total_ms": 6.0},
            "stateless.post_root": {"count": 1, "total_ms": 20.0},
            "stateless.post_root_plan": {"count": 1, "total_ms": 3.0},
        },
    }
    breakdown, unattributed, wall = critpath.attribute(record)
    assert wall == 100.0
    assert set(breakdown) <= set(critpath.PHASES)
    # witness_verify tiles exactly: 5 + 2 + 3 + 10 + dispatch(20) = 40
    assert breakdown["queue_wait"] == 5.0
    assert breakdown["prefetch"] == 2.0
    assert breakdown["pack"] == 3.0
    assert breakdown["resolve"] == 10.0
    assert breakdown["dispatch"] == pytest.approx(20.0)
    # execute tiles: sig_wait(6) + evm(24) = 30
    assert breakdown["sig_wait"] == 6.0
    assert breakdown["evm"] == pytest.approx(24.0)
    # post_root tiles: plan(3) + root_wait(4) + post_root(13) = 20
    assert breakdown["root_plan"] == 3.0
    assert breakdown["root_wait"] == 4.0
    assert breakdown["post_root"] == pytest.approx(13.0)
    assert breakdown["sig_rows"] == 1.0
    assert breakdown["witness_decode"] == 8.0
    # top-level phases: 1 + 40 + 8 + 30 + 20 = 99 -> residual 1
    assert sum(breakdown.values()) == pytest.approx(99.0)
    assert unattributed == pytest.approx(1.0)


def test_attribute_clips_overstated_batch_stages():
    """A batch-record stage timing can exceed the request's own phase
    window (coalesced neighbors, pipeline overlap): clipping must keep
    the witness sub-tiling bounded by the phase the request measured —
    attributed can never exceed wall."""
    record = {
        "span": "verify_block",
        "duration_ms": 10.0,
        "queue_wait_ms": 50.0,  # overstated vs the 8ms phase
        "pack_ms": 50.0,
        "resolve_ms": 50.0,
        "phases": {
            "stateless.witness_verify": {"count": 1, "total_ms": 8.0},
        },
    }
    breakdown, unattributed, wall = critpath.attribute(record)
    assert breakdown["queue_wait"] == 8.0
    assert "pack" not in breakdown  # nothing left after the clip
    assert sum(breakdown.values()) == pytest.approx(8.0)
    assert unattributed == pytest.approx(2.0)
    # malformed records: no phases at all -> everything unattributed
    b2, u2, w2 = critpath.attribute({"span": "verify_block", "duration_ms": 5.0})
    assert b2 == {} and u2 == 5.0 and w2 == 5.0


def test_rollup_disabled_emits_nothing():
    m0 = metrics.snapshot()["counters"].get("critpath.requests", 0)
    critpath.configure(enabled=False)
    with span("verify_block", block=1, nodes=0, codes=0):
        time.sleep(0.001)
    assert metrics.snapshot()["counters"].get("critpath.requests", 0) == m0


# ---------------------------------------------------------------------------
# derived quantiles + the shared front-door bucket table
# ---------------------------------------------------------------------------


def test_histogram_quantile_interpolation():
    buckets = (0.1, 0.2, 0.4)
    # 10 samples in (0.1, 0.2]: p50 -> half-way through that bucket
    counts = [0, 10, 0, 0]
    assert histogram_quantile(buckets, counts, 0.5) == pytest.approx(0.15)
    # uniform across the first two buckets
    assert histogram_quantile(buckets, [5, 5, 0, 0], 0.5) == pytest.approx(0.1)
    # rank in the +Inf slot clamps to the last finite bound
    assert histogram_quantile(buckets, [0, 0, 0, 4], 0.99) == 0.4
    # empty histogram
    assert histogram_quantile(buckets, [0, 0, 0, 0], 0.5) == 0.0


def test_prometheus_text_carries_derived_quantile_gauges():
    m = Metrics()
    for v in (0.003,) * 50 + (0.2,) * 50:
        m.observe_hist("engine_api.request_seconds", v, buckets=REQUEST_SECONDS_BUCKETS)
    text = m.prometheus_text()
    lines = {l.split(" ")[0]: l for l in text.splitlines() if not l.startswith("#")}
    assert "phant_engine_api_request_seconds_p50" in lines
    assert "phant_engine_api_request_seconds_p99" in lines
    p99 = float(lines["phant_engine_api_request_seconds_p99"].split(" ")[1])
    # 99th of 50x3ms + 50x200ms sits in the (0.1, 0.25] bucket
    assert 0.1 < p99 <= 0.25
    assert "# TYPE phant_engine_api_request_seconds_p99 gauge" in text
    # labeled families derive per-series quantiles
    m.observe_hist("critpath.phase_seconds", 0.05, phase="evm")
    text = m.prometheus_text()
    assert 'phant_critpath_phase_seconds_p99{phase="evm"}' in text


def test_front_door_histogram_rides_the_shared_bucket_table():
    """The request-latency bucket table is ONE module-level constant with
    an overload tail — buckets freeze at first observation, so a drifted
    second call site would silently split the family, and without the
    tail the derived p99 clamps at 10s exactly under overload."""
    assert REQUEST_SECONDS_BUCKETS[-2:] == (30.0, 60.0)
    import phant_tpu.engine_api.server as server_mod

    assert server_mod.REQUEST_SECONDS_BUCKETS is REQUEST_SECONDS_BUCKETS


MS = 1_000_000  # ns


def _waited_record(stages: dict, waits, decode) -> dict:
    """A verify_block record as the served path reports it since PR 29:
    measured waits for the witness lane, the decode between them, the
    batch's measured stages (all on one clock, ns)."""
    intervals = [["stateless.witness_verify", a, b] for a, b in waits]
    intervals.insert(1, ["stateless.witness_decode", *decode])
    total = lambda name: sum(b - a for n, a, b in intervals if n == name) / MS  # noqa: E731
    return {
        "span": "verify_block",
        "duration_ms": 100.0,
        "start_ns": 0,
        "end_ns": 100 * MS,
        # the record's *_ms say what the lanes took, not what the handler
        # waited: measured stages outrank them
        "queue_wait_ms": 5.0, "prefetch_ms": 9.0, "pack_ms": 10.0, "resolve_ms": 50.0,
        "stages": stages,
        "intervals": intervals,
        "phases": {
            "stateless.witness_verify": {"count": len(waits), "total_ms": total("stateless.witness_verify")},
            "stateless.witness_decode": {"count": 1, "total_ms": total("stateless.witness_decode")},
        },
    }


def test_attribute_cuts_the_handlers_waits_at_the_measured_stages():
    """Two waits around the decode; resolve runs 35..90, so 35..58 of it
    (and the 30..35 the resolve worker spent on another lane's batch) lie
    under witness_decode: in no phase. What is left tiles exactly."""
    record = _waited_record(
        {"prefetch": [5 * MS, 14 * MS], "pack": [20 * MS, 30 * MS], "resolve": [35 * MS, 90 * MS]},
        waits=[(0, 30 * MS), (58 * MS, 92 * MS)],
        decode=(30 * MS, 58 * MS),
    )
    breakdown, unattributed, wall = critpath.attribute(record)
    assert breakdown == pytest.approx(
        {
            "queue_wait": 5.0,  # admission to the batch's first stage
            "prefetch": 9.0,
            "pack": 10.0,
            "dispatch": 6.0 + 2.0,  # 14..20 between stages, 90..92 the tail
            "resolve": 32.0,  # 58..90 of its 55 ms: the part waited for
            "witness_decode": 28.0,  # its measured width, untouched
        }
    )
    witness = sum(v for k, v in breakdown.items() if k != "witness_decode")
    assert witness == pytest.approx(30.0 + 34.0)  # the two waits, exactly
    assert unattributed == pytest.approx(wall - 92.0)


def test_attribute_with_overlapping_stages_tiles_exactly_and_hides_the_decode():
    """Stage intervals that overlap each other (a coalesced neighbour's
    pack still running under this batch's resolve) and the decode: every
    nanosecond of a wait goes to ONE phase, the earlier stage winning the
    overlap; the seconds under witness_decode go to none."""
    record = _waited_record(
        {"prefetch": [2 * MS, 12 * MS], "pack": [10 * MS, 26 * MS], "resolve": [24 * MS, 70 * MS]},
        waits=[(0, 25 * MS), (50 * MS, 75 * MS)],
        decode=(25 * MS, 50 * MS),
    )
    breakdown, _unattributed, _wall = critpath.attribute(record)
    assert breakdown == pytest.approx(
        {
            "queue_wait": 2.0,
            "prefetch": 10.0,  # 2..12, the overlap 10..12 with pack is prefetch's
            "pack": 13.0,  # 12..25: cut by the first wait's end
            "resolve": 20.0,  # 50..70: 24..50 ran under pack's tail and the decode
            "dispatch": 5.0,  # 70..75
            "witness_decode": 25.0,
        }
    )
    waited = (25 + 25) * 1.0
    assert sum(v for k, v in breakdown.items() if k != "witness_decode") == pytest.approx(waited)
    # hidden: resolve ran 46 ms, 20 of them waited for; pack's 25..26 too
    assert breakdown["resolve"] + breakdown["pack"] < 46.0 + 16.0
    # tile_wait itself: contiguous, exclusive, inside the wait
    pieces = critpath.tile_wait(50 * MS, 75 * MS, record["stages"])
    assert pieces == [("resolve", 50 * MS, 70 * MS), ("dispatch", 70 * MS, 75 * MS)]


@pytest.mark.parametrize(
    "stages",
    [None, {}, {"resolve": "soon"}, {"pack": [5, 1]}, {"dispatch": [3 * MS, 9 * MS]}],
    ids=["none", "empty", "malformed", "backwards", "depth1"],
)
def test_attribute_without_lane_stages_never_claims_more_than_the_wait(stages):
    record = _waited_record(stages, waits=[(0, 12 * MS)], decode=(12 * MS, 20 * MS))
    record["prefetch_ms"] = record["pack_ms"] = record["resolve_ms"] = None
    breakdown, _un, _wall = critpath.attribute(record)
    witness = {k: v for k, v in breakdown.items() if k != "witness_decode"}
    assert sum(witness.values()) == pytest.approx(12.0)
    if stages == {"dispatch": [3 * MS, 9 * MS]}:
        # a depth-1 batch: one fused stage ends the queue wait
        assert witness == pytest.approx({"queue_wait": 3.0, "dispatch": 9.0})
    else:
        # no measured stage: the record's own queue_wait_ms, clipped
        assert witness == pytest.approx({"queue_wait": 5.0, "dispatch": 7.0})


# ---------------------------------------------------------------------------
# the second clock (PR 38): the handler's CPU inside each phase
# ---------------------------------------------------------------------------

#: the span's phases with the labels `attribute` tiles each into
_SUB_TILINGS = {
    "stateless.sig_rows": ("sig_rows",),
    "stateless.witness_decode": ("witness_decode",),
    "stateless.witness_verify": ("queue_wait", "prefetch", "pack", "dispatch", "resolve"),
    "stateless.execute": ("sig_wait", "evm"),
    "stateless.post_root": ("root_plan", "root_wait", "post_root"),
}


def _flat_record(cpu: dict) -> dict:
    """`test_attribute_tiles_wall_clock_exactly`'s record with `cpu_ms`
    beside each `total_ms`, as `Span.to_dict` reports them since PR 38."""
    walls = {
        "stateless.sig_rows": 1.0,
        "stateless.witness_verify": 40.0,
        "stateless.witness_decode": 8.0,
        "stateless.execute": 30.0,
        "sched.sig_wait": 6.0,
        "stateless.post_root": 20.0,
        "stateless.post_root_plan": 3.0,
    }
    return {
        "span": "verify_block",
        "duration_ms": 100.0,
        "queue_wait_ms": 5.0, "prefetch_ms": 2.0, "pack_ms": 3.0, "resolve_ms": 10.0,
        "root_queue_wait_ms": 4.0,
        "phases": {
            k: {"count": 1, "total_ms": w, **({"cpu_ms": cpu[k]} if k in cpu else {})}
            for k, w in walls.items()
        },
    }  # fmt: skip


def _staged_record(cpu: dict) -> dict:
    record = _waited_record(
        {"prefetch": [5 * MS, 14 * MS], "pack": [20 * MS, 30 * MS], "resolve": [35 * MS, 90 * MS]},
        waits=[(0, 30 * MS), (58 * MS, 92 * MS)],
        decode=(30 * MS, 58 * MS),
    )
    for k, v in cpu.items():
        record["phases"][k]["cpu_ms"] = v
    return record


_ALONE = {  # a lone request: every phase computes, the waits sleep
    "stateless.sig_rows": 0.9, "stateless.witness_verify": 0.4, "stateless.witness_decode": 7.5,
    "stateless.execute": 23.0, "sched.sig_wait": 0.1, "stateless.post_root": 15.0,
    "stateless.post_root_plan": 2.9,
}  # fmt: skip
_IN_A_CROWD = {  # sixteen handlers on one lock: a tenth of each phase is work
    "stateless.sig_rows": 0.1, "stateless.witness_verify": 0.2, "stateless.witness_decode": 0.8,
    "stateless.execute": 3.0, "sched.sig_wait": 0.0, "stateless.post_root": 2.0,
    "stateless.post_root_plan": 0.3,
}  # fmt: skip
_OVERSTATED = {  # more CPU than wall (a nested phase's rounding, a clock's step)
    "stateless.sig_rows": 1.5, "stateless.witness_verify": 45.0, "stateless.witness_decode": 8.0,
    "stateless.execute": 31.0, "sched.sig_wait": 7.0, "stateless.post_root": 25.0,
    "stateless.post_root_plan": 3.5,
}  # fmt: skip


@pytest.mark.parametrize(
    "record",
    [
        _flat_record(_ALONE),
        _flat_record(_IN_A_CROWD),
        _flat_record(_OVERSTATED),
        _flat_record({"stateless.execute": 12.0}),  # one phase alone carries a reading
        _staged_record({"stateless.witness_verify": 1.5, "stateless.witness_decode": 27.0}),
        _staged_record({"stateless.witness_verify": 70.0, "stateless.witness_decode": 30.0}),
    ],
    ids=["alone", "crowd", "overstated", "one-phase", "staged", "staged-overstated"],
)
def test_attribute_cpu_never_passes_the_wall_and_tiles_its_parents(record):
    """cpu <= wall for every phase, and each parent's CPU is the sum of
    what its sub-tiling was given plus what the wall could not hold (a
    record that overstates it: a CPU clock that steps by ticks), which
    comes back apart, under the parent's catch-all: nothing counted
    twice, nothing invented, nothing dropped."""
    breakdown, _un, _wall = critpath.attribute(record)
    cpu, over = critpath.attribute_cpu(record, breakdown)
    assert set(cpu) <= set(breakdown)
    assert set(over) <= {labels[0] for _p, _n, labels in critpath._CPU_TILING}  # the catch-alls
    for label, c in cpu.items():
        assert 0.0 < c <= breakdown[label] + 1e-9, label
    phases = record["phases"]
    for parent, labels in _SUB_TILINGS.items():
        if parent not in phases:
            continue
        read = phases[parent].get("cpu_ms", 0.0)
        if parent == "stateless.execute":  # sig_wait's CPU is its own reading's
            own = min(phases["sched.sig_wait"].get("cpu_ms", 0.0), breakdown["sig_wait"])
            read = max(read - phases["sched.sig_wait"].get("cpu_ms", 0.0), 0.0) + own
        wall = sum(breakdown.get(l, 0.0) for l in labels)
        given = sum(cpu.get(l, 0.0) for l in labels)
        assert given == pytest.approx(min(read, wall)), parent
    # nothing dropped: what the parents read is given to a phase or kept apart
    read_of = lambda name: phases.get(name, {}).get("cpu_ms", 0.0)  # noqa: E731
    total = sum(
        max(read_of(parent) - sum(read_of(n) for n in nested), 0.0)
        for parent, nested, _labels in critpath._CPU_TILING
    )
    assert sum(cpu.values()) + sum(over.values()) == pytest.approx(total)


def test_attribute_cpu_goes_where_the_walls_remainder_goes():
    """A parent's CPU lands in its catch-all first (`dispatch`, `evm`,
    `post_root`): the cuts a lane's stage claimed get only what the
    catch-all's wall cannot hold, the handler slept there."""
    record = _flat_record(_ALONE)
    breakdown, _un, _wall = critpath.attribute(record)
    cpu, over = critpath.attribute_cpu(record, breakdown)
    assert over == {}
    assert cpu == pytest.approx(
        {
            "sig_rows": 0.9,
            "witness_decode": 7.5,
            "dispatch": 0.4,  # of its 20 ms of wall; queue_wait .. resolve: none
            "evm": 22.9,  # execute's 23.0 less sig_wait's own 0.1
            "sig_wait": 0.1,
            "post_root": 12.1,  # post_root's 15.0 less the plan's 2.9
            "root_plan": 2.9,
        }
    )
    spill = _flat_record({"stateless.witness_verify": 26.0})
    breakdown, _un, _wall = critpath.attribute(spill)
    cpu, over = critpath.attribute_cpu(spill, breakdown)
    assert cpu == pytest.approx({"dispatch": 20.0, "queue_wait": 5.0, "resolve": 1.0}) and over == {}
    # a tick of 10 ms charged to a phase of 1 ms: the phase's wall, and the rest apart
    tick = _flat_record({"stateless.sig_rows": 10.0})
    cpu, over = critpath.attribute_cpu(tick, critpath.attribute(tick)[0])
    assert cpu == pytest.approx({"sig_rows": 1.0}) and over == pytest.approx({"sig_rows": 9.0})


def _hist(name: str, phase: str) -> tuple:
    h = metrics.snapshot()["histograms"].get(f'{name}{{phase="{phase}"}}')
    return (0, 0.0) if h is None else (h["count"], h["sum"])


@pytest.mark.parametrize("with_cpu", [False, True], ids=["before-pr38", "since-pr38"])
def test_rollup_observes_the_cpu_families_only_where_the_span_read_the_clock(with_cpu):
    """A record from before the second clock (no `cpu_ms`: an old flight
    dump replayed, a hand-made one) rolls up as it did and observes
    neither new family; one with it observes both for every phase of
    `critpath.phase_seconds`, and cpu + off-CPU is the wall."""
    record = _flat_record(_IN_A_CROWD if with_cpu else {})
    assert (critpath.attribute_cpu(record, critpath.attribute(record)[0]) is None) is not with_cpu
    families = ("critpath.phase_seconds", "critpath.phase_cpu_seconds", "critpath.phase_offcpu_seconds")
    before = {(f, p): _hist(f, p) for f in families for p in critpath.PHASES}
    n0 = metrics.snapshot()["counters"].get("critpath.requests", 0)
    critpath.rollup(record)
    assert metrics.snapshot()["counters"]["critpath.requests"] == n0 + 1
    for phase in critpath.PHASES:
        wall, cpu, off = (
            tuple(a - b for a, b in zip(_hist(f, phase), before[f, phase])) for f in families
        )
        assert wall[0] == 1 and wall[1] > 0.0, phase  # all twelve labels in this record
        if not with_cpu:
            assert cpu == off == (0, 0.0), phase
            continue
        assert cpu[0] == off[0] == 1, phase
        assert 0.0 <= cpu[1] <= wall[1] and cpu[1] + off[1] == pytest.approx(wall[1]), phase


def test_rollup_books_a_ticks_excess_against_the_labels_next_requests():
    """The chip's host steps the thread's CPU clock by 10 ms: `sig_rows`
    (1 ms) reads a whole tick in one request of ten and 0 in the rest. The
    family never says more CPU than wall, and over the ten requests it
    says all ten milliseconds: none dropped, none waited."""
    metrics._cpu_carry.pop('critpath.phase_cpu_seconds{phase="sig_rows"}', None)
    families = ("critpath.phase_seconds", "critpath.phase_cpu_seconds", "critpath.phase_offcpu_seconds")
    before = [_hist(f, "sig_rows") for f in families]
    critpath.rollup(_flat_record({"stateless.sig_rows": 10.0}))
    mid = _hist("critpath.phase_cpu_seconds", "sig_rows")
    assert mid[1] - before[1][1] == pytest.approx(0.001)  # this request's own wall, no more
    for _ in range(10):
        critpath.rollup(_flat_record({"stateless.sig_rows": 0.0}))
    wall, cpu, off = (
        tuple(a - b for a, b in zip(_hist(f, "sig_rows"), b0)) for f, b0 in zip(families, before)
    )
    assert wall == pytest.approx((11, 0.011)) and cpu == pytest.approx((11, 0.010))
    assert off == pytest.approx((11, 0.001))  # the eleventh request's: the tick was spent


# ---------------------------------------------------------------------------
# coverage >= 95% on the REAL serving path: depths 1 and 2, three lanes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2])
def test_coverage_on_serving_path_all_three_lanes(depth, monkeypatch):
    """The tentpole acceptance: real engine_executeStatelessPayloadV1
    traffic over HTTP with the witness lane, the batched root lane, AND
    the sig lane engaged must attribute >= 95% of every request's wall
    clock — and the span must carry all three lanes' batch records
    without clobbering each other (the root_ prefix fix)."""
    monkeypatch.setenv("PHANT_BATCHED_ROOT", "1")
    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    records: list = []
    rec_lock = threading.Lock()

    def sink(rec):
        if rec.get("span") == "verify_block":
            with rec_lock:
                records.append(rec)

    from phant_tpu.utils.trace import add_span_sink, remove_span_sink

    chain, rpc, want_root = _stateless_request()
    critpath.reset_totals()
    add_span_sink(sink)
    server = EngineAPIServer(
        chain,
        host="127.0.0.1",
        port=0,
        sched_config=SchedulerConfig(
            max_batch=8, max_wait_ms=5.0, pipeline_depth=depth
        ),
    )
    server.serve_in_background()
    try:
        base = f"http://127.0.0.1:{server.port}"
        with ThreadPoolExecutor(max_workers=4) as pool:
            for code, body in pool.map(
                lambda _i: _post(base, rpc), range(8)
            ):
                assert code == 200 and body["result"]["status"] == "VALID", body
                assert body["result"]["stateRoot"] == want_root
        st = server.scheduler.stats_snapshot()
    finally:
        remove_span_sink(sink)
        server.shutdown()
    # all three engine lanes actually served this traffic
    assert st["batches"] >= 1
    assert st["root_batches"] >= 1, st
    assert st["sig_batches"] >= 1, st
    wall, attr = critpath.totals()
    assert wall > 0
    coverage = 100.0 * attr / wall
    assert coverage >= 95.0, f"coverage {coverage:.2f}% at depth {depth}"
    # the span carries all three lanes' records side by side
    assert records
    rec = records[-1]
    assert "batch_id" in rec  # witness record, bare keys
    assert "root_batch_id" in rec  # root record, prefixed (the clobber fix)
    assert "sig_batch_id" in rec  # sig record, prefixed
    # and the critpath family saw the lanes' phases
    hists = metrics.snapshot()["histograms"]
    for ph in ("queue_wait", "evm", "sig_wait", "witness_decode"):
        assert f'critpath.phase_seconds{{phase="{ph}"}}' in hists, ph


# ---------------------------------------------------------------------------
# /debug/profile: single-flight + artifact on disk
# ---------------------------------------------------------------------------


def test_profile_endpoint_single_flight_and_artifact(tmp_path, monkeypatch):
    monkeypatch.setenv("PHANT_PROFILE_DIR", str(tmp_path))
    chain, _rpc, _root = _stateless_request()
    server = EngineAPIServer(chain, host="127.0.0.1", port=0)
    server.serve_in_background()
    try:
        base = f"http://127.0.0.1:{server.port}"
        results: dict = {}

        def first():
            results["first"] = _post_raw(
                base, "/debug/profile?seconds=1.5", timeout=300
            )

        t = threading.Thread(target=first)
        t.start()
        time.sleep(0.4)  # the first capture is mid-window
        code2, body2 = _post_raw(base, "/debug/profile?seconds=1")
        assert code2 == 503, body2  # single-flight: overlap sheds
        assert "in flight" in body2["error"]
        # a request served inside the capture's window (PR 26)
        code3, body3 = _post(base, _rpc)
        assert code3 == 200 and body3["result"]["status"] == "VALID", body3
        # stop_trace serializes the whole process's XLA metadata — in a
        # long-lived warm process that takes tens of seconds on this box
        # (the capture WINDOW stays the clamped seconds; the tail is
        # artifact serialization), so the join is generous
        t.join(300)
        code1, body1 = results["first"]
        assert code1 == 200, body1
        assert body1["path"].startswith(str(tmp_path))
        assert os.path.isdir(body1["path"])
        assert body1["artifacts"] >= 1  # xplane/trace artifacts on disk
        found = [
            f
            for _d, _s, files in os.walk(body1["path"])
            for f in files
        ]
        assert found, "no profiler artifacts written"
    finally:
        server.shutdown()
    # the capture runs with the profiler's Python tracer OFF and its host
    # tracer at level 1 (utils/trace.jax_profile): it holds the program's
    # own `phant/` events with the request's trace_id, and none of the
    # Python tracer's (which names Python frames `$file:line function`)
    import glob

    from jax.profiler import ProfileData

    (xplane,) = glob.glob(
        os.path.join(body1["path"], "plugins/profile/*/*.xplane.pb")
    )
    events = [
        (ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(xplane).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    ]
    ours = {name: stats for name, stats in events if name.startswith("phant/")}
    assert {
        "phant/request", "phant/decode", "phant/verify_block", "phant/evm",
        "phant/reply",
    } <= set(ours), sorted(ours)
    assert ours["phant/verify_block"]["trace_id"] == ours["phant/request"]["trace_id"]
    assert not [name for name, _stats in events if name.startswith("$")]


def test_profile_cap_and_validation(tmp_path, monkeypatch):
    monkeypatch.setenv("PHANT_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("PHANT_PROFILE_MAX_S", "0.3")
    # the hard cap clamps a fat-fingered window (and the standalone
    # MetricsServer serves the same debug POST surface). The clamp proof
    # is the ECHOED window (the actual trace duration): total wall time
    # additionally carries stop_trace's serialization tail, which scales
    # with the process's prior XLA activity — a guard-released capture
    # also proves single-flight reuse after the previous test's release
    srv = MetricsServer(host="127.0.0.1", port=0)
    srv.serve_in_background()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        code, body = _post_raw(base, "/debug/profile?seconds=3600", timeout=300)
        assert code == 200 and body["seconds"] == 0.3
        code, body = _post_raw(base, "/debug/profile?seconds=abc")
        assert code == 400
        code, body = _post_raw(base, "/debug/profile?seconds=-1")
        assert code == 400
        code, body = _post_raw(base, "/debug/nope")
        assert code == 404
    finally:
        srv.shutdown()


def test_debug_post_drains_body_on_keepalive_connection(tmp_path, monkeypatch):
    """These are HTTP/1.1 keep-alive sockets: a POST /debug/profile that
    carries a body must have it drained before the reply, or the NEXT
    request on the same connection parses from the leftover bytes."""
    import http.client

    monkeypatch.setenv("PHANT_PROFILE_DIR", str(tmp_path))
    srv = MetricsServer(host="127.0.0.1", port=0)
    srv.serve_in_background()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        conn.request(
            "POST",
            "/debug/profile?seconds=abc",
            body=b'{"seconds": 1, "pad": "' + b"x" * 256 + b'"}',
            headers={"Content-Type": "application/json"},
        )
        r1 = conn.getresponse()
        assert r1.status == 400
        r1.read()
        # SAME socket: without the drain this desyncs into garbage
        conn.request("GET", "/healthz")
        r2 = conn.getresponse()
        assert r2.status == 200
        json.loads(r2.read())
        conn.close()
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# /debug/slow: exemplar capture under an induced slow request
# ---------------------------------------------------------------------------


def test_slow_exemplar_capture_and_endpoint(monkeypatch):
    """A request past --slo-budget-ms lands in /debug/slow as a full
    span tree with a stage-named breakdown; a per-phase override
    triggers on its own phase."""
    monkeypatch.setenv("PHANT_SLO_BUDGET_MS", "1.0")
    chain, rpc, _root = _stateless_request()
    critpath.slow.clear()
    server = EngineAPIServer(
        chain,
        host="127.0.0.1",
        port=0,
        sched_config=SchedulerConfig(max_batch=4, max_wait_ms=2.0),
    )
    server.serve_in_background()
    try:
        base = f"http://127.0.0.1:{server.port}"
        code, body = _post(base, rpc)
        assert code == 200 and body["result"]["status"] == "VALID"
        status, slow_body = _get_json(base, "/debug/slow")
        assert status == 200
        assert slow_body["budget_ms"] == 1.0
        recs = slow_body["records"]
        assert recs, "a >1ms stateless execution must have been captured"
        rec = recs[-1]
        assert rec["kind"] == "obs.slow_capture"
        assert rec["trigger"] == "wall"
        assert rec["over_ms"] > 0
        assert set(rec["breakdown_ms"]) <= set(critpath.PHASES)
        assert rec["span"]["span"] == "verify_block"
        assert "phases" in rec["span"]
        counters = metrics.snapshot()["counters"]
        assert counters.get('obs.slow_captures{trigger="wall"}', 0) >= 1
    finally:
        server.shutdown()
    # per-phase override: an impossible evm budget fires with the phase
    # as the trigger even though the wall budget is huge
    critpath.configure(
        budget_ms=60_000.0, phase_budgets_ms={"evm": 0.0001}
    )
    critpath.slow.clear()
    with trace_context(), span("verify_block", block=1, nodes=0, codes=0):
        with metrics.phase("stateless.execute"):
            time.sleep(0.002)
    recs = critpath.slow.records()
    assert recs and recs[-1]["trigger"] == "evm"


def test_slow_capture_off_by_default():
    critpath.slow.clear()
    with span("verify_block", block=2, nodes=0, codes=0):
        time.sleep(0.002)
    assert critpath.slow.records() == []
