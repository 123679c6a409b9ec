"""scripts/trace_gaps.py: the device's idle gaps blamed on the request's
`phant/` phases. The attribution is pure (made-up intervals here), and one
trace recorded on the CPU profiler inside the test shows that the program's
annotations arrive in the host plane with their trace_id."""

from __future__ import annotations

import glob
import os
import sys
import threading
import time

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts")
)

import trace_gaps  # noqa: E402

H, L = "handler-1", "executor"


def _ann(start, end, name, tid="t1", thread=H, **attrs):
    return (start, end, "phant/" + name, tid, thread, attrs)


#: one request on a handler thread, its witness wait served by a lane
#: thread, a second request on another handler thread, a full collection
ANNOTATIONS = [
    _ann(0, 100, "request"),
    _ann(0, 10, "read"),
    _ann(10, 20, "json"),
    _ann(20, 30, "decode"),
    _ann(30, 90, "verify_block"),
    _ann(30, 60, "witness_verify"),
    _ann(60, 85, "evm"),
    _ann(70, 75, "sig_wait"),
    _ann(90, 100, "reply"),
    _ann(35, 45, "witness.pack", tid="t0|t1", thread=L, batch_id=7),
    _ann(45, 50, "witness.dispatch", tid="t0|t1", thread=L, batch_id=7),
    _ann(46, 49, "device_enqueue", tid="t0|t1", thread=L, lane="witness"),
    _ann(52, 58, "witness.resolve", tid="t1", thread="resolver"),
    _ann(53, 57, "device_sync", tid="t1", thread="resolver", lane="witness"),
    _ann(36, 40, "sig.pack", tid="t1", thread=L),  # another lane: not the wait's
    _ann(50, 150, "request", tid="t2", thread="handler-2"),
    _ann(50, 150, "read", tid="t2", thread="handler-2"),
    _ann(80, 82, "gc", tid="", thread="resolver", generation=2),
]


def _phases_of(tid):
    return [(s, e, p) for s, e, p, t in trace_gaps.request_phases(ANNOTATIONS) if t == tid]


def test_innermost_flattens_nested_events():
    flat = trace_gaps.innermost([(0, 10, "a"), (2, 8, "b"), (3, 4, "c"), (12, 14, "d")])
    assert flat == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 8, "b"), (8, 10, "a"), (12, 14, "d")]


def test_request_phases_tile_the_request_with_innermost_names():
    got = _phases_of("t1")
    # contiguous over the whole request, nothing twice
    assert got[0][0] == 0 and got[-1][1] == 100
    assert all(a[1] == b[0] for a, b in zip(got, got[1:]))
    names = [p for _s, _e, p in got]
    assert names[:3] == ["read", "json", "decode"]
    assert names[-1] == "reply"
    # sig_wait is inside evm: the innermost wins, evm resumes after it
    # a wait that no lane event serves keeps its plain name
    assert (60, 70, "evm") in got and (70, 75, "sig_wait") in got


@pytest.mark.parametrize(
    "segment",
    [
        (30, 35, "witness_verify:queue_wait"),
        (35, 45, "witness_verify:witness.pack"),
        (45, 46, "witness_verify:witness.dispatch"),
        (46, 49, "witness_verify:device_enqueue"),
        (50, 52, "witness_verify:in_flight"),
        (53, 57, "witness_verify:device_sync"),
        (58, 60, "witness_verify:in_flight"),
    ],
)
def test_a_wait_is_cut_by_its_own_lanes_events(segment):
    assert segment in _phases_of("t1")


def test_another_lanes_events_do_not_cut_the_wait():
    assert not any("sig.pack" in p for _s, _e, p in _phases_of("t1"))


def test_a_full_collection_is_gc_for_every_request_in_flight():
    assert (80, 82, "gc") in _phases_of("t1")
    assert (80, 82, "gc") in _phases_of("t2")


@pytest.mark.parametrize(
    "gaps,want",
    [
        # one request in flight: the whole gap to its phase
        ([(2, 8)], {"read": 6}),
        # a gap over a boundary is cut there
        ([(5, 15)], {"read": 5, "json": 5}),
        # two requests in flight: split equally
        ([(60, 70)], {"evm": 5, "read": 5}),
        # nobody in flight
        ([(200, 230)], {"no_request": 30}),
        # the end of the last request, then nobody
        ([(140, 160)], {"read": 10, "no_request": 10}),
    ],
)
def test_attribute_on_made_up_intervals(gaps, want):
    got = trace_gaps.attribute(gaps, trace_gaps.request_phases(ANNOTATIONS))
    assert got["by_phase"] == pytest.approx(want)
    # every idle second is named
    assert sum(got["by_phase"].values()) == pytest.approx(sum(e - s for s, e in gaps))


def test_whole_requests_window_and_clip():
    """A request cut by the capture's edge leaves phases and no
    `phant/request` event: the table is made inside the whole requests."""
    cut = [
        _ann(-30, -5, "evm", tid="t0", thread="handler-0"),  # its request began before the capture
        *ANNOTATIONS,
        _ann(160, 170, "read", tid="t9", thread="handler-9"),  # and this one ends after it
    ]
    assert trace_gaps.whole_requests(cut) == (0, 150)
    assert trace_gaps.whole_requests([a for a in cut if a[2] != "phant/request"]) is None
    # the device's idle inside that window, its two ends included
    busy = [(-20, -10), (-5, 10), (40, 50), (140, 170), (180, 190)]
    assert trace_gaps.idle_in(busy, (0, 150)) == [(10, 40), (50, 140)]
    assert trace_gaps.idle_in([(20, 30)], (0, 150)) == [(0, 20), (30, 150)]
    assert trace_gaps.idle_in([], (0, 150)) == [(0, 150)]


def test_attribute_by_request():
    got = trace_gaps.attribute([(60, 70)], trace_gaps.request_phases(ANNOTATIONS))
    assert got["by_request"] == {"t1": {"evm": 5}, "t2": {"read": 5}}


def test_cpu_profiler_capture_carries_the_programs_annotations(tmp_path):
    """One trace recorded here: a request-shaped frame span with marks, a
    child span with a phase, and a lane stage on another thread, captured
    with `jax_profile` (host tracer at level 1, Python tracer off). The
    events arrive in the host plane named `phant/...` with the trace_id,
    and `trace_gaps` reads phases and lane cuts out of them."""
    from phant_tpu.utils.trace import (
        jax_profile,
        lane_stage,
        metrics,
        span,
        trace_context,
    )

    stages: dict = {}

    def lane():
        with lane_stage(stages, "pack", ["feedc0de"], 41):
            with metrics.phase("witness_engine.pack"):
                # long enough that a few ms of preemption between the stage's
                # clock reads and the event's stay inside the 20 % the last
                # assertion allows (at 4 ms one whole run in three failed)
                time.sleep(0.04)

    with jax_profile(str(tmp_path)):
        with trace_context("feedc0de"), span("request", frame=True) as req:
            req.resume = "reply"
            req.mark("read")
            time.sleep(0.002)
            req.mark("decode")
            with span("verify_block", block=1):
                with metrics.phase("stateless.witness_verify"):
                    time.sleep(0.002)
                    t = threading.Thread(target=lane, name="lane-thread")
                    t.start()
                    t.join(10)
                with metrics.phase("stateless.execute"):
                    time.sleep(0.002)
            time.sleep(0.001)
    (xplane,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    annotations, busy = trace_gaps.load(xplane)
    assert busy == []  # no TPU plane in a CPU capture
    names = {a[2] for a in annotations}
    assert {
        "phant/request", "phant/read", "phant/decode", "phant/reply",
        "phant/verify_block", "phant/witness_verify", "phant/evm",
        "phant/witness.pack",
    } <= names
    assert all(a[3] == "feedc0de" for a in annotations)
    (pack,) = [a for a in annotations if a[2] == "phant/witness.pack"]
    (request,) = [a for a in annotations if a[2] == "phant/request"]
    assert pack[5]["batch_id"] == 41 and pack[4] != request[4]  # its own thread
    # the stage the batch record carries and the event in the trace are
    # the same interval, each on its clock
    assert stages["pack"][1] - stages["pack"][0] == pytest.approx(pack[1] - pack[0], rel=0.2)
    phases = trace_gaps.request_phases(annotations)
    # between two phases the span itself is the innermost event open:
    # slivers named `request` / `verify_block`, kept (they are time too)
    labels = [p for _s, _e, p, _t in phases if p not in ("request", "verify_block")]
    assert labels[0] == "read" and labels[-1] == "reply"
    assert "witness_verify:witness.pack" in labels and "evm" in labels
    # a made-up gap over the lane's pack is blamed on it
    got = trace_gaps.attribute([(pack[0], pack[1])], phases)
    assert got["by_phase"] == {"witness_verify:witness.pack": pack[1] - pack[0]}
