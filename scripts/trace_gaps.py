#!/usr/bin/env python3
"""Blame the device's idle gaps on the request's phases.

    python3 scripts/trace_gaps.py <trace.xplane.pb> [--top N] [--json]

The program writes its spans, phases and lane stages into the profiler's
trace as `phant/<name>` events with the request's `trace_id`
(phant_tpu/utils/trace.py: `annotate`, ANNOTATIONS). They lie in the host
plane on the device events' time axis, so every gap between two device
operations can be laid over what each request in flight was doing then:

  * a request is in flight from the start to the end of its `phant/request`
    event; its phase at a moment is the INNERMOST `phant/` event open on
    that handler thread (`read`, `json`, `decode`, `sig_rows`, `evm`, ...);
  * while the handler thread only waits for a lane (`witness_verify`,
    `sig_wait`, `post_root`), the phase is cut further by that lane's events
    for the same trace_id on the lane's threads: `witness_verify:queue_wait`
    before the lane's first stage, `witness_verify:witness.pack`,
    `...:device_enqueue`, `...:device_sync`, and `...:in_flight` where no
    lane thread is at work for the request (the device or the pipeline
    owns it); a wait with no lane event at all (the root lane off: the
    post-root is a host walk) keeps its plain name;
  * a full collection (`phant/gc`) stops every thread: `gc`, whoever ran it;
  * a gap's seconds are split equally among the requests in flight, and go
    to `no_request` where none is (the client's turn, the socket).

The profiler records an event only if it BEGINS and ENDS inside the
capture, so a request cut by the capture's edge leaves its inner phases
and no `phant/request`. The table is therefore made over the window of the
whole requests, first `phant/request` start to last `phant/request` end
(`whole_requests`, `idle_in`), and says how much idle lies outside it. A
capture that starts and stops at a request's end (one client) loses nothing.

`request_phases` and `attribute` are pure functions on interval lists
(tests/test_trace_gaps.py); only `load` touches the file. The device
planes are read with the benchmark's own reducer
(benchmarks/harness/trace_reduce.py), which this script edits nothing of.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

#: the handler-thread phases that are waits for a lane, and the lane
WAITS = {
    "phant/witness_verify": "witness",
    "phant/sig_wait": "sig",
    "phant/post_root": "root",
}
NO_REQUEST = "no_request"


def _short(name: str) -> str:
    return name[6:] if name.startswith("phant/") else name


def innermost(events: list) -> list:
    """[(start, end, name)] flat and disjoint: at every moment covered by
    `events` (start, end, name), the name of the innermost one, i.e. of
    the latest-started event still open."""
    edges = sorted({t for s, e, _n in events for t in (s, e)})
    evs = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    out = []
    for a, b in zip(edges, edges[1:]):
        best = None
        for s, e, n in evs:
            if s > a:
                break
            if e >= b and s <= a:
                best = n  # later in start order: opened later, so inside
        if best is not None:
            if out and out[-1][2] == best and out[-1][1] == a:
                out[-1] = (out[-1][0], b, best)
            else:
                out.append((a, b, best))
    return out


def _cut(segments: list, cutters: list, label_inside: str) -> list:
    """`segments` (start, end, label) with every part that one of the
    sorted, disjoint `cutters` (start, end) covers relabelled."""
    out = []
    for s, e, label in segments:
        at = s
        for cs, ce in cutters:
            if ce <= at or cs >= e:
                continue
            if cs > at:
                out.append((at, cs, label))
            out.append((max(cs, at), min(ce, e), label_inside))
            at = min(ce, e)
        if at < e:
            out.append((at, e, label))
    return out


def request_phases(annotations: list) -> list:
    """[(start, end, phase, trace_id)]: what each request in flight was
    doing, from the program's annotations
    [(start, end, name, trace_id, thread, attrs)], `trace_id` as the event
    carries it (a lane batch's ids joined by "|"). Pure."""
    gcs = sorted((s, e) for s, e, n, _t, _th, _a in annotations if n == "phant/gc")
    out = []
    for s, e, name, tid, thread, _attrs in annotations:
        if name != "phant/request" or not tid:
            continue
        mine = [
            (a, b, n)
            for a, b, n, t, th, _x in annotations
            if th == thread and t == tid and a >= s and b <= e and n != "phant/gc"
        ]
        segments = []
        for a, b, n in innermost(mine):
            lane = WAITS.get(n)
            if lane is None:
                segments.append((a, b, _short(n)))
                continue
            # the lane's own events for this request, on its threads
            lane_events = [
                (max(x, a), min(y, b), m)
                for x, y, m, t, th, attrs in annotations
                if th != thread
                and t
                and tid in t.split("|")
                and y > a
                and x < b
                and (m.startswith(f"phant/{lane}.") or attrs.get("lane") == lane)
            ]
            flat = innermost(lane_events)
            wait = _short(n)
            if not flat:  # no lane served it: the handler did the work
                segments.append((a, b, wait))
                continue
            at = flat[0][0]
            parts = [(a, at, f"{wait}:queue_wait")] if at > a else []
            for x, y, m in flat:
                if x > at:
                    parts.append((at, x, f"{wait}:in_flight"))
                parts.append((x, y, f"{wait}:{_short(m)}"))
                at = y
            if at < b:
                parts.append((at, b, f"{wait}:in_flight"))
            segments.extend(parts)
        segments = _cut(segments, gcs, "gc")
        out.extend((a, b, label, tid) for a, b, label in segments if b > a)
    return sorted(out)


def whole_requests(annotations: list) -> tuple | None:
    """(start, end) from the first `phant/request` event's start to the
    last one's end: the window in which every request has all its events."""
    spans = [(s, e) for s, e, n, t, _th, _a in annotations if n == "phant/request" and t]
    if not spans:
        return None
    return min(s for s, _e in spans), max(e for _s, e in spans)


def idle_in(busy: list, window: tuple) -> list:
    """The gaps [(start, end)] that the sorted, disjoint busy intervals
    leave inside `window`, its two ends included."""
    lo, hi = window
    gaps, at = [], lo
    for s, e in busy:
        if e <= lo or s >= hi:
            continue
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    return gaps


def attribute(gaps: list, phases: list) -> dict:
    """The idle gaps [(start, end)] over the requests' phases
    [(start, end, phase, request)]: seconds (in the intervals' unit) by
    phase and by request and phase. A moment's share is split equally
    among the requests in flight; with none it is `no_request`. Pure."""
    by_phase: dict = {}
    by_request: dict = {}
    for gs, ge in gaps:
        inside = [p for p in phases if p[1] > gs and p[0] < ge]
        edges = sorted({gs, ge, *(t for p in inside for t in p[:2] if gs < t < ge)})
        for a, b in zip(edges, edges[1:]):
            open_now = [p for p in inside if p[0] <= a and p[1] >= b]
            if not open_now:
                by_phase[NO_REQUEST] = by_phase.get(NO_REQUEST, 0) + (b - a)
                continue
            share = (b - a) / len(open_now)
            for _s, _e, phase, request in open_now:
                by_phase[phase] = by_phase.get(phase, 0) + share
                mine = by_request.setdefault(request, {})
                mine[phase] = mine.get(phase, 0) + share
    return {"by_phase": by_phase, "by_request": by_request}


def load(path: str) -> tuple:
    """(annotations, busy) of one xplane file: the `phant/` events of the
    host planes, and the busiest device's merged intervals of operation.
    Without a TPU plane (a CPU capture) `busy` is empty."""
    sys.path.insert(0, str(ROOT / "benchmarks"))
    from harness import trace_reduce
    from jax.profiler import ProfileData

    annotations = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            # a line is a thread; Python's threads all carry the process's
            # name there, so the line's place in the plane tells them apart
            thread = f"{line.name}#{i}"
            for ev in line.events:
                if not ev.name.startswith("phant/"):
                    continue
                attrs = {k: v for k, v in ev.stats}
                start = int(ev.start_ns)
                annotations.append(
                    (
                        start,
                        start + int(ev.duration_ns),
                        ev.name,
                        str(attrs.pop("trace_id", "")),
                        thread,
                        attrs,
                    )
                )
    busiest, most = [], -1
    for _dev, lines in trace_reduce.load_planes(path).items():
        busy = lines.get(trace_reduce.OPS_LINE) or []
        seconds = sum(e - s for s, e in busy)
        if busy and seconds > most:
            most, busiest = seconds, busy
    return annotations, busiest


def table(result: dict, idle_ns: float, top: int) -> str:
    rows = sorted(result["by_phase"].items(), key=lambda kv: -kv[1])
    out = ["| phase | idle s | share |", "| --- | --- | --- |"]
    for phase, ns in rows[:top]:
        out.append(f"| {phase} | {ns / 1e9:.4f} | {100 * ns / idle_ns:.1f} % |")
    rest = sum(ns for _p, ns in rows[top:])
    if rest:
        out.append(f"| ({len(rows) - top} more) | {rest / 1e9:.4f} | {100 * rest / idle_ns:.1f} % |")
    out += ["", "| request | idle s | most of it under |", "| --- | --- | --- |"]
    for request, mine in sorted(result["by_request"].items(), key=lambda kv: -sum(kv[1].values())):
        lead = sorted(mine.items(), key=lambda kv: -kv[1])[:3]
        out.append(
            f"| {request} | {sum(mine.values()) / 1e9:.4f} | "
            + ", ".join(f"{p} {ns / 1e9:.4f}" for p, ns in lead)
            + " |"
        )
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--top", type=int, default=24)
    ap.add_argument("--json", action="store_true", help="the result as one JSON object")
    args = ap.parse_args(argv)
    annotations, busy = load(args.xplane)
    window = whole_requests(annotations)
    capture = (busy[0][0], busy[-1][1]) if busy else None
    gaps = idle_in(busy, window) if window and busy else []
    result = attribute(gaps, request_phases(annotations))
    idle = sum(e - s for s, e in gaps)
    if args.json:
        print(json.dumps({"idle_ns": idle, "window": window, "annotations": len(annotations), **result}))
        return 0
    print(f"{len(annotations)} phant/ events; device operations from {capture}")
    if not idle:
        print("no idle gap inside a window of whole requests in this trace")
        return 0
    all_idle = sum(e - s for s, e in idle_in(busy, capture))
    print(
        f"window of whole requests: {(window[1] - window[0]) / 1e9:.4f} s, the device idle "
        f"{idle / 1e9:.4f} s of it ({len(gaps)} gaps); between its first and last operation "
        f"of the capture the device was idle {all_idle / 1e9:.4f} s"
    )
    print(table(result, idle, args.top))
    named = sum(result["by_phase"].values())
    print(f"\nattributed to a phase or to {NO_REQUEST}: {100 * named / idle:.2f} % of the window's idle seconds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
