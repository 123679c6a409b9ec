"""Unified timeline export (PR 16): tail-sampled Perfetto traces.

PR 15 built the instruments — critpath phase tiling, `/debug/slow`, the
on-demand profiler — but each is an island: span records are ring
entries, batch intervals are flight records, and the XLA profiler
writes its own directory. Nothing lines them up on ONE time axis. This
module is that axis: an always-on, bounded-memory timeline recorder — a
third span sink plus taps on the scheduler/mesh batch finishers —
whose `export(window_s)` renders the recent past as Chrome-trace JSON
(the `traceEvents` object format) that Perfetto loads directly:

* pid 1 "requests"   — one track per HTTP handler thread; each kept
  request is a `verify_block` slice with its critpath phases as
  sub-slices at their MEASURED offsets: the span's own child intervals
  (utils/trace.py) on the handler thread, and inside the witness wait
  the lane stages' start and end from the batch record (`stages`);
* pid 2 "lanes"      — one track per (lane, device): witness/root/sig
  batch slices with prefetch/pack/dispatch/resolve sub-stages, keyed
  by batch_id;
* pid 4 "profiler"   — one slice + start/end instants per
  `POST /debug/profile` capture inside the window, so the XLA device
  trace can be laid alongside the host timeline (clock-sync metadata
  rides in `metadata.clock_sync`).

Flow events stitch a request to the merged batches that served it: the
request slice emits a `ph:"s"` per (lane, batch_id) it carries
(`batch_id` / `root_batch_id` / `sig_batch_id` span attrs), and the
batch slice answers with a `ph:"f", bp:"e"` — one arrow per kept
request, id `lane:batch_id:trace_id`. Pairing is guaranteed at export
time: a request only emits an `s` for a batch present in the window,
and a batch only emits `f`s for kept requests that reference it.

Full recording at 1000 blocks/s is unaffordable, so retention is
TAIL-SAMPLED at span close, in priority order:

  error    the request crashed (-32052 / any exception) — always kept
  slo      wall clock blew `--slo-budget-ms` (critpath's budget) — kept
  p99      the request is the rolling per-phase p99 exemplar (internal
           per-phase bucket counts; thresholds recached every 32
           requests once a phase has enough samples)
  sample   uniform 1-in-N (`--timeline-sample-n` / env), injectable RNG

and everything else drops with `reason=sampled_out`. Sampling is never
silent: `obs.timeline_kept{reason=}` + `obs.timeline_dropped{reason=
sampled_out}` reconcile EXACTLY with offered load (tests assert it),
and a kept entry later evicted by ring overflow counts
`reason=ring_full` separately.

Config is resolved ONCE and memoized (`_Config`, exactly the critpath
pattern — the env-read-per-event anti-pattern the r14 signer fix
removed stays dead): `refresh_from_env()` re-reads (the Engine API
server calls it at construction, after the CLI wrote its flags into
the env), `configure()` overrides directly (tests).
`PHANT_TIMELINE=0` disables the whole layer.

Thread-safety: one module lock guards the rings, the tail-sample
counters, and the p99 state; every tap is O(1) dict work under it.
The sink must never fail the traced work — span() swallows sink
exceptions, and the batch taps are called outside scheduler locks.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

from phant_tpu.obs import critpath
from phant_tpu.obs.flight import flight
from phant_tpu.utils.trace import DEFAULT_BUCKETS, histogram_quantile, metrics

#: keep-reason priority order (first match wins); the vocabulary of the
#: `obs.timeline_kept{reason=}` family
KEEP_REASONS: Tuple[str, ...] = ("error", "slo", "p99", "sample")

#: drop reasons: `sampled_out` at the span-close decision (reconciles
#: with offered load), `ring_full` when overflow evicts a KEPT entry
DROP_REASONS: Tuple[str, ...] = ("sampled_out", "ring_full")

#: recompute the per-phase p99 thresholds every this many sink calls —
#: a histogram_quantile over 15 buckets x 12 phases is cheap but not
#: per-request cheap
_P99_RECACHE_EVERY = 32

#: a phase needs this many samples before its p99 threshold is trusted
#: (an empty histogram's "p99" would keep everything)
_P99_MIN_COUNT = 64


class _Config:
    __slots__ = ("enabled", "sample_n", "ring", "dirpath", "keep")

    def __init__(
        self,
        enabled: bool,
        sample_n: int,
        ring: int,
        dirpath: str,
        keep: int,
    ):
        self.enabled = enabled
        self.sample_n = sample_n
        self.ring = ring
        self.dirpath = dirpath
        self.keep = keep


def _config_from_env() -> _Config:
    def _int(name: str, default: int, floor: int = 0) -> int:
        try:
            v = int(os.environ.get(name, str(default)) or str(default))
        except ValueError:
            return default
        return max(v, floor)

    return _Config(
        enabled=os.environ.get("PHANT_TIMELINE", "1") not in ("0", ""),
        sample_n=_int("PHANT_TIMELINE_SAMPLE_N", 16),
        ring=_int("PHANT_TIMELINE_RING", 1024, floor=1),
        dirpath=os.environ.get("PHANT_TIMELINE_DIR", ""),
        keep=_int("PHANT_TIMELINE_KEEP", 8, floor=1),
    )


_cfg: _Config = _config_from_env()
_lock = threading.Lock()

#: uniform 1-in-N sampler; tests inject a seeded Random via
#: configure(rng=...) so the sample decision sequence is pinned
_rng = random.Random()

# the rings (all bounded by cfg.ring except profiles, which are rare):
# requests/batches carry the flow-joinable entries,
# occupancy slices, profiles the clock-sync markers
_requests: deque = deque(maxlen=_cfg.ring)
_batches: deque = deque(maxlen=_cfg.ring)
_profiles: deque = deque(maxlen=16)

# tail-sample accounting (mirrored to obs.timeline_{kept,dropped})
_kept: Dict[str, int] = {}
_dropped: Dict[str, int] = {}

# rolling per-phase p99 exemplar state: non-cumulative DEFAULT_BUCKETS
# counts (+Inf slot) per critpath phase, thresholds recached every
# _P99_RECACHE_EVERY sink calls
_phase_counts: Dict[str, List[int]] = {}
_p99_ms: Dict[str, float] = {}
_since_recache = 0

#: per-export spool suffix (same-second exports stay distinct)
_spool_seq = 0


def refresh_from_env() -> None:
    """Re-resolve the memoized config from the environment (the Engine
    API server calls this at construction so `--timeline-*` flags take
    effect; tests call it after monkeypatching). A ring-size change
    rebuilds the deques, keeping the newest entries."""
    global _cfg
    with _lock:
        _cfg = _config_from_env()
        _resize_locked(_cfg.ring)


def configure(
    enabled: Optional[bool] = None,
    sample_n: Optional[int] = None,
    ring: Optional[int] = None,
    dirpath: Optional[str] = None,
    keep: Optional[int] = None,
    rng: Optional[random.Random] = None,
) -> None:
    """Override the memoized config directly (tests); None leaves a
    field as-is. `rng` replaces the uniform
    sampler's generator (determinism for tests)."""
    global _cfg, _rng
    with _lock:
        _cfg = _Config(
            enabled=_cfg.enabled if enabled is None else enabled,
            sample_n=_cfg.sample_n if sample_n is None else max(int(sample_n), 0),
            ring=_cfg.ring if ring is None else max(int(ring), 1),
            dirpath=_cfg.dirpath if dirpath is None else dirpath,
            keep=_cfg.keep if keep is None else max(int(keep), 1),
        )
        if rng is not None:
            _rng = rng
        _resize_locked(_cfg.ring)


def _resize_locked(n: int) -> None:
    global _requests, _batches
    if _requests.maxlen != n:
        _requests = deque(_requests, maxlen=n)
        _batches = deque(_batches, maxlen=n)


def enabled() -> bool:
    """Is the timeline recorder on? Read by the batch taps before
    building their entry dicts."""
    return _cfg.enabled


def capacity() -> int:
    """The request-ring capacity (echoed by /healthz `debug_rings`)."""
    return _cfg.ring


def stats() -> Dict[str, Dict[str, int]]:
    """{'kept': {reason: n}, 'dropped': {reason: n}} since process start
    or the last reset() — the reconciliation surface: sum(kept.values())
    + dropped['sampled_out'] == offered requests (ring_full evictions
    count previously-KEPT entries, separately)."""
    with _lock:
        return {"kept": dict(_kept), "dropped": dict(_dropped)}


def reset() -> None:
    """Clear the rings, the tail-sample counters, and the p99 state
    (tests and `scripts/soak.py` start from a clean slate)."""
    global _since_recache
    with _lock:
        _requests.clear()
        _batches.clear()
        _profiles.clear()
        _kept.clear()
        _dropped.clear()
        _phase_counts.clear()
        _p99_ms.clear()
        _since_recache = 0


# -- tail-sampled span sink (registered by phant_tpu/obs/__init__.py) --------


def _bucket_observe_locked(phase: str, v_ms: float) -> None:
    counts = _phase_counts.get(phase)
    if counts is None:
        counts = _phase_counts[phase] = [0] * (len(DEFAULT_BUCKETS) + 1)
    v_s = v_ms / 1e3
    for i, ub in enumerate(DEFAULT_BUCKETS):
        if v_s <= ub:
            counts[i] += 1
            return
    counts[-1] += 1


def _recache_p99_locked() -> None:
    for phase, counts in _phase_counts.items():
        if sum(counts) >= _P99_MIN_COUNT:
            _p99_ms[phase] = (
                histogram_quantile(DEFAULT_BUCKETS, counts, 0.99) * 1e3
            )


def _keep_reason_locked(
    record: dict, breakdown: Dict[str, float], wall_ms: float
) -> Optional[str]:
    if record.get("error"):
        return "error"
    budget = critpath.budget_ms()
    if budget > 0 and wall_ms > budget:
        return "slo"
    for phase, v in breakdown.items():
        thr = _p99_ms.get(phase, 0.0)
        if thr > 0.0 and v >= thr:
            return "p99"
    n = _cfg.sample_n
    if n == 1 or (n > 1 and _rng.randrange(n) == 0):
        return "sample"
    return None


#: the span's child intervals that are critpath phases as they stand
_HANDLER_SLICES = {
    "stateless.sig_rows": "sig_rows",
    "stateless.witness_decode": "witness_decode",
    "sched.sig_wait": "sig_wait",
    "stateless.execute": "evm",
    "stateless.post_root_plan": "root_plan",
    "stateless.post_root": "post_root",
}


def measured_slices(record: dict) -> List[Tuple[str, int, int]]:
    """(critpath phase, offset_us from the span's start, dur_us) of one
    `verify_block` record, from its measured intervals: the handler
    thread's phases as the span recorded them (`sig_wait` lies inside
    `evm`, `root_plan` inside `post_root`: nested slices), and each of
    the handler's waits for the witness lane (to the launch, to the
    verdict, `witness_decode` between them) cut at the lane stages' own
    clock readings, which are on the span's clock (`critpath.tile_wait`):
    `queue_wait` up to the first stage, `prefetch`, `pack`, `resolve`
    where the handler waited under them, `dispatch` where no stage of
    the batch ran."""
    t_span = record.get("start_ns")
    if not isinstance(t_span, int):
        return []
    out: List[Tuple[str, int, int]] = []

    def put(name: str, t0: int, t1: int) -> None:
        if t1 > t0:
            out.append((name, (t0 - t_span) // 1000, max((t1 - t0) // 1000, 1)))

    for name, t0, t1 in record.get("intervals") or ():
        label = _HANDLER_SLICES.get(name)
        if label is not None:
            put(label, t0, t1)
        elif name == "stateless.witness_verify":
            # critpath's own cut of a wait: the two cannot disagree
            for piece in critpath.tile_wait(t0, t1, record.get("stages")):
                put(*piece)
    return out


def on_span(record: dict) -> None:
    """THE third span sink: tail-sample one top-level `verify_block`
    record into the request ring at span close."""
    if record.get("span") != "verify_block":
        return
    cfg = _cfg
    if not cfg.enabled:
        return
    end_wall = time.time()
    breakdown, _unattributed, wall = critpath.attribute(record)
    if wall <= 0.0:
        return
    flows: List[Tuple[str, int]] = []
    for lane, key in (
        ("witness", "batch_id"),
        ("root", "root_batch_id"),
        ("sig", "sig_batch_id"),
    ):
        bid = record.get(key)
        if isinstance(bid, int):
            flows.append((lane, bid))
    thread = threading.current_thread()
    with _lock:
        global _since_recache
        _since_recache += 1
        if _since_recache >= _P99_RECACHE_EVERY:
            _since_recache = 0
            _recache_p99_locked()
        reason = _keep_reason_locked(record, breakdown, wall)
        for phase, v in breakdown.items():
            _bucket_observe_locked(phase, v)
        evicted = False
        if reason is None:
            _dropped["sampled_out"] = _dropped.get("sampled_out", 0) + 1
        else:
            if len(_requests) == _requests.maxlen:
                # overflow evicts the OLDEST kept entry — counted so a
                # too-small ring can never silently eat the tail
                _dropped["ring_full"] = _dropped.get("ring_full", 0) + 1
                evicted = True
            _requests.append(
                {
                    "end": end_wall,
                    "dur_ms": wall,
                    "trace_id": record.get("trace_id"),
                    "tid": thread.ident,
                    "thread": thread.name,
                    "reason": reason,
                    "block": record.get("block"),
                    "error": record.get("error"),
                    "phases": {k: round(v, 3) for k, v in breakdown.items()},
                    "slices": measured_slices(record),
                    "flows": flows,
                }
            )
            _kept[reason] = _kept.get(reason, 0) + 1
    if reason is None:
        metrics.count("obs.timeline_dropped", reason="sampled_out")
    else:
        metrics.count("obs.timeline_kept", reason=reason)
        if evicted:
            metrics.count("obs.timeline_dropped", reason="ring_full")


# -- batch / profiler taps ---------------------------------------------------


def record_batch(
    record: dict,
    lane: str,
    duration_ms: float,
    trace_ids: Sequence[Optional[str]],
) -> None:
    """One finished lane batch (called by the scheduler's witness/plan
    finishers and, through them, every mesh lane + megabatch): the
    [picked, done] interval with its stage timings, keyed by batch_id —
    the `f` side of the request flow arrows."""
    if not _cfg.enabled:
        return
    entry = {
        "end": time.time(),
        "dur_ms": float(duration_ms),
        "lane": lane,
        "device": str(record.get("device", "0")),
        "batch_id": record.get("batch_id"),
        "batch_size": record.get("batch_size"),
        "backend": record.get("backend"),
        "bucket_bytes": record.get("bucket_bytes"),
        "trace_ids": [t for t in trace_ids if t],
    }
    for key in ("prefetch_ms", "pack_ms", "resolve_ms"):
        v = record.get(key)
        if isinstance(v, (int, float)) and v > 0:
            entry[key] = float(v)
    with _lock:
        _batches.append(entry)


def record_profile(path: str, start_wall: float, end_wall: float) -> None:
    """One on-demand profiler capture window (POST /debug/profile):
    start/end markers on the profiler track + `metadata.clock_sync`, so
    the XLA device trace under `path` can be laid alongside the host
    timeline."""
    if not _cfg.enabled:
        return
    with _lock:
        _profiles.append(
            {"path": path, "start": start_wall, "end": end_wall}
        )


# -- export ------------------------------------------------------------------

#: Chrome-trace process ids (one per track family); M metadata names them
_PID_REQUESTS = 1
_PID_LANES = 2
_PID_PROFILER = 4


def _us(t: float) -> int:
    return int(t * 1e6)


def export(window_s: float) -> dict:
    """Render the last `window_s` seconds as a Chrome-trace JSON object
    (Perfetto-loadable `traceEvents` + metadata). Spools a rotated copy
    under the configured timeline dir when one is set."""
    now = time.time()
    cutoff = now - float(window_s)
    with _lock:
        reqs = [r for r in _requests if r["end"] >= cutoff]
        bats = [b for b in _batches if b["end"] >= cutoff]
        profs = [p for p in _profiles if p["end"] >= cutoff]
        kept = dict(_kept)
        dropped = dict(_dropped)
    events: List[dict] = []

    def meta(pid: int, name: str, tid: Optional[int] = None) -> None:
        ev = {
            "ph": "M",
            "pid": pid,
            "ts": 0,
            "name": "process_name" if tid is None else "thread_name",
            "args": {"name": name},
        }
        if tid is not None:
            ev["tid"] = tid
        else:
            ev["tid"] = 0
        events.append(ev)

    # -- requests (pid 1): one track per handler thread ----------------------
    if reqs:
        meta(_PID_REQUESTS, "requests")
    threads_named = set()
    batch_keys = {(b["lane"], b["batch_id"]) for b in bats}
    # (lane, batch_id) -> [(flow_id, s_ts_us)] for the batch-side `f`s
    flow_refs: Dict[Tuple[str, int], List[Tuple[str, int]]] = {}
    for r in reqs:
        tid = int(r["tid"] or 0)
        if tid not in threads_named:
            threads_named.add(tid)
            meta(_PID_REQUESTS, str(r["thread"]), tid=tid)
        start_us = _us(r["end"] - r["dur_ms"] / 1e3)
        dur_us = int(r["dur_ms"] * 1e3)
        events.append(
            {
                "ph": "X",
                "pid": _PID_REQUESTS,
                "tid": tid,
                "ts": start_us,
                "dur": max(dur_us, 1),
                "name": "verify_block",
                "cat": "request",
                "args": {
                    "trace_id": r["trace_id"],
                    "block": r["block"],
                    "reason": r["reason"],
                    "error": r["error"],
                },
            }
        )
        # phase sub-slices at their measured offsets (measured_slices)
        for phase, off_us, pdur in r["slices"]:
            events.append(
                {
                    "ph": "X",
                    "pid": _PID_REQUESTS,
                    "tid": tid,
                    "ts": start_us + off_us,
                    "dur": pdur,
                    "name": phase,
                    "cat": "phase",
                    "args": {"ms": r["phases"].get(phase)},
                }
            )
        for lane, bid in r["flows"]:
            if (lane, bid) not in batch_keys:
                continue  # the serving batch fell outside the window
            fid = f"{lane}:{bid}:{r['trace_id']}"
            s_ts = start_us + 1
            events.append(
                {
                    "ph": "s",
                    "pid": _PID_REQUESTS,
                    "tid": tid,
                    "ts": s_ts,
                    "name": "serves",
                    "cat": "batch_link",
                    "id": fid,
                }
            )
            flow_refs.setdefault((lane, bid), []).append((fid, s_ts))

    # -- lanes (pid 2): one track per (lane, device) -------------------------
    if bats:
        meta(_PID_LANES, "lanes")
    lane_tids: Dict[Tuple[str, str], int] = {}
    for key in sorted({(b["lane"], b["device"]) for b in bats}):
        lane_tids[key] = len(lane_tids) + 1
        meta(_PID_LANES, f"{key[0]} lane · dev {key[1]}", tid=lane_tids[key])
    for b in bats:
        tid = lane_tids[(b["lane"], b["device"])]
        start_us = _us(b["end"] - b["dur_ms"] / 1e3)
        dur_us = max(int(b["dur_ms"] * 1e3), 1)
        events.append(
            {
                "ph": "X",
                "pid": _PID_LANES,
                "tid": tid,
                "ts": start_us,
                "dur": dur_us,
                "name": f"{b['lane']} batch",
                "cat": "batch",
                "args": {
                    "batch_id": b["batch_id"],
                    "batch_size": b["batch_size"],
                    "backend": b["backend"],
                    "bucket_bytes": b["bucket_bytes"],
                    "requests": len(b["trace_ids"]),
                },
            }
        )
        # stage sub-slices: prefetch/pack at the start, resolve at the
        # end, dispatch = the remainder in between (clipped so stages
        # can never claim more than the batch interval)
        rem = dur_us
        off = start_us
        for stage in ("prefetch", "pack"):
            v = b.get(f"{stage}_ms")
            if not v:
                continue
            sdur = min(int(v * 1e3), rem)
            if sdur <= 0:
                continue
            events.append(
                {
                    "ph": "X",
                    "pid": _PID_LANES,
                    "tid": tid,
                    "ts": off,
                    "dur": sdur,
                    "name": stage,
                    "cat": "stage",
                    "args": {"ms": v},
                }
            )
            off += sdur
            rem -= sdur
        rdur = 0
        rv = b.get("resolve_ms")
        if rv:
            rdur = min(int(rv * 1e3), rem)
            if rdur > 0:
                events.append(
                    {
                        "ph": "X",
                        "pid": _PID_LANES,
                        "tid": tid,
                        "ts": start_us + dur_us - rdur,
                        "dur": rdur,
                        "name": "resolve",
                        "cat": "stage",
                        "args": {"ms": rv},
                    }
                )
                rem -= rdur
        if rem > 0:
            events.append(
                {
                    "ph": "X",
                    "pid": _PID_LANES,
                    "tid": tid,
                    "ts": off,
                    "dur": rem,
                    "name": "dispatch",
                    "cat": "stage",
                    "args": {},
                }
            )
        # the `f` side of the flow arrows: one per kept request this
        # batch served, bound to the enclosing batch slice (bp: "e"),
        # clamped after its `s` so begin/end always pair in order
        for fid, s_ts in flow_refs.get((b["lane"], b["batch_id"]), ()):
            events.append(
                {
                    "ph": "f",
                    "bp": "e",
                    "pid": _PID_LANES,
                    "tid": tid,
                    "ts": max(start_us + dur_us // 2, s_ts + 1),
                    "name": "serves",
                    "cat": "batch_link",
                    "id": fid,
                }
            )

    # -- profiler (pid 4): capture windows + clock-sync instants -------------
    clock_sync = []
    if profs:
        meta(_PID_PROFILER, "profiler")
        meta(_PID_PROFILER, "xla capture", tid=1)
    for p in profs:
        s_us, e_us = _us(p["start"]), _us(p["end"])
        events.append(
            {
                "ph": "X",
                "pid": _PID_PROFILER,
                "tid": 1,
                "ts": s_us,
                "dur": max(e_us - s_us, 1),
                "name": "xla_capture",
                "cat": "profile",
                "args": {"path": p["path"]},
            }
        )
        for name, ts in (("capture_start", s_us), ("capture_end", e_us)):
            events.append(
                {
                    "ph": "i",
                    "s": "t",
                    "pid": _PID_PROFILER,
                    "tid": 1,
                    "ts": ts,
                    "name": name,
                    "cat": "profile",
                    "args": {"path": p["path"]},
                }
            )
        clock_sync.append(
            {"path": p["path"], "start_us": s_us, "end_us": e_us}
        )

    payload = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "metadata": {
            "window_s": float(window_s),
            "exported_at": now,
            "kept": kept,
            "dropped": dropped,
            "requests": len(reqs),
            "batches": len(bats),
            "clock_sync": clock_sync,
        },
    }
    metrics.count("obs.timeline_exports")
    flight.record(
        "obs.timeline_export",
        window_s=float(window_s),
        events=len(events),
        requests=len(reqs),
        batches=len(bats),
    )
    _spool(payload)
    return payload


def _spool(payload: dict) -> Optional[str]:
    """Write one rotated export file under the configured timeline dir
    (no-op when unset); best-effort — a spool failure must never fail
    the GET that triggered the export."""
    cfg = _cfg
    if not cfg.dirpath:
        return None
    global _spool_seq
    with _lock:
        _spool_seq += 1
        n = _spool_seq
    stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
    path = os.path.join(
        cfg.dirpath, f"timeline-{stamp}-{os.getpid()}-{n}.json"
    )
    try:
        os.makedirs(cfg.dirpath, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, default=str)
        os.replace(tmp, path)
        spooled = sorted(
            f for f in os.listdir(cfg.dirpath)
            if f.startswith("timeline-") and f.endswith(".json")
        )
        for stale in spooled[: -cfg.keep]:
            os.unlink(os.path.join(cfg.dirpath, stale))
    except OSError:
        return None
    return path
