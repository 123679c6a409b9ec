"""Flight recorder: a bounded ring of recent observability records.

A dead server must leave a postmortem without log scraping (a killed
process once left NO artifact at all — the flight recorder is the
serving-side answer to that failure mode).
The ring holds the most recent span records, error records, and scheduler
state transitions (admit / shed / batch-start / batch-done / crash /
stall), each stamped with a wall clock, a monotonic sequence number, and —
when recorded inside a `trace_context` — the request's `trace_id`.

Three surfaces:

* `GET /debug/flight` (engine_api/server.py) serves the live ring as JSON;
* `dump(reason)` writes the ring to `build/flight/` as one JSON file —
  triggered on executor crash (serving/scheduler.py `_die`), on `/healthz`
  flipping to 503, and on SIGTERM (phant_tpu/__main__.py), and counted in
  `flight.dumps{reason=...}`; retention keeps the newest
  `PHANT_FLIGHT_KEEP` (default 16) dump files;
* tests/tools read `records()` directly.

Record kinds are vocabulary-gated: every `kind` passed to `record()` must
be a literal with a `trace.SPAN_HELP` entry (phantlint SPANNAME), exactly
as metric names are gated by METRIC_HELP.

Thread-safety: one lock guards the deque and the sequence counter; a
record is one dict build + append under it, cheap enough for the admission
path. `dump()` snapshots under the lock and writes outside it.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import List, Optional

from phant_tpu.utils.trace import current_trace_id, metrics

#: default ring capacity (records); override with --flight-ring /
#: PHANT_FLIGHT_RING (PHANT_FLIGHT_CAPACITY kept as the legacy alias)
_DEFAULT_CAPACITY = 2048


def _capacity_from_env() -> int:
    """Resolve the global ring's capacity ONCE (module import and
    `refresh_from_env()` — never per record): PHANT_FLIGHT_RING wins,
    the pre-PR-16 PHANT_FLIGHT_CAPACITY spelling still works."""
    raw = os.environ.get(
        "PHANT_FLIGHT_RING",
        os.environ.get("PHANT_FLIGHT_CAPACITY", str(_DEFAULT_CAPACITY)),
    )
    try:
        v = int(raw or str(_DEFAULT_CAPACITY))
    except ValueError:
        return _DEFAULT_CAPACITY
    return max(v, 1)


def _flight_dir() -> str:
    d = os.environ.get("PHANT_FLIGHT_DIR")
    if d:
        return d
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, "build", "flight")


class FlightRecorder:
    """Bounded, thread-safe ring of observability records."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self._seq = 0
        self._dump_seq = 0

    def record(self, kind: str, **fields) -> None:
        """Append one record. `kind` must be a SPAN_HELP-cataloged literal
        (phantlint SPANNAME). A `trace_id` is attached automatically when
        the calling thread is inside a `trace_context` (explicit
        `trace_id=` wins)."""
        if "trace_id" not in fields:
            tid = current_trace_id()
            if tid is not None:
                fields["trace_id"] = tid
        rec = {"kind": kind, "t": time.time(), **fields}
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            self._ring.append(rec)

    def records(self) -> List[dict]:
        """Snapshot of the ring, oldest first."""
        with self._lock:
            return list(self._ring)

    def ring_capacity(self) -> int:
        """The capacity, read under the ring lock: handler threads report
        it (/healthz debug_rings) while resize()/refresh_from_env()
        rewrite it — the unlocked attribute read was phantsan's first
        real-tree catch (the same field the pre-PR-16 dump() bug tore)."""
        with self._lock:
            return self.capacity

    def snapshot(self) -> dict:
        """Capacity AND records from one lock region — a /debug/flight
        reply must not pair a post-resize capacity with a pre-resize
        ring."""
        with self._lock:
            return {"capacity": self.capacity, "records": list(self._ring)}

    def resize(self, capacity: int) -> None:
        """Rebuild the ring at a new capacity, keeping the NEWEST records
        (a shrink drops from the oldest end — ring semantics)."""
        capacity = max(int(capacity), 1)
        with self._lock:
            if self._ring.maxlen != capacity:
                self._ring = deque(self._ring, maxlen=capacity)
            self.capacity = capacity

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    # -- postmortem dumps ----------------------------------------------------

    def dump(self, reason: str, dirpath: Optional[str] = None) -> Optional[str]:
        """Write the ring to `<dir>/flight-<utc>-<reason>-<pid>.json` and
        return the path (None when the write itself fails — a postmortem
        path must never take the process down with it). Prunes the dump dir
        to the newest PHANT_FLIGHT_KEEP files."""
        d = dirpath or _flight_dir()
        snap = self.records()
        with self._lock:
            self._dump_seq += 1
            dump_n = self._dump_seq  # same-second same-reason dumps stay distinct
            cap = self.capacity  # resize() mutates under the same lock
        payload = {
            "reason": reason,
            "dumped_at": time.time(),
            "pid": os.getpid(),
            "capacity": cap,
            "records": snap,
        }
        stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
        path = os.path.join(
            d, f"flight-{stamp}-{reason}-{os.getpid()}-{dump_n}.json"
        )
        try:
            os.makedirs(d, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, default=str)
            os.replace(tmp, path)
        except OSError:
            return None
        metrics.count("flight.dumps", reason=reason)
        self.record("flight.dump", reason=reason, path=path, n_records=len(snap))
        self._prune(d)
        return path

    @staticmethod
    def _prune(d: str) -> None:
        keep = int(os.environ.get("PHANT_FLIGHT_KEEP", "16"))
        try:
            dumps = sorted(
                f for f in os.listdir(d)
                if f.startswith("flight-") and f.endswith(".json")
            )
            for stale in dumps[:-keep] if keep > 0 else []:
                os.unlink(os.path.join(d, stale))
        except OSError:
            pass  # retention is best-effort; the fresh dump already landed


#: process-global recorder (importable singleton, like trace.metrics)
flight = FlightRecorder(capacity=_capacity_from_env())


def refresh_from_env() -> None:
    """Re-resolve the global ring's capacity from the environment (the
    Engine API server calls this at construction, after the CLI wrote
    `--flight-ring` into the env — the once-at-construction contract,
    NOT re-read per record)."""
    flight.resize(_capacity_from_env())
