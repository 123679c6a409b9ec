"""Executor stall watchdog.

An executor that CRASHES is already loud (scheduler `_die`: futures fail
fast, `/healthz` 503, flight dump). An executor that STALLS — wedged
inside a device call that never returns — is silent: the queue grows,
requests time out one by one, and nothing says why. The watchdog closes that gap: a daemon thread polls the
scheduler's in-flight state and, when the batch being executed has
out-lived its deadline, records the stall ONCE per batch as

* `sched.watchdog_stalls` (counter) and
* a `sched.stall` flight event carrying the batch id, lane, overdue time,
  and the trace ids of every coalesced request —

so a postmortem dump of a wedged server names the batch that wedged it.
The scheduler starts one per instance (serving/scheduler.py) and stops it
on shutdown/death; detection is passive (the watchdog never kills or
requeues — policy stays with the operator/orchestrator).
"""

from __future__ import annotations

import threading
from typing import Optional

from phant_tpu.obs.flight import flight
from phant_tpu.utils.trace import metrics

#: default poll interval (seconds); a stall is a seconds-scale condition
_DEFAULT_INTERVAL_S = 0.25


# the one mutable field, _last_flagged, is read and written ONLY by the
# watchdog's own worker thread (_run); start/stop touch the Event, which
# carries its own lock
class Watchdog:  # phantlint: disable=THREADSHARE — worker-thread-private state
    """Polls `source()` — a callable returning the in-flight descriptor
    `{"batch_id", "lane", "started", "deadline", "trace_ids"}` or None —
    and records each batch's first deadline overrun."""

    def __init__(self, source, interval_s: float = _DEFAULT_INTERVAL_S):
        self._source = source
        self._interval_s = interval_s
        self._stop = threading.Event()
        self._last_flagged: Optional[int] = None  # batch_id, once per batch
        self._thread = threading.Thread(
            target=self._run, name="phant-obs-watchdog", daemon=True
        )

    def start(self) -> "Watchdog":
        self._thread.start()
        return self

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout)

    def _run(self) -> None:
        import time

        while not self._stop.wait(self._interval_s):
            try:
                st = self._source()
            except Exception:
                continue  # a racing shutdown must not kill the watchdog
            if st is None or st.get("deadline") is None:
                continue
            now = time.monotonic()
            if now <= st["deadline"] or st.get("batch_id") == self._last_flagged:
                continue
            self._last_flagged = st.get("batch_id")
            overdue_ms = round((now - st["deadline"]) * 1e3, 1)
            metrics.count("sched.watchdog_stalls")
            flight.record(
                "sched.stall",
                batch_id=st.get("batch_id"),
                lane=st.get("lane"),
                # which pipeline stage the wedged batch was in (pack/
                # dispatch/resolve — serving/scheduler.py descriptors),
                # and which mesh device lane was running it (None on the
                # single-executor path): a wedged chip gets NAMED
                stage=st.get("stage"),
                device=st.get("device"),
                inflight_ms=round((now - st["started"]) * 1e3, 1),
                overdue_ms=overdue_ms,
                trace_ids=st.get("trace_ids"),
            )
