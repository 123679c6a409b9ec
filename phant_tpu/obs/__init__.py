"""Request-scoped tracing and postmortem layer (on top of utils/trace.py).

PR 1 gave the process metrics and per-block spans; PR 3 gave the
continuous-batching scheduler. What was still missing is REQUEST identity
across the scheduler boundary — nothing tied an
`engine_executeStatelessPayloadV1` call to the queue wait, bucket, batch,
and device dispatch that served it — and any postmortem when the process
died. This package is that layer:

* **Trace context** (`utils/trace.py trace_context`): the Engine API
  server opens one per POST; the span a request opens and the scheduler
  jobs it submits all carry the request's `trace_id`. The scheduler
  attaches a batch record (`batch_id`, `queue_wait_ms`, `bucket_bytes`,
  `batch_size`, `backend`, cache hit/miss counts) to every job it
  executes, and `stateless.join_witness` folds it into the
  request's top-level span — concurrent requests coalesced into one batch
  each get their own span linked by the shared `batch_id`.
* **Flight recorder** (`flight.py`): a bounded thread-safe ring of span /
  error / scheduler-transition records, served live at `GET /debug/flight`
  and dumped to `build/flight/` on executor crash, on `/healthz` flipping
  to 503, and on SIGTERM.
* **Watchdog** (`watchdog.py`): detects the executor stalling inside a
  batch (deadline overrun without a crash) and records it as a metric +
  flight event.
* **Critical-path attribution** (`critpath.py`, PR 15): a second span
  sink tiles every `verify_block` request's wall clock into the
  `critpath.*` phase family (queue wait / prefetch / pack / dispatch /
  resolve / sig_wait / EVM / post-root ...), gauges the unattributed
  residual (the honesty check), and captures SLO-busting requests as
  full span trees into a dedicated ring (`GET /debug/slow`).
* **On-demand profiler** (`profiler.py`, PR 15): `POST /debug/profile`
  grabs a single-flight-guarded, hard-capped `jax_profile` window from a
  live server.
* **Timeline export** (`timeline.py`, PR 16): a third span sink plus
  batch/profiler taps tail-sample the serving path into a bounded
  recorder, rendered as Perfetto-loadable Chrome-trace JSON at
  `GET /debug/timeline?window=S` — requests (phases at their measured
  offsets) and lane batches on one time axis, stitched by flow events.
* **Measured intervals** (`utils/trace.py`, PR 26): spans carry ids, a
  start and an end on one clock, phases are child intervals, and all of
  it is written into the profiler's trace as `phant/` events; what the
  device is busy or idle under is read from that trace
  (`scripts/trace_gaps.py`), and the host's own time at the device from
  `device.host_seconds{lane=,op=}`.

Importing this package registers the flight recorder, the critpath
rollup, and the timeline recorder as span sinks, so any module that
touches obs gets span mirroring, attribution, and timeline capture for
free; the registrations are idempotent.
"""

from __future__ import annotations

from phant_tpu.obs import critpath, timeline
from phant_tpu.obs.flight import FlightRecorder, flight
from phant_tpu.obs.watchdog import Watchdog
from phant_tpu.utils.trace import add_span_sink

__all__ = [
    "FlightRecorder",
    "Watchdog",
    "critpath",
    "flight",
    "record_span",
    "timeline",
]


def record_span(record: dict) -> None:
    """The span sink: mirror every top-level span record into the ring."""
    flight.record("span", span=record)


add_span_sink(record_span)
add_span_sink(critpath.rollup)
add_span_sink(timeline.on_span)
