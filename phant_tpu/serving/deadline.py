"""The clock request deadlines run on.

A request's deadline sheds load: a job that waited out its deadline in
the queue is failed (`-32051`) instead of executed. A device program's
first call per shape compiles inside the executor thread — seconds to
minutes (the ecrecover ladders) — and everything queued behind it waits,
although nothing is overloaded. Counted against the deadline, that made a
freshly started `--crypto_backend=tpu` server answer 503 to every client
until its programs were compiled.

So deadlines run on monotonic time MINUS the wall-clock seconds the
serving threads (executor, resolve worker, mesh lanes — `serving_thread`)
have spent compiling, by jax's own account (trace, lowering, backend
compile; a persistent-cache hit counts its retrieval). A compile that
holds the executor is credited back the moment it ends, before the
executor looks at its queue again: a cold server answers late, a loaded
one still sheds. A compile on any other thread (the mesh boot prewarm, a
caller's own jax work) holds no queue and counts for nothing. With no jax
in the process (the cpu backend) the clock is `time.monotonic()`.

The same listeners are the program's own count of the programs it builds:
`jit.compiles{thread=serving|other}` (one per backend compile and one per
load from the persistent cache: both mean a shape the process had not run
yet), `jit.serving_compile_seconds` (the credit above), and through
`utils/trace.note_compile` a `compile` interval of the span open on the
compiling thread, `compile_ms` on the lane batch bound to it, and a
`phant/compile` marker in the profiler's trace: a request or a batch that
stood behind a compile says so.
"""

from __future__ import annotations

import threading
import time

from phant_tpu.utils.trace import metrics, note_compile

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _BACKEND_COMPILE,
)

_lock = threading.Lock()
_tls = threading.local()  # .serving: this thread's compiles hold a queue
_started = False
_compile_s = 0.0  # length of the union of the compile intervals seen so far
_spans: list = []  # that union's recent part: disjoint (start, end), ascending


def _count_compile(serving: bool) -> None:
    metrics.count("jit.compiles", thread="serving" if serving else "other")


def _on_duration(event: str, secs: float, **_kw) -> None:
    """jax calls this at the END of each phase, on the compiling thread.
    Lanes compile concurrently (one per chip) and traces nest, so only the
    part of [end - secs, end] that no earlier interval covers is added:
    the credit never exceeds the wall clock. One big trace reports tens of
    thousands of nested ones, so this is O(1) amortised: an interval ends
    after every earlier one, hence overlaps only a tail of `_spans`."""
    global _compile_s
    if event not in _COMPILE_EVENTS:
        return
    serving = getattr(_tls, "serving", False)
    if event == _BACKEND_COMPILE:
        _count_compile(serving)
        note_compile(secs)
    if not serving:
        return
    end = time.monotonic()
    start = merged_start = end - secs
    with _lock:
        covered = 0.0
        while _spans and _spans[-1][1] >= start:
            a, b = _spans.pop()
            covered += b - max(a, start)
            merged_start = min(merged_start, a)
        _compile_s += secs - covered
        _spans.append((merged_start, end))
        if len(_spans) > 4096:  # old intervals overlap nothing new
            del _spans[:2048]
        total = _compile_s
    metrics.gauge_set("jit.serving_compile_seconds", total)


def _on_event(event: str, **_kw) -> None:
    """A program loaded from the persistent cache instead of compiled: the
    other way a shape is first built (its seconds reach `_on_duration`
    as the compile events the retrieval stands in for)."""
    if event == _CACHE_HIT:
        _count_compile(getattr(_tls, "serving", False))


def serving_thread() -> None:
    """Mark the calling thread as one whose compiles hold a job queue."""
    _tls.serving = True


def start_compile_clock() -> None:
    """Subscribe to jax's compile durations, once per process. Called by
    the scheduler when it serves the tpu backend (jax is loaded then)."""
    global _started
    with _lock:
        if _started:
            return
        _started = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def expiry(seconds: float) -> float:
    """The deadline of a job admitted now with `seconds` to live."""
    with _lock:
        return time.monotonic() - _compile_s + seconds


def passed(deadline: float | None, at: float | None = None) -> bool:
    """Whether `deadline` (from `expiry`; None = none) has passed at
    monotonic time `at` (default: now)."""
    if deadline is None:
        return False
    with _lock:
        return (time.monotonic() if at is None else at) - _compile_s > deadline
