"""On-demand TPU profiler capture (PR 15): POST /debug/profile.

`jax_profile` (utils/trace.py) existed since PR 1 — but only as a
context manager reachable from the `--trace-logdir` flag,
i.e. you had to DECIDE to profile before starting the server. A real-v5e
load run wants the opposite: the server is mid-traffic, a latency gauge
looks wrong, grab an XLA trace of the NEXT T seconds without restarting.
`capture(seconds)` is that: it wraps `jax.profiler.start/stop_trace`
around a sleep on the calling (HTTP handler) thread while the serving
threads keep working — the profiler records the whole process, so the
capture window sees every lane's dispatches.

Guards, because this is a debug surface on a serving box:

* SINGLE-FLIGHT — jax supports one active trace per process; a second
  capture attempt raises `ProfileBusy` (the server maps it to HTTP 503)
  instead of corrupting the first.
* HARD CAP — the window is clamped to PHANT_PROFILE_MAX_S (default 30):
  a fat-fingered `seconds=3600` must not pin a handler thread (and the
  profiler's memory growth) for an hour.
* The trace directory defaults to `build/profile/` and is overridden by
  `--profile-dir` / PHANT_PROFILE_DIR; each capture gets its own
  timestamped subdirectory so repeated grabs never overwrite.

Every capture leaves an `obs.profile` flight record (directory, window,
artifact count) so the postmortem ring knows a profiler ran — a capture
perturbs the very latencies it measures, and the audit trail keeps that
honest. View artifacts with TensorBoard or Perfetto (xplane/trace.json).
"""

from __future__ import annotations

import math
import os
import threading
import time

from phant_tpu.obs.flight import flight

#: default hard cap on one capture window (seconds)
_DEFAULT_MAX_S = 30.0


class ProfileBusy(Exception):
    """A capture is already in flight (jax allows one trace per process)."""


class ProfileError(Exception):
    """The profiler itself failed (jax absent, trace dir unwritable, ...)."""


_inflight = threading.Lock()
#: per-capture suffix; only ever touched under the _inflight guard
_seq = 0


def profile_dir() -> str:
    d = os.environ.get("PHANT_PROFILE_DIR")
    if d:
        return d
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(root, "build", "profile")


def max_seconds() -> float:
    try:
        v = float(os.environ.get("PHANT_PROFILE_MAX_S", str(_DEFAULT_MAX_S)))
    except ValueError:
        return _DEFAULT_MAX_S
    return v if v > 0 else _DEFAULT_MAX_S


def capture(seconds: float) -> dict:
    """Run one profiler capture of `seconds` (clamped to the hard cap);
    returns {"path", "seconds", "artifacts"}. Raises ValueError on a
    non-positive/non-finite window, ProfileBusy on overlap, ProfileError
    when the profiler fails. Blocks the CALLING thread for the window —
    the HTTP handler thread, by design: the reply lands when the
    artifacts are on disk."""
    s = float(seconds)
    if not math.isfinite(s) or s <= 0:
        raise ValueError(f"profile window must be a positive number, got {seconds!r}")
    s = min(s, max_seconds())
    if not _inflight.acquire(blocking=False):
        raise ProfileBusy("a profiler capture is already in flight")
    try:
        global _seq
        _seq += 1
        n = _seq
        stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
        path = os.path.join(
            profile_dir(), f"profile-{stamp}-{os.getpid()}-{n}"
        )
        t_start = time.time()
        try:
            os.makedirs(path, exist_ok=True)
            from phant_tpu.utils.trace import jax_profile

            with jax_profile(path):
                time.sleep(s)
        except Exception as e:
            raise ProfileError(f"profiler capture failed: {e!r}") from e
        t_end = time.time()
        artifacts = sum(len(files) for _d, _sub, files in os.walk(path))
        flight.record("obs.profile", path=path, seconds=s, artifacts=artifacts)
        # clock-sync marker: the capture window lands on the timeline's
        # profiler track so the XLA device trace can be laid alongside
        from phant_tpu.obs import timeline

        timeline.record_profile(path, t_start, t_end)
        return {"path": path, "seconds": s, "artifacts": artifacts}
    finally:
        _inflight.release()
