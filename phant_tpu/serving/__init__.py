"""Serving subsystem: the continuous-batching verification scheduler.

`scheduler.py` holds the machinery (admission queue, shape-bucketed batch
assembler, single executor thread, `verify_many()`); this package root
holds the process-global *active scheduler* slot:

* the Engine API server installs its scheduler here on construction and
  uninstalls it on shutdown;
* `stateless.admit_witness` / `join_witness` (and their synchronous
  face `verify_witness_nodes`) route witness verification through the
  active scheduler when one is installed (so concurrent
  `engine_executeStatelessPayloadV1` handler threads coalesce their
  linked-multiproof checks into one engine/device dispatch) and falls
  back to the direct shared-engine path otherwise — offline callers
  and tests that never installed a scheduler are untouched;
* `/healthz` (engine_api/server.py) reads the active scheduler's state
  and turns an executor crash into a 503.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from phant_tpu.serving.qos import (
    DEFAULT_TENANT,
    PRIORITY_BACKFILL,
    PRIORITY_HEAD,
    current_priority,
    current_tenant,
    parse_weights,
    sanitize_tenant,
    tenant_context,
)
from phant_tpu.serving.mesh_exec import MeshExecutorPool, affinity_device
from phant_tpu.serving.scheduler import (
    DeadlineExpired,
    QueueFull,
    SchedulerConfig,
    SchedulerDown,
    SchedulerError,
    VerificationScheduler,
)

__all__ = [
    "DEFAULT_TENANT",
    "PRIORITY_BACKFILL",
    "PRIORITY_HEAD",
    "LANE_PROGRAMS",
    "DeadlineExpired",
    "MeshExecutorPool",
    "QueueFull",
    "SchedulerConfig",
    "SchedulerDown",
    "SchedulerError",
    "VerificationScheduler",
    "active_scheduler",
    "affinity_device",
    "boot_lanes",
    "current_priority",
    "current_tenant",
    "install",
    "on_cpu",
    "parse_weights",
    "sanitize_tenant",
    "tenant_context",
    "uninstall",
]

log = logging.getLogger(__name__)

_active: Optional[VerificationScheduler] = None
_active_lock = threading.Lock()


def install(scheduler: VerificationScheduler) -> Optional[VerificationScheduler]:
    """Make `scheduler` the process's active scheduler; returns the one it
    displaced (None normally — two live servers would fight over the slot,
    and the last one in wins, same as binding a port twice would)."""
    global _active
    with _active_lock:
        prev, _active = _active, scheduler
    return prev


def uninstall(scheduler: VerificationScheduler) -> None:
    """Clear the slot IF `scheduler` still owns it (a later install wins)."""
    global _active
    with _active_lock:
        if _active is scheduler:
            _active = None


def active_scheduler() -> Optional[VerificationScheduler]:
    """The installed scheduler, or None (read is lock-free: a stale read
    just takes the direct-engine path for one call)."""
    return _active


#: the served device programs whose shapes `lanes.program_shapes` counts
#: (the root program's are `root.plan_shapes`)
LANE_PROGRAMS = ("ecrecover", "verdict", "gather", "update")


def on_cpu() -> bool:
    import jax

    return jax.default_backend() == "cpu"


def boot_lanes(scheduler: VerificationScheduler) -> None:
    """What every entry point that owns a scheduler runs before its first
    job (the Engine API server's constructor, the replay CLI's builder):
    `lanes.program_shapes{program=}` is on /metrics from the start; and
    where the witness lane launches on an accelerator, the resident table's
    update, verdict and gather programs are built here on every rung of
    their ladders, on the table the scheduler will use, before the port
    answers or the first segment is sent (`engine_api/server._boot_root_lane`'s
    twin): which requests share a wave is up to a 5 ms window (20 ms in a
    replay), so no warm-up of a client's could reach every rung (PERF.md
    section 7, fault 0c). `ecrecover_kernel` is not built here: it has ONE
    rung, which the first request builds whatever wave it comes in
    (`secp256k1_jax.SIG_LADDER` says why). On the CPU, where tests and dry
    runs force the lanes, a rung is built when first met."""
    from phant_tpu.backend import crypto_backend, jax_device_ok
    from phant_tpu.utils.rungs import export_shapes
    from phant_tpu.utils.trace import metrics

    export_shapes(LANE_PROGRAMS)
    if crypto_backend() != "tpu" or not jax_device_ok() or on_cpu():
        return
    t0 = time.monotonic()
    n = scheduler.prewarm_lanes()
    dt = time.monotonic() - t0
    metrics.gauge_set("lanes.prewarm_seconds", dt)
    log.info("witness lane: %d programs built in %.1fs", n, dt)
