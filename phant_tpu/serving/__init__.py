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

import threading
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
    "DeadlineExpired",
    "MeshExecutorPool",
    "QueueFull",
    "SchedulerConfig",
    "SchedulerDown",
    "SchedulerError",
    "VerificationScheduler",
    "active_scheduler",
    "affinity_device",
    "current_priority",
    "current_tenant",
    "install",
    "parse_weights",
    "sanitize_tenant",
    "tenant_context",
    "uninstall",
]

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
