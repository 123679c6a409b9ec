"""Continuous-batching verification scheduler.

The Engine API server used to execute one request at a time behind a
global lock: concurrent CL requests queued on a mutex and each one paid a
batch-of-1 engine dispatch — the exact opposite of the framework's win
condition (vmapping witness verification across hundreds of blocks per
device dispatch). This module gives the serving path the inference-server
shape instead:

    admission queue  ->  batch assembler  ->  single executor thread

* **Admission: per-tenant lanes + quotas (QoS, serving/qos.py)** — every
  request carries a tenant tag (the Engine API server binds it from the
  `X-Phant-Tenant` header via `tenant_context`; untagged submissions land
  in the `default` lane) and a priority class. Witness jobs queue in a
  per-tenant FIFO lane; the total across lanes is bounded by
  `queue_depth` and each lane by `tenant_quota` (0 = unbounded), so one
  backfill tenant can no longer fill the whole queue. A full lane sheds
  with `QueueFull` (`-32050`, `sched.rejected{reason=tenant_quota,
  tenant=...}`); a full queue sheds `reason=queue_full` — unless the
  arriving job is head-of-chain (`PRIORITY_HEAD`: the serial mutation
  lane, or a witness request marked `X-Phant-Priority: head`), in which
  case a queued victim is evicted to make room (`reason=evicted`, same
  `-32050` code). The shed order is fixed and documented: backfill
  first (a head-class arrival at its tenant quota evicts its OWN
  tenant's newest backfill; a full queue evicts the deepest lane's
  newest backfill), head-class witness jobs only for an arriving SERIAL
  mutation with no backfill left, and the serial mutation lane NEVER —
  a mutation can only be rejected when the queue is full of OTHER
  serial mutations (its own class's backlog). Eviction also never
  touches `wait_for_space` (verify_many) jobs, whose contract is
  completion. Every request still carries a deadline; expiry while
  queued fails with `DeadlineExpired` (`-32051`) without touching the
  engine. The deadline's clock excludes the seconds this process spends
  compiling device programs (serving/deadline.py): a compile holding
  the executor is not load, so a cold server answers late, not 503.
* **Dequeue: priority + weighted fairness** — the serial mutation lane
  preempts all queued witness work (head-of-chain `newPayload` must not
  sit behind a backfill burst); among witness lanes, lanes whose head is
  `PRIORITY_HEAD` are served before backfill lanes, and the tenant is
  chosen by smooth weighted round-robin (qos.WeightedFairPicker,
  `tenant_weights`) so a 10:1 offered-load imbalance cannot starve the
  light tenant — each lane stays FIFO internally.
* **Batch assembler** — coalesces *witness-verification* requests into
  shape buckets (bucket key = total witness bytes rounded up to a power
  of two, the same rounding the device keccak path pads its blob buffer
  to, utils/rungs.pow2ceil), so the padded device buffers of one
  batch stay dense; same-bucket jobs coalesce ACROSS tenant lanes (the
  engine dispatch is tenant-blind; fairness is enforced at head pick).
  `sched.padding_waste` reports the unused fraction of the padded
  buffer. Assembly runs under a `max_batch` / ADAPTIVE-wait policy
  (qos.AdaptiveWait): a batch executes as soon as it is full, and an
  under-full batch waits at most `wait_ms(queue_depth)` from its head
  request's admission — the full `max_wait_ms` when the scheduler is
  idle (a lone request gets its coalescing window), decaying to
  `min_wait_ms` as the queue approaches one full batch, because then
  the backlog IS the batch and further waiting is pure added latency.
  The chosen wait is exported as the `sched.adaptive_wait_ms` gauge,
  changes are counted in `sched.adaptive_wait_adjustments` and recorded
  as `sched.adapt_wait` flight events; `adaptive_wait=False` pins the
  static `max_wait_ms` policy (the pre-QoS behavior).
* **Executor** — ONE thread drains buckets into the engine and resolves
  per-request futures. The same thread runs *serial* jobs
  (state-mutating `engine_newPayload*` execution) one at a time, in
  admission order — which is what replaces the server's global execution
  lock: mutation is serialized by the executor, not by a mutex held
  across the whole request.
* **Pipeline** (`pipeline_depth`, default 2 via
  PHANT_SCHED_PIPELINE_DEPTH / `--sched-pipeline-depth`) — with depth
  >= 2 the executor splits witness execution through the engine's
  two-phase API (ops/witness_engine.py `begin_batch`/`resolve_batch`):
  it PACKS batch N+1 (bucket assembly + lock-held intern scan) and
  DISPATCHES its novel-node keccak with no host sync while a dedicated
  *resolve worker* thread RESOLVES batch N (digest readback / GIL-free C
  hashing outside the engine lock, then commit + linkage join). JAX's
  async dispatch means the device was idle during host packing and the
  host idle during device compute — this is the overlap that closes it,
  the same double-buffered-prefetch shape inference servers use. At
  most `pipeline_depth` batches are in flight; the executor blocks on a
  full pipeline (`sched.pipeline_stall` names resolve as the
  bottleneck). Depth 1 — or an engine without `begin_batch` — is the
  pre-pipeline behavior, byte-identical inline verify_batch execution.
  The serial lane drains the WHOLE pipeline first, so mutation stays
  exclusive against in-flight witness work; futures still complete in
  admission order per requester (the resolve worker is FIFO). On crash
  paths, dispatched-but-unresolved handles are released through the
  engine's `abandon_batch` (when it has one) so a shared engine that
  outlives a dead scheduler never leaks in-flight leases. Handle
  resolution order is a per-scheduler property only — the engine accepts
  any interleaving, so several schedulers can share one engine.
  `witness_async` hands the submitter a `PendingVerdict`: it can wait
  for its batch's LAUNCH (`begin_batch` returned; every path that ends
  a job without one releases the waiter too), do host work that needs
  nothing from the verdict while the device computes it, and join later
  (stateless.execute_stateless decodes the witness in between).
* **Mesh dispatch** (`mesh_devices` >= 1 via `--sched-mesh N` /
  PHANT_SCHED_MESH) — admission, tenant-fair head pick, and batch
  assembly stay GLOBAL, but execution fans out to a `MeshExecutorPool`
  (serving/mesh_exec.py): one pipelined executor per mesh device, each
  owning a `WitnessEngine` pinned to that device, with stable
  bucket-affinity routing (a shape keeps hitting the same device's
  intern table) and least-loaded spillover once the home lane backs up.
  `mesh_dispatch="megabatch"` additionally sends a single-bucket batch
  that fills `max_batch` through ONE whole-mesh sharded fused kernel
  call. The serial lane drains the whole pool first (mutation stays
  exclusive against every device), any lane crash takes the scheduler
  down exactly like an executor crash — with every device's
  dispatched-but-unresolved handles abandoned — and batch/stall/crash
  records carry the `device` that ran them.
* **Lifecycle** — `shutdown(drain=True)` stops admission and lets the
  executor finish everything queued AND everything in the pipeline
  (graceful drain); an exception escaping batch execution — in either
  thread — marks the scheduler DOWN: the crashed batch, everything
  queued, and every dispatched-but-unresolved handle fail fast with
  `SchedulerDown` (`-32052`), later submits are rejected immediately,
  `/healthz` reports 503 with `executor_alive: false`
  (engine_api/server.py `_healthz_payload`), and the crash flight
  record names the pipeline STAGE that died (pack/dispatch/resolve).

`verify_many()` is the synchronous offline face of the same machinery:
the spec runner (`--sched`), `scripts/soak.py` and tests push whole witness
spans through the identical admission/assembly/executor code and get an
(n,) bool verdict array back — the batching code measured offline is the
batching code serving traffic.

Observability (phant_tpu/obs/, PR 4): every job carries the submitting
request's `trace_id` (utils/trace.py trace_context — the Engine API server
opens one per POST), admissions/sheds/batch transitions land in the flight
recorder ring, and the executor attaches a per-batch record (`batch_id`,
`batch_size`, `bucket_bytes`, `backend`, cache hit/miss deltas,
`queue_wait_ms`) to each job it resolves — `verify_traced()` hands it back
so the request's span stays joinable to the batch that served it. An obs
watchdog thread per scheduler flags the in-flight batch out-living its
deadline (`sched.watchdog_stalls` + a `sched.stall` flight event); an
executor crash additionally dumps the ring to build/flight/ (the
postmortem artifact a dead server leaves behind).

Thread-safety: one lock (`_lock`) guards the queue and lifecycle state;
`_cond` wraps that same lock, so every wait/notify runs under it. The
registry's and flight recorder's own locks never take ours, so metric and
flight publishes cannot deadlock against admission (same discipline as
ops/witness_engine.py).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from phant_tpu.obs import critpath, timeline
from phant_tpu.obs.flight import flight
from phant_tpu.obs.watchdog import Watchdog
from phant_tpu.serving import deadline as deadline_clock
from phant_tpu.serving.qos import (
    DEFAULT_TENANT,
    OVERFLOW_TENANT,
    PRIORITY_BACKFILL,
    PRIORITY_HEAD,
    AdaptiveWait,
    WeightedFairPicker,
    current_priority,
    current_tenant,
    parse_weights,
)
from phant_tpu.utils.rungs import pow2ceil as _pow2ceil
from phant_tpu.utils.trace import (
    clock_ns,
    current_trace_id,
    fold_stages,
    lane_stage,
    metrics,
)

log = logging.getLogger("phant_tpu.serving")


class SchedulerError(Exception):
    """Base for scheduler rejections; carries the JSON-RPC error code and
    HTTP status the Engine API server maps the rejection to."""

    code = -32000
    http_status = 503


class QueueFull(SchedulerError):
    """Admission queue at `queue_depth`: overload, shed the request."""

    code = -32050


class DeadlineExpired(SchedulerError):
    """The request's deadline passed before the executor reached it."""

    code = -32051


class SchedulerDown(SchedulerError):
    """The executor has crashed or the scheduler is shutting down."""

    code = -32052


def _default_pipeline_depth() -> int:
    """PHANT_SCHED_PIPELINE_DEPTH, default 2 (overlap pack of batch N+1
    with resolve of batch N). Depth 1 is the pre-pipeline serialized
    behavior: the executor runs pack -> dispatch -> resolve inline."""
    return int(os.environ.get("PHANT_SCHED_PIPELINE_DEPTH", "2"))


def _default_prefetch() -> bool:
    """PHANT_SCHED_PREFETCH, default on: with pipeline_depth >= 2, a
    dedicated prefetch worker runs batch N+1's witness decode + advisory
    intern-table novelty pre-scan (ops/witness_engine.py prefetch_batch)
    while batch N is in dispatch/resolve — the 4th pipeline stage
    (prefetch -> pack -> dispatch -> resolve). 0 / `--sched-prefetch 0`
    pins the PR-5 3-stage behavior. Prefetch is advisory end to end: the
    pack-time scan under the engine lock stays the authoritative commit,
    so a stale plan costs the perf win and nothing else."""
    return os.environ.get("PHANT_SCHED_PREFETCH", "1") not in ("0", "")


def _default_tenant_quota() -> int:
    """PHANT_SCHED_TENANT_QUOTA: per-tenant queued-witness cap; 0 (the
    default) means only the global queue_depth bounds a lane."""
    return int(os.environ.get("PHANT_SCHED_TENANT_QUOTA", "0"))


def _default_adaptive_wait() -> bool:
    """PHANT_SCHED_ADAPTIVE_WAIT, default on: shrink the assembly wait as
    the queue deepens, widen it when idle (qos.AdaptiveWait). 0 pins the
    static max_wait_ms policy."""
    return os.environ.get("PHANT_SCHED_ADAPTIVE_WAIT", "1") not in ("0", "")


def _default_min_wait_ms() -> float:
    """PHANT_SCHED_MIN_WAIT_MS: the adaptive-wait floor once the queue
    holds a full batch (the backlog IS the batch)."""
    return float(os.environ.get("PHANT_SCHED_MIN_WAIT_MS", "0.2"))


def _default_tenant_weights() -> dict:
    """PHANT_SCHED_TENANT_WEIGHTS (`name:weight,...`): weighted-fair
    dequeue shares; unlisted tenants weigh 1."""
    return parse_weights(os.environ.get("PHANT_SCHED_TENANT_WEIGHTS"))


def _default_max_tenants() -> int:
    """PHANT_SCHED_MAX_TENANTS: distinct tenant lanes tracked before new
    tags fold into the shared OVERFLOW lane — an attacker spraying random
    X-Phant-Tenant headers must not grow per-tenant state (or metric
    cardinality) without bound."""
    return int(os.environ.get("PHANT_SCHED_MAX_TENANTS", "64"))


def _default_mesh_devices() -> int:
    """PHANT_SCHED_MESH (`--sched-mesh N`): per-device executors behind
    the batch assembler. 0 (default) = the single-executor path; N >= 1
    fans dispatch out over a MeshExecutorPool of N device-pinned
    engines (N=1 is a one-lane pool — useful as the A/B control)."""
    return int(os.environ.get("PHANT_SCHED_MESH", "0"))


def _default_mesh_dispatch() -> str:
    """PHANT_SCHED_MESH_DISPATCH: `affinity` (default — bucket-affinity
    routing with spillover) or `megabatch` (a full single-bucket batch
    additionally dispatches as ONE whole-mesh sharded kernel call)."""
    return os.environ.get("PHANT_SCHED_MESH_DISPATCH", "affinity")


def _default_mesh_spill_depth() -> int:
    """PHANT_SCHED_MESH_SPILL: batches a bucket's home device may have
    outstanding before new batches spill to the least-loaded device."""
    return int(os.environ.get("PHANT_SCHED_MESH_SPILL", "2"))


def _default_megabatch_backlog_k() -> int:
    """PHANT_SCHED_MEGABATCH_BACKLOG_K: with `mesh_dispatch=megabatch`,
    ALSO fire the whole-mesh fused dispatch whenever the queued
    same-bucket work (current batch + still-queued same-bucket jobs) is
    >= mesh_width x k — sustained overload engages fusion without the
    operator sizing max_batch. 0 (default) keeps the full-batch-only
    trigger."""
    return int(os.environ.get("PHANT_SCHED_MEGABATCH_BACKLOG_K", "0"))


@dataclass
class SchedulerConfig:
    """Knobs, surfaced as `--sched-*` CLI flags (phant_tpu/__main__.py)."""

    max_batch: int = 128  # requests per assembled witness batch
    max_wait_ms: float = 5.0  # assembly-wait ceiling for an under-full batch
    queue_depth: int = 512  # admission-queue bound (overload -> QueueFull)
    deadline_ms: float = 30_000.0  # default per-request deadline; <=0 = none
    # witness batches in flight between pack and resolve (>=2 pipelines:
    # the executor packs/dispatches batch N+1 while the resolve worker
    # reads back + joins batch N); 1 = today's serialized execution
    pipeline_depth: int = field(default_factory=_default_pipeline_depth)
    # 4th pipeline stage (PR 9): prefetch worker decodes + pre-scans batch
    # N+1 while batch N is in dispatch/resolve. On whenever
    # pipeline_depth >= 2; `--sched-prefetch 0` / PHANT_SCHED_PREFETCH=0
    # opts out (the 3-stage PR-5 pipeline)
    prefetch: bool = field(default_factory=_default_prefetch)
    # --- multi-tenant QoS (serving/qos.py) ---------------------------------
    # per-tenant queued-witness cap (0 = global queue_depth only)
    tenant_quota: int = field(default_factory=_default_tenant_quota)
    # weighted-fair dequeue shares; unlisted tenants weigh 1.0
    tenant_weights: dict = field(default_factory=_default_tenant_weights)
    # queue-depth-adaptive assembly wait (False = static max_wait_ms)
    adaptive_wait: bool = field(default_factory=_default_adaptive_wait)
    # adaptive-wait floor (reached once the queue holds ~one full batch)
    min_wait_ms: float = field(default_factory=_default_min_wait_ms)
    # distinct tenant lanes before fold-over into OVERFLOW_TENANT
    max_tenants: int = field(default_factory=_default_max_tenants)
    # --- mesh dispatch (serving/mesh_exec.py) ------------------------------
    # per-device executors behind the assembler (0 = single-executor path)
    mesh_devices: int = field(default_factory=_default_mesh_devices)
    # "affinity" (bucket->device routing + spillover) or "megabatch"
    mesh_dispatch: str = field(default_factory=_default_mesh_dispatch)
    # home-device backlog at which a batch spills to the least-loaded lane
    mesh_spill_depth: int = field(default_factory=_default_mesh_spill_depth)
    # megabatch backlog trigger: fuse when queued same-bucket work >=
    # mesh width x k (0 = full-batch-only, the pre-trigger behavior)
    megabatch_backlog_k: int = field(default_factory=_default_megabatch_backlog_k)
    # per-lane engine injection (tests: doubles, shared engines);
    # None = one device-pinned WitnessEngine per lane
    mesh_engine_factory: Optional[Callable] = None
    # root-lane engine injection (tests/soak: poisoned engines, forced
    # device floors); None = the process-shared ops/root_engine.py engine
    # (mesh lanes build one PINNED RootEngine per device instead)
    root_engine_factory: Optional[Callable] = None
    # sig-lane engine injection (tests/soak: poisoned engines, forced
    # device floors); None = the process-shared ops/sig_engine.py engine
    # (mesh lanes build one PINNED SigEngine per device instead)
    sig_engine_factory: Optional[Callable] = None


_WITNESS = "witness"
_SERIAL = "serial"
#: post-root lane (PR 11): jobs carry a fused account+storage HashPlan
#: (stateless.WitnessStateDB.post_root_plan) and coalesce per level-shape
#: bucket into ONE ops/root_engine.py dispatch — the same admission /
#: fairness / assembly / pipeline / crash machinery as the witness lane
#: (the RootEngine speaks the WitnessEngine two-phase protocol). Root
#: buckets are NEGATIVE ints (-(level count)) so they can never collide
#: with the witness lane's pow2-byte buckets (>= 1).
_ROOT = "root"
#: sender-recovery lane (PR 14): jobs carry one request's signature rows
#: (signer.TxSigner.signature_rows) and coalesce into ONE merged
#: ops/sig_engine.py ecrecover dispatch — the same admission / fairness /
#: assembly / pipeline / crash machinery as the witness and root lanes
#: (the SigEngine speaks the WitnessEngine two-phase protocol). Rows are
#: freely concatenable (no per-request shape constraint — the kernel
#: pow2-pads the merged batch), so EVERY sig job shares one fixed bucket:
#: a large negative sentinel far below any root bucket (-(level count),
#: bounded by trie depth) and disjoint from witness pow2 buckets (>= 1).
_SIG = "sig"
_SIG_BUCKET = -(1 << 20)

#: _next_batch(block=False) found nothing queued (distinct from None =
#: closed/dead): the prefetching executor re-evaluates its pending work
_NO_BATCH = object()

#: batch-size histogram buckets (requests per engine dispatch)
_BATCH_BUCKETS: Tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def _safe_resolve(future: Future, result) -> None:
    """set_result tolerating a concurrent _die: with two scheduler threads,
    the resolve worker can complete a batch in the same instant the
    executor fails everything — losing that race must not raise
    InvalidStateError out of the winner."""
    try:
        future.set_result(result)
    except Exception:
        pass  # already failed by _die; the waiter got the crash


def _safe_fail(future: Future, exc: BaseException) -> None:
    if not future.done():
        try:
            future.set_exception(exc)
        except Exception:
            pass  # resolved in the race window; the waiter got a verdict


def batch_record_from_stats(
    batch_id: int, batch_size: int, bucket: int, s0: Optional[dict], s1: Optional[dict]
) -> dict:
    """The inline (fused verify_batch) batch record from an engine-stats
    delta: cache hits/misses plus the backend classification. Shared by
    the single-executor inline path and the mesh lanes' inline path so
    record semantics can never diverge between them. Deltas are
    batch-attributable as long as the caller is the engine's only
    concurrent user (true per executor/lane in the serving shapes)."""
    record = {
        "batch_id": batch_id,
        "batch_size": batch_size,
        "bucket_bytes": bucket,
        "stage": "dispatch",
    }
    if s0 is not None and s1 is not None:
        record["cache_hits"] = s1.get("hits", 0) - s0.get("hits", 0)
        record["cache_misses"] = s1.get("hashed", 0) - s0.get("hashed", 0)
        if s1.get("device_batches", 0) > s0.get("device_batches", 0):
            record["backend"] = "device"
        elif s1.get("native_batches", 0) > s0.get("native_batches", 0):
            record["backend"] = "native"
        else:
            record["backend"] = "cached"  # zero novel nodes: no hashing
    return record


def batch_record_from_handle(
    handle, batch_id: int, batch_size: int, bucket: int
) -> dict:
    """The two-phase batch record from the HANDLE (never an engine-stats
    delta: with batches overlapping in a pipeline, a delta would blend
    batch N's resolve with batch N+1's pack). `cache_misses` is the
    UNIQUE novel count (handle.n_novel) so identical traffic reads the
    same at every depth and lane — `miss` also counts within-batch
    duplicate occurrences. Shared by the resolve worker and the mesh
    lanes."""
    record = {
        "batch_id": batch_id,
        "batch_size": batch_size,
        "bucket_bytes": bucket,
        "stage": "resolve",
    }
    total = getattr(handle, "total", None)
    miss = getattr(handle, "miss", None)
    n_novel = getattr(handle, "n_novel", None)
    if total is not None and miss is not None:
        record["cache_hits"] = total - miss
        record["cache_misses"] = n_novel if n_novel is not None else miss
    if getattr(handle, "resident", None) is not None:
        # device-resident route: verdict + novel hashing on device
        # against the persistent intern table (ops/witness_resident.py)
        record["backend"] = "resident"
    elif getattr(handle, "device", None) is not None:
        record["backend"] = "device"
    elif n_novel if n_novel is not None else miss:
        record["backend"] = "native"
    else:
        record["backend"] = "cached"  # zero novel nodes: no hashing
    return record


def root_record_from_handle(
    handle, batch_id: int, batch_size: int, bucket: int
) -> dict:
    """The root-lane batch record: backend (device dispatch vs the
    offload-gated host walk) and the merged payload come off the
    RootHandle. Shared by the resolve worker and the mesh lanes, like the
    witness record builders above."""
    return {
        "batch_id": batch_id,
        "batch_size": batch_size,
        "bucket_bytes": bucket,
        "stage": "resolve",
        "lane": _ROOT,
        "backend": getattr(handle, "backend", None) or "host",
        "payload_bytes": getattr(handle, "payload", None),
    }


def sig_record_from_handle(
    handle, batch_id: int, batch_size: int, bucket: int
) -> dict:
    """The sig-lane batch record: backend (merged device dispatch vs the
    offload-gated fused native batch / scalar fallback) and the merged
    row count come off the SigHandle. Shared by the resolve worker and
    the mesh lanes, like the witness and root record builders above."""
    return {
        "batch_id": batch_id,
        "batch_size": batch_size,
        "bucket_bytes": bucket,
        "stage": "resolve",
        "lane": _SIG,
        "backend": getattr(handle, "backend", None) or "native",
        "merged_rows": getattr(handle, "n_rows", None),
    }


def _first_stage_s(record: dict, picked: float) -> float:
    """When a batch's first lane stage began, in `time.monotonic()`
    seconds (the span clock, utils/trace.clock_ns, is the same clock in
    nanoseconds); `picked` for a record that carries no measured stage."""
    starts = [se[0] for se in (record.get("stages") or {}).values()]
    return min(starts) / 1e9 if starts else picked


def _abandon_handle(engine, handle) -> None:
    """Release a dispatched-but-unresolved engine handle on a crash path.
    The shared engine outlives a dead scheduler; a leaked handle would
    pin its in-flight count and defer generation flushes forever
    (ops/witness_engine.py abandon_batch). Best-effort: the scheduler is
    already dying, a second failure here must not mask the first."""
    abandon = getattr(engine, "abandon_batch", None)
    if abandon is None:
        return
    try:
        abandon(handle)
    except Exception:
        log.warning("abandon_batch failed on a crash path", exc_info=True)


@dataclass
class _Job:
    kind: str
    future: Future
    admitted: float  # monotonic admission time
    deadline: Optional[float]  # expiry on serving/deadline.py's clock, None = none
    # QoS: the tenant lane this job queues in (folded through the
    # max_tenants cap at admission) and its priority class. `sheddable`
    # is False for wait_for_space admissions (verify_many): their
    # contract is completion, so the eviction policy must never pick
    # them as overload victims.
    tenant: str = DEFAULT_TENANT
    priority: int = PRIORITY_BACKFILL
    sheddable: bool = True
    # witness lane
    root: bytes = b""
    nodes: Sequence[bytes] = ()
    nbytes: int = 0
    bucket: int = 0
    # root lane: the request's fused post-root HashPlan
    plan: Optional[object] = None
    # sig lane: the request's signature rows (signer.SigRows)
    rows: Optional[object] = None
    # serial lane
    fn: Optional[Callable] = None
    # observability: the submitting request's trace context, and the batch
    # record the executor attaches before resolving the future (set-then-
    # resolve ordering means a waiter that saw result() also sees meta)
    trace_id: Optional[str] = None
    meta: Optional[dict] = None
    # the launch signal (PendingVerdict.wait_launched): set by the
    # executor once the job's batch is on its way to the engine
    # (_pipeline_handoff, begin_batch returned) and, through the future's
    # done callback, by EVERY path that completes or fails the job —
    # inline, depth-1 and mesh completion, shed at execution, expiry,
    # eviction, _die — so a waiter waits for "launched or done", never
    # for a launch that will not come. An Event: written by a scheduler
    # thread, read by the handler (the lockset rule that caught
    # _exec_stage).
    launched: threading.Event = field(default_factory=threading.Event)

    def __post_init__(self) -> None:
        # the callback holds the event alone: no cycle through the job
        self.future.add_done_callback(lambda _f, ev=self.launched: ev.set())


class PendingVerdict:
    """One admitted witness verification as its submitter holds it
    (`VerificationScheduler.witness_async`): the verdict is JOINED where
    it is first needed, not awaited where it is requested. The request
    path (stateless.execute_stateless) waits for the launch, decodes the
    witness while the lane threads stand in the device's readbacks, and
    joins before anything acts on the witness."""

    __slots__ = ("_job", "_done_ns")

    def __init__(self, job: _Job):
        self._job = job
        self._done_ns: List[int] = []
        job.future.add_done_callback(
            lambda _f, at=self._done_ns: at.append(clock_ns())
        )

    def wait_launched(self) -> None:
        """Block until the job's batch has been handed to the engine, or
        the job is done (whichever path ended it)."""
        self._job.launched.wait()

    @property
    def done_ns(self) -> Optional[int]:
        """When the verdict (or the failure) arrived, on the span clock;
        None while it has not."""
        return self._done_ns[0] if self._done_ns else None

    def join(self) -> Tuple[bool, Optional[dict]]:
        """(verdict, batch record); scheduler rejections raise."""
        return bool(self._job.future.result()), self._job.meta


class VerificationScheduler:
    """Continuous-batching scheduler over a `WitnessEngine`.

    `engine` defaults to the process-shared memoized engine
    (stateless.shared_witness_engine), resolved lazily at first execution
    so constructing a scheduler never imports jax-adjacent modules.
    """

    def __init__(
        self,
        engine: Optional[object] = None,
        config: Optional[SchedulerConfig] = None,
    ):
        self.config = config or SchedulerConfig()
        # config is immutable after construction; the locked regions read
        # these unpacked copies so `self.config` itself stays a lock-free
        # introspection surface (state(), _deadline())
        self._max_batch = self.config.max_batch
        self._max_wait_s = self.config.max_wait_ms / 1e3
        self._queue_depth = self.config.queue_depth
        self._pipe_depth = max(1, self.config.pipeline_depth)
        self._quota = max(0, self.config.tenant_quota)
        self._max_tenants = max(1, self.config.max_tenants)
        # deadlines do not count compile seconds (serving/deadline.py);
        # jax is in the process iff the tpu backend resolved a device
        from phant_tpu.backend import jax_device_ok

        if jax_device_ok():
            deadline_clock.start_compile_clock()
        # QoS policy objects (serving/qos.py): both are only ever touched
        # under _lock, so they need no locking of their own
        self._picker = WeightedFairPicker(self.config.tenant_weights)
        self._wait_policy: Optional[AdaptiveWait] = (
            AdaptiveWait(
                self.config.max_wait_ms,
                min_wait_ms=self.config.min_wait_ms,
                full_depth=self.config.max_batch,
            )
            if self.config.adaptive_wait
            else None
        )
        self._engine = engine
        # root-lane engine, resolved lazily on the first root batch (the
        # shared ops/root_engine.py engine unless the config injects one)
        self._root_engine = None
        # sig-lane engine, resolved lazily on the first sig batch (the
        # shared ops/sig_engine.py engine unless the config injects one)
        self._sig_engine = None
        # guards the three lazy engine memos above — dedicated lock, NOT
        # self._lock: the first resolve builds an engine (seconds of
        # compile), and admission must not block behind it. The executor
        # resolves, the resolve worker reads the memo on its fallback
        # path: without the lock that pair is a lockset race (phantsan)
        self._engine_lock = threading.Lock()
        # mesh dispatch: per-device executors behind the assembler. The
        # pool is built here (its engines are jax-free until the device
        # route engages) and the scheduler's own resolve worker is NOT —
        # each mesh lane runs its own begin/resolve pipeline.
        self._pool = None
        if self.config.mesh_devices >= 1:
            from phant_tpu.serving.mesh_exec import MeshExecutorPool

            self._pool = MeshExecutorPool(
                self.config.mesh_devices,
                pipeline_depth=self._pipe_depth,
                spill_depth=self.config.mesh_spill_depth,
                dispatch=self.config.mesh_dispatch,
                max_batch=self._max_batch,
                backlog_k=self.config.megabatch_backlog_k,
                prefetch=self.config.prefetch,
                engine=engine,
                engine_factory=self.config.mesh_engine_factory,
                # root lane: an injected factory is index-blind (doubles);
                # the default builds one PINNED RootEngine per lane
                root_engine_factory=(
                    (lambda _i: self.config.root_engine_factory())
                    if self.config.root_engine_factory is not None
                    else None
                ),
                # sig lane: same shape — injected factories are
                # index-blind, the default pins one SigEngine per lane
                sig_engine_factory=(
                    (lambda _i: self.config.sig_engine_factory())
                    if self.config.sig_engine_factory is not None
                    else None
                ),
                on_done=self._mesh_done,
                on_stage=self._mesh_stage,
                on_skip=self._mesh_skip,
                on_expired=self._shed_expired,
                on_crash=self._mesh_crash,
            )
        # chaos drill (obs): PHANT_SCHED_CHAOS_CRASH=1 makes the FIRST
        # witness batch crash the executor — the supported way to fire-
        # drill the postmortem path (flight dump, /healthz 503, -32052
        # fail-fast) against a live server / the real CLI
        import os

        chaos = os.environ.get("PHANT_SCHED_CHAOS_CRASH")
        self._chaos_crash = chaos == "1"
        # PHANT_SCHED_CHAOS_CRASH=prefetch: the first plan the PREFETCH
        # worker computes raises instead — the fire drill for the
        # 4th-stage crash path (stage-named record, -32052 fail-fast)
        self._chaos_prefetch = chaos == "prefetch"
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        # admission state (guarded by _lock): the serial mutation lane is
        # its own strict-FIFO queue (never shed by overload policy, only
        # by deadline/death); witness jobs queue per tenant
        self._serial_q: List[_Job] = []
        self._lanes: dict = {}  # tenant -> List[_Job], FIFO per lane
        self._tenant_stats: dict = {}  # tenant -> admitted/served/shed
        self._last_wait_ms: Optional[float] = None  # adaptive-wait memo
        self._closed = False
        self._dead: Optional[BaseException] = None
        # observability: monotone batch ids + the in-flight descriptors the
        # obs watchdog polls, oldest first (all guarded by _lock). With
        # pipelining, up to pipeline_depth witness batches are in flight.
        self._batch_seq = 0
        self._inflight_list: List[dict] = []
        # pipeline state (guarded by _lock): items awaiting the resolve
        # worker, whether it is mid-resolve, and the stage the executor is
        # in (named by the crash record when the executor dies)
        self._resolve_q: List[dict] = []
        self._resolving = False
        self._exec_stage = "pack"
        # 4-stage pipeline state (guarded by _lock): batches the executor
        # assembled and handed to the prefetch worker. `_prefetch_q` is
        # the worker's input; `_prefetch_pending` is the executor's FIFO
        # of the same items (popped when the plan is consumed) — _die
        # drains BOTH so no future is stranded mid-prefetch. The
        # lookahead bounds how many assembled batches wait on plans.
        self._prefetch_on = (
            self.config.prefetch
            and self._pipe_depth >= 2
            and self._pool is None  # mesh lanes prefetch per lane
        )
        self._prefetch_q: List[dict] = []
        self._prefetch_pending: List[dict] = []
        self._prefetch_lookahead = 2
        self.stats = {
            "requests": 0,
            "batches": 0,
            "serial_jobs": 0,
            "coalesced": 0,
            "batched_requests": 0,
            "max_batch_seen": 0,
            "pipelined_batches": 0,
            # 4-stage pipeline: batches whose decode + novelty pre-scan ran
            # on the prefetch worker (stage 0) before pack consumed the plan
            "prefetched_batches": 0,
            # mesh dispatch: batches routed into the per-device pool, and
            # full single-bucket batches sent as whole-mesh fused calls
            "mesh_batches": 0,
            "megabatches": 0,
            # megabatches fired by the backlog-depth trigger (queued
            # same-bucket work >= mesh width x k) rather than a full batch
            "megabatch_backlog_triggers": 0,
            "rejected": 0,
            # QoS: backfill jobs evicted to admit head-of-chain work, and
            # how often the adaptive policy changed the assembly wait
            "evicted": 0,
            "wait_adjustments": 0,
            # post-root lane (PR 11): batches through ops/root_engine.py
            # and requests that shared a coalesced root dispatch
            "root_batches": 0,
            "root_requests": 0,
            "root_coalesced": 0,
            # sender-recovery lane (PR 14): batches through
            # ops/sig_engine.py and requests that shared a merged
            # ecrecover dispatch
            "sig_batches": 0,
            "sig_requests": 0,
            "sig_coalesced": 0,
        }
        metrics.gauge_set("sched.pipeline_depth", self._pipe_depth)
        self._thread = threading.Thread(
            target=self._run, name="phant-sched-exec", daemon=True
        )
        self._thread.start()
        self._resolve_thread: Optional[threading.Thread] = None
        if self._pipe_depth > 1 and self._pool is None:
            self._resolve_thread = threading.Thread(
                target=self._resolve_run, name="phant-sched-resolve", daemon=True
            )
            self._resolve_thread.start()
        self._prefetch_thread: Optional[threading.Thread] = None
        if self._prefetch_on:
            self._prefetch_thread = threading.Thread(
                target=self._prefetch_run, name="phant-sched-prefetch", daemon=True
            )
            self._prefetch_thread.start()
        self._watchdog = Watchdog(self.inflight_state).start()

    # -- context manager (offline verify_many use) ---------------------------

    def __enter__(self) -> "VerificationScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=True)

    # -- admission -----------------------------------------------------------

    def _witness_job(
        self,
        root: bytes,
        nodes: Sequence[bytes],
        deadline_s: Optional[float],
        tenant: Optional[str],
        priority: Optional[int],
    ) -> _Job:
        nodes = list(nodes)
        nbytes = sum(map(len, nodes))
        return _Job(
            kind=_WITNESS,
            future=Future(),
            admitted=time.monotonic(),
            deadline=self._deadline(deadline_s),
            # QoS identity: an explicit argument wins, otherwise the
            # thread's tenant_context (the Engine API server binds one per
            # request, qos.py) — offline callers land in DEFAULT_TENANT
            tenant=tenant if tenant is not None else current_tenant(),
            priority=priority if priority is not None else current_priority(),
            root=root,
            nodes=nodes,
            nbytes=nbytes,
            bucket=_pow2ceil(nbytes),
            trace_id=current_trace_id(),
        )

    def submit_witness(
        self,
        root: bytes,
        nodes: Sequence[bytes],
        deadline_s: Optional[float] = None,
        wait_for_space: bool = False,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ) -> Future:
        """Queue one (root, nodes) linked-multiproof verification; the
        future resolves to the bool verdict. `wait_for_space` blocks on a
        full queue instead of rejecting (offline verify_many); the online
        serving path never waits — overload must shed, not stack.
        `tenant`/`priority` default to the thread's tenant_context."""
        job = self._witness_job(root, nodes, deadline_s, tenant, priority)
        self._admit(job, wait_for_space)
        return job.future

    def witness_async(
        self,
        root: bytes,
        nodes: Sequence[bytes],
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ) -> PendingVerdict:
        """Admit one witness verification NOW and return its
        `PendingVerdict` — the split face the request path uses
        (stateless.execute_stateless; the witness twin of `sig_async`):
        wait for the launch, decode under the device's work, join
        `(verdict, batch record)` before execution. A shed at admission
        raises here, as from `verify_traced`."""
        job = self._witness_job(root, nodes, deadline_s, tenant, priority)
        self._admit(job, False)
        return PendingVerdict(job)

    def verify_traced(
        self,
        root: bytes,
        nodes: Sequence[bytes],
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ) -> Tuple[bool, Optional[dict]]:
        """One witness verification through the batching path, returning
        (verdict, batch record). The record — `batch_id`, `batch_size`,
        `bucket_bytes`, `backend`, cache hit/miss deltas, `queue_wait_ms` —
        is what joins the caller's span to the shared engine dispatch that
        served it (stateless.join_witness folds it into the open
        `verify_block` span). Scheduler rejections raise as usual."""
        return self.witness_async(
            root, nodes, deadline_s, tenant, priority
        ).join()

    def submit_serial(
        self,
        fn: Callable,
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> Future:
        """Queue an exclusive job: the executor runs `fn()` with nothing
        else in flight — the replacement for the server's global execution
        lock (state-mutating newPayload execution). `fn`'s return value
        resolves the future; an exception from `fn` is request-scoped and
        lands on the future (it does NOT kill the executor). Serial jobs
        are always PRIORITY_HEAD: they preempt queued witness work and are
        never shed to make room for anything."""
        job = _Job(
            kind=_SERIAL,
            future=Future(),
            admitted=time.monotonic(),
            deadline=self._deadline(deadline_s),
            tenant=tenant if tenant is not None else current_tenant(),
            priority=PRIORITY_HEAD,
            fn=fn,
            trace_id=current_trace_id(),
        )
        self._admit(job, False)
        return job.future

    # -- root lane (batched post-state roots, PR 11) -------------------------

    def _root_job(
        self,
        plan,
        deadline_s: Optional[float],
        tenant: Optional[str],
        priority: Optional[int],
    ) -> _Job:
        # level-shape bucket: plans with the same depth coalesce into one
        # merged dispatch (its rung of mpt_jax.PLAN_LADDER is chosen at
        # merge time, whatever the levels' widths); NEGATIVE so it never
        # collides with the witness pow2 buckets
        from phant_tpu.ops.mpt_jax import plan_payload_bytes

        return _Job(
            kind=_ROOT,
            future=Future(),
            admitted=time.monotonic(),
            deadline=self._deadline(deadline_s),
            tenant=tenant if tenant is not None else current_tenant(),
            priority=priority if priority is not None else current_priority(),
            plan=plan,
            nbytes=plan_payload_bytes(plan),
            bucket=-len(plan.levels),
            trace_id=current_trace_id(),
        )

    def submit_root(
        self,
        plan,
        deadline_s: Optional[float] = None,
        wait_for_space: bool = False,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ) -> Future:
        """Queue one fused post-root HashPlan (ops/mpt_jax, built by
        stateless.WitnessStateDB.post_root_plan); the future resolves to
        the plan's out-row digests (storage roots in patch order, the
        post root LAST). Admission, per-tenant QoS, deadlines, and
        overload shedding are the witness lane's — same codes, same shed
        order."""
        job = self._root_job(plan, deadline_s, tenant, priority)
        self._admit(job, wait_for_space)
        return job.future

    def root_traced(
        self,
        plan,
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ) -> Tuple[List[bytes], Optional[dict]]:
        """One post root through the batching path, returning (out
        digests, batch record) — the root twin of verify_traced; the
        record joins the caller's `verify_block` span to the coalesced
        root dispatch that served it."""
        job = self._root_job(plan, deadline_s, tenant, priority)
        self._admit(job, False)
        return job.future.result(), job.meta

    def root_many(self, plans: Sequence) -> List[List[bytes]]:
        """Out digests for a span of plans, pushed through the SAME
        admission/assembly/executor path the server uses — the offline
        face of the root lane (soak, tests). Blocks on queue space and
        applies no deadline, like verify_many."""
        if threading.current_thread() in (
            self._thread,
            self._resolve_thread,
            self._prefetch_thread,
        ):
            raise RuntimeError(
                "root_many called from a scheduler thread (deadlock)"
            )
        futs = [
            self.submit_root(p, deadline_s=float("inf"), wait_for_space=True)
            for p in plans
        ]
        return [f.result() for f in futs]

    def accepts_root(self) -> bool:
        """Can the CURRENT thread route a post root through this
        scheduler? The root lane shares the witness lane's consumers and
        lifecycle, so the answer is the same."""
        return self.accepts_witness()

    def root_backlog(self) -> int:
        """Root jobs currently queued — the lone-request guard's company
        signal (stateless.compute_post_root): with nobody to coalesce
        with, a sub-break-even request skips plan construction entirely
        and keeps the host walk."""
        with self._lock:
            return sum(
                1
                for lane in self._lanes.values()
                for j in lane
                if j.kind == _ROOT
            )

    def _resolve_root_engine(self):
        # config is read OUTSIDE the lock (immutable after __init__; a
        # config touch under _engine_lock would make LOCK demand the lock
        # at every other config read in the class)
        factory = self.config.root_engine_factory
        with self._engine_lock:
            if self._root_engine is None:
                if factory is not None:
                    self._root_engine = factory()
                else:
                    from phant_tpu.ops.root_engine import shared_root_engine

                    self._root_engine = shared_root_engine()
            return self._root_engine

    # -- sig lane (coalesced sender recovery, PR 14) --------------------------

    def _sig_job(
        self,
        rows,
        deadline_s: Optional[float],
        tenant: Optional[str],
        priority: Optional[int],
    ) -> _Job:
        # ONE fixed bucket for every sig job: signature rows concatenate
        # freely (the merged batch pow2-pads inside the kernel), so all
        # concurrent requests' rows coalesce — the whole point of the lane
        return _Job(
            kind=_SIG,
            future=Future(),
            admitted=time.monotonic(),
            deadline=self._deadline(deadline_s),
            tenant=tenant if tenant is not None else current_tenant(),
            priority=priority if priority is not None else current_priority(),
            rows=rows,
            nbytes=rows.n,
            bucket=_SIG_BUCKET,
            trace_id=current_trace_id(),
        )

    def submit_sig(
        self,
        rows,
        deadline_s: Optional[float] = None,
        wait_for_space: bool = False,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ) -> Future:
        """Queue one request's signature rows (signer.SigRows, built by
        `TxSigner.signature_rows`); the future resolves to the request's
        sender list in tx order (None = invalid signature — the caller
        owns the error attribution, chain.apply_body). Admission,
        per-tenant QoS, deadlines, and overload shedding are the witness
        lane's — same codes, same shed order."""
        job = self._sig_job(rows, deadline_s, tenant, priority)
        self._admit(job, wait_for_space)
        return job.future

    def sig_async(
        self,
        rows,
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ):
        """Dispatch one request's sender recovery NOW and return
        `resolve() -> (senders, batch record)` — the split face the
        request path uses (stateless.dispatch_sender_recovery): recovery
        dispatches at decode time and joins just before EVM execution,
        so the merged ecrecover hides under witness verification."""
        job = self._sig_job(rows, deadline_s, tenant, priority)
        self._admit(job, False)

        def resolve():
            return job.future.result(), job.meta

        return resolve

    def sig_traced(
        self,
        rows,
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ) -> Tuple[List[Optional[bytes]], Optional[dict]]:
        """One request's senders through the batching path, returning
        (senders, batch record) — the sig twin of verify_traced/
        root_traced; the record joins the caller's span to the merged
        ecrecover dispatch that served it."""
        return self.sig_async(rows, deadline_s, tenant, priority)()

    def sig_many(self, rows_list: Sequence) -> List[List[Optional[bytes]]]:
        """Sender slices for a span of requests' rows, pushed through the
        SAME admission/assembly/executor path the server uses — the
        offline face of the sig lane (soak, tests). Blocks on
        queue space and applies no deadline, like verify_many."""
        if threading.current_thread() in (
            self._thread,
            self._resolve_thread,
            self._prefetch_thread,
        ):
            raise RuntimeError(
                "sig_many called from a scheduler thread (deadlock)"
            )
        futs = [
            self.submit_sig(r, deadline_s=float("inf"), wait_for_space=True)
            for r in rows_list
        ]
        return [f.result() for f in futs]

    def accepts_sig(self) -> bool:
        """Can the CURRENT thread route sender recovery through this
        scheduler? The sig lane shares the witness lane's consumers and
        lifecycle, so the answer is the same."""
        return self.accepts_witness()

    def sig_backlog(self) -> int:
        """Signature ROWS currently queued on the sig lane (txs, not
        jobs — sig jobs coalesce freely, so rows are the unit of queued
        device work). The replay engine's lookahead pacer
        (phant_tpu/replay/engine.py) holds segment N+1's dispatch while
        the lane still has more than a segment's worth of rows queued,
        so a deep replay pipeline cannot monopolize the admission queue
        it shares with live serving traffic — the root twin is
        root_backlog (the lone-request guard's company signal)."""
        with self._lock:
            return sum(
                j.nbytes
                for lane in self._lanes.values()
                for j in lane
                if j.kind == _SIG
            )

    def _resolve_sig_engine(self):
        factory = self.config.sig_engine_factory  # outside the lock, as above
        with self._engine_lock:
            if self._sig_engine is None:
                if factory is not None:
                    self._sig_engine = factory()
                else:
                    from phant_tpu.ops.sig_engine import shared_sig_engine

                    self._sig_engine = shared_sig_engine()
            return self._sig_engine

    @staticmethod
    def _payload_of(jobs: List[_Job], kind: str) -> list:
        """The engine-facing batch payload: (root, nodes) tuples for the
        witness lane, HashPlans for the root lane, SigRows for the sig
        lane."""
        if kind == _ROOT:
            return [j.plan for j in jobs]
        if kind == _SIG:
            return [j.rows for j in jobs]
        return [(j.root, j.nodes) for j in jobs]

    def _deadline(self, deadline_s: Optional[float]) -> Optional[float]:
        if deadline_s is None:
            d = self.config.deadline_ms / 1e3
        else:
            d = deadline_s
        if d <= 0 or d == float("inf"):
            return None
        return deadline_clock.expiry(d)

    # -- QoS locked helpers --------------------------------------------------

    def _lane_key_locked(self, tenant: str) -> str:
        """Fold a tenant tag through the max_tenants cap: known tenants
        keep their lane, new ones beyond the cap share OVERFLOW_TENANT
        (bounded per-tenant state and metric cardinality under a
        header-spraying client)."""
        if tenant in self._tenant_stats or len(self._tenant_stats) < self._max_tenants:
            return tenant
        return OVERFLOW_TENANT

    def _account_evicted_locked(self, victim: _Job, victims: List[_Job]) -> None:
        """Stats for one eviction victim under the lock; the metric/flight
        publishes and the future failure happen outside it (victims)."""
        self.stats["rejected"] += 1
        self.stats["evicted"] += 1
        self._tenant_locked(victim.tenant)["shed"] += 1
        victims.append(victim)

    def _tenant_locked(self, tenant: str) -> dict:
        st = self._tenant_stats.get(tenant)
        if st is None:
            st = self._tenant_stats[tenant] = {
                "admitted": 0,
                "served": 0,
                "shed": 0,
            }
        return st

    def _qlen_locked(self) -> int:
        return len(self._serial_q) + self._wit_len_locked()

    def _wit_len_locked(self) -> int:
        # lanes are bounded by max_tenants (default 64): summing is O(1)-ish
        return sum(len(lane) for lane in self._lanes.values())

    def _enqueue_locked(self, job: _Job) -> None:
        if job.kind == _SERIAL:
            self._serial_q.append(job)
        else:
            self._lanes.setdefault(job.tenant, []).append(job)

    @staticmethod
    def _evict_from_lane_locked(
        lane: List[_Job], allow_head: bool = False
    ) -> Optional[_Job]:
        """Newest sheddable backfill job of `lane` (newest head-class
        witness job as a fallback when `allow_head`); wait_for_space
        (verify_many) jobs are never victims — their contract is
        completion, not load shedding."""
        for want_backfill in (True, False) if allow_head else (True,):
            for i in range(len(lane) - 1, -1, -1):
                j = lane[i]
                if not j.sheddable:
                    continue
                if (j.priority != PRIORITY_HEAD) == want_backfill:
                    return lane.pop(i)
        return None

    def _evict_witness_locked(self, for_serial: bool) -> Optional[_Job]:
        """Pick the load-shed victim that makes room for an arriving
        head-of-chain job: the NEWEST backfill job of the DEEPEST lane —
        backfill first (deepest lane first: the tenant most over its fair
        share pays). When the arrival is a SERIAL mutation and every
        queued witness job is head-class, the newest head-class witness
        job is evicted instead: the serial lane outranks every witness
        class and must only ever be shed by its OWN backlog. Never
        evicts the serial lane, never a wait_for_space job. None when
        nothing is sheddable."""
        for allow_head in (False, True) if for_serial else (False,):
            deepest = sorted(
                (lane for lane in self._lanes.values() if lane),
                key=len,
                reverse=True,
            )
            for lane in deepest:
                victim = self._evict_from_lane_locked(lane, allow_head=allow_head)
                if victim is not None:
                    return victim
        return None

    def _admit(self, job: _Job, wait_for_space: bool) -> None:
        reason = None
        victims: List[_Job] = []
        lane_depth = None
        job.sheddable = not wait_for_space
        with self._lock:
            job.tenant = self._lane_key_locked(job.tenant)
            while True:
                if self._dead is not None:
                    reason, err = "down", SchedulerDown(
                        f"scheduler executor is down: {self._dead!r}"
                    )
                    break
                if self._closed:
                    reason, err = "shutdown", SchedulerDown(
                        "scheduler is shutting down"
                    )
                    break
                if (
                    job.kind == _WITNESS
                    and self._quota
                    and len(self._lanes.get(job.tenant, ())) >= self._quota
                ):
                    # the per-tenant cap sheds BEFORE the global bound: one
                    # tenant's burst stays that tenant's problem. An
                    # offline wait_for_space caller (verify_many) BLOCKS on
                    # its quota exactly as it blocks on the global bound —
                    # completion, not load shedding, is its contract — and
                    # a HEAD-class arrival evicts its own tenant's newest
                    # backfill job first: head work is only ever shed by
                    # pressure from its own class
                    if wait_for_space:
                        self._cond.wait(0.05)
                        continue
                    if job.priority == PRIORITY_HEAD:
                        v = self._evict_from_lane_locked(
                            self._lanes[job.tenant]
                        )
                        if v is not None:
                            self._account_evicted_locked(v, victims)
                            continue  # lane has room now; re-run the checks
                    reason, err = "tenant_quota", QueueFull(
                        f"tenant {job.tenant!r} queue quota full ({self._quota})"
                    )
                    break
                if self._qlen_locked() < self._queue_depth:
                    self._enqueue_locked(job)
                elif job.priority == PRIORITY_HEAD and (
                    v := self._evict_witness_locked(
                        for_serial=job.kind == _SERIAL
                    )
                ) is not None:
                    # global queue full but the arrival is head-of-chain:
                    # shed the newest backfill job (for a serial mutation,
                    # the newest head-class witness job as a fallback)
                    # instead of the head work — the documented shed order;
                    # same -32050 code, distinct reason so the postmortem
                    # tells them apart
                    self._account_evicted_locked(v, victims)
                    self._enqueue_locked(job)
                elif not wait_for_space:
                    reason, err = "queue_full", QueueFull(
                        f"admission queue full ({self._queue_depth})"
                    )
                    break
                else:
                    self._cond.wait(0.05)
                    continue
                self.stats["requests"] += 1
                self._tenant_locked(job.tenant)["admitted"] += 1
                depth = self._qlen_locked()
                if job.kind == _WITNESS:
                    lane_depth = len(self._lanes[job.tenant])
                self._cond.notify_all()
                break
            if reason is not None:
                self.stats["rejected"] += 1
                self._tenant_locked(job.tenant)["shed"] += 1
        for victim in victims:
            metrics.count("sched.rejected", reason="evicted", tenant=victim.tenant)
            metrics.count("sched.backfill_evictions", tenant=victim.tenant)
            flight.record(
                "sched.shed",
                reason="evicted",
                lane=victim.kind,
                tenant=victim.tenant,
                trace_id=victim.trace_id,
            )
            victim.future.set_exception(
                QueueFull("evicted to admit head-of-chain work")
            )
        if reason is not None:
            metrics.count("sched.rejected", reason=reason, tenant=job.tenant)
            flight.record(
                "sched.shed",
                reason=reason,
                lane=job.kind,
                tenant=job.tenant,
                trace_id=job.trace_id,
            )
            raise err
        metrics.gauge_set("sched.queue_depth", depth)
        if lane_depth is not None:
            metrics.gauge_set(
                "sched.tenant_queue_depth", lane_depth, tenant=job.tenant
            )
        flight.record(
            "sched.admit",
            lane=job.kind,
            tenant=job.tenant,
            priority=job.priority,
            bucket_bytes=job.bucket if job.kind == _WITNESS else None,
            queue_depth=depth,
            trace_id=job.trace_id,
        )

    # -- the synchronous offline face ---------------------------------------

    def verify_many(
        self, witnesses: Sequence[Tuple[bytes, Sequence[bytes]]]
    ) -> np.ndarray:
        """(n,) bool verdicts for a span of (root, nodes) witnesses, pushed
        through the SAME admission/assembly/executor path the server uses —
        the offline API for the spec runner, `scripts/soak.py` and tests. Blocks on
        queue space instead of rejecting (offline callers want completion,
        not load shedding) and applies no deadline."""
        if threading.current_thread() in (
            self._thread,
            self._resolve_thread,
            self._prefetch_thread,
        ):
            raise RuntimeError(
                "verify_many called from a scheduler thread (deadlock)"
            )
        futs = [
            self.submit_witness(
                root, nodes, deadline_s=float("inf"), wait_for_space=True
            )
            for root, nodes in witnesses
        ]
        return np.fromiter(
            (bool(f.result()) for f in futs), bool, count=len(futs)
        )

    def accepts_witness(self) -> bool:
        """Can the CURRENT thread route a witness verification through this
        scheduler? False on the executor/resolve threads themselves
        (submitting from either would deadlock: they are the consumers)
        and once the scheduler is down or draining — callers fall back to
        the direct engine path."""
        if threading.current_thread() in (
            self._thread,
            self._resolve_thread,
            self._prefetch_thread,
        ):
            return False
        with self._lock:
            return self._dead is None and not self._closed

    # -- introspection -------------------------------------------------------

    def state(self) -> dict:
        """Liveness surface for `/healthz` (engine_api/server.py)."""
        with self._lock:
            depth = self._qlen_locked()
            tenant_depths = {
                t: len(lane) for t, lane in self._lanes.items() if lane
            }
            dead = self._dead
            inflight = len(self._resolve_q) + (1 if self._resolving else 0)
            prefetch_pending = len(self._prefetch_pending)
        alive = dead is None and self._thread.is_alive()
        if self._resolve_thread is not None:
            # a dead resolve worker is just as fatal as a dead executor:
            # dispatched handles would never complete
            alive = alive and self._resolve_thread.is_alive()
        if self._prefetch_thread is not None:
            # same for the prefetch worker: pending batches would never
            # get plans and the executor would wait on them forever
            alive = alive and self._prefetch_thread.is_alive()
        mesh = self._pool.state() if self._pool is not None else None
        if mesh is not None:
            # any dead device lane means routed batches would never
            # complete — as fatal as the executor itself (healthz 503)
            alive = alive and mesh["all_alive"]
            inflight = sum(
                d["queued"] + d["inflight"] for d in mesh["per_device"].values()
            )
        out = {
            "queue_depth": depth,
            "tenant_depths": tenant_depths,
            "executor_alive": alive,
            "max_batch": self.config.max_batch,
            "max_wait_ms": self.config.max_wait_ms,
            # config echoes read off the immutable config, not the
            # unpacked copies the locked regions use (lock-free surface)
            "adaptive_wait": self.config.adaptive_wait,
            "tenant_quota": self.config.tenant_quota,
            "pipeline_depth": self._pipe_depth,
            "pipeline_inflight": inflight,
            # the 4th stage's EFFECTIVE state: the scheduler's own worker,
            # or (mesh mode) the per-lane prefetch the pool runs instead —
            # healthz must not say "off" while every lane prefetches
            "prefetch": self._prefetch_on
            or bool(mesh is not None and mesh.get("prefetch")),
            "prefetch_pending": prefetch_pending,
        }
        if mesh is not None:
            out["mesh"] = mesh
        if dead is not None:
            out["error"] = repr(dead)
        return out

    def stats_snapshot(self) -> dict:
        with self._lock:
            st = dict(self.stats)
            st["tenants"] = {
                t: dict(ts) for t, ts in self._tenant_stats.items()
            }
        b = st["batches"]
        st["mean_batch"] = round(st["batched_requests"] / b, 2) if b else 0.0
        st["pipeline_depth"] = self._pipe_depth
        if self._pool is not None:
            st["mesh"] = self._pool.stats()
            # mesh mode runs the prefetch stage per LANE (the scheduler's
            # own worker is off) — fold the pool's count into the
            # top-level stat so `prefetched_batches` answers "did the 4th
            # stage run" the same way in every deployment shape
            st["prefetched_batches"] += st["mesh"]["prefetched_batches"]
        return st

    def inflight_state(self) -> Optional[dict]:
        """The OLDEST batch currently in flight — `batch_id`, `lane`,
        `stage`, `started`/`deadline` (monotonic), `trace_ids` — or None
        when idle. Polled by the obs watchdog to flag deadline-overrun
        stalls; with pipelining the oldest unresolved batch is the one a
        wedged device call strands first."""
        with self._lock:
            return dict(self._inflight_list[0]) if self._inflight_list else None

    # -- lifecycle -----------------------------------------------------------

    def shutdown(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop admission; `drain=True` lets the executor finish everything
        already queued before it exits, `drain=False` fails the queue fast.
        Idempotent."""
        with self._lock:
            self._closed = True
            dropped: List[_Job] = []
            if not drain:
                dropped.extend(self._serial_q)
                self._serial_q.clear()
                for lane in self._lanes.values():
                    dropped.extend(lane)
                self._lanes.clear()
            self._cond.notify_all()
        for job in dropped:
            job.future.set_exception(
                SchedulerDown("scheduler shut down before execution")
            )
        self._thread.join(timeout)
        if self._resolve_thread is not None:
            self._resolve_thread.join(timeout)
        if self._prefetch_thread is not None:
            self._prefetch_thread.join(timeout)
        if self._pool is not None:
            # the executor's graceful exit already drained every lane
            # (_drain_pipeline); this stops the lane threads
            self._pool.shutdown(timeout)
        self._watchdog.stop(1.0)
        metrics.gauge_set("sched.queue_depth", 0)

    # -- executor ------------------------------------------------------------

    def _set_exec_stage(self, stage: str) -> None:
        """Crash-record breadcrumb: the executor names the stage it is in
        at each boundary, and _run's except handler reads it for _die.
        Under _lock — writer and reader are different threads when a mesh
        lane or the chaos drill kills the executor mid-batch, and the
        unlocked attribute was a phantsan lockset race."""
        with self._lock:
            self._exec_stage = stage

    def _run(self) -> None:
        deadline_clock.serving_thread()
        batch: List[_Job] = []
        try:
            while True:
                if self._prefetch_on:
                    step = self._next_step_prefetching()
                    if step == "loop":
                        continue
                    if isinstance(step, dict):
                        batch = step["jobs"]
                        self._execute_prefetched(step)
                        batch = []
                        continue
                    batch = step  # serial batch or None (exit)
                else:
                    batch = self._next_batch()
                if batch is None:
                    # graceful exit: every dispatched handle must resolve
                    # before the executor reports done (shutdown drains the
                    # admission queue AND the in-flight pipeline)
                    self._drain_pipeline()
                    with self._lock:
                        self._exec_done = True
                        self._cond.notify_all()
                    return
                self._execute(batch)
                batch = []
        except BaseException as e:  # systemic: engine/internal failure
            with self._lock:
                stage = self._exec_stage
            self._die(e, batch or [], stage=stage)

    _exec_done = False  # executor returned cleanly (resolve worker exits)

    # -- 4th pipeline stage: the prefetch worker (PR 9) ----------------------

    def _next_step_prefetching(self) -> object:
        """One executor decision under the 4-stage pipeline: top up the
        prefetch lookahead from the admission queue, or consume the
        oldest planned batch. Returns "loop" (decision made, go again),
        a pending item dict (execute it), a serial batch, or None
        (graceful exit — pending is empty by then)."""
        with self._lock:
            has_serial = bool(self._serial_q)
            can_assemble = any(self._lanes.values())
            pending = len(self._prefetch_pending)
            # a finished plan beats topping up — but only while the
            # worker still has queued work (pending > 1). At pending == 1
            # the top-up comes FIRST: it hands the worker its next batch
            # before this thread blocks in the pipeline handoff, which is
            # exactly the window the prefetch is meant to hide under
            # (draining to empty here measured hidden_pct 87 -> 0: the
            # worker idled through every handoff stall). The top-up is
            # cheap even off-saturation — assembly breaks its coalescing
            # wait the moment a plan turns ready below.
            head_ready = pending > 1 and self._prefetch_pending[0]["ready"]
            if self._dead is not None:
                return None
        if pending and (
            has_serial
            or head_ready
            or not can_assemble
            or pending >= self._prefetch_lookahead
        ):
            # oldest planned batch first: the serial lane preempts the
            # QUEUE, never work already past admission — and pending must
            # drain before a serial job gets exclusivity anyway
            return self._pop_prefetched()
        batch = self._next_batch(block=(pending == 0))
        if batch is _NO_BATCH:
            return "loop"  # queued work vanished (expiry); re-evaluate
        if batch is None or batch[0].kind == _SERIAL:
            # pending was empty at the snapshot, but a close() or a
            # serial arrival can RACE the two lock windows — and both
            # graceful exit and serial exclusivity require the planned
            # batches executed first (their futures would otherwise
            # strand). Push a raced serial head back (index 0 keeps it
            # the serial queue's head — admission order holds) and
            # drain the oldest plan; the next pass re-takes the serial
            # job / the exit with pending truly empty.
            with self._lock:
                raced = bool(self._prefetch_pending)
                if raced and batch is not None:
                    self._serial_q.insert(0, batch[0])
            if raced:
                return self._pop_prefetched()
            return batch
        self._submit_prefetch(batch)
        return "loop"

    def _submit_prefetch(self, batch: List[_Job]) -> None:
        """Hand one assembled witness batch to the prefetch worker: the
        batch enters the flight list NOW (stage="prefetch" — the obs
        watchdog and stall records see the 4th stage), and the executor
        picks the plan up in FIFO order once the worker finishes it."""
        now = time.monotonic()
        for j in batch:
            metrics.observe_hist("sched.queue_wait_seconds", now - j.admitted)
        if self.config.deadline_ms > 0:
            stall_deadline: Optional[float] = now + self.config.deadline_ms / 1e3
        else:
            stall_deadline = None
        trace_ids = [j.trace_id for j in batch]
        kind = batch[0].kind
        item = {
            "jobs": batch,
            "kind": kind,
            # the SAME list object goes to prefetch_batch and begin_batch:
            # plan identity is how the engine knows the plan matches
            # (witness tuples or root HashPlans alike)
            "payload": self._payload_of(batch, kind),
            "picked": now,
            "plan": None,
            "ready": False,
            # each stage's measured [start_ns, end_ns], written by the
            # thread that runs it (utils/trace.lane_stage)
            "stages": {},
        }
        with self._lock:
            self._batch_seq += 1
            item["batch_id"] = batch_id = self._batch_seq
            self._inflight_list.append(
                {
                    "batch_id": batch_id,
                    "lane": kind,
                    "stage": "prefetch",
                    "device": None,
                    "started": now,
                    "deadline": stall_deadline,
                    "trace_ids": trace_ids,
                }
            )
            self._prefetch_q.append(item)
            self._prefetch_pending.append(item)
            depth = len(self._prefetch_pending)
            self._cond.notify_all()
        metrics.gauge_set("sched.prefetch_depth", depth)
        flight.record(
            "sched.batch_start",
            batch_id=batch_id,
            lane=kind,
            stage="prefetch",
            batch_size=len(batch),
            bucket_bytes=batch[0].bucket,
            tenants=sorted({j.tenant for j in batch}),
            trace_ids=trace_ids,
        )

    def _pop_prefetched(self) -> dict:
        """The oldest pending batch, once its plan is ready. The wait here
        is the overlap audit: time the executor spends blocked on a plan
        is prefetch cost that did NOT hide under dispatch/resolve
        (sched.prefetch_wait vs the witness_engine.prefetch phase)."""
        t0 = time.perf_counter()
        with self._lock:
            # _die may have emptied _prefetch_pending between the
            # caller's pending>0 check and this lock acquisition — the
            # combined condition re-checks emptiness so a crash lands on
            # the SchedulerDown below, not an IndexError
            while self._dead is None and not (
                self._prefetch_pending and self._prefetch_pending[0]["ready"]
            ):
                self._cond.wait(0.05)
            dead = self._dead
            if dead is None:
                item = self._prefetch_pending.pop(0)
                depth = len(self._prefetch_pending)
        metrics.observe("sched.prefetch_wait", time.perf_counter() - t0)
        if dead is not None:
            raise SchedulerDown(f"prefetch worker is down: {dead!r}")
        metrics.gauge_set("sched.prefetch_depth", depth)
        return item

    def _execute_prefetched(self, item: dict) -> None:
        """Pack + dispatch one PREFETCHED batch (its flight descriptor and
        batch_start record exist since _submit_prefetch): the 4-stage
        twin of _execute_witness_pipelined, consuming the worker's plan
        so pack's under-lock work shrinks to the re-check + commit."""
        batch_id = item["batch_id"]
        self._set_exec_stage("pack")
        with self._lock:
            for d in self._inflight_list:
                if d["batch_id"] == batch_id:
                    d["stage"] = "pack"
        plan = item["plan"]
        try:
            self._execute_prefetched_inner(item, plan)
        except BaseException:
            # an exception leaving this frame lands in _die, which can no
            # longer see this item (popped from _prefetch_pending): give
            # the plan's staging leases back before propagating. release()
            # is idempotent and consumption nulls the plan's lease fields,
            # so a plan begin_batch already consumed/released is a no-op.
            if plan is not None:
                plan.release()
            raise

    def _execute_prefetched_inner(self, item: dict, plan) -> None:
        batch_id = item["batch_id"]
        jobs = self._shed_or_keep(item["jobs"], time.monotonic())
        if self._chaos_crash:
            raise RuntimeError(
                "chaos drill: PHANT_SCHED_CHAOS_CRASH=1 induced executor crash"
            )
        kind = item.get("kind", _WITNESS)
        if kind in (_ROOT, _SIG):
            # root/sig batches always have a two-phase engine; a fully-
            # shed batch just releases the prefetch merge
            if not jobs:
                if plan is not None:
                    plan.release()
                with self._lock:
                    self._drop_inflight_locked(batch_id)
                return
            self._pipeline_handoff(
                jobs,
                batch_id,
                self._resolve_root_engine()
                if kind == _ROOT
                else self._resolve_sig_engine(),
                item["picked"],
                plan=plan,
                prefetch_ms=item.get("prefetch_ms"),
                stages=item["stages"],
                plan_payload=item["payload"],
                plan_njobs=len(item["jobs"]),
                kind=kind,
            )
            return
        engine = self._resolve_engine()
        if not jobs or not (
            self._pipe_depth > 1 and hasattr(engine, "begin_batch")
        ):
            # everything expired, or a begin-less engine double: release
            # the unused plan's staging leases and (if any jobs survive)
            # fall back to the inline path — _execute_witness IS that
            # path (one copy; its re-shed of already-kept jobs is a no-op
            # and its chaos check is unreachable past the one above)
            if plan is not None:
                plan.release()
            if jobs:
                with self._lock:
                    for d in self._inflight_list:
                        if d["batch_id"] == batch_id:
                            d["stage"] = "dispatch"
                self._execute_witness(jobs, batch_id, engine, item["picked"])
            with self._lock:
                self._drop_inflight_locked(batch_id)
            return
        self._pipeline_handoff(
            jobs,
            batch_id,
            engine,
            item["picked"],
            plan=plan,
            prefetch_ms=item.get("prefetch_ms"),
            stages=item["stages"],
            plan_payload=item["payload"],
            plan_njobs=len(item["jobs"]),
        )

    def _pipeline_handoff(
        self,
        jobs: List[_Job],
        batch_id: int,
        engine,
        picked: float,
        plan=None,
        prefetch_ms: Optional[float] = None,
        plan_payload=None,
        plan_njobs: int = 0,
        kind: str = _WITNESS,
        stages: Optional[dict] = None,
    ) -> None:
        """Shared tail of the pipelined witness paths (3- and 4-stage):
        wait for a pipeline slot, re-shed expired jobs, begin_batch —
        consuming the prefetch plan when one rode along — and hand the
        handle to the resolve worker. The bounded depth is the stall
        signal: a hot resolve stage shows up as sched.pipeline_stall."""
        depth = self._pipe_depth
        t_wait = time.perf_counter()
        with self._lock:
            while (
                len(self._resolve_q) + (1 if self._resolving else 0) >= depth
                and self._dead is None
            ):
                self._cond.wait(0.05)
            dead = self._dead
        metrics.observe("sched.pipeline_stall", time.perf_counter() - t_wait)
        if dead is not None:
            # the resolve worker died while we waited: fail this batch the
            # same way _die failed everything else, and stop the executor
            if plan is not None:
                plan.release()
            raise SchedulerDown(f"resolve worker is down: {dead!r}")
        # deadlines re-checked AFTER the slot wait: a wedged resolve stage
        # can hold the pipeline full long past a job's deadline, and an
        # expired job must shed (its waiter is gone) rather than spend
        # pack/dispatch/resolve work
        jobs = self._shed_or_keep(jobs, time.monotonic())
        if not jobs:
            if plan is not None:
                plan.release()
            with self._lock:
                self._drop_inflight_locked(batch_id)
            return
        if plan_payload is not None and len(jobs) == plan_njobs:
            # the SAME list object the plan was computed over — identity
            # is how begin_batch knows the plan matches; any shed along
            # the way invalidates it and begin_batch drops it, correctly
            payload = plan_payload
        else:
            payload = self._payload_of(jobs, kind)
        t_pack = time.perf_counter()
        stages = {} if stages is None else stages
        with lane_stage(stages, "pack", [j.trace_id for j in jobs], batch_id):
            if plan is not None:
                handle = engine.begin_batch(payload, prefetch=plan)
            else:
                handle = engine.begin_batch(payload)
        pipe_item = {
            "jobs": jobs,
            "handle": handle,
            "batch_id": batch_id,
            "picked": picked,
            "kind": kind,
            "engine": engine,
            "pack_ms": round((time.perf_counter() - t_pack) * 1e3, 3),
            "stages": stages,
        }
        if prefetch_ms is not None:
            pipe_item["prefetch_ms"] = prefetch_ms
        # launched: the batch's device work is enqueued (no host sync),
        # and from here to the verdict the lane threads stand in C
        # readbacks with the interpreter lock released — the window a
        # waiting handler decodes under (PendingVerdict.wait_launched)
        for j in jobs:
            j.launched.set()
        with self._lock:
            dead = self._dead
            if dead is None:
                self._resolve_q.append(pipe_item)
        if dead is not None:
            # the worker died while we packed: the just-begun handle will
            # never be resolved — release its engine lease before failing
            _abandon_handle(engine, handle)
            raise SchedulerDown(f"resolve worker is down: {dead!r}")
        with self._lock:
            self.stats["pipelined_batches"] += 1
            inflight = len(self._resolve_q) + (1 if self._resolving else 0)
            self._cond.notify_all()
        metrics.gauge_set("sched.pipeline_inflight", inflight)

    def _prefetch_run(self) -> None:
        """The prefetch worker: witness decode + advisory novelty
        pre-scan for each assembled batch (ops/witness_engine.py
        prefetch_batch — lock-free against the committed tables), while
        the executor packs/dispatches earlier batches and the resolve
        worker resolves still-earlier ones. A crash here is systemic
        (_die, stage="prefetch"): in-flight work fails fast with -32052,
        exactly like the other stages."""
        item: Optional[dict] = None
        try:
            while True:
                with self._lock:
                    while (
                        not self._prefetch_q
                        and not self._exec_done
                        and self._dead is None
                    ):
                        self._cond.wait()
                    if self._dead is not None:
                        return  # _die already failed everything queued
                    if not self._prefetch_q:
                        return  # executor done; pending is drained
                    item = self._prefetch_q.pop(0)
                if self._chaos_prefetch:
                    raise RuntimeError(
                        "chaos drill: PHANT_SCHED_CHAOS_CRASH=prefetch "
                        "induced prefetch-stage crash"
                    )
                if item.get("kind") == _ROOT:
                    # root lane: the 4th stage runs the PLAN LOWERING —
                    # merging the batch's HashPlans into the pooled
                    # staging blob (ops/root_engine.py prefetch_batch)
                    engine = self._resolve_root_engine()
                elif item.get("kind") == _SIG:
                    # sig lane: the 4th stage runs the ROW LOWERING —
                    # concatenating the batch's signature rows and the
                    # u256 -> limb encode (ops/sig_engine.py
                    # prefetch_batch)
                    engine = self._resolve_sig_engine()
                else:
                    engine = self._resolve_engine()
                pf = getattr(engine, "prefetch_batch", None)
                plan = None
                if pf is not None:
                    t0 = time.perf_counter()
                    with lane_stage(
                        item["stages"],
                        "prefetch",
                        [j.trace_id for j in item["jobs"]],
                        item["batch_id"],
                    ):
                        plan = pf(item["payload"])
                    pf_ms = round((time.perf_counter() - t0) * 1e3, 3)
                with self._lock:
                    orphaned = self._dead is not None
                    if not orphaned:
                        item["plan"] = plan
                        if pf is not None:
                            # a prefetch-less engine double still flows
                            # through the worker, but nothing was decoded
                            # or pre-scanned — stats/metrics must not
                            # report a 4th stage that never ran
                            item["prefetch_ms"] = pf_ms
                            self.stats["prefetched_batches"] += 1
                        item["ready"] = True
                        self._cond.notify_all()
                if orphaned:
                    # _die ran while this plan was computing: it cleared
                    # _prefetch_pending and saw plan=None on this item,
                    # so nobody else will release these staging leases —
                    # drop them back to the pool here, or the shared
                    # engine's _staging loses them for good
                    if plan is not None:
                        plan.release()
                    return
                if pf is not None:
                    metrics.count("sched.prefetch_batches")
                item = None
        except BaseException as e:  # systemic: prefetch-stage failure
            self._die(e, item["jobs"] if item else [], stage="prefetch")

    def _drain_pipeline(self) -> None:
        """Block until every dispatched handle has resolved (or the
        scheduler died). Called by the executor before serial jobs —
        the serial lane stays exclusive with ALL witness work, not just
        the executor's own — and on graceful shutdown. With mesh dispatch
        the barrier covers every DEVICE lane: a state mutation must not
        run while any chip still holds in-flight witness work."""
        with self._lock:
            while (self._resolve_q or self._resolving) and self._dead is None:
                self._cond.wait(0.05)
        if self._pool is not None:
            self._pool.drain()

    def _next_batch(self, block: bool = True):
        with self._lock:
            while True:
                self._expire_locked()
                if self._dead is not None:
                    # the resolve worker died and failed everything: exit
                    # instead of idling in wait() until shutdown
                    return None
                if self._serial_q or any(self._lanes.values()):
                    break
                if self._closed:
                    return None
                if not block:
                    # prefetching executor with planned batches pending:
                    # it must not idle here while a ready plan waits
                    return _NO_BATCH
                self._cond.wait()
            if self._serial_q:
                # priority order: the serial mutation lane (head-of-chain
                # newPayload/forkchoiceUpdated) preempts ALL queued
                # witness work — a chain-head update must never sit
                # behind a backfill burst
                head = self._serial_q.pop(0)
                batch = [head]
            else:
                head = self._pick_witness_locked()
                batch = self._assemble_locked(head)
            depth = self._qlen_locked()
            tenant_depths = {
                j.tenant: len(self._lanes.get(j.tenant, ())) for j in batch
            }
            self._cond.notify_all()  # wake submitters waiting for space
        metrics.gauge_set("sched.queue_depth", depth)
        for tenant, lane_depth in tenant_depths.items():
            metrics.gauge_set("sched.tenant_queue_depth", lane_depth, tenant=tenant)
        return batch

    def _pick_witness_locked(self) -> _Job:
        """Choose the next witness head: lanes whose head request is
        PRIORITY_HEAD beat backfill lanes, and the tenant among the
        eligible class comes from the smooth-weighted-round-robin picker
        — fairness is across lanes; each lane stays FIFO internally.
        Caller holds `_lock` and guarantees at least one non-empty lane."""
        cands = [t for t, lane in self._lanes.items() if lane]
        head_cands = [
            t for t in cands if self._lanes[t][0].priority == PRIORITY_HEAD
        ]
        tenant = self._picker.pick(head_cands or cands)
        return self._lanes[tenant].pop(0)

    def _assembly_wait_s_locked(self) -> float:
        """The adaptive batching wait (qos.AdaptiveWait): re-evaluated on
        every assembly pass against the CURRENT queue depth, exported as
        the `sched.adaptive_wait_ms` gauge, with changes counted and
        flight-recorded. Static max_wait_ms when adaptive_wait is off."""
        if self._wait_policy is None:
            return self._max_wait_s
        chosen_ms = round(self._wait_policy.wait_ms(self._wit_len_locked()), 2)
        if chosen_ms != self._last_wait_ms:
            if self._last_wait_ms is not None:
                self.stats["wait_adjustments"] += 1
                metrics.count("sched.adaptive_wait_adjustments")
                flight.record(
                    "sched.adapt_wait",
                    wait_ms=chosen_ms,
                    prev_wait_ms=self._last_wait_ms,
                    queue_depth=self._wit_len_locked(),
                )
            self._last_wait_ms = chosen_ms
            metrics.gauge_set("sched.adaptive_wait_ms", chosen_ms)
        return chosen_ms / 1e3

    def _assemble_locked(self, head: _Job) -> List[_Job]:
        """Coalesce same-bucket witness jobs behind `head` under the
        max_batch / adaptive-wait policy. Same-bucket jobs join from
        EVERY tenant lane (the engine dispatch is tenant-blind; fairness
        was already enforced by the head pick), each lane drained FIFO.
        Caller holds `_lock`; the cond wait releases it so submitters
        keep admitting while we wait."""
        batch = [head]
        # evaluate the adaptive policy once per batch up front (so the
        # exported gauge tracks every batch, including the full-backlog
        # ones that never reach the wait below), then again on every pass
        self._assembly_wait_s_locked()
        while True:
            for lane in self._lanes.values():
                i = 0
                while i < len(lane) and len(batch) < self._max_batch:
                    if lane[i].bucket == head.bucket:
                        batch.append(lane.pop(i))
                    else:
                        i += 1
                if len(batch) >= self._max_batch:
                    break
            if len(batch) >= self._max_batch or self._closed:
                break
            if self._prefetch_pending and self._prefetch_pending[0]["ready"]:
                # 4-stage pipeline: a finished plan is waiting on this
                # thread — dispatching it beats further coalescing here
                # (waiting out the window would serialize the whole
                # pipeline behind one batch's assembly, the exact
                # bubble the prefetch stage exists to remove)
                break
            # the wait window shrinks as the queue deepens (a full
            # backlog needs no coalescing delay) and is re-evaluated
            # after every wakeup — a burst landing mid-wait cuts the
            # remaining window short
            wait_until = head.admitted + self._assembly_wait_s_locked()
            now = time.monotonic()
            if now >= wait_until:
                break
            self._cond.wait(wait_until - now)
        return batch

    def _shed_expired(self, job: _Job) -> None:
        """Deadline shed at execution time: one place keeps the stats
        snapshot and the `sched.rejected` metric in agreement (the soak
        gate asserts on the snapshot)."""
        with self._lock:
            self.stats["rejected"] += 1
            self._tenant_locked(job.tenant)["shed"] += 1
        metrics.count("sched.rejected", reason="deadline", tenant=job.tenant)
        flight.record(
            "sched.shed",
            reason="deadline",
            lane=job.kind,
            tenant=job.tenant,
            trace_id=job.trace_id,
        )
        job.future.set_exception(
            DeadlineExpired("deadline expired while queued")
        )

    def _expire_locked(self) -> None:
        """Fail queued jobs whose deadline has passed (without executing)."""
        now = time.monotonic()
        expired: List[_Job] = []
        for q in (self._serial_q, *self._lanes.values()):
            live = [j for j in q if not deadline_clock.passed(j.deadline, now)]
            if len(live) != len(q):
                kept = set(map(id, live))
                expired.extend(j for j in q if id(j) not in kept)
                q[:] = live
        if not expired:
            return
        self.stats["rejected"] += len(expired)
        for j in expired:
            self._tenant_locked(j.tenant)["shed"] += 1
            # set_exception never raises here: these futures have no
            # waiter-side cancellation path
            j.future.set_exception(
                DeadlineExpired("deadline expired while queued")
            )
            metrics.count("sched.rejected", reason="deadline", tenant=j.tenant)
            flight.record(
                "sched.shed",
                reason="deadline",
                lane=j.kind,
                tenant=j.tenant,
                trace_id=j.trace_id,
            )

    def _execute(self, batch: List[_Job]) -> None:
        now = time.monotonic()
        for j in batch:
            metrics.observe_hist("sched.queue_wait_seconds", now - j.admitted)
        lane = batch[0].kind
        # the stall bound the obs watchdog polls against: a full execution
        # allowance (config.deadline_ms) from PICKUP time — never the jobs'
        # admission deadlines, or a batch picked up with 0.2s of a 30s
        # deadline left would flag a perfectly healthy executor as stalled
        # and bury the real wedged-device signal
        if self.config.deadline_ms > 0:
            stall_deadline: Optional[float] = now + self.config.deadline_ms / 1e3
        else:
            stall_deadline = None
        trace_ids = [j.trace_id for j in batch]
        pipelined = False
        if lane == _SERIAL:
            # serial exclusivity covers the PIPELINE too: a state mutation
            # must not run while dispatched witness handles are in flight
            self._set_exec_stage("serial")
            self._drain_pipeline()
            with self._lock:
                dead = self._dead
            if dead is not None:
                # the drain ended because the scheduler DIED, not because
                # the pipeline emptied: a state mutation must not commit
                # on a server whose /healthz already reports it down
                _safe_fail(
                    batch[0].future,
                    SchedulerDown(f"scheduler executor crashed: {dead!r}"),
                )
                return
            stage = "serial"
        elif self._pool is not None:
            # mesh fan-out: the lane executor advances the stage (and
            # names its device) once the batch is routed; "dispatch" is
            # what an un-routed mesh batch is doing from this thread's
            # point of view
            engine = None
            pipelined = False
            stage = "dispatch"
            self._set_exec_stage(stage)
        else:
            self._set_exec_stage("pack")  # provisional: engine resolution
            if lane == _ROOT:
                engine = self._resolve_root_engine()
            elif lane == _SIG:
                engine = self._resolve_sig_engine()
            else:
                engine = self._resolve_engine()
            pipelined = self._pipe_depth > 1 and hasattr(engine, "begin_batch")
            # stage vocabulary: pipelined batches move pack -> dispatch ->
            # resolve; a depth-1/inline batch runs all three fused under
            # "dispatch" (the engine round-trip the executor blocks on).
            # _exec_stage must AGREE with the batch_start record — a
            # depth-1 crash (chaos drill included) has no pack stage
            stage = "pack" if pipelined else "dispatch"
            self._set_exec_stage(stage)
        with self._lock:
            self._batch_seq += 1
            batch_id = self._batch_seq
            self._inflight_list.append(
                {
                    "batch_id": batch_id,
                    "lane": lane,
                    "stage": stage,
                    "device": None,  # set by the mesh pool once routed
                    "started": now,
                    "deadline": stall_deadline,
                    "trace_ids": trace_ids,
                }
            )
        flight.record(
            "sched.batch_start",
            batch_id=batch_id,
            lane=lane,
            stage=stage,
            batch_size=len(batch),
            bucket_bytes=batch[0].bucket if lane == _WITNESS else None,
            tenants=sorted({j.tenant for j in batch}),
            trace_ids=trace_ids,
        )
        if pipelined:
            # the descriptor stays in flight until the resolve worker
            # finishes the batch (or _die clears everything)
            self._execute_witness_pipelined(batch, batch_id, engine, now, kind=lane)
            return
        if lane in (_WITNESS, _ROOT, _SIG) and self._pool is not None:
            # the descriptor stays in flight until the mesh lane finishes
            # the batch (_mesh_done/_mesh_skip) or _die clears everything
            if lane in (_ROOT, _SIG):
                self._execute_lane_mesh(batch, batch_id, now)
            else:
                self._execute_witness_mesh(batch, batch_id, now)
            return
        try:
            if lane == _SERIAL:
                self._execute_serial(batch[0], batch_id)
            elif lane == _ROOT:
                self._execute_roots(batch, batch_id, engine, now)
            elif lane == _SIG:
                self._execute_sigs(batch, batch_id, engine, now)
            else:
                self._execute_witness(batch, batch_id, engine, now)
        finally:
            with self._lock:
                self._drop_inflight_locked(batch_id)

    def _drop_inflight_locked(self, batch_id: int) -> None:
        self._inflight_list = [
            d for d in self._inflight_list if d["batch_id"] != batch_id
        ]

    def _execute_serial(self, job: _Job, batch_id: int) -> None:
        metrics.count("sched.batches", lane="serial")
        metrics.count("sched.tenant_served", tenant=job.tenant)
        with self._lock:
            self.stats["serial_jobs"] += 1
            self._tenant_locked(job.tenant)["served"] += 1
        if deadline_clock.passed(job.deadline):
            self._shed_expired(job)
            return
        t0 = time.monotonic()

        def done(ok: bool, **extra) -> None:
            # the postmortem must distinguish a failed mutation from a
            # successful one — `ok` is the serial lane's n_ok analog
            flight.record(
                "sched.batch_done",
                batch_id=batch_id,
                lane=_SERIAL,
                batch_size=1,
                tenants=[job.tenant],
                ok=ok,
                duration_ms=round((time.monotonic() - t0) * 1e3, 3),
                queue_wait_ms=round((t0 - job.admitted) * 1e3, 3),
                trace_ids=[job.trace_id],
                **extra,
            )

        try:
            result = job.fn()
        except Exception as e:  # request-scoped: the job failed, not us
            done(False, error=repr(e)[:160])
            job.future.set_exception(e)
            return
        done(True)
        job.future.set_result(result)

    @staticmethod
    def _engine_cache_stats(engine) -> Optional[dict]:
        """hits/hashed/device/native counters of the engine, or None when
        the engine exposes no stats (custom test doubles)."""
        snap = getattr(engine, "stats_snapshot", None)
        if snap is None:
            return None
        try:
            return snap()
        except Exception:
            return None

    def _shed_or_keep(self, batch: List[_Job], now: float) -> List[_Job]:
        jobs = []
        for j in batch:
            if deadline_clock.passed(j.deadline, now):
                self._shed_expired(j)
            else:
                jobs.append(j)
        return jobs

    def _execute_witness(
        self, batch: List[_Job], batch_id: int, engine, picked: float
    ) -> None:
        """Depth-1/inline execution: one verify_batch round-trip on the
        executor thread (pack + dispatch + resolve fused) — exactly the
        pre-pipeline behavior."""
        jobs = self._shed_or_keep(batch, picked)
        if not jobs:
            return
        if self._chaos_crash:
            raise RuntimeError(
                "chaos drill: PHANT_SCHED_CHAOS_CRASH=1 induced executor crash"
            )
        self._set_exec_stage("dispatch")
        s0 = self._engine_cache_stats(engine)
        # the engine/device dispatch this scheduler exists for: one
        # verify_batch over the whole coalesced bucket. An exception here
        # is systemic (malformed witnesses yield False verdicts, and the
        # engine falls back device->native internally), so it propagates
        # to _run and takes the executor down — requests fail fast rather
        # than silently retrying into a broken engine.
        stages: dict = {}
        with lane_stage(
            stages, "dispatch", [j.trace_id for j in jobs], batch_id
        ):
            verdicts = engine.verify_batch([(j.root, j.nodes) for j in jobs])
        s1 = self._engine_cache_stats(engine)
        record = batch_record_from_stats(
            batch_id, len(jobs), jobs[0].bucket, s0, s1
        )
        fold_stages(record, stages)
        self._finish_witness_jobs(jobs, verdicts, record, picked)

    def _execute_witness_pipelined(
        self,
        batch: List[_Job],
        batch_id: int,
        engine,
        picked: float,
        kind: str = _WITNESS,
    ) -> None:
        """Pack + dispatch on the executor thread, resolve on the resolve
        worker: begin_batch holds the engine lock only for the intern
        scan (witness lane) or runs the plan merge (root lane) and
        enqueues the device work with NO host sync, so this thread moves
        straight on to assembling (and packing) the next batch while the
        device computes and the worker resolves."""
        jobs = self._shed_or_keep(batch, picked)
        if not jobs:
            with self._lock:
                self._drop_inflight_locked(batch_id)
            return
        if self._chaos_crash:
            raise RuntimeError(
                "chaos drill: PHANT_SCHED_CHAOS_CRASH=1 induced executor crash"
            )
        self._pipeline_handoff(jobs, batch_id, engine, picked, kind=kind)

    def _execute_roots(
        self, batch: List[_Job], batch_id: int, engine, picked: float
    ) -> None:
        """Depth-1/inline root execution: one begin+resolve round trip on
        the executor thread (the root_many shape) — the coalesced batch
        still merges into ONE dispatch; only the pipeline overlap is
        absent."""
        jobs = self._shed_or_keep(batch, picked)
        if not jobs:
            return
        self._set_exec_stage("dispatch")
        stages: dict = {}
        ids = [j.trace_id for j in jobs]
        with lane_stage(stages, "pack", ids, batch_id):
            handle = engine.begin_batch([j.plan for j in jobs])
        with lane_stage(stages, "resolve", ids, batch_id):
            results = engine.resolve_batch(handle)
        record = root_record_from_handle(
            handle, batch_id, len(jobs), jobs[0].bucket
        )
        record["stage"] = "dispatch"  # fused begin+resolve, like depth-1
        fold_stages(record, stages)
        self._finish_root_jobs(jobs, results, record, picked)

    def _finish_plan_jobs(
        self,
        jobs: List[_Job],
        results,
        record: dict,
        picked: float,
        lane: str,
        emit: Callable[[int], None],
    ) -> None:
        """Shared completion tail of the root AND sig lanes: per-job meta
        + future resolution (each future gets ITS request's result
        slice), the batch_done record, and the coalescing metrics/stats
        — one definition so the two lanes can never diverge (the
        copy-divergence class this repo keeps eliminating). `emit(n)`
        publishes the lane's own counters: metric names must stay string
        LITERALS at their emit site (the METRICNAME contract), so each
        lane wrapper passes a closure instead of a name."""
        n = len(jobs)
        done = time.monotonic()
        served: dict = {}
        for j in jobs:
            served[j.tenant] = served.get(j.tenant, 0) + 1
        flight.record(
            "sched.batch_done",
            duration_ms=round((done - picked) * 1e3, 3),
            n_ok=n,
            tenants=sorted(served),
            trace_ids=[j.trace_id for j in jobs],
            **record,
        )
        # timeline tap: the [picked, done] interval lands on the lane's
        # track, keyed by batch_id — the `f` side of the flow stitching
        timeline.record_batch(
            record,
            lane=lane,
            duration_ms=round((done - picked) * 1e3, 3),
            trace_ids=[j.trace_id for j in jobs],
        )
        metrics.observe_hist("sched.batch_size", n, buckets=_BATCH_BUCKETS)
        metrics.count("sched.batches", lane=lane)
        emit(n)
        for tenant, cnt in served.items():
            metrics.count("sched.tenant_served", cnt, tenant=tenant)
        with self._lock:
            st = self.stats
            st["batches"] += 1
            st["batched_requests"] += n
            st[lane + "_batches"] += 1
            st[lane + "_requests"] += n
            if n > 1:
                st[lane + "_coalesced"] += n
                st["coalesced"] += n
        # futures resolve LAST: the future is the publication point, so a
        # waiter that observed its result must also observe the batch in
        # stats_snapshot()/metrics/flight (phantsan caught the inversion —
        # resolve-then-count let a freshly-unblocked caller read a
        # snapshot the batch had not reached yet)
        for j, result in zip(jobs, results):
            # meta BEFORE set_result (the *_traced ordering contract)
            j.meta = {
                **record,
                "tenant": j.tenant,
                "queue_wait_ms": round((picked - j.admitted) * 1e3, 3),
            }
            _safe_resolve(j.future, result)
            if n > st["max_batch_seen"]:
                st["max_batch_seen"] = n
            for tenant, cnt in served.items():
                self._tenant_locked(tenant)["served"] += cnt

    def _finish_root_jobs(
        self, jobs: List[_Job], results, record: dict, picked: float
    ) -> None:
        """Root-lane completion: each future gets ITS plan's out digests
        (storage roots in patch order, post root last)."""

        def emit(n: int) -> None:
            metrics.count(
                "sched.root_batches", backend=record.get("backend", "host")
            )
            if n > 1:
                metrics.count("sched.root_coalesced", n)

        self._finish_plan_jobs(jobs, results, record, picked, _ROOT, emit)

    def _execute_sigs(
        self, batch: List[_Job], batch_id: int, engine, picked: float
    ) -> None:
        """Depth-1/inline sig execution: one begin+resolve round trip on
        the executor thread (the sig_many shape) — the coalesced batch
        still merges into ONE dispatch; only the pipeline overlap is
        absent."""
        jobs = self._shed_or_keep(batch, picked)
        if not jobs:
            return
        self._set_exec_stage("dispatch")
        stages: dict = {}
        ids = [j.trace_id for j in jobs]
        with lane_stage(stages, "pack", ids, batch_id):
            handle = engine.begin_batch([j.rows for j in jobs])
        with lane_stage(stages, "resolve", ids, batch_id):
            results = engine.resolve_batch(handle)
        record = sig_record_from_handle(
            handle, batch_id, len(jobs), jobs[0].bucket
        )
        record["stage"] = "dispatch"  # fused begin+resolve, like depth-1
        fold_stages(record, stages)
        self._finish_sig_jobs(jobs, results, record, picked)

    def _finish_sig_jobs(
        self, jobs: List[_Job], results, record: dict, picked: float
    ) -> None:
        """Sig-lane completion: each future gets ITS request's sender
        slice (tx order; None = invalid signature)."""

        def emit(n: int) -> None:
            metrics.count(
                "sched.sig_batches", backend=record.get("backend", "native")
            )
            if n > 1:
                metrics.count("sched.sig_coalesced", n)

        self._finish_plan_jobs(jobs, results, record, picked, _SIG, emit)

    # -- mesh dispatch (mesh_devices >= 1, serving/mesh_exec.py) -------------

    def _execute_witness_mesh(
        self, batch: List[_Job], batch_id: int, picked: float
    ) -> None:
        """Fan one assembled batch out to the per-device pool: the
        whole-mesh megabatch path when the batch fills `max_batch` from a
        single bucket (megabatch mode), bucket-affinity routing with
        spillover otherwise. Affinity batches complete asynchronously on
        their lane (_mesh_done drops the descriptor); this thread goes
        straight back to assembling the next batch — that overlap is the
        mesh pipeline."""
        jobs = self._shed_or_keep(batch, picked)
        if not jobs:
            with self._lock:
                self._drop_inflight_locked(batch_id)
            return
        if self._chaos_crash:
            raise RuntimeError(
                "chaos drill: PHANT_SCHED_CHAOS_CRASH=1 induced executor crash"
            )
        pool = self._pool
        # backlog-depth trigger input: the same-bucket jobs STILL queued
        # after assembly (assembly drained the bucket up to max_batch, so
        # a non-zero leftover means sustained same-shape pressure). The
        # queue walk holds the global lock — only pay it when the
        # trigger can actually consume it (megabatch mode, k > 0), never
        # on the default affinity hot path.
        backlog = 0
        if pool.backlog_wanted():
            bucket = jobs[0].bucket
            with self._lock:
                backlog = sum(
                    1
                    for lane in self._lanes.values()
                    for qj in lane
                    if qj.kind == _WITNESS and qj.bucket == bucket
                )
        why = pool.megabatch_wanted(len(jobs), backlog)
        if why:
            from phant_tpu.serving.mesh_exec import MegabatchUnsupported

            try:
                verdicts, record = pool.run_megabatch(jobs, batch_id)
            except MegabatchUnsupported:
                pass  # this batch can't take the fused path: route it
            else:
                with self._lock:
                    self.stats["megabatches"] += 1
                    if why == "backlog":
                        self.stats["megabatch_backlog_triggers"] += 1
                if why == "backlog":
                    # fusion engaged by sustained overload, not a full
                    # batch — the trigger the operator tunes with
                    # --sched-megabatch-backlog-k
                    metrics.count("sched.megabatch_backlog_triggers")
                self._finish_witness_jobs(jobs, verdicts, record, picked)
                with self._lock:
                    self._drop_inflight_locked(batch_id)
                return
        device = pool.submit(jobs, batch_id, picked)
        if device is None:
            # a lane crashed while we waited for a slot: stop the executor
            # the same way a dead resolve worker does
            raise SchedulerDown("mesh executor pool is down")
        with self._lock:
            self.stats["mesh_batches"] += 1
            for d in self._inflight_list:
                if d["batch_id"] == batch_id:
                    d["device"] = device

    def _execute_lane_mesh(
        self, batch: List[_Job], batch_id: int, picked: float
    ) -> None:
        """Fan one root or sig batch out to the per-device pool:
        bucket-affinity routing (a level shape keeps hitting the same
        lane's pinned RootEngine; every sig batch shares one bucket, so
        one lane's pinned SigEngine keeps its compiled ecrecover shapes
        warm, with spillover as the load balancer) with the same
        backpressure as witness batches. Root/sig batches never take the
        megabatch path — the lane's merged dispatch IS the fusion."""
        jobs = self._shed_or_keep(batch, picked)
        if not jobs:
            with self._lock:
                self._drop_inflight_locked(batch_id)
            return
        device = self._pool.submit(jobs, batch_id, picked)
        if device is None:
            raise SchedulerDown("mesh executor pool is down")
        with self._lock:
            self.stats["mesh_batches"] += 1
            for d in self._inflight_list:
                if d["batch_id"] == batch_id:
                    d["device"] = device

    def _mesh_done(self, jobs, verdicts, record, picked, batch_id) -> None:
        """Lane completion (pool thread): the shared completion tail —
        witness, root, or sig by the jobs' kind — then the watchdog
        descriptor drops."""
        if jobs and jobs[0].kind == _ROOT:
            self._finish_root_jobs(jobs, verdicts, record, picked)
        elif jobs and jobs[0].kind == _SIG:
            self._finish_sig_jobs(jobs, verdicts, record, picked)
        else:
            self._finish_witness_jobs(jobs, verdicts, record, picked)
        with self._lock:
            self._drop_inflight_locked(batch_id)
            self._cond.notify_all()

    def _mesh_skip(self, batch_id) -> None:
        """Every job of a routed batch expired on its lane: nothing ran."""
        with self._lock:
            self._drop_inflight_locked(batch_id)
            self._cond.notify_all()

    def _mesh_stage(self, batch_id, stage, device) -> None:
        """Stage tracking for the obs watchdog: the lane reports which
        pipeline stage a routed batch is in, and on which device — a
        wedged device call shows up as a stall record NAMING the device."""
        with self._lock:
            for d in self._inflight_list:
                if d["batch_id"] == batch_id:
                    d["stage"] = stage
                    d["device"] = device

    def _mesh_crash(self, exc, jobs, stage, device) -> None:
        """A lane crashed (pool thread): scheduler-wide death, stage and
        device named in the crash record."""
        self._die(exc, list(jobs), stage=stage, device=device)

    def _finish_witness_jobs(
        self, jobs: List[_Job], verdicts, record: dict, picked: float
    ) -> None:
        """Shared completion tail of both witness paths: per-job meta +
        future resolution, the batch_done flight record, and the batching
        metrics/stats."""
        n = len(jobs)
        total = sum(j.nbytes for j in jobs)
        padded = _pow2ceil(total)
        done = time.monotonic()
        served: dict = {}
        for j in jobs:
            served[j.tenant] = served.get(j.tenant, 0) + 1
        flight.record(
            "sched.batch_done",
            lane=_WITNESS,
            duration_ms=round((done - picked) * 1e3, 3),
            n_ok=int(sum(bool(ok) for ok in verdicts)),
            tenants=sorted(served),
            trace_ids=[j.trace_id for j in jobs],
            **record,
        )
        # timeline tap: every witness completion funnels here (inline,
        # pipelined, mesh lane, megabatch) — one tap covers them all
        timeline.record_batch(
            record,
            lane=_WITNESS,
            duration_ms=round((done - picked) * 1e3, 3),
            trace_ids=[j.trace_id for j in jobs],
        )
        metrics.observe_hist("sched.batch_size", n, buckets=_BATCH_BUCKETS)
        # a wave of copies or a wave of work: four clients' one head is 1
        # here, four operators' four heads 4 (by pre-state root, as the
        # witness engine's phant/witness.dispatch blocks= counts them)
        metrics.observe_hist(
            "sched.batch_blocks", len({j.root for j in jobs}), buckets=_BATCH_BUCKETS
        )
        metrics.count("sched.batches", lane="witness")
        for tenant, cnt in served.items():
            # the per-tenant progress counter the no-starvation gates
            # (loadgen, soak) watch
            metrics.count("sched.tenant_served", cnt, tenant=tenant)
        # beside it, what each tenant's jobs waited from admission to the
        # start of their batch's first lane stage (critpath's queue_wait);
        # `tenant` went through the max_tenants fold at admission
        started = _first_stage_s(record, picked)
        for j in jobs:
            metrics.observe_hist(
                "sched.tenant_wait_seconds",
                max(started - j.admitted, 0.0),
                tenant=j.tenant,
            )
        metrics.gauge_set(
            "sched.padding_waste", round(1.0 - total / padded, 4) if padded else 0.0
        )
        if n > 1:
            metrics.count("sched.coalesced_requests", n)
        with self._lock:
            st = self.stats
            st["batches"] += 1
            st["batched_requests"] += n
            if n > 1:
                st["coalesced"] += n
            if n > st["max_batch_seen"]:
                st["max_batch_seen"] = n
            for tenant, cnt in served.items():
                self._tenant_locked(tenant)["served"] += cnt
        # futures resolve LAST (see _finish_plan_jobs): a waiter that saw
        # its verdict must also see the batch in stats and metrics
        for j, ok in zip(jobs, verdicts):
            # meta BEFORE set_result: a waiter that observed the verdict
            # must also observe its batch record (verify_traced)
            j.meta = {
                **record,
                "tenant": j.tenant,
                "queue_wait_ms": round((picked - j.admitted) * 1e3, 3),
            }
            _safe_resolve(j.future, bool(ok))

    # -- resolve worker (pipeline_depth > 1) ---------------------------------

    def _resolve_run(self) -> None:
        deadline_clock.serving_thread()
        item: Optional[dict] = None
        try:
            while True:
                with self._lock:
                    while (
                        not self._resolve_q
                        and not self._exec_done
                        and self._dead is None
                    ):
                        self._cond.wait()
                    if self._dead is not None:
                        return  # _die already failed everything queued
                    if not self._resolve_q:
                        return  # executor done and the pipeline is drained
                    item = self._resolve_q.pop(0)
                    self._resolving = True
                    for d in self._inflight_list:
                        if d["batch_id"] == item["batch_id"]:
                            d["stage"] = "resolve"
                    self._cond.notify_all()
                try:
                    self._resolve_one(item)
                finally:
                    with self._lock:
                        self._resolving = False
                        self._drop_inflight_locked(item["batch_id"])
                        inflight = len(self._resolve_q)
                        self._cond.notify_all()
                    metrics.gauge_set("sched.pipeline_inflight", inflight)
                item = None
        except BaseException as e:  # systemic: readback/commit failure
            # resolve_batch releases its own handle on failure; a crash
            # elsewhere in the loop still must not leak it
            if item is not None:
                _abandon_handle(
                    item.get("engine") or self._resolve_engine(), item["handle"]
                )
            self._die(e, item["jobs"] if item else [], stage="resolve")

    def _resolve_one(self, item: dict) -> None:
        jobs = item["jobs"]
        handle = item["handle"]
        engine = item.get("engine") or self._resolve_engine()
        t0 = time.monotonic()
        stages = item["stages"]
        with lane_stage(
            stages, "resolve", [j.trace_id for j in jobs], item["batch_id"]
        ):
            results = engine.resolve_batch(handle)
        if item.get("kind") == _ROOT:
            record = root_record_from_handle(
                handle, item["batch_id"], len(jobs), jobs[0].bucket
            )
            finish = self._finish_root_jobs
        elif item.get("kind") == _SIG:
            record = sig_record_from_handle(
                handle, item["batch_id"], len(jobs), jobs[0].bucket
            )
            finish = self._finish_sig_jobs
        else:
            record = batch_record_from_handle(
                handle, item["batch_id"], len(jobs), jobs[0].bucket
            )
            finish = self._finish_witness_jobs
        record["pack_ms"] = item["pack_ms"]
        if "prefetch_ms" in item:
            record["prefetch_ms"] = item["prefetch_ms"]
        record["resolve_ms"] = round((time.monotonic() - t0) * 1e3, 3)
        fold_stages(record, stages)
        finish(jobs, results, record, item["picked"])

    def prewarm_lanes(self) -> int:
        """Build the resident table's programs, which the witness lane
        launches, on every rung of their ladders (utils/rungs.py): a
        server's boot on an accelerator, before its port answers. Returns
        the programs built or loaded. (`ecrecover_kernel` has one rung,
        which the first request builds whatever its wave:
        `secp256k1_jax.SIG_LADDER` says why.) A mesh pool's pinned engines
        are not reached: each builds on its own chip when first met
        (PERF.md section 7, m)."""
        if self._pool is not None:
            return 0
        return self._resolve_engine().prewarm_resident()

    def _resolve_engine(self):
        with self._engine_lock:
            if self._engine is None:
                from phant_tpu.stateless import shared_witness_engine

                self._engine = shared_witness_engine()
            return self._engine

    def _die(
        self,
        exc: BaseException,
        batch: List[_Job],
        stage: Optional[str] = None,
        device=None,
    ) -> None:
        """Mark the scheduler DOWN and fail fast: the crashing batch, every
        queued job, AND every dispatched-but-unresolved pipeline handle.
        `stage` names where execution died — pack/dispatch (executor),
        resolve (resolve worker), serial — so the postmortem pinpoints the
        pipeline stage; `device` names the mesh lane when one crashed.
        With mesh dispatch the pool dies too: queued-but-unbegun batches
        fail fast here, and every surviving lane abandons its own
        dispatched handles (no engine leaks a lease). Idempotent-by-
        first-caller: when the second thread of a pipelined scheduler
        trips over the first thread's corpse, it only fails its own
        victims (one crash record, one dump)."""
        with self._lock:
            first = self._dead is None
            if first:
                self._dead = exc
            victims = list(batch) + self._serial_q
            for lane in self._lanes.values():
                victims.extend(lane)
            dropped_items = list(self._resolve_q)
            for item in dropped_items:
                victims.extend(item["jobs"])
            # batches mid-prefetch (queued for the worker or awaiting
            # pickup) fail fast too; their plans' staging leases release
            # outside the lock. The crashing batch may still sit in
            # _prefetch_pending — _safe_fail tolerates the double-fail.
            dropped_plans = list(self._prefetch_pending)
            for item in dropped_plans:
                victims.extend(item["jobs"])
            self._serial_q = []
            self._lanes = {}
            self._resolve_q = []
            self._prefetch_q = []
            self._prefetch_pending = []
            self._inflight_list = []
            batch_id = self._batch_seq
            self._cond.notify_all()
        for item in dropped_items:
            # never resolved, never will be: release the engine leases so
            # a shared engine keeps evicting after this scheduler's death
            # (each pipe item carries ITS engine — witness or root)
            _abandon_handle(item.get("engine") or self._resolve_engine(), item["handle"])
        for item in dropped_plans:
            plan = item.get("plan")
            if plan is not None:
                try:
                    plan.release()  # unconsumed staging leases -> pool
                except Exception:
                    log.warning("plan release failed on a crash path", exc_info=True)
        pool_failed = 0
        if self._pool is not None:
            # queued-but-unbegun mesh batches fail fast here; lanes
            # abandon their own begun handles as they observe the death
            pool_failed = self._pool.kill(exc)
        if first:
            log.error("scheduler executor crashed: %r", exc, exc_info=exc)
            metrics.count("sched.executor_crashes")
            # the postmortem FIRST: record the crash (with the crashing
            # batch's ids and the stage that died) and dump the whole ring
            # to build/flight/ — by the time a waiter observes its
            # SchedulerDown, the artifact already exists
            flight.record(
                "sched.executor_crash",
                batch_id=batch_id,
                stage=stage,
                device=device,
                error=repr(exc),
                crashed_trace_ids=[j.trace_id for j in batch],
                n_failed_fast=len(victims) + pool_failed,
            )
            flight.dump("executor_crash")
        for j in victims:
            _safe_fail(
                j.future, SchedulerDown(f"scheduler executor crashed: {exc!r}")
            )
        metrics.gauge_set("sched.queue_depth", 0)
        metrics.gauge_set("sched.pipeline_inflight", 0)
        self._watchdog.stop(0.0)
