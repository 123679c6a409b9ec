"""Mesh-sharded serving execution: one pipelined executor per device.

The continuous-batching scheduler (serving/scheduler.py) assembles
shape-bucketed witness batches — but until this module every assembled
batch executed through ONE executor against ONE engine on ONE device,
while `phant_tpu/parallel/mesh.py` already proved near-linear weak scaling
for the sharded witness/ecrecover kernels. `MeshExecutorPool` closes that
gap for the SERVING path:

* **N executors, one per mesh device**, each owning a `WitnessEngine`
  pinned to that device (`device_index=i`, ops/witness_engine.py): the
  engine's intern table and its novel-node device dispatches live on one
  chip. Each executor runs the PR-5 two-phase protocol as a depth-bounded
  software pipeline in its own thread — begin (pack + async dispatch)
  batch N+1, then resolve batch N — so host packing on lane A overlaps
  device compute on lanes A..N simultaneously.
* **Bucket-affinity routing** — a STABLE hash (splitmix64 over the shape
  bucket) maps each bucket to a home device, so a given witness shape
  keeps hitting the same device's intern table across batches and
  restarts. This is what preserves the cross-block node reuse the
  Patricia-trie analysis (PAPERS.md 2408.14217) quantifies: hit rate is a
  property of the TABLE, and affinity keeps the table warm. When the home
  device's backlog exceeds `spill_depth`, the batch spills to the
  least-loaded device instead — under single-bucket saturation spillover
  IS the load balancer (a re-hash on a cold table costs less than an
  idle mesh), and the per-device dispatch counters make the tradeoff
  visible.
* **Megabatch dispatch** (`dispatch="megabatch"`) — when one bucket fills
  the assembler's whole `max_batch`, the pool can instead dispatch the
  batch as ONE device-sharded kernel call over the whole mesh
  (parallel/mesh.py witness_verify_fused_sharded): the fused cold path,
  no memoization, every device computing one slice of the same batch.
  That trades the intern tables for full-mesh utilization — right when
  the backlog is deep and novel-dense, wrong for steady-state reuse-heavy
  traffic, which is why it is a mode, not the default. Unsupported
  batches (oversized nodes, non-power-of-two mesh, no jax devices) fall
  back to affinity routing.
* **Crash semantics** match the scheduler's: any executor crash marks the
  WHOLE scheduler down (`on_crash` -> `_die`), and every lane abandons
  its dispatched-but-unresolved handles through `engine.abandon_batch`
  so no engine leaks in-flight leases (a leaked lease defers generation
  flushes forever — the PR-5 review lesson, now per device).
* **Prewarm** — pool start compiles the sharded serving executables once
  (parallel/mesh.py prewarm_sharded, via the AOT executable memo) when
  the device backend is live, so the process-global compile-cache
  suspension windows fire at boot instead of per-dispatch mid-traffic.

Observability: `sched.device_queue_depth{device=}` /
`sched.device_dispatch{device=}` / `sched.device_stall` /
`sched.mesh_megabatches` metrics, and every batch/stall/crash record the
scheduler emits for a mesh batch carries the `device` that ran it.

Thread-safety: one lock (`_lock`) + its Condition guard the queues,
per-device load counts, and lifecycle flags; `*_locked` helpers touch
them. Engine calls, metric publishes, and the scheduler callbacks all run
OUTSIDE the lock (the engine and registry carry their own locks — same
discipline as scheduler.py).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from phant_tpu.serving import deadline as deadline_clock
from phant_tpu.utils.trace import fold_stages, lane_stage, metrics

log = logging.getLogger("phant_tpu.serving.mesh")

_M64 = (1 << 64) - 1


def _mix64(x: int) -> int:
    """splitmix64 finalizer: a stable, well-distributed 64-bit mix."""
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return (x ^ (x >> 31)) & _M64


def affinity_device(bucket: int, n_devices: int) -> int:
    """The stable bucket -> home-device map. Pure and process-independent
    (no PYTHONHASHSEED dependence): the same bucket lands on the same
    device across batches, restarts, and hosts — the property the
    per-device intern tables' hit rates ride on. Buckets are powers of
    two, so the raw value is mixed first (a plain modulo would alias
    every bucket of one residue class onto one device)."""
    if n_devices <= 1:
        return 0
    return _mix64(int(bucket)) % n_devices


class MegabatchUnsupported(Exception):
    """This batch cannot take the whole-mesh fused path; route by
    affinity instead (oversized nodes, non-pow2 mesh, jax absent)."""


def _default_engine_factory(index: int):
    """Per-device engine, sized exactly like the process-shared one
    (stateless.shared_witness_engine) but pinned to mesh device `index`."""
    import os

    from phant_tpu.ops.witness_engine import WitnessEngine

    return WitnessEngine(
        max_nodes=int(os.environ.get("PHANT_WITNESS_CACHE", 1 << 20)),
        device_batch_floor=int(os.environ.get("PHANT_TPU_MIN_KECCAK", -1)),
        device_index=index,
    )


def _default_root_engine_factory(index: int):
    """Per-device ROOT engine (ops/root_engine.py), pinned to mesh device
    `index`: a root batch routed to this lane merges + hashes on the
    lane's own chip — the post-root twin of the pinned witness engine."""
    from phant_tpu.ops.root_engine import RootEngine

    return RootEngine(device_index=index)


def _default_sig_engine_factory(index: int):
    """Per-device SIG engine (ops/sig_engine.py), pinned to mesh device
    `index`: a sender-recovery batch routed to this lane runs its merged
    ecrecover on the lane's own chip — the sig twin of the pinned
    witness/root engines."""
    from phant_tpu.ops.sig_engine import SigEngine

    return SigEngine(device_index=index)


def _abandon(engine, handle) -> None:
    """Best-effort lease release on a crash path — the scheduler's helper,
    imported lazily (scheduler.py is always loaded before a pool exists;
    a top-level import would be the one cycle in the package)."""
    from phant_tpu.serving.scheduler import _abandon_handle

    _abandon_handle(engine, handle)


def _engine_stats(engine) -> Optional[dict]:
    snap = getattr(engine, "stats_snapshot", None)
    if snap is None:
        return None
    try:
        return snap()
    except Exception:
        return None


class _PoolDead(Exception):
    """Internal: another lane crashed; this lane must clean up and exit."""


class MeshExecutorPool:
    """N per-device pipelined executors behind the verification scheduler.

    The scheduler keeps global admission, tenant-fair head pick, and batch
    assembly; only DISPATCH fans out here. `submit()` routes one assembled
    same-bucket batch to a device lane (affinity + spillover) and blocks
    for backpressure when every lane is full — the scheduler's admission
    queue, not a hidden pool queue, is where overload must land.

    `engine` shares ONE engine across all lanes (the two-phase API accepts
    any handle interleaving, so this is sound — one intern table, no
    affinity benefit); the default builds one pinned engine per device
    (`engine_factory`). Callbacks (`on_done`/`on_stage`/`on_skip`/
    `on_expired`/`on_crash`) are the scheduler's completion, stage-
    tracking, deadline-shed, and death hooks; all fire on pool threads.
    """

    def __init__(
        self,
        n_devices: int,
        *,
        pipeline_depth: int = 2,
        spill_depth: int = 2,
        dispatch: str = "affinity",
        max_batch: int = 128,
        backlog_k: int = 0,
        prefetch: bool = True,
        engine: Optional[object] = None,
        engine_factory: Optional[Callable[[int], object]] = None,
        root_engine_factory: Optional[Callable[[int], object]] = None,
        sig_engine_factory: Optional[Callable[[int], object]] = None,
        on_done: Callable = None,
        on_stage: Callable = None,
        on_skip: Callable = None,
        on_expired: Callable = None,
        on_crash: Callable = None,
        prewarm: bool = True,
    ):
        if n_devices < 1:
            raise ValueError(f"mesh pool needs >= 1 device, got {n_devices}")
        if dispatch not in ("affinity", "megabatch"):
            raise ValueError(f"mesh dispatch must be affinity|megabatch, got {dispatch!r}")
        self._n = n_devices
        self._depth = max(1, pipeline_depth)
        self._spill = max(1, spill_depth)
        # hard per-lane bound: queued + begun-not-finished. Above it the
        # submitter waits — backpressure flows to the admission queue.
        self._bound = self._spill + self._depth
        self._dispatch_mode = dispatch
        self._max_batch = max_batch
        self._backlog_k = max(0, backlog_k)
        # per-lane prefetch stage (PR 9): with a two-phase engine, the
        # lane runs the witness decode + advisory novelty pre-scan
        # (engine.prefetch_batch) before pack — on the lane thread, which
        # is exactly when the lane's PREVIOUS batch is computing on its
        # device (dispatch) or resolving, so the decode hides under them
        self._prefetch = prefetch and self._depth > 1
        if engine_factory is None:
            if engine is not None:
                engine_factory = lambda _i: engine
            else:
                engine_factory = _default_engine_factory
        self._engines = [engine_factory(i) for i in range(self._n)]
        # root lane: one pinned RootEngine per device, built LAZILY on the
        # first root batch a lane sees (construction may touch jax) and
        # only ever from its own lane thread — no lock needed
        self._root_factory = root_engine_factory or _default_root_engine_factory
        self._root_engines: List[Optional[object]] = [None] * self._n
        # sig lane: one pinned SigEngine per device, same lazy lane-thread
        # construction discipline as the root engines above
        self._sig_factory = sig_engine_factory or _default_sig_engine_factory
        self._sig_engines: List[Optional[object]] = [None] * self._n
        self._on_done = on_done or (lambda *a: None)
        self._on_stage = on_stage or (lambda *a: None)
        self._on_skip = on_skip or (lambda *a: None)
        self._on_expired = on_expired or (lambda *a: None)
        self._on_crash = on_crash or (lambda *a: None)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        # per-device state, all guarded by _lock
        self._queues: List[List[dict]] = [[] for _ in range(self._n)]
        self._inflight_n = [0] * self._n  # taken-but-unfinished batches
        self._dispatches = [0] * self._n
        self._served = [0] * self._n
        self._spills = 0
        self._megabatches = 0
        self._prefetched = 0
        self._closed = False
        self._dead: Optional[BaseException] = None
        self._mega_mesh = None  # memoized (mesh, ok) probe for megabatch
        self._threads = [
            threading.Thread(
                target=self._run_executor,
                args=(i,),
                name=f"phant-mesh-exec-{i}",
                daemon=True,
            )
            for i in range(self._n)
        ]
        for t in self._threads:
            t.start()
        metrics.gauge_set("sched.mesh_devices", self._n)
        # sharded executables the boot prewarm compiled (None: not run, or
        # failed — then counted in backend.device_fallbacks too)
        self.prewarm_compiled: Optional[int] = None
        self._prewarm_thread = threading.Thread(
            target=self._prewarm, name="phant-mesh-prewarm", daemon=True
        )
        if prewarm:
            self._prewarm_thread.start()

    # -- routing -------------------------------------------------------------

    def _load_locked(self, d: int) -> int:
        return len(self._queues[d]) + self._inflight_n[d]

    def submit(self, jobs: Sequence, batch_id: int, picked: float) -> Optional[int]:
        """Route one assembled same-bucket batch to a device lane; returns
        the device index, or None when the pool is dead (the caller raises
        SchedulerDown). Blocks while every lane is at its bound — the
        wait is exported as `sched.device_stall`, the mesh twin of
        `sched.pipeline_stall`."""
        bucket = jobs[0].bucket
        item = {"jobs": list(jobs), "batch_id": batch_id, "picked": picked}
        # immutable pool shape, read lock-free (write-once in __init__ —
        # the locked regions below only ever see these locals)
        n, spill, bound = self._n, self._spill, self._bound
        home = affinity_device(bucket, n)
        t0 = time.perf_counter()
        with self._lock:
            while True:
                if self._dead is not None:
                    return None
                d = home
                if self._load_locked(d) >= spill:
                    # home lane is backed up: spill to the least-loaded
                    # device (ties break on the lowest index — stable)
                    d = min(range(n), key=self._load_locked)
                if self._load_locked(d) < bound:
                    break
                self._cond.wait(0.05)
            if d != home:
                self._spills += 1
            self._queues[d].append(item)
            self._dispatches[d] += 1
            depth = len(self._queues[d])
            self._cond.notify_all()
        metrics.observe("sched.device_stall", time.perf_counter() - t0)
        metrics.count("sched.device_dispatch", device=str(d))
        metrics.gauge_set("sched.device_queue_depth", depth, device=str(d))
        return d

    # -- megabatch (whole-mesh fused dispatch) -------------------------------

    def backlog_wanted(self) -> bool:
        """Would `megabatch_wanted` ever read a backlog count? The
        scheduler's same-bucket backlog scan walks every queued job
        under the global lock — it must only run when the trigger can
        actually consume it (megabatch mode with k > 0), never on the
        default affinity hot path."""
        return self._dispatch_mode == "megabatch" and self._backlog_k > 0

    def megabatch_wanted(self, n_jobs: int, backlog: int = 0) -> str:
        """Should this single-bucket batch take the whole-mesh fused
        path? Returns a truthy REASON ("full" / "backlog") or "".

        * "full" — `megabatch` mode and the bucket FILLED the assembler
          (`max_batch` same-shape jobs at once): the pre-trigger
          behavior.
        * "backlog" — `backlog_k > 0` and the queued same-bucket work
          (this batch plus `backlog` still-queued same-bucket jobs) is
          >= mesh_width x k: sustained same-shape overload engages
          fusion WITHOUT the operator sizing max_batch
          (`--sched-megabatch-backlog-k`; counted by the scheduler in
          `sched.megabatch_backlog_triggers`)."""
        if self._dispatch_mode != "megabatch":
            return ""
        if n_jobs >= max(self._max_batch, self._n):
            return "full"
        if self._backlog_k > 0 and n_jobs + backlog >= self._n * self._backlog_k:
            return "backlog"
        return ""

    def _megabatch_mesh(self):
        """The whole-mesh Mesh for fused dispatch, probed once. Raises
        MegabatchUnsupported (memoized as failure) when jax cannot supply
        the devices or the mesh size is not a power of two (the fused
        pack pads node counts to powers of two; a non-pow2 mesh cannot
        evenly shard them)."""
        if self._mega_mesh is None:
            ok, mesh = False, None
            if self._n & (self._n - 1) == 0:
                try:
                    from phant_tpu.parallel.mesh import make_mesh

                    mesh = make_mesh(self._n)
                    ok = True
                except Exception:
                    log.warning(
                        "megabatch disabled: no %d-device mesh", self._n,
                        exc_info=True,
                    )
            self._mega_mesh = (ok, mesh)
        ok, mesh = self._mega_mesh
        if not ok:
            raise MegabatchUnsupported(f"no {self._n}-device mesh")
        return mesh

    def run_megabatch(self, jobs: Sequence, batch_id: int):
        """(verdicts, record): ONE device-sharded fused verification of the
        whole batch across the mesh (witness_verify_fused_sharded — cold
        path, no intern tables). Runs on the CALLER's thread: the dispatch
        occupies every device, so there is nothing to overlap with.
        Raises MegabatchUnsupported when this batch cannot take the fused
        path; the caller falls back to affinity routing."""
        mesh = self._megabatch_mesh()
        from phant_tpu.ops.witness_jax import (
            WITNESS_MAX_CHUNKS,
            _pow2ceil,
            pack_witness_fused,
            roots_to_words,
        )
        from phant_tpu.parallel.mesh import witness_verify_fused_sharded

        node_lists = [list(j.nodes) for j in jobs]
        try:
            blob, meta16 = pack_witness_fused(
                node_lists, WITNESS_MAX_CHUNKS, min_pad=self._n
            )
        except ValueError as e:
            # oversized node / uint16 overflow: the kernel cannot express
            # this batch — not an executor failure
            raise MegabatchUnsupported(str(e)) from None
        # pow2-pad the blob byte axis too, so repeat megabatches land on a
        # small set of compiled shapes (the AOT executable memo keys on
        # shape — an unpadded ragged blob would compile per batch)
        padded = np.zeros(_pow2ceil(len(blob)), np.uint8)
        padded[: len(blob)] = blob
        roots = roots_to_words([j.root for j in jobs])
        t0 = time.monotonic()
        out = witness_verify_fused_sharded(
            mesh,
            padded,
            meta16,
            roots,
            max_chunks=WITNESS_MAX_CHUNKS,
            n_blocks=len(jobs),
        )
        # the verdict readback is this batch's resolve — an honest sync
        # (HOSTSYNC's cross-module taint does not reach here; comment,
        # not a dead disable annotation)
        verdicts = np.asarray(out)
        with self._lock:
            self._megabatches += 1
            n_mega = self._megabatches
        metrics.count("sched.mesh_megabatches")
        metrics.count("sched.device_dispatch", device="mesh")
        record = {
            "batch_id": batch_id,
            "batch_size": len(jobs),
            "bucket_bytes": jobs[0].bucket,
            "stage": "dispatch",
            "backend": "mesh_fused",
            "device": "mesh",
            "mesh_devices": self._n,
            "resolve_ms": round((time.monotonic() - t0) * 1e3, 3),
        }
        log.debug("megabatch %d: %d blocks over %d devices", n_mega, len(jobs), self._n)
        return verdicts, record

    # -- per-device executor -------------------------------------------------

    def _live_jobs(self, item: dict) -> Optional[list]:
        """Deadline re-check at pickup time on the LANE: a batch can sit in
        a backed-up lane past its jobs' deadlines, and an expired job must
        shed (its waiter is gone) rather than spend engine work — the same
        contract as the scheduler's post-slot-wait re-check."""
        now = time.monotonic()
        live = [j for j in item["jobs"] if not deadline_clock.passed(j.deadline, now)]
        if len(live) != len(item["jobs"]):
            kept = set(map(id, live))
            for j in item["jobs"]:
                if id(j) not in kept:
                    self._on_expired(j)
        return live or None

    def _root_engine_for(self, i: int):
        """The lane's pinned RootEngine, built lazily on its first root
        batch (only ever touched from lane thread `i`)."""
        eng = self._root_engines[i]
        if eng is None:
            eng = self._root_engines[i] = self._root_factory(i)
        return eng

    def _sig_engine_for(self, i: int):
        """The lane's pinned SigEngine, built lazily on its first sig
        batch (only ever touched from lane thread `i`)."""
        eng = self._sig_engines[i]
        if eng is None:
            eng = self._sig_engines[i] = self._sig_factory(i)
        return eng

    def _run_executor(self, i: int) -> None:
        deadline_clock.serving_thread()
        engine = self._engines[i]
        # immutable pipeline depth, read lock-free (write-once in __init__)
        depth_cap = self._depth
        two_phase = depth_cap > 1 and hasattr(engine, "begin_batch")
        # [(item, handle, engine)] begun, unresolved — a root batch's
        # handle belongs to the lane's RootEngine, a witness batch's to
        # the lane's WitnessEngine; crash paths abandon each against ITS
        # engine
        inflight: List[tuple] = []
        cur: Optional[dict] = None
        stage = "pack"
        try:
            while True:
                item = None
                with self._lock:
                    while True:
                        if self._dead is not None:
                            # carry the crash out of the locked region
                            raise _PoolDead(self._dead)
                        if self._queues[i] and (
                            not two_phase or len(inflight) < depth_cap
                        ):
                            item = self._queues[i].pop(0)
                            self._inflight_n[i] += 1
                            break
                        if inflight:
                            break  # nothing takeable: drain own pipeline
                        if self._closed:
                            return
                        self._cond.wait(0.1)
                    depth = len(self._queues[i])
                    self._cond.notify_all()  # a slot freed: wake submitters
                metrics.gauge_set(
                    "sched.device_queue_depth", depth, device=str(i)
                )
                if item is not None:
                    jobs = self._live_jobs(item)
                    if jobs is None:
                        self._finish_accounting(i)
                        self._on_skip(item["batch_id"])
                        continue
                    item["jobs"] = jobs
                    # lazy import like every scheduler symbol here (the
                    # package-cycle discipline, see _abandon)
                    from phant_tpu.serving.scheduler import _ROOT, _SIG

                    is_root = jobs[0].kind == _ROOT
                    is_sig = jobs[0].kind == _SIG
                    if is_root:
                        eng = self._root_engine_for(i)
                    elif is_sig:
                        eng = self._sig_engine_for(i)
                    else:
                        eng = engine
                    cur, stage = item, "pack"
                    if two_phase or ((is_root or is_sig) and depth_cap > 1):
                        # the SAME payload list goes to prefetch and
                        # begin: plan identity is the engine's match check
                        # (witness tuples / root HashPlans / SigRows alike)
                        if is_root:
                            wits = [j.plan for j in jobs]
                        elif is_sig:
                            wits = [j.rows for j in jobs]
                        else:
                            wits = [(j.root, j.nodes) for j in jobs]
                        plan = None
                        pf = getattr(eng, "prefetch_batch", None)
                        if self._prefetch and pf is not None:
                            stage = "prefetch"
                            self._on_stage(item["batch_id"], "prefetch", i)
                            t0 = time.perf_counter()
                            with lane_stage(
                                item.setdefault("stages", {}),
                                "prefetch",
                                [j.trace_id for j in jobs],
                                item["batch_id"],
                            ):
                                plan = pf(wits)
                            item["prefetch_ms"] = round(
                                (time.perf_counter() - t0) * 1e3, 3
                            )
                            metrics.count("sched.prefetch_batches")
                            with self._lock:
                                self._prefetched += 1
                        stage = "pack"
                        self._on_stage(item["batch_id"], "pack", i)
                        t0 = time.perf_counter()
                        try:
                            with lane_stage(
                                item.setdefault("stages", {}),
                                "pack",
                                [j.trace_id for j in jobs],
                                item["batch_id"],
                            ):
                                if plan is not None:
                                    handle = eng.begin_batch(
                                        wits, prefetch=plan
                                    )
                                else:
                                    handle = eng.begin_batch(wits)
                        except BaseException:
                            # a lane death here reaches _die, which never
                            # sees lane-local plans: return the staging
                            # leases before propagating (idempotent; a
                            # consumed/released plan is a no-op)
                            if plan is not None:
                                plan.release()
                            raise
                        item["pack_ms"] = round(
                            (time.perf_counter() - t0) * 1e3, 3
                        )
                        inflight.append((item, handle, eng))
                        stage = "dispatch"
                        self._on_stage(item["batch_id"], "dispatch", i)
                        cur = None
                        with self._lock:
                            more = bool(self._queues[i]) and len(inflight) < depth_cap
                        if more:
                            # overlap: begin the NEXT batch while this
                            # one's device dispatch computes
                            continue
                    else:
                        stage = "dispatch"
                        self._on_stage(item["batch_id"], "dispatch", i)
                        if is_root:
                            verdicts, record = self._roots_inline(eng, item)
                        elif is_sig:
                            verdicts, record = self._sigs_inline(eng, item)
                        else:
                            verdicts, record = self._verify_inline(eng, item)
                        cur = None
                        self._finish(i, item, verdicts, record)
                        continue
                if inflight:
                    item2, handle, eng2 = inflight.pop(0)
                    cur, stage = item2, "resolve"
                    self._on_stage(item2["batch_id"], "resolve", i)
                    t0 = time.monotonic()
                    with lane_stage(
                        item2.setdefault("stages", {}),
                        "resolve",
                        [j.trace_id for j in item2["jobs"]],
                        item2["batch_id"],
                    ):
                        verdicts = eng2.resolve_batch(handle)
                    record = self._record_from_handle(handle, item2)
                    record["resolve_ms"] = round(
                        (time.monotonic() - t0) * 1e3, 3
                    )
                    cur = None
                    self._finish(i, item2, verdicts, record)
        except _PoolDead as dead:
            # another lane crashed: abandon this lane's handles (the
            # engines outlive the pool — leases must not leak) and fail the
            # begun-but-unresolved jobs nobody else knows about
            self._cleanup_inflight(inflight, dead.args[0])
            return
        except BaseException as e:  # systemic: this lane crashed
            for it, h, hg in inflight:
                _abandon(hg, h)
                if it is not cur:
                    self._fail_jobs(it["jobs"], e)
            # the crashing batch's jobs ride to scheduler._die via
            # on_crash (it fails their futures with the crash record)
            self._on_crash(e, cur["jobs"] if cur else [], stage, i)

    def _cleanup_inflight(self, inflight, exc) -> None:
        for it, h, hg in inflight:
            _abandon(hg, h)
            self._fail_jobs(it["jobs"], exc)

    def _fail_jobs(self, jobs, exc) -> None:
        from phant_tpu.serving.scheduler import SchedulerDown

        for j in jobs:
            if not j.future.done():
                try:
                    j.future.set_exception(
                        SchedulerDown(f"mesh executor crashed: {exc!r}")
                    )
                except Exception:
                    pass  # lost the race to another failure path

    def _finish_accounting(self, i: int) -> None:
        with self._lock:
            self._inflight_n[i] -= 1
            self._cond.notify_all()

    def _finish(self, i: int, item: dict, verdicts, record: dict) -> None:
        record["device"] = i
        # stage timings measured on the lane thread ride the record so
        # the timeline's batch sub-slices (and critpath's tiling) see
        # the mesh path too — prefetch_ms used to be dropped here
        for key in ("pack_ms", "prefetch_ms"):
            if key in item:
                record.setdefault(key, item[key])
        if item.get("stages"):
            fold_stages(record, item["stages"])
        with self._lock:
            self._inflight_n[i] -= 1
            self._served[i] += 1
            self._cond.notify_all()
        self._on_done(item["jobs"], verdicts, record, item["picked"], item["batch_id"])

    @staticmethod
    def _verify_inline(engine, item: dict):
        """Depth-1 (or no-begin_batch engine) lane execution: one fused
        verify_batch round trip, record from the engine-stats delta —
        sound per lane because each lane is its engine's only caller.
        The record builders are the SCHEDULER's (lazy import): record
        semantics must be identical at every depth and lane."""
        from phant_tpu.serving.scheduler import batch_record_from_stats

        jobs = item["jobs"]
        s0 = _engine_stats(engine)
        verdicts = engine.verify_batch([(j.root, j.nodes) for j in jobs])
        s1 = _engine_stats(engine)
        record = batch_record_from_stats(
            item["batch_id"], len(jobs), jobs[0].bucket, s0, s1
        )
        return verdicts, record

    @staticmethod
    def _lane_inline(engine, item: dict, payload, record_builder):
        """Depth-1 root/sig-lane execution: one fused begin+resolve
        against the lane's pinned engine (the root_many/sig_many shape)
        — one definition for both lanes; the callers supply the payload
        list and the scheduler's record builder."""
        jobs = item["jobs"]
        handle = engine.begin_batch(payload)
        results = engine.resolve_batch(handle)
        record = record_builder(
            handle, item["batch_id"], len(jobs), jobs[0].bucket
        )
        record["stage"] = "dispatch"
        return results, record

    def _roots_inline(self, engine, item: dict):
        from phant_tpu.serving.scheduler import root_record_from_handle

        return self._lane_inline(
            engine,
            item,
            [j.plan for j in item["jobs"]],
            root_record_from_handle,
        )

    def _sigs_inline(self, engine, item: dict):
        from phant_tpu.serving.scheduler import sig_record_from_handle

        return self._lane_inline(
            engine,
            item,
            [j.rows for j in item["jobs"]],
            sig_record_from_handle,
        )

    @staticmethod
    def _record_from_handle(handle, item: dict) -> dict:
        from phant_tpu.serving.scheduler import (
            _ROOT,
            _SIG,
            batch_record_from_handle,
            root_record_from_handle,
            sig_record_from_handle,
        )

        jobs = item["jobs"]
        if jobs and jobs[0].kind == _ROOT:
            builder = root_record_from_handle
        elif jobs and jobs[0].kind == _SIG:
            builder = sig_record_from_handle
        else:
            builder = batch_record_from_handle
        record = builder(handle, item["batch_id"], len(jobs), jobs[0].bucket)
        if "prefetch_ms" in item:
            record["prefetch_ms"] = item["prefetch_ms"]
        return record

    # -- lifecycle -----------------------------------------------------------

    def _prewarm(self) -> None:
        """Background boot prewarm: compile the sharded serving executables
        once (parallel/mesh.py prewarm_sharded) when the device backend is
        live, so no serving batch pays a cold shard_map compile — and the
        compile-cache suspension windows all fire before traffic."""
        from phant_tpu.backend import crypto_backend, device_fallback, jax_device_ok

        if crypto_backend() != "tpu" or not jax_device_ok():
            return
        try:
            from phant_tpu.parallel.mesh import make_mesh, prewarm_sharded

            self.prewarm_compiled = prewarm_sharded(make_mesh(self._n))
            log.info(
                "mesh prewarm: %d sharded executables compiled", self.prewarm_compiled
            )
        except Exception:
            # prewarm is an optimization, never a liveness dependency — but
            # a failure is counted where an operator (and chip_smoke.py,
            # which requires zero) sees it: the first sharded batch will
            # meet the same compiler
            device_fallback("mesh_prewarm")
            log.warning("mesh prewarm failed", exc_info=True)

    def drain(self) -> None:
        """Block until every lane is idle (queues empty, nothing begun and
        unresolved) or the pool is dead — the serial mutation lane's
        exclusivity barrier and the graceful-shutdown wait."""
        n = self._n
        with self._lock:
            while self._dead is None and (
                any(self._queues[d] or self._inflight_n[d] for d in range(n))
            ):
                self._cond.wait(0.05)

    def kill(self, exc: BaseException) -> int:
        """Mark the pool dead (scheduler `_die`): queued-but-unbegun
        batches fail fast here; each lane thread abandons its OWN begun
        handles and fails their jobs when it observes the death. Returns
        how many queued jobs were failed fast. Idempotent."""
        with self._lock:
            if self._dead is None:
                self._dead = exc
            dropped: List[dict] = []
            for q in self._queues:
                dropped.extend(q)
                q.clear()
            self._cond.notify_all()
        n = 0
        for item in dropped:
            self._fail_jobs(item["jobs"], exc)
            n += len(item["jobs"])
        for d in range(self._n):
            metrics.gauge_set("sched.device_queue_depth", 0, device=str(d))
        return n

    def shutdown(self, timeout: float = 30.0) -> None:
        """Stop the lanes after the queues drain; `drain()` first for a
        graceful stop (the scheduler's shutdown path does)."""
        with self._lock:
            self._closed = True
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout)

    # -- introspection -------------------------------------------------------

    def alive(self) -> bool:
        with self._lock:
            dead = self._dead
        return dead is None and all(t.is_alive() for t in self._threads)

    def state(self) -> dict:
        """Per-device liveness + load for `/healthz` (the scheduler embeds
        this under `scheduler.mesh`)."""
        # thread liveness and the pool shape are lock-free reads (threads
        # list is write-once; is_alive is the interpreter's own state)
        alive_list = [t.is_alive() for t in self._threads]
        n = self._n
        with self._lock:
            per_device = {
                str(d): {
                    "alive": alive_list[d],
                    "queued": len(self._queues[d]),
                    "inflight": self._inflight_n[d],
                    "dispatches": self._dispatches[d],
                }
                for d in range(n)
            }
            dead = self._dead
        out = {
            "devices": n,
            "dispatch": self._dispatch_mode,
            "prefetch": self._prefetch,
            "all_alive": dead is None and all(alive_list),
            "per_device": per_device,
        }
        if dead is not None:
            out["error"] = repr(dead)
        return out

    def stats(self) -> dict:
        n = self._n
        with self._lock:
            return {
                "devices": n,
                "dispatches": list(self._dispatches),
                "served": list(self._served),
                "spills": self._spills,
                "megabatches": self._megabatches,
                "prefetched_batches": self._prefetched,
            }

    def engines(self) -> list:
        """The per-lane engines (tests assert lease accounting on them)."""
        return list(self._engines)

    def lane_engines(self, kind: str = "witness") -> list:
        """Per-lane engine snapshot by lane kind: "witness" = the pinned
        WitnessEngines (always built), "root"/"sig" = the lazily-built
        pinned RootEngines/SigEngines with None for lanes whose first
        batch of that kind hasn't arrived. Replay's mesh fan-out test
        reads this to assert per-lane RESIDENT intern tables — segments
        sharded across lanes must populate each lane's own engine, not
        funnel through a shared one."""
        if kind == "witness":
            return list(self._engines)
        if kind == "root":
            return list(self._root_engines)
        if kind == "sig":
            return list(self._sig_engines)
        raise ValueError(f"unknown lane kind {kind!r}")
