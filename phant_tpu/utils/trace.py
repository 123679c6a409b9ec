"""Tracing, metrics, and profiling.

The reference's only observability is scoped debug logging (reference:
std.log.scoped(.evmone)/(.vm) at src/blockchain/vm.zig:25,130 and the
startup banner at src/main.zig:116-118); evmone's tracer is compiled but
never installed (reference: build.zig:118). This framework upgrades that
slot (SURVEY §5) to:

- `phase(name)` — nestable wall-clock timers aggregated into a process
  metrics registry (count / total / min / max per phase),
- `metrics` — counters (optionally labeled), gauges, fixed-bucket latency
  histograms, and phase timers, with a `report()` table, a deep-copied
  `snapshot()`, and a `prometheus_text()` standard text exposition,
- `span(name, **attrs)` — a thread-safe (thread-local-stacked) per-block
  span tracer: every top-level span emits ONE structured-JSON log line
  carrying its duration, its nested phase timings, and any child spans,
- `trace_context(trace_id)` / `current_trace_id()` — a per-thread request
  identity: the Engine API server opens a context per POST and every span
  opened inside it (on that thread) carries the `trace_id`, so a request's
  span record stays joinable to the scheduler batch that served it even
  after coalescing (phant_tpu/obs/ holds the flight-recorder side),
- `add_span_sink(fn)` — top-level span records additionally fan out to
  registered sinks (the obs flight recorder registers one),
- `jax_profile(logdir)` — a context manager around the JAX profiler for
  device traces of the TPU kernels,
- `scoped_logger(scope)` — the reference's scoped-logger idiom.

Prometheus naming: internal metric names are dotted ("engine_api.requests");
the exposition sanitizes them to `phant_[a-z0-9_]+` families (counters gain
a `_total` suffix, phase timers a `_seconds` summary suffix). Every exported
family must have an entry in METRIC_HELP — `make metrics-lint` enforces it.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import json
import logging
import os
import re
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple


def scoped_logger(scope: str) -> logging.Logger:
    """(reference: std.log.scoped, e.g. src/blockchain/vm.zig:25)"""
    return logging.getLogger(f"phant_tpu.{scope}")


@dataclass
class TimerStat:
    count: int = 0
    total_s: float = 0.0
    min_s: float = float("inf")
    max_s: float = 0.0

    def add(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        self.min_s = min(self.min_s, dt)
        self.max_s = max(self.max_s, dt)

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0


#: default latency buckets (seconds) — sub-ms kernel dispatches up through
#: multi-second stateless executions
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)

#: THE request-latency bucket table (engine_api.request_seconds and any
#: future front-door latency family): one module-level constant so call
#: sites can never drift apart on bucket bounds — a histogram's buckets
#: are frozen at first observation, so two call sites with different
#: tables would silently split the family. Extends DEFAULT_BUCKETS with
#: an overload tail (PR 6's open-loop sweeps measured 15s p99s before
#: the stateless gate existed): without buckets past 10s, the derived
#: p99 gauge clamps to the last finite bound exactly when an operator
#: most needs it.
REQUEST_SECONDS_BUCKETS: Tuple[float, ...] = DEFAULT_BUCKETS + (30.0, 60.0)


def histogram_quantile(
    buckets: Sequence[float], counts: Sequence[int], q: float
) -> float:
    """Bucket-interpolated quantile over a fixed-bucket histogram (the
    Prometheus `histogram_quantile` estimate, computed server-side so a
    curl of /metrics answers "what's p99" without a PromQL engine).

    `counts[i]` is the NON-cumulative count for bucket upper bound
    `buckets[i]`, with `counts[-1]` the +Inf overflow slot — the
    Histogram dataclass layout. Linear interpolation inside the target
    bucket (lower bound 0 for the first); a target landing in the +Inf
    slot clamps to the last finite bound (same behavior as PromQL —
    the estimate is a floor there, which is why the exposition also
    carries the exact `_sum`/`_count`). Returns 0.0 for an empty
    histogram. An ESTIMATE by construction: resolution is the bucket
    width around the target rank, never exact order statistics."""
    total = sum(counts)
    if total <= 0:
        return 0.0
    rank = q * total
    cum = 0.0
    for i, ub in enumerate(buckets):
        prev_cum = cum
        cum += counts[i]
        if cum >= rank:
            lo = buckets[i - 1] if i > 0 else 0.0
            if counts[i] <= 0:
                return float(ub)
            frac = (rank - prev_cum) / counts[i]
            return float(lo + (ub - lo) * min(max(frac, 0.0), 1.0))
    # target rank lives in the +Inf slot: clamp to the last finite bound
    return float(buckets[-1]) if buckets else 0.0


@dataclass
class Histogram:
    """Fixed-bucket histogram: cumulative-style exposition is derived at
    render time; `counts[i]` is the count for bucket upper bound
    `buckets[i]`, with one extra slot for +Inf."""

    buckets: Tuple[float, ...] = DEFAULT_BUCKETS
    counts: List[int] = field(default_factory=list)
    sum: float = 0.0
    count: int = 0

    def __post_init__(self) -> None:
        if not self.counts:
            self.counts = [0] * (len(self.buckets) + 1)

    def add(self, value: float) -> None:
        self.sum += value
        self.count += 1
        for i, ub in enumerate(self.buckets):
            if value <= ub:
                self.counts[i] += 1
                return
        self.counts[-1] += 1


def _labels_key(name: str, labels: dict) -> str:
    """Composite storage key `name{k="v",...}` with sorted label names —
    one flat dict keeps snapshot() trivially JSON-able."""
    if not labels:
        return name
    inner = ",".join(f'{k}="{labels[k]}"' for k in sorted(labels))
    return f"{name}{{{inner}}}"


def split_labels(key: str) -> Tuple[str, str]:
    """Inverse of _labels_key: ("name", 'k="v",...') — label part empty
    for unlabeled metrics."""
    if key.endswith("}") and "{" in key:
        name, _, rest = key.partition("{")
        return name, rest[:-1]
    return key, ""


def prometheus_name(name: str) -> str:
    """Sanitize a dotted internal name to a `phant_[a-z0-9_]+` family."""
    s = re.sub(r"[^a-zA-Z0-9_]", "_", name).lower()
    return s if s.startswith("phant_") else "phant_" + s


#: help strings for every exported metric family, keyed by INTERNAL base
#: name (pre-sanitization, no labels). `make metrics-lint` fails the build
#: when an exported family has no entry here — metric-name drift is caught
#: at test time, not on a dashboard.
METRIC_HELP: Dict[str, str] = {
    # engine API server
    "engine_api.requests": "Engine API JSON-RPC requests by method",
    "engine_api.unknown_method": "Engine API requests for unknown methods (one bucket: untrusted strings)",
    "engine_api.request_errors": "Engine API requests answered with a JSON-RPC error or HTTP >= 400",
    "engine_api.client_disconnects": "Engine API responses aborted by client disconnect (BrokenPipe/ConnectionReset)",
    "engine_api.inflight": "Engine API requests currently being handled",
    "engine_api.request_seconds": "Engine API request latency (decode + handle + reply)",
    "engine_api.phase_seconds": "The front end's own share of a POST, by phase, timed where the work happens (engine_api/server.py): read = headers parsed -> body read; json = json.loads; gate = the wait for a slot of the stateless gate; decode = payload and witness hex/RLP decode and the block-hash check, up to where verify_block opens; reply = result -> bytes -> written. With verify_block's wall clock they tile engine_api.request_seconds",
    "engine_api.request_body_bytes": "Bytes of the POST bodies the front end read (Content-Length as received, counted in engine_api/server.py where the body is read): what the read, json and decode phases of engine_api.phase_seconds worked on; a block with its witness in hex JSON is 1.0 MB at 225 transactions and 5.9 MB at the gas limit",
    "engine_api.phase_cpu_seconds": "CPU seconds the handler thread ran inside each front-end phase of a POST (the thread's CPU clock read at the marks of engine_api.phase_seconds, one observation a phase a request, never above the phase's wall: what a reading of a clock that steps by ticks holds beyond it is booked against the series' next observations, Metrics.observe_split)",
    "engine_api.phase_offcpu_seconds": "engine_api.phase_seconds less engine_api.phase_cpu_seconds, phase by phase: what the handler thread waited inside the phase. In json, decode and reply it waits for nothing by design, so that is its wait for a turn at the interpreter lock; read waits for the socket, gate for a slot",
    "engine_api.decode_payload": "JSON -> ExecutionPayload decode phase",
    "engine_api.new_payload": "engine_newPayloadV2/V3/V4 handler phase",
    "engine_api.execute_stateless": "engine_executeStatelessPayloadV1 handler phase",
    # stateless execution
    "stateless.blocks_verified": "Stateless payloads fully executed and root-checked",
    "stateless.errors": "Stateless executions aborted, by exception kind",
    "stateless.witness_verify": "The handler's waits for the linked-multiproof witness verdict: one inline verification without a scheduler; with one, the wait for its batch's launch and the wait at the join, the decode between them",
    "stateless.witness_decode": "Witness -> WitnessStateDB materialization phase (with a scheduler it runs while the verdict is computed, before the join)",
    "stateless.verdict_joins": "Witness verdicts joined before execution, by state: ready = the verdict was there when the handler came to join after decoding, waited = the handler still had to wait for it",
    "stateless.decode_hidden_seconds": "Seconds of a request's witness_decode that ran before its verdict arrived: host work hidden under the witness lane's device time",
    "stateless.witness_nodes_decoded": "Witness nodes decoded (digest map built) on the request path — exactly once per payload; a doubled count per payload is a reintroduced second decode",
    "stateless.execute": "Block execution phase over the witness-backed state",
    "stateless.post_root": "Post-state-root recompute phase over the partial trie (host walk or the batched root lane)",
    "stateless.post_root_plan": "Fused account+storage hash-plan build on the request thread (WitnessStateDB.post_root_plan) before root-lane submission",
    "stateless.sig_rows": "Signature-row build on the request thread (TxSigner.signature_rows — host keccak over RLP) before sig-lane submission",
    # memoized witness engine
    "witness_engine.interned_digests": "Unique 32-byte digests currently interned (nodes + child refs)",
    "witness_engine.cache_hits": "Witness nodes served from the interning cache",
    "witness_engine.cache_misses": "Witness nodes that had to be hashed (novel nodes)",
    "witness_engine.evictions": "Generation flushes of the interned set (max_nodes crossed), by tier: deep = shallow pins retained, only deeper tiers evicted; full = everything dropped; twin = python-twin-only flush on a C-core engine",
    "witness_engine.novel_bytes_hashed": "Bytes of novel witness nodes hashed",
    "witness_engine.verify_batch": "Whole verify_batch calls (scan + hash + linkage)",
    "witness_engine.intern": "Interning/scan phase of verify_batch (cache probe + table insert)",
    "witness_engine.hash": "Novel-node keccak phase of verify_batch (includes the C-side commit+join on the finish_native fast path)",
    "witness_engine.linkage_join": "Parent->child linkage join / verdict phase of verify_batch",
    # pipelined two-phase engine API (begin_batch/resolve_batch)
    "witness_engine.prefetch": "Prefetch stage: witness decode + advisory novelty pre-scan + staging pre-fill for the NEXT batch, off the serving critical path (prefetch_batch)",
    "witness_engine.prefetch_plan_hits": "Prefetch plans whose candidate-novel set the authoritative pack-time scan confirmed (staging leases reused)",
    "witness_engine.prefetch_plan_stale": "Prefetch plans dropped stale at pack time (concurrent commit / generation flush) — a perf miss, never a correctness event",
    "witness_engine.pack": "Pack stage: host batch assembly + lock-held intern-table scan (begin_batch); with a prefetch plan, the under-lock re-check + commit only",
    "witness_engine.dispatch": "Dispatch stage: device keccak enqueue of the novel nodes, no host sync (begin_batch)",
    "witness_engine.resolve": "Resolve stage: digest readback/hash outside the lock + commit + linkage join (resolve_batch)",
    # cache_hit_rate vs trie_depth (PHANT_DEPTH_HIST=1): per-depth scan
    # outcome, labels "0".."6", "7+", "u" (unreachable from the root)
    "witness_engine.depth_hits": "Witness-node cache hits by trie depth under the block root (depth-skewed reuse, PAPERS.md 2408.14217)",
    "witness_engine.depth_misses": "Witness-node cache misses (novel nodes) by trie depth under the block root",
    # batched post-state roots (ops/root_engine.py)
    "witness_engine.root_prefetch": "Root-lane prefetch stage: merging a batch's HashPlans into the pooled staging blob OFF the serving critical path (RootEngine.prefetch_batch)",
    "witness_engine.root_pack": "Root-lane pack stage: offload-gate routing + plan merge (or prefetch-merge consumption) (RootEngine.begin_batch)",
    "witness_engine.root_dispatch": "Root-lane dispatch stage: merged-program device enqueue, no host sync",
    "witness_engine.root_resolve": "Root-lane resolve stage: out-row digest readback (device) or the per-plan host mirror",
    "witness_engine.root_batches": "Root batches executed, by backend (device = merged dispatch; host = the offload-gated host walk)",
    "root.plan_shapes": "Distinct shapes of the served root program (ops/mpt_jax._hash_plan_outputs) this process has run: one a rung of mpt_jax.PLAN_LADDER and device, so it stops growing; exported from server start",
    "root.plan_rows": "Rows hashed by served root programs, by kind: real = trie nodes of the merged plans, pad = the empty rows of their strips (what the ladder's fixed strip costs in keccak work)",
    "root.plan_rung": "Root batches by the ladder rung their merged plan was laid out on (over = above the top rung: hashed on the host)",
    "mpt.node_encodings": "Trie nodes encoded, by the encoder that did it: native = the extension's node encoder (native/pyext.cc), python = the fallback where the process runs without the extension; counted once a root computation (a host walk's root_hash, a hash plan's finish) with the nodes it encoded",
    "mpt.ref_hashes": "keccak-256 digests a host walk computed for the trie nodes it encoded, by the encoder that did it (native | python): tallied in the walk and counted once a root computation (Trie.root_hash) beside mpt.node_encodings. A node is hashed once, where its entry of the trie's memo (structure, encoding, reference) is built, and a clean child's reference is read from there: at most one digest a node encoded, so a ratio to mpt.node_encodings above 1 means clean siblings are hashed again. A walk that raises part-way books neither counter, and the entries it had built stay in the memo, so the next root does not count them either: the counters then read low by the same nodes, the roots are right",
    "evm.native_frames": "Frames of bytecode the native VM (native/evm.cc) ran, nested ones too, by the host binding they went through: ext = the extension's EvmHost (native/pyext.cc), the only one there is; counted when a block's binding is closed (evm/native_vm.BlockHost.close). A plain transfer runs no frame; under the Python interpreter (no toolchain, --evm_backend=python) the family stands still",
    "evm.host_bindings": "Host bindings of the native VM built: one a block whose transactions reach code (Blockchain.run_block), one a message for an Evm outside a block",
    "root.prewarm_seconds": "Seconds the server took at start to build the root program on every rung of the ladder (only with the device root lane on and an accelerator under it)",
    # coalesced sender recovery (ops/sig_engine.py)
    "witness_engine.sig_prefetch": "Sig-lane prefetch stage: merging a batch's signature rows + the u256 -> limb encode OFF the serving critical path (SigEngine.prefetch_batch)",
    "witness_engine.sig_pack": "Sig-lane pack stage: offload-gate routing + row merge (or prefetch-merge consumption) (SigEngine.begin_batch)",
    "witness_engine.sig_dispatch": "Sig-lane dispatch stage: merged ecrecover kernel enqueue, no host sync",
    "witness_engine.sig_resolve": "Sig-lane resolve stage: sender-address readback (device) or the fused native batch / scalar fallback over the same merged rows",
    "witness_engine.sig_batches": "Sig batches executed, by backend (device = merged ecrecover dispatch; native/scalar = the offload-gated host routes)",
    # device-resident intern table (ops/witness_resident.py)
    "witness_resident.rows": "Rows resident on device (digest + child-ref rows, persistent across batches)",
    "witness_resident.uploaded_nodes": "Truly-novel nodes uploaded to the resident table (after the host prune)",
    "witness_resident.uploaded_bytes": "Truly-novel bytes uploaded to the resident table — the ONLY recurring h2d payload of the resident route",
    "witness_resident.update_rows": "Rows of the batches handed to the resident update program, by kind: real = novel nodes, pad = the zero rows up to the launch's rung of witness_resident.ROW_LADDER (hashed and dropped)",
    "witness_resident.update_bytes": "Bytes the resident update's row form uploads, by kind: payload = what the novel nodes hold, pad = the zeros that fill each row to 680 bytes and the pad rows",
    "witness_resident.verdict_rows": "Node rows of the launches of the resident verdict program, by kind: real = witness nodes, pad = the empty rows up to the launch's rung of witness_resident.VERDICT_LADDER",
    # the shapes served device programs run on (utils/rungs.py)
    "lanes.program_shapes": "Distinct shapes each served device program has run on in this process, by program (ecrecover: device and signature rung; verdict, gather, update: device, table rows and rung): a server on an accelerator builds every rung of the table's ladders before its port answers and the first request ecrecover's one, so it stops there; exported from server start",
    "lanes.launches": "Launches of served device programs by program and rung (verdict: rows x blocks), the boot's own among them; a rung that first appears after server start was built inside a request",
    "lanes.split_launches": "Launches of waves above a ladder's top rung, which go out as several launches of the top rung, by program",
    "lanes.oversize_launches": "Launches outside a ladder, by program: one block of more witness nodes than the verdict ladder's lone-block rung holds (16,384: a gas-limit block of plain transfers fits, one of 14,285 cold storage reads does not) keeps a shape of its own, built inside the request",
    "lanes.prewarm_seconds": "Seconds the server's constructor took to build the resident table's update, verdict and gather programs on every rung of their ladders (only with an accelerator under it)",
    "sig.rows": "Signature rows of the launches of the ecrecover kernel, by kind: real = signatures, pad = the filler rows up to the launch's rung of secp256k1_jax.SIG_LADDER",
    "witness_resident.dispatch": "Resident dispatch phase: prune + row assignment + update/verdict enqueue, no host sync",
    "witness_resident.resolve": "Resident resolve phase: verdict (1 B/block) + core-novel digest readback (the honest sync)",
    # continuous-batching scheduler (phant_tpu/serving/)
    "sched.queue_depth": "Verification requests currently in the scheduler admission queue (all lanes)",
    "sched.tenant_queue_depth": "Witness requests currently queued, by tenant lane",
    "sched.batch_size": "Assembled witness-batch sizes (requests per engine dispatch)",
    "sched.batch_blocks": "Distinct blocks a witness batch, by pre-state root: 1 for a wave of one head's copies, the batch's size for a wave of different blocks",
    "sched.queue_wait_seconds": "Admission-to-execution wait per scheduled request",
    "sched.coalesced_requests": "Requests that shared an engine batch with at least one other request",
    "sched.rejected": "Overload rejections by reason (queue_full/tenant_quota/evicted/saturated/deadline/down/shutdown) and tenant",
    "sched.tenant_served": "Requests completed by the scheduler, by tenant (the no-starvation progress counter)",
    "sched.tenant_wait_seconds": "A witness job's wait from admission to the start of its batch's first lane stage (the interval critpath calls queue_wait), by tenant lane; observed where sched.tenant_served is counted, label set bounded by max_tenants",
    "sched.backfill_evictions": "Witness jobs evicted to admit head-of-chain work (backfill first; head-class witness only for a serial mutation), by shed tenant",
    "sched.adaptive_wait_ms": "Current adaptive batching wait chosen by the queue-depth policy (serving/qos.py)",
    "sched.adaptive_wait_adjustments": "Times the adaptive policy changed the assembly wait (shrink under load, widen when idle)",
    "sched.batches": "Scheduler executions by lane (witness batches / serial jobs)",
    "sched.padding_waste": "Unused fraction of the padded device buffer the last witness batch would occupy",
    "sched.executor_crashes": "Scheduler executor crashes (scheduler marked down, /healthz -> 503)",
    "sched.pipeline_depth": "Configured pipeline depth (1 = serialized pack/dispatch/resolve, the pre-pipeline behavior)",
    "sched.pipeline_inflight": "Witness batches currently between begin_batch and resolve_batch",
    "sched.pipeline_stall": "Executor waits for a free pipeline slot (resolve stage is the bottleneck)",
    # 4th pipeline stage: the prefetch worker (PR 9)
    "sched.prefetch_batches": "Witness batches whose decode + novelty pre-scan ran on the prefetch stage (scheduler worker or mesh lane) before pack",
    "sched.prefetch_wait": "Executor waits for a batch's prefetch plan — prefetch cost that did NOT hide under dispatch/resolve (the overlap audit against the witness_engine.prefetch phase)",
    "sched.prefetch_depth": "Assembled witness batches currently waiting on the prefetch worker (the lookahead occupancy)",
    # root lane (batched post-state roots, serving/scheduler.py)
    "sched.root_batches": "Root-lane batches executed by the scheduler, by backend (device/host per the offload gate)",
    "sched.root_coalesced": "Root-lane requests that shared a coalesced root dispatch with at least one other request",
    # sig lane (coalesced sender recovery, serving/scheduler.py)
    "sched.sig_batches": "Sig-lane batches executed by the scheduler, by backend (device/native/scalar per the offload gate)",
    "sched.sig_coalesced": "Sig-lane requests that shared a merged ecrecover dispatch with at least one other request",
    "sched.sig_wait": "Request thread blocks joining its sig-lane senders at execute time — recovery cost that did NOT hide under witness verification (the overlap audit against the witness_engine.sig_* phases)",
    # mesh-sharded dispatch (phant_tpu/serving/mesh_exec.py)
    "sched.mesh_devices": "Device lanes in the mesh executor pool (--sched-mesh)",
    "sched.device_queue_depth": "Witness batches queued on a mesh device lane, by device",
    "sched.device_dispatch": "Witness batches routed to a mesh device lane (device='mesh' = whole-mesh megabatch), by device",
    "sched.device_stall": "Scheduler waits for a free mesh lane slot (every device at its bound)",
    "sched.mesh_megabatches": "Full single-bucket batches dispatched as one whole-mesh sharded fused kernel call",
    "sched.megabatch_backlog_triggers": "Megabatches fired by the backlog-depth trigger (queued same-bucket work >= mesh width x k) rather than a full batch",
    # measured host time at the device, the collector, compiles (utils/trace.py,
    # serving/deadline.py)
    "device.host_seconds": "Seconds a host thread spent at the device, by lane (witness/sig/root) and op: enqueue = upload + program launch with no wait (begin); sync = the readback, i.e. the thread stood BLOCKED on the chip (resolve). The measurement critpath's `dispatch` remainder is not",
    "device.host_cpu_seconds": "CPU seconds the host thread ran inside each visit to the device that device.host_seconds times, by lane and op: a sync that burns CPU is a spin, an enqueue of far more wall than CPU is a launch that stood behind the interpreter lock",
    "lanes.stage_seconds": "Wall seconds of each lane stage (the stage timers witness_engine.<lane_>prefetch|pack|dispatch|resolve), by lane (witness/sig/root) and stage: one observation a batch, on the scheduler or mesh-pool thread that ran the stage (utils/trace.Metrics.phase)",
    "lanes.stage_cpu_seconds": "CPU seconds the lane's thread ran inside each stage of lanes.stage_seconds, booked as engine_api.phase_cpu_seconds is (native code run with the interpreter lock released counts: native.unlocked_seconds says how much of it the extension saw)",
    "lanes.stage_offcpu_seconds": "lanes.stage_seconds less lanes.stage_cpu_seconds, observation by observation: what the lane's thread waited inside the stage. prefetch, pack and dispatch wait for nothing but a turn at the interpreter lock (and the engine's own lock); resolve waits for the device's readback too",
    "runtime.process_cpu_seconds": "CPU seconds of the whole process by mode (os.times: user, and system = the kernel on the process's behalf, e.g. handing the interpreter lock from thread to thread; the two sum to time.process_time), every thread: XLA's pool, the collector, whatever shares the process; set at every exposition (/metrics)",
    "native.unlocked_seconds": "Seconds the extension (native/pyext.cc) ran native work with the interpreter lock released, by site (scan, verdict, commit, commit_hash, hash, finish_commit: the witness engine's scan, hash and commit; keccak: the scalar keccak256 of a long input), from the lock's release to the work's end; read from the extension's own clocks at every exposition (/metrics), 0 in a process without it",
    "native.lock_retake_seconds": "Seconds the extension waited to take the interpreter lock back after native work it ran unlocked, by site: the one place the program MEASURES a wait for the lock (everywhere else it is wall less CPU)",
    "native.keccak_calls": "Scalar keccak256 calls the extension served (crypto/keccak.keccak256 -> native/pyext.cc) since process start, by what they did with the interpreter lock: held = an input under KECCAK_UNLOCK_BYTES (4 KiB) hashed without a hand-over, released = a longer one hashed with the lock given away (clocked as native.unlocked_seconds{site=keccak}); counted in C++ and read at every exposition (/metrics), 0 in a process without the extension, where the ctypes library or the Python spec hashes uncounted",
    "jit.compiles": "Programs jax first built in this process, by thread (serving = a scheduler thread whose compile holds a job queue; other): one per backend compile and one per load from the persistent cache",
    "jit.serving_compile_seconds": "Wall-clock seconds the serving threads have spent compiling (union of jax's trace/lower/compile intervals): the credit the request deadline clock runs on (serving/deadline.py)",
    "runtime.gc_pause_seconds": "Pauses of CPython's collector in this process, by generation, from the one gc.callbacks entry the server installs (every collection; a full one, generation 2, is also a `gc` interval of every request span open then; generation=deep is the tenure policy's own full collection of everything, run while no request is in flight)",
    "runtime.gc_tenures": "Full collections after which the survivors were moved to CPython's permanent generation (gc.freeze), so that no later collection walks them again: the tenure policy of serving/collector.py, in force while an Engine API server is up",
    "runtime.gc_tenured_objects": "Objects in the permanent generation (gc.get_freeze_count) as of the last count: counted where the collector's log is flushed after a tenure, at most once a minute while requests are in flight (a walk of that generation, 15 ms a million objects), at once when none has been for some seconds; objects that died by reference count since are still in it",
    "runtime.gc_deep_collections": "Deep collections: everything tenured handed back, collected and tenured again, when no request has been in flight for some seconds, the tenured count has grown by a twentieth and the last one is five minutes ago: frees cyclic garbage that formed among tenured objects",
    # observability layer (phant_tpu/obs/)
    "sched.watchdog_stalls": "Executor stalls detected by the obs watchdog (in-flight batch past its deadline)",
    "flight.dumps": "Flight-recorder postmortem dumps written, by trigger reason",
    # per-request critical-path attribution (phant_tpu/obs/critpath.py)
    "critpath.phase_seconds": "Per-request critical-path phase time at verify_block span close, by phase (sig_rows/queue_wait/prefetch/pack/dispatch/resolve/witness_decode/sig_wait/evm/root_plan/root_wait/post_root) — phases tile the request's wall clock; derived from the span's own phase timers plus the batch records the serving lanes attach",
    "critpath.phase_cpu_seconds": "CPU seconds the handler thread ran inside each critical-path phase of critpath.phase_seconds (the thread's CPU clock read beside the span clock at each phase's ends; a parent's CPU goes where its wall remainder goes: dispatch, evm, post_root), one observation for each of that family's, booked as engine_api.phase_cpu_seconds is",
    "critpath.phase_offcpu_seconds": "critpath.phase_seconds less critpath.phase_cpu_seconds: what the handler thread waited inside the phase. In sig_rows, witness_decode, evm and root_plan it waits for nothing by design, so that is its wait for a turn at the interpreter lock; the other phases are waits for a lane by definition",
    "critpath.wall_seconds": "verify_block request wall clock as seen by the critical-path rollup (the denominator of the coverage gauges)",
    "critpath.coverage_pct": "Cumulative attributed share of verify_block wall clock (the >=95% acceptance surface: anything lower means the phase tiling is missing a real cost)",
    "critpath.unattributed_pct": "Cumulative UNattributed share of verify_block wall clock (100 - coverage) — the honesty-check residual gauge",
    "critpath.requests": "verify_block spans rolled up by the critical-path attribution sink",
    "obs.slow_captures": "Requests captured into the /debug/slow flight ring, by trigger (wall = --slo-budget-ms exceeded; near = landed in the top PHANT_SLO_NEAR_PCT of the budget, sampled; a phase name = that phase's env budget exceeded)",
    # unified timeline export (phant_tpu/obs/timeline.py)
    "obs.timeline_kept": "Requests kept by the timeline tail-sampler at span close, by reason (error = crashed request, slo = wall budget blown, p99 = rolling per-phase p99 exemplar, sample = uniform 1-in-N)",
    "obs.timeline_dropped": "Requests dropped by the timeline tail-sampler, by reason (sampled_out = span-close decision — kept + sampled_out reconciles with offered load; ring_full = a previously-KEPT entry evicted by ring overflow, counted separately)",
    "obs.timeline_exports": "Timeline exports rendered (GET /debug/timeline and the optional spool-to-dir copies)",
    # commitment schemes (phant_tpu/commitment/)
    "commitment.state_views": "Witness-backed state views constructed, by commitment scheme (mpt/binary) — the per-request scheme selector's audit trail",
    "commitment.witness_nodes": "Witness nodes generated by full-state witness collection (spec runner / differential harnesses), by scheme",
    "commitment.translated_fixtures": "Spec fixtures re-committed under an alternate commitment scheme (commitment/translate.py)",
    "commitment.translated_blocks": "Fixture blocks re-sealed with alternate-scheme state roots during fixture translation",
    # historical replay engine (phant_tpu/replay/)
    "replay.segments": "Chain segments imported by the replay engine (prefetch/pack/dispatch/resolve pipeline turns)",
    "replay.blocks": "Blocks successfully imported by the replay engine",
    "replay.txs": "Transactions imported by the replay engine (the merged-ecrecover row volume)",
    "replay.block_failures": "Consensus-invalid blocks that stopped a replay (the replay.block_failed flight record carries the attribution)",
    "replay.lane_fallbacks": "Segments degraded to a local megabatch after a scheduler-lane failure, by stage (prefetch/pack/dispatch/resolve; -32052 in-flight-only semantics)",
    "replay.root_groups": "Deferred segment-root groups resolved, by backend (device = one vmapped _hash_plans_batched program per structure-sharing run; host = singleton/unplannable walks)",
    "replay.prefetch": "Replay prefetch stage: building segment N+1's merged signature rows (host keccak over RLP) under segment N's EVM execution",
    "replay.pack": "Replay pack stage: submitting segment N+1's witness megabatch to the witness lane",
    "replay.dispatch": "Replay dispatch stage: launching segment N+1's merged ecrecover on the sig lane (incl. the sig-backlog pacing wait)",
    "replay.sig_wait": "Replay blocks joining a segment's merged senders at execute time — recovery cost that did NOT hide under the previous segment's EVM (the overlap audit)",
    "replay.witness_wait": "Replay blocks joining a segment's witness verdicts at execute time",
    "replay.root_wait": "Deferred segment-root lowering + readback at segment end (the one root sync per segment)",
    "replay.segment_seconds": "Whole-segment resolve+execute wall clock (the blocks/s denominator at segment granularity)",
    "replay.block_latency_seconds": "A block's seconds in the replay pipeline: from the moment its segment is handed to the pipeline (the lookahead worker, or the run loop at depth 1, begins the segment's prefetch) to the block's verdict (under the host walk when run_block returns or raises; under deferred roots when the segment's roots are read back). One observation a verdict; BlockVerdict.latency_s is the same reading",
    "replay.execute_seconds": "Seconds a segment's blocks spent in Blockchain.run_block less the state root computed inside it: header checks, the transactions, the receipts root. One observation a segment",
    "replay.root_seconds": "Seconds a segment spent on its blocks' post-state roots, by backend: host = StateDB.state_root inside run_block, the walk over the dirty paths of the retained trie (the first one hashes the whole trie; a later one encodes and hashes the dirty nodes alone, their clean siblings' references read from the trie's memo); device = the deferred route, flush + build_hash_plan a block, then the lowering and the readback (replay.root_wait is that last part). One observation a segment",
    "replay.ready_wait_seconds": "Seconds the run loop waited for the lookahead worker to hand over a prepared segment (0 at depth 1, where the segment is prepared inline): what of prefetch, pack and dispatch did NOT hide under the segment before. One observation a segment",
    "replay.phase_cpu_seconds": "CPU seconds of the thread that ran each phase of a replayed segment (prefetch, pack, dispatch on the lookahead worker; ready_wait, sig_wait, witness_wait, execute, root on the run loop), by the thread's CPU clock read beside the span clock at the phase's ends; one observation a phase a segment, booked as engine_api.phase_cpu_seconds is (Metrics.observe_split)",
    "replay.phase_offcpu_seconds": "A replayed segment's phase wall less replay.phase_cpu_seconds, observation by observation: what the phase's thread waited, for a lane (sig_wait, witness_wait), for the lookahead (ready_wait), or for its turn at the interpreter lock",
    "replay.segment_blocks": "Configured blocks per replay segment (--segment)",
    "replay.pipeline_depth": "Configured replay pipeline depth (1 = fully inline; >= 2 = segment N+1 prepared under segment N's execution)",
    # crypto backend dispatch
    "keccak.batches": "Batched keccak dispatches by backend",
    "keccak.bytes": "Payload bytes submitted to batched keccak by backend",
    "keccak.device_dispatch": "Host->device upload + kernel dispatch phase",
    "keccak.host_readback": "Device->host digest readback (the honest sync) phase",
    "backend.selected": "Crypto-backend selections by backend (process start + later switches)",
    "backend.device_fallbacks": "Run-time degradations after a healthy start: a device or device-lane failure made this site serve its batch from the host (by site; chip_smoke.py requires zero)",
    "backend.offload_decisions": "Adaptive offload-gate verdicts by outcome (device/native)",
}


#: the trace vocabulary: every `span(name, ...)` name and every flight-event
#: kind (`flight.record(kind, ...)`, phant_tpu/obs/flight.py) must have an
#: entry here — phantlint's SPANNAME rule enforces it exactly the way
#: METRICNAME enforces METRIC_HELP, so span/flight names stay literal,
#: documented, and free of dead catalog entries.
SPAN_HELP: Dict[str, str] = {
    # spans (top-level records carry trace_id + the scheduler batch fields)
    "request": "One Engine API POST on its handler thread, headers parsed -> reply written: the FRAME around verify_block (its parent_id), tiled by the front end's intervals read/json/gate/decode/reply; reported to the sinks when a verify_block ran under it",
    "verify_block": "One stateless payload execution: witness_verify/witness_decode/execute/post_root phases plus the serving batch fields (batch_id, queue_wait_ms, ...)",
    # flight-event kinds (phant_tpu/obs/flight.py ring records)
    "span": "A completed top-level span record (mirrored from the span sink)",
    "error": "An exception record (stateless execution aborts and other instrumented failures)",
    "sched.admit": "A request admitted to the scheduler queue (carries tenant + priority)",
    "sched.shed": "A request shed at admission, execution, or the stateless concurrency gate (queue_full/tenant_quota/evicted/saturated/deadline/down/shutdown; carries the shed tenant)",
    "sched.adapt_wait": "The adaptive batching policy changed the assembly wait (old/new wait + queue depth)",
    "sched.batch_start": "The executor picked up a batch (witness lane) or serial job",
    "sched.batch_done": "A batch/serial job finished; carries the batch record (size, bucket, backend, cache counts, trace ids)",
    "sched.executor_crash": "The scheduler executor died; carries the crashing batch's ids",
    "sched.stall": "The obs watchdog found the in-flight batch past its deadline",
    "flight.dump": "A postmortem dump was written to disk (reason + path)",
    "obs.slow_capture": "A request blew its SLO budget (--slo-budget-ms wall clock, or a per-phase env override): carries the FULL span tree plus the critical-path breakdown — metrics say THAT it was slow, this exemplar says WHY (served at /debug/slow)",
    "obs.profile": "An on-demand TPU profiler capture ran (POST /debug/profile): carries the trace directory, the captured window, and the artifact count",
    "obs.timeline_export": "A timeline export was rendered (GET /debug/timeline / spool): carries the window, event count, and how many requests/batches landed in it",
    "replay.segment_crash": "A scheduler lane failed a replay segment's in-flight work (stage-named: prefetch/pack/dispatch/resolve; carries the SchedulerDown/-32052 code); the segment degraded to its local megabatch fallback and the import continued",
    "replay.block_failed": "A consensus-invalid block stopped a replay import (stage-named; carries the block index/number and the BlockError text) — earlier blocks stand, run_blocks semantics",
}


class Metrics:
    """Process-global counters, gauges, histograms, and phase timers
    (thread-safe; `snapshot()` deep-copies under the lock so exposition
    never reads torn values)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._timers: Dict[str, TimerStat] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, Histogram] = {}
        self._cpu_carry: Dict[str, float] = {}  # observe_split: CPU read, not yet booked

    def count(self, name: str, delta: int = 1, **labels) -> None:
        key = _labels_key(name, labels)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + delta

    def gauge_set(self, name: str, value: float, **labels) -> None:
        key = _labels_key(name, labels)
        with self._lock:
            self._gauges[key] = value

    def gauge_add(self, name: str, delta: float, **labels) -> None:
        key = _labels_key(name, labels)
        with self._lock:
            self._gauges[key] = self._gauges.get(key, 0) + delta

    def observe(
        self,
        name: str,
        seconds: float,
        end_ns: Optional[int] = None,
        cpu_ns: Optional[int] = None,
    ) -> None:
        """Add `seconds` to the phase timer `name` and, inside an open
        span, record the child interval that ends at `end_ns` (default:
        now) on the span clock, with the `cpu_ns` this thread ran in it
        where the caller read the thread's CPU clock at its two ends."""
        with self._lock:
            self._timers.setdefault(name, TimerStat()).add(seconds)
        sp = current_span()
        if sp is not None:
            if end_ns is None:
                end_ns = clock_ns()
            sp.add_interval(name, end_ns - int(seconds * 1e9), end_ns, cpu_ns)

    def observe_hist(
        self,
        name: str,
        value: float,
        buckets: Optional[Tuple[float, ...]] = None,
        **labels,
    ) -> None:
        with self._lock:
            self._hist_add(_labels_key(name, labels), value, buckets)

    def _hist_add(self, key: str, value: float, buckets) -> None:
        """Under `self._lock`."""
        h = self._hists.get(key)
        if h is None:
            h = self._hists[key] = Histogram(buckets or DEFAULT_BUCKETS)
        h.add(value)

    def observe_split(
        self,
        cpu_name: str,
        offcpu_name: str,
        wall_s: float,
        cpu_s: float,
        buckets: Optional[Tuple[float, ...]] = None,
        **labels,
    ) -> None:
        """Book `cpu_s` of thread CPU against a phase of `wall_s`: one
        observation of `cpu_name`, never above the wall, and one of
        `offcpu_name`, the rest of the wall, so the two sum to the phase's
        wall observation by observation. Where the thread's CPU clock
        steps by a scheduler tick (10 ms on the chip's host, PERF.md
        section 6, PR 38: a reading is then 0 or a whole tick, whatever
        the phase's width), what a reading holds beyond its phase is
        carried to the next observations of the same series: dropping it
        would book too little CPU and too much waiting, phase after
        phase, and the sums stay true to the clock this way."""
        key, wall_s = _labels_key(cpu_name, labels), max(wall_s, 0.0)
        with self._lock:
            have = self._cpu_carry.get(key, 0.0) + max(cpu_s, 0.0)
            booked = min(have, wall_s)
            self._cpu_carry[key] = have - booked
            self._hist_add(key, booked, buckets)
            self._hist_add(_labels_key(offcpu_name, labels), wall_s - booked, buckets)

    @contextlib.contextmanager
    def phase(self, name: str, **attrs) -> Iterator[None]:
        """Time a phase: `with metrics.phase("engine_api.new_payload"): ...`.
        Inside an open span the phase is a measured child interval of it,
        and where ANNOTATIONS names it, an event of the profiler's trace,
        which carries `attrs` (a lane's dispatch says its `rung`). Both
        clocks are read at both ends (`cpu_clock_ns` inside `clock_ns`):
        wall less cpu is what the thread waited, for a device, a queue or
        its turn at the interpreter lock. A lane's stage timer
        (LANE_STAGES) is also one observation of the `lanes.stage_*`
        families, on the thread that ran the stage."""
        ann = annotate(ANNOTATIONS.get(name), **attrs)
        t0 = clock_ns()
        c0 = cpu_clock_ns()
        try:
            yield
        finally:
            cpu = cpu_clock_ns() - c0
            t1 = clock_ns()
            end_annotation(ann)
            self.observe(name, (t1 - t0) / 1e9, end_ns=t1, cpu_ns=cpu)
            at = LANE_STAGES.get(name)
            if at is not None:
                lane, stage = at
                wall = (t1 - t0) / 1e9
                self.observe_hist("lanes.stage_seconds", wall, lane=lane, stage=stage)
                self.observe_split(
                    "lanes.stage_cpu_seconds",
                    "lanes.stage_offcpu_seconds",
                    wall,
                    cpu / 1e9,
                    lane=lane,
                    stage=stage,
                )

    def snapshot(self) -> dict:
        """Deep copy of every table under the lock: TimerStat/Histogram
        objects keep mutating concurrently, and exposition must never read
        a torn (count updated, sum not yet) pair."""
        flush_gc()
        with self._lock:
            return {
                "counters": dict(self._counters),
                "timers": {
                    k: {
                        "count": v.count,
                        "total_s": v.total_s,
                        "mean_s": v.mean_s,
                        "min_s": v.min_s,
                        "max_s": v.max_s,
                    }
                    for k, v in self._timers.items()
                },
                "gauges": dict(self._gauges),
                "histograms": {
                    k: {
                        "buckets": list(h.buckets),
                        "counts": list(h.counts),
                        "sum": h.sum,
                        "count": h.count,
                    }
                    for k, h in self._hists.items()
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._timers.clear()
            self._gauges.clear()
            self._hists.clear()
            self._cpu_carry.clear()

    def report(self) -> str:
        """Box table of every phase/counter (same presentation family as the
        chain-config dump, reference: src/config/config.zig:67-90)."""
        snap = self.snapshot()
        rows = [("metric", "count", "total", "mean")]
        for name, c in sorted(snap["counters"].items()):
            rows.append((name, str(c), "-", "-"))
        for name, g in sorted(snap["gauges"].items()):
            rows.append((name, f"{g:g}", "-", "-"))
        for name, h in sorted(snap["histograms"].items()):
            rows.append((name, str(h["count"]), f"{h['sum'] * 1e3:.2f}ms", "-"))
        for name, t in sorted(snap["timers"].items()):
            rows.append(
                (
                    name,
                    str(t["count"]),
                    f"{t['total_s'] * 1e3:.2f}ms",
                    f"{t['mean_s'] * 1e3:.3f}ms",
                )
            )
        widths = [max(len(r[i]) for r in rows) for i in range(4)]

        def line(l, m, r):
            return l + m.join("─" * (w + 2) for w in widths) + r

        out = [line("┌", "┬", "┐")]
        for i, row in enumerate(rows):
            out.append("│" + "│".join(f" {c.ljust(w)} " for c, w in zip(row, widths)) + "│")
            if i == 0:
                out.append(line("├", "┼", "┤"))
        out.append(line("└", "┴", "┘"))
        return "\n".join(out)

    # -- Prometheus text exposition -----------------------------------------

    def prometheus_text(self) -> str:
        """Standard Prometheus text exposition (version 0.0.4) of every
        table. Counters export as `<family>_total`, phase timers as
        `<family>_seconds` summaries (count/sum), histograms with
        cumulative `_bucket{le=...}` series. What is read rather than
        counted is read here, once an exposition: the process's CPU seconds,
        all threads (XLA's pool, the collector and whatever shares the
        process with the server, so that what no span covers has a size),
        and the extension's lock clocks and its count of scalar hashes (zeros
        where it is not loaded)."""
        from phant_tpu.utils import native  # here: it loads nothing until asked

        cpu = os.times()  # what time.process_time() sums, apart
        self.gauge_set("runtime.process_cpu_seconds", cpu.user, mode="user")
        self.gauge_set("runtime.process_cpu_seconds", cpu.system, mode="system")
        for site, (unlocked, retake) in native.lock_clocks().items():
            self.gauge_set("native.unlocked_seconds", unlocked, site=site)
            self.gauge_set("native.lock_retake_seconds", retake, site=site)
        for lock, calls in native.keccak_calls().items():
            self.gauge_set("native.keccak_calls", calls, lock=lock)
        snap = self.snapshot()
        out: List[str] = []
        emitted_help: set = set()

        def header(base: str, family: str, mtype: str) -> None:
            if family in emitted_help:
                return
            emitted_help.add(family)
            help_s = METRIC_HELP.get(base)
            if help_s:
                out.append(f"# HELP {family} {help_s}")
            out.append(f"# TYPE {family} {mtype}")

        def fmt(v: float) -> str:
            return repr(v) if isinstance(v, float) else str(v)

        # group labeled series under one family so HELP/TYPE emit once
        for key in sorted(snap["counters"]):
            base, labels = split_labels(key)
            family = prometheus_name(base)
            if not family.endswith("_total"):
                family += "_total"
            header(base, family, "counter")
            lab = f"{{{labels}}}" if labels else ""
            out.append(f"{family}{lab} {snap['counters'][key]}")
        for key in sorted(snap["gauges"]):
            base, labels = split_labels(key)
            family = prometheus_name(base)
            header(base, family, "gauge")
            lab = f"{{{labels}}}" if labels else ""
            out.append(f"{family}{lab} {fmt(snap['gauges'][key])}")
        for key in sorted(snap["histograms"]):
            base, labels = split_labels(key)
            family = prometheus_name(base)
            header(base, family, "histogram")
            h = snap["histograms"][key]
            cum = 0
            for ub, c in zip(h["buckets"], h["counts"]):
                cum += c
                lab = f'le="{fmt(float(ub))}"' + (f",{labels}" if labels else "")
                out.append(f"{family}_bucket{{{lab}}} {cum}")
            lab = 'le="+Inf"' + (f",{labels}" if labels else "")
            out.append(f"{family}_bucket{{{lab}}} {h['count']}")
            lab = f"{{{labels}}}" if labels else ""
            out.append(f"{family}_sum{lab} {fmt(h['sum'])}")
            out.append(f"{family}_count{lab} {h['count']}")
        # derived p50/p99 gauges per histogram family: bucket-interpolated
        # at scrape time (histogram_quantile above — an estimate bounded
        # by bucket resolution, never exact order statistics; the raw
        # bucket series stay the authoritative data). Emitted as separate
        # `<family>_p50`/`<family>_p99` gauge families so a dashboard-less
        # operator can read quantiles straight off a curl.
        for q, suffix in ((0.5, "_p50"), (0.99, "_p99")):
            for key in sorted(snap["histograms"]):
                h = snap["histograms"][key]
                if h["count"] <= 0:
                    continue
                base, labels = split_labels(key)
                family = prometheus_name(base) + suffix
                if family not in emitted_help:
                    emitted_help.add(family)
                    out.append(
                        f"# HELP {family} bucket-interpolated "
                        f"p{int(q * 100)} of {prometheus_name(base)} "
                        "(derived at scrape; estimate, not exact)"
                    )
                    out.append(f"# TYPE {family} gauge")
                lab = f"{{{labels}}}" if labels else ""
                v = histogram_quantile(h["buckets"], h["counts"], q)
                out.append(f"{family}{lab} {fmt(float(v))}")
        for key in sorted(snap["timers"]):
            base, labels = split_labels(key)
            family = prometheus_name(base)
            if not family.endswith("_seconds"):
                family += "_seconds"
            header(base, family, "summary")
            lab = f"{{{labels}}}" if labels else ""
            t = snap["timers"][key]
            out.append(f"{family}_sum{lab} {fmt(t['total_s'])}")
            out.append(f"{family}_count{lab} {t['count']}")
        return "\n".join(out) + "\n"


#: process-global registry (importable singleton)
metrics = Metrics()


def phase(name: str):
    """Module-level shorthand for `metrics.phase(name)`."""
    return metrics.phase(name)


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

_span_log = logging.getLogger("phant_tpu.span")
_span_tls = threading.local()

#: THE clock of every span, interval and lane stage: the one the deadline
#: clock (serving/deadline.py) and the benchmark harness already read, so a
#: span's `start_ns`/`end_ns` compare with both without conversion
clock_ns = time.monotonic_ns

#: the CPU clock of the calling thread (CLOCK_THREAD_CPUTIME_ID), read at the
#: same two instants as `clock_ns` wherever a phase, a mark, a lane stage or
#: a visit to the device is timed, on the thread that runs it: wall less cpu
#: is what the thread WAITED (for the device, a queue, or its turn at the
#: interpreter lock; in a phase that waits for nothing by design, the lock).
#: It counts native code run with the lock released too (`ctypes`, XLA), so
#: it bounds what a thread asked of the lock from above
cpu_clock_ns = time.thread_time_ns

#: span ids: process-unique, ascending (`next()` on a count is atomic)
_span_ids = itertools.count(1)

#: top-level span records (dicts) fan out here in addition to the log line;
#: the obs flight recorder registers a sink (phant_tpu/obs/__init__.py).
#: Mutated only via add/remove below; iteration reads a snapshot reference.
_span_sinks: List = []

#: the spans that are open and top-level (a frame, or a frame's child) right
#: now, by id: the collector's callback writes each full collection into
#: every one of them. Single dict operations only, so no lock
_open_spans: Dict[int, "Span"] = {}
_last_close_ns: List[int] = [clock_ns()]  # when the last of them closed, on the span clock


def idle_seconds() -> Optional[float]:
    """Seconds since the last top-level span closed (since import where none
    has), or None while one is open: a request is in flight."""
    if _open_spans:
        return None
    return (clock_ns() - _last_close_ns[0]) / 1e9


def add_span_sink(fn) -> None:
    """Register `fn(record: dict)` to receive every TOP-LEVEL span record.
    Idempotent per function object. Sinks must be fast and non-raising
    (exceptions are swallowed: tracing must never fail the traced work)."""
    if fn not in _span_sinks:
        _span_sinks.append(fn)


def remove_span_sink(fn) -> None:
    if fn in _span_sinks:
        _span_sinks.remove(fn)


def new_trace_id() -> str:
    """16-hex-char request identity (collision-safe at serving volumes)."""
    import os as _os

    return _os.urandom(8).hex()


def current_trace_id() -> Optional[str]:
    """The trace id of the innermost open trace_context on this thread."""
    stack = getattr(_span_tls, "trace_ids", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def trace_context(trace_id: Optional[str] = None) -> Iterator[str]:
    """Bind a request identity to the current thread: spans opened inside
    (and scheduler submissions made inside, phant_tpu/serving/) carry this
    `trace_id`. Nests; the Engine API server opens one per POST."""
    tid = trace_id or new_trace_id()
    stack = getattr(_span_tls, "trace_ids", None)
    if stack is None:
        stack = _span_tls.trace_ids = []
    stack.append(tid)
    try:
        yield tid
    finally:
        stack.pop()


@contextlib.contextmanager
def lane_stage(
    stages: dict, stage: str, trace_ids: Sequence[Optional[str]], batch_id
) -> Iterator[None]:
    """One stage of a lane batch, on the (lane) thread that runs it. The
    batch is bound to the thread while the stage runs: annotations opened
    inside carry the batch's `trace_id`s (joined by "|": the profiler's
    event names keep their attributes comma-separated) and `batch_id`. The
    stage's measured `[start_ns, end_ns]` is written into `stages` (the
    dict the batch record carries beside its `*_ms`), the CPU nanoseconds
    this thread ran between them under `cpu_ns`, and `compile_ns` where
    jax reported a compile on this thread meanwhile: the batch stood
    behind it."""
    ctx = {"batch_id": batch_id, "compile_ns": 0}
    prev = getattr(_span_tls, "lane", None)
    _span_tls.lane = ctx
    ids = "|".join(t for t in trace_ids if t)
    bound = trace_context(ids) if ids else contextlib.nullcontext()
    t0 = clock_ns()
    c0 = cpu_clock_ns()
    try:
        with bound:
            yield
    finally:
        cpu = cpu_clock_ns() - c0
        stages[stage] = [t0, clock_ns()]
        stages.setdefault("cpu_ns", {})[stage] = cpu
        if ctx["compile_ns"]:
            stages["compile_ns"] = stages.get("compile_ns", 0) + ctx["compile_ns"]
        _span_tls.lane = prev


def fold_stages(record: dict, stages: dict) -> None:
    """`lane_stage`'s measurements into the batch record: `stages`, each
    stage's [start_ns, end_ns] beside the `*_ms` it is the ends of,
    `stage_cpu_ms`, the CPU each stage's thread ran inside them, and
    `compile_ms` where the batch stood behind a compile."""
    stages = dict(stages)
    compile_ns = stages.pop("compile_ns", 0)
    if compile_ns:
        record["compile_ms"] = round(compile_ns / 1e6, 3)
    cpu_ns = stages.pop("cpu_ns", None)
    if cpu_ns:
        record["stage_cpu_ms"] = {k: round(v / 1e6, 3) for k, v in cpu_ns.items()}
    if stages:
        record["stages"] = stages


# -- the device trace's clock ------------------------------------------------

#: phase timer -> the name it carries in the profiler's trace. With the
#: names that `span`, `Span.mark`, `device_host`, `note_compile` and the
#: collector's callback open themselves (`phant/<span name>`, the front
#: end's five, `phant/device_enqueue`, `phant/device_sync`, `phant/compile`,
#: `phant/gc`) this is the whole vocabulary: PERF.md section 3 lists each
#: name with the metric it serves, and scripts/trace_gaps.py reads them
ANNOTATIONS: Dict[str, str] = {
    # the handler thread's phases of a request (critpath's names)
    "stateless.sig_rows": "phant/sig_rows",
    "stateless.witness_verify": "phant/witness_verify",
    "stateless.witness_decode": "phant/witness_decode",
    "stateless.execute": "phant/evm",
    "sched.sig_wait": "phant/sig_wait",
    "stateless.post_root_plan": "phant/root_plan",
    "stateless.post_root": "phant/post_root",
    # the lanes' stages, on the thread that runs each
    "witness_engine.prefetch": "phant/witness.prefetch",
    "witness_engine.pack": "phant/witness.pack",
    "witness_engine.dispatch": "phant/witness.dispatch",
    "witness_engine.resolve": "phant/witness.resolve",
    "witness_engine.sig_prefetch": "phant/sig.prefetch",
    "witness_engine.sig_pack": "phant/sig.pack",
    "witness_engine.sig_dispatch": "phant/sig.dispatch",
    "witness_engine.sig_resolve": "phant/sig.resolve",
    "witness_engine.root_prefetch": "phant/root.prefetch",
    "witness_engine.root_pack": "phant/root.pack",
    "witness_engine.root_dispatch": "phant/root.dispatch",
    "witness_engine.root_resolve": "phant/root.resolve",
}

#: the lanes' stage timers -> (lane, stage): `Metrics.phase` observes each
#: into `lanes.stage_seconds{lane=,stage=}` and its cpu/offcpu twins, once a
#: batch, on the scheduler (or mesh pool) thread that ran the stage
LANE_STAGES: Dict[str, Tuple[str, str]] = {
    name: tuple(ann[len("phant/"):].split("."))
    for name, ann in ANNOTATIONS.items()
    if name.startswith("witness_engine.")
}

_trace_me = None  # jax's TraceMe class, once jax is in the process


def annotate(name: Optional[str], **attrs):
    """An ENTERED `jax.profiler.TraceAnnotation(name, ...)` carrying this
    thread's `trace_id` and `batch_id`, or None: where `name` is None,
    jax is not in the process (the cpu backend must not import it) or no
    profiler is running. The caller ends it with `end_annotation`. With
    no profiler running this is one flag test."""
    global _trace_me
    if name is None:
        return None
    tm = _trace_me
    if tm is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation as tm
        except ImportError:  # jax is mid-import on another thread
            return None
        _trace_me = tm
    if not tm.is_enabled():
        return None
    tid = current_trace_id()
    if tid is not None:
        attrs.setdefault("trace_id", tid)
    lane = getattr(_span_tls, "lane", None)
    if lane is not None and lane["batch_id"] is not None:
        attrs.setdefault("batch_id", lane["batch_id"])
    ann = tm(name, **attrs)
    ann.__enter__()
    return ann


def end_annotation(ann) -> None:
    """End what `annotate` returned (None: there was nothing to end)."""
    if ann is not None:
        ann.__exit__(None, None, None)


@contextlib.contextmanager
def device_host(lane: str, op: str) -> Iterator[None]:
    """Time a host thread at the device: `op` "enqueue" is an upload and a
    program launch that does not wait, "sync" is a readback, i.e. the
    seconds the thread stood blocked on the chip. Observed into
    `device.host_seconds{lane=,op=}`, with the CPU the thread ran
    meanwhile in `device.host_cpu_seconds` (a `sync` that burns CPU is a
    spin; an `enqueue` of far more wall than CPU stood behind the
    interpreter lock), and shown in the profiler's trace as
    `phant/device_enqueue` / `phant/device_sync`."""
    ann = annotate("phant/device_" + op, lane=lane)
    t0 = clock_ns()
    c0 = cpu_clock_ns()
    try:
        yield
    finally:
        cpu = (cpu_clock_ns() - c0) / 1e9
        dt = (clock_ns() - t0) / 1e9
        end_annotation(ann)
        metrics.observe_hist("device.host_seconds", dt, lane=lane, op=op)
        metrics.observe_hist("device.host_cpu_seconds", cpu, lane=lane, op=op)


def note_compile(seconds: float) -> None:
    """A program was built on this thread and the build ended now (jax
    reports a compile only at its end; serving/deadline.py hears it): a
    `compile` interval of the span open here, `compile_ns` of the lane
    batch bound here, and a `phant/compile` marker that carries the
    seconds, since an annotation cannot be opened in the past."""
    end = clock_ns()
    ns = int(seconds * 1e9)
    sp = current_span()
    if sp is not None:
        sp.add_interval("compile", end - ns, end)
    lane = getattr(_span_tls, "lane", None)
    if lane is not None:
        lane["compile_ns"] += ns
    end_annotation(annotate("phant/compile", seconds=seconds))


class Span:
    """One traced operation: a measured interval (`start_ns`, `end_ns` on
    `clock_ns`) with a process-unique `span_id` and its parent's, the
    child intervals `(name, start_ns, end_ns)` of the phases that ran
    inside it (fed by Metrics.observe / Metrics.phase), and any child
    spans. `phases`, the per-name count and total the sinks read, is
    derived from the intervals, so the two cannot disagree; `cpu_ns` is
    the CPU nanoseconds this thread ran inside each name's intervals,
    kept by name beside them (the intervals stay triples for their
    readers, and the collector's `gc` entries have no CPU of the
    request's). Spans stack
    per-thread (thread-local), which is the thread-safety mechanism:
    concurrent request threads each trace their own block without
    locking; the one writer from outside, the collector's callback, only
    appends to `intervals`.

    A FRAME span (`span(name, frame=True)`: the Engine API server's
    `request`) stands around top-level spans without making them children:
    a span opened directly under it is still reported to the sinks as a
    top-level record, with the frame's id as its `parent_id`. `mark(name)`
    tiles a frame with contiguous intervals (the front end's phases)."""

    __slots__ = (
        "name",
        "attrs",
        "duration_s",
        "intervals",
        "cpu_ns",
        "children",
        "span_id",
        "parent_id",
        "start_ns",
        "end_ns",
        "frame",
        "resume",
        "reported",
        "_mark",
    )

    def __init__(self, name: str, attrs: dict, frame: bool = False):
        self.name = name
        self.attrs = attrs
        self.duration_s = 0.0
        self.intervals: List[Tuple[str, int, int]] = []
        self.cpu_ns: Dict[str, int] = {}
        self.children: List[dict] = []
        self.span_id = next(_span_ids)
        self.parent_id: Optional[int] = None
        self.start_ns = 0
        self.end_ns = 0
        self.frame = frame
        self.resume: Optional[str] = None  # the mark a closing child leaves
        self.reported = 0  # spans reported to the sinks from under a frame
        self._mark = None  # (name, start_ns, annotation, cpu at start) of the open mark

    def add_interval(
        self, name: str, start_ns: int, end_ns: int, cpu_ns: Optional[int] = None
    ) -> None:
        self.intervals.append((name, start_ns, end_ns))
        if cpu_ns is not None:
            self.cpu_ns[name] = self.cpu_ns.get(name, 0) + cpu_ns

    def mark(self, name: Optional[str], at: Optional[int] = None) -> None:
        """End the interval the last `mark` began and begin `name` (None:
        nothing, the span's time belongs to a child or to nobody), at
        clock reading `at` (default: now). Marks are contiguous, and
        `span` hands a frame the child's own start and end readings, so
        marks and children tile the frame exactly, less the stretches
        marked None. The thread's CPU clock is read here, on the thread
        the span stacks on, whatever `at` says: `span` calls within
        microseconds of the reading it hands over. The reading is kept as
        it is (where the clock steps by ticks it can pass the mark's
        width): whoever observes it books it (`Metrics.observe_split`)."""
        if (None if self._mark is None else self._mark[0]) == name:
            return  # the open mark already, or nothing open and nothing to begin
        now = clock_ns() if at is None else at
        cpu = cpu_clock_ns()
        if self._mark is not None:
            prev, t0, ann, c0 = self._mark
            end_annotation(ann)
            self.add_interval(prev, t0, now, cpu - c0)
        self._mark = (
            None if name is None else (name, now, annotate("phant/" + name), cpu)
        )

    @property
    def phases(self) -> Dict[str, List[float]]:
        """name -> [count, total_s] over the intervals."""
        return _phases_of(tuple(self.intervals))

    def to_dict(self) -> dict:
        d: dict = {"span": self.name, **self.attrs}
        d["duration_ms"] = round(self.duration_s * 1e3, 3)
        d["span_id"] = self.span_id
        if self.parent_id is not None:
            d["parent_id"] = self.parent_id
        d["start_ns"] = self.start_ns
        d["end_ns"] = self.end_ns
        intervals = tuple(self.intervals)
        if intervals:
            d["phases"] = {
                k: {"count": c, "total_ms": round(t * 1e3, 3)}
                for k, (c, t) in _phases_of(intervals).items()
            }
            # the CPU this thread ran inside a name's intervals: total_ms
            # less cpu_ms is what it waited there
            for k, ns in tuple(self.cpu_ns.items()):
                d["phases"][k]["cpu_ms"] = round(ns / 1e6, 3)
            # [name, start_ns, end_ns]: of this span, so of its trace_id
            d["intervals"] = [list(iv) for iv in intervals]
        if self.children:
            d["children"] = self.children
        return d


def _phases_of(intervals) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for name, t0, t1 in intervals:
        st = out.get(name)
        if st is None:
            out[name] = [1, (t1 - t0) / 1e9]
        else:
            st[0] += 1
            st[1] += (t1 - t0) / 1e9
    return out


def current_span() -> Optional[Span]:
    stack = getattr(_span_tls, "stack", None)
    return stack[-1] if stack else None


def _report(sp: Span) -> None:
    """One top-level record: to the sinks and the span log."""
    sinks = tuple(_span_sinks)  # snapshot: a concurrent
    # remove_span_sink must not shift the list mid-iteration
    if sinks or _span_log.isEnabledFor(logging.INFO):
        # serialization is per-block work on the serving hot path —
        # skip it entirely when nobody listens
        record = sp.to_dict()
        for sink in sinks:
            try:
                sink(record)
            except Exception:  # tracing must never fail the work
                pass
        if _span_log.isEnabledFor(logging.INFO):
            _span_log.info(json.dumps(record, default=str))


@contextlib.contextmanager
def span(name: str, frame: bool = False, **attrs) -> Iterator[Span]:
    """Trace one operation: `with span("verify_block", block=n): ...`.

    Phase timings recorded inside (via `metrics.phase` / `observe`) attach
    to the innermost open span of the current thread as measured child
    intervals. A nested span folds its summary into its parent; each
    TOP-LEVEL span emits one structured-JSON log line (logger
    `phant_tpu.span`, INFO) with the nested phase timings — the per-block
    trace record — and fans the same record out to registered span sinks
    (the obs flight recorder). A span opened inside a `trace_context`
    carries its `trace_id`. A span directly under a FRAME span (see Span)
    counts as top-level; the frame itself is reported when it closes, if a
    span was reported from under it. While a profiler runs, the span is
    also an event `phant/<name>` of its trace."""
    if "trace_id" not in attrs:
        tid = current_trace_id()
        if tid is not None:
            attrs["trace_id"] = tid
    sp = Span(name, attrs, frame)
    stack = getattr(_span_tls, "stack", None)
    if stack is None:
        stack = _span_tls.stack = []
    parent = stack[-1] if stack else None
    top = parent is None or parent.frame
    t0 = sp.start_ns = clock_ns()
    if parent is not None:
        sp.parent_id = parent.span_id
        if parent.frame:
            parent.mark(None, at=t0)  # the frame's own time stops here
    stack.append(sp)
    if top:
        _open_spans[sp.span_id] = sp
    ann = annotate("phant/" + name)
    try:
        yield sp
    finally:
        t1 = sp.end_ns = clock_ns()
        sp.mark(None, at=t1)
        sp.duration_s = (t1 - t0) / 1e9
        end_annotation(ann)
        stack.pop()
        if top:
            _open_spans.pop(sp.span_id, None)
            _last_close_ns[0] = t1
            if parent is not None:
                parent.reported += 1
                parent.mark(parent.resume, at=t1)
            flush_gc()
            if not frame or sp.reported:
                _report(sp)
        else:
            parent.children.append(sp.to_dict())


# -- the collector -----------------------------------------------------------

#: (generation, start_ns, end_ns) of collections not yet in the registry.
#: The callback only appends here: a collection can start under any
#: allocation, also one made while this thread holds the registry's lock,
#: so the callback may take no lock. `flush_gc` moves them into
#: `runtime.gc_pause_seconds` at every top-level span's close and at every
#: snapshot; beyond `maxlen` the oldest are dropped
_gc_log: deque = deque(maxlen=1 << 16)
_gc_open: List = [0, None]  # start_ns and annotation of the collection running
_gc_watchers = 0
_gc_lock = threading.Lock()
#: the collector's policy while a server is up (serving/collector.py sets
#: and clears it): `_on_gc` tells it of every full collection's end, still
#: inside the collector, and `flush_gc` lets it publish what it counted there
gc_policy = None


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_open[0] = clock_ns()
        if info["generation"] == 2:
            _gc_open[1] = annotate("phant/gc", generation=2)
        return
    t1 = clock_ns()
    t0, ann = _gc_open
    _gc_open[1] = None
    end_annotation(ann)
    if not t0:
        return  # installed while this collection ran: no start was seen
    _gc_open[0] = 0
    gen = info["generation"]
    full = gen == 2
    policy = gc_policy
    if full and policy is not None and policy.full_collection_ended():
        gen = "deep"  # the policy's own collection, run while nobody waits
    _gc_log.append((gen, t0, t1))
    if full:
        # a full collection stops every thread: an interval of every
        # request that is open now
        for sp in list(_open_spans.values()):
            sp.intervals.append(("gc", t0, t1))


def flush_gc() -> None:
    """Move the collections logged so far into the registry."""
    while _gc_log:
        try:
            gen, t0, t1 = _gc_log.popleft()
        except IndexError:  # another thread flushed it
            return
        metrics.observe_hist(
            "runtime.gc_pause_seconds", (t1 - t0) / 1e9, generation=str(gen)
        )
    policy = gc_policy
    if policy is not None:
        policy.flush()


def watch_gc() -> None:
    """Install the collector's callback (counted: the Engine API server
    calls this at start and `unwatch_gc` at shutdown)."""
    global _gc_watchers
    with _gc_lock:
        _gc_watchers += 1
        if _gc_watchers == 1:
            gc.callbacks.append(_on_gc)


def unwatch_gc() -> None:
    global _gc_watchers
    with _gc_lock:
        if _gc_watchers == 0:
            return
        _gc_watchers -= 1
        if _gc_watchers == 0:
            try:
                gc.callbacks.remove(_on_gc)
            except ValueError:
                pass
    flush_gc()


@contextlib.contextmanager
def jax_profile(logdir: Optional[str] = None) -> Iterator[None]:
    """Capture a JAX/XLA device trace (view with TensorBoard or Perfetto);
    no-op when logdir is None so call sites can be left in production code.
    The profiler's Python tracer is OFF and its host tracer at level 1:
    with jax's default (the Python tracer on) a capture slowed this server
    fourteenfold (PERF.md section 3) and measured the profiler; at level 1
    the host plane holds the program's own `phant/` annotations."""
    if logdir is None:
        yield
        return
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
