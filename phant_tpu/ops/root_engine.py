"""Batched post-state-root recomputation across serving requests.

The paper's stateless hot loop is TWO batched kernels — witness keccak and
post-state-root recomputation — but until this module only the first ever
rode the batched/pipelined/mesh-sharded serving path: every
`engine_executeStatelessPayloadV1` paid its post root as serial host
Python (`WitnessStateDB.state_root()` — keccak per node, per request, per
storage trie). This engine closes that gap: each request builds ONE fused
account+storage `HashPlan` on its own handler thread
(stateless.WitnessStateDB.post_root_plan — host structural work,
embarrassingly parallel), and the serving scheduler's root lane hands
concurrent requests' plans here, where they MERGE into one level-aligned
device program (ops/mpt_jax.merge_plans + `_hash_plan_outputs`): K
requests' dirty subtrees hash in max(depth) sequential keccak rounds and
one dispatch instead of K host walks.

THE OFFLOAD-GATE STORY (single source of truth — stateless.PartialTrie
and mpt.trie_root_hash point here): a post-root re-hash ships template
bytes to the device and reads 32 B/root back, so the decision is the same
link-aware cost model every other hashing route uses
(backend.device_offload_pays — upload + round trip must beat hashing the
same bytes natively). One witness subtree is a few hundred nodes, BELOW
the break-even alone: a lone request therefore keeps the host walk, and
the round-2 invariant — never slower than cpu end-to-end — survives by
construction. Coalescing is what changes the verdict: the merged payload
of a full batch clears the bar the way a single request cannot, the exact
below-break-even-alone / wins-when-batched shape cross-request coalescing
already rehabilitated for witness keccak. `device_floor` >= 0 overrides
the model (0 forces the device — the XLA-CPU proxy/tests knob; the env
twin is PHANT_ROOT_DEVICE_FLOOR).

Protocol: `prefetch_batch` / `begin_batch` / `resolve_batch` /
`abandon_batch` / the fused `root_many` — deliberately the same names and
semantics as WitnessEngine's two-phase API, so the scheduler's pipeline,
crash paths (handle abandonment), prefetch worker, and mesh lanes drive
either engine through one code path. Dispatch enqueues with ZERO host
sync (HOSTSYNC-scoped); resolve pays the readback. Merged staging blobs
lease from the same process-global pool as witness staging
(witness_engine._staging), keyed by the rung's size, returned at resolve
(or abandon) exactly like witness pack leases.

THE PROGRAM'S SHAPES: a merged plan is laid out on a rung of
`mpt_jax.PLAN_LADDER` (strips of 128 rows; steps, blob and read-back rows
grow together), so `_hash_plan_outputs` is built once a rung and never
for a block's own sizes; `prewarm_ladder` builds every rung when a
server starts on an accelerator. `root.plan_shapes` counts the shapes
this process has dispatched, `root.plan_rung{rung=}` the dispatches by
rung (`over` = a batch above the top rung, hashed on the host), and
`root.plan_rows{kind=real|pad}` what the strips' padding costs in keccak
rows.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from phant_tpu.utils.trace import device_host, metrics

#: the (device, rung) pairs `_hash_plan_outputs` has run on in this process:
#: its shapes, since a rung fixes every one of them (`root.plan_shapes`)
_plan_shapes: set = set()
_plan_shapes_lock = threading.Lock()


def note_plan_shape(key: Optional[tuple] = None) -> None:
    """Count `key` among the shapes run and set `root.plan_shapes`; with
    no key, only set the gauge: a server calls that at its start, so the
    family is on /metrics before the first root program (0 then)."""
    with _plan_shapes_lock:
        if key is not None:
            _plan_shapes.add(key)
        n = len(_plan_shapes)
    metrics.gauge_set("root.plan_shapes", n)


def _run_empty(rung):
    """The served program once on `rung`, over zeros and with no strip to
    walk: what builds it. Returns the unresolved output. The zeros are
    the host's, uploaded: made on the device each shape would be a small
    program of its own (33 more built at start, my chip runs, PR 28)."""
    import jax.numpy as jnp

    from phant_tpu.ops.mpt_jax import (
        MPT_MAX_CHUNKS,
        STRIP_HOLES,
        STRIP_ROWS,
        _hash_plan_outputs,
    )

    rows = jnp.asarray(np.zeros((rung.steps, STRIP_ROWS), np.int32))
    holes = jnp.asarray(np.zeros((rung.steps, STRIP_HOLES), np.int32))
    return _hash_plan_outputs(
        jnp.asarray(np.zeros(rung.blob, np.uint8)),
        rows,
        rows,
        holes,
        holes,
        jnp.asarray(np.int32(0)),
        jnp.asarray(np.zeros(rung.outs, np.int32)),
        max_chunks=MPT_MAX_CHUNKS,
    )


def prewarm_ladder(device=None) -> Tuple[int, float]:
    """Build the served root program on every rung of the ladder, so that
    no request ever waits for one: called when a server starts with the
    root lane on and an accelerator under it (engine_api/server.py).
    Returns the rungs built and the seconds it took
    (`root.prewarm_seconds`)."""
    import time

    import jax

    from phant_tpu.ops.mpt_jax import PLAN_LADDER

    t0 = time.monotonic()
    with jax.default_device(device):
        for k, r in enumerate(PLAN_LADDER):
            _run_empty(r).block_until_ready()  # phantlint: disable=HOSTSYNC — boot prewarm: the build is the point
            note_plan_shape((device, k))
    dt = time.monotonic() - t0
    metrics.gauge_set("root.prewarm_seconds", dt)
    return len(PLAN_LADDER), dt


class RootPrefetch:
    """Output of `RootEngine.prefetch_batch`: the merged plan + filled
    staging lease, computed OFF the serving critical path (the scheduler's
    prefetch worker / a mesh lane's prefetch stage). Advisory by identity:
    `begin_batch(plans, prefetch=...)` only consumes it when `plans` is
    the SAME list object the merge ran over; anything else releases it.
    `release()` is idempotent (consumption nulls the lease)."""

    __slots__ = ("plans", "merged", "outs", "lease", "payload")

    def __init__(self, plans, merged, outs, lease, payload):
        self.plans = plans
        self.merged = merged
        self.outs = outs
        self.lease = lease  # (key, entry) from the shared staging pool
        self.payload = payload

    def release(self) -> None:
        if self.lease is not None:
            from phant_tpu.ops.witness_engine import _staging

            key, entry = self.lease
            self.lease = self.merged = self.outs = None
            _staging.give(key, entry)


class RootHandle:
    """One in-flight root batch between `begin_batch` and `resolve_batch`.
    Opaque to callers; `resolved` flips once the digests were returned
    (or the handle was abandoned on a crash path)."""

    __slots__ = (
        "plans",
        "merged",
        "outs",        # per-plan merged out rows (device route)
        "lease",
        "device_out",  # unresolved (Rp, 8) u32 device array
        "backend",     # "device" | "host"
        "payload",
        "resolved",
    )

    def __init__(self):
        for name in self.__slots__:
            setattr(self, name, None)
        self.resolved = False


class RootEngine:
    """Cross-request post-root executor (see module docstring).

    `device_index` pins dispatches to one mesh device — the serving pool
    gives each lane its own pinned RootEngine, so root batches routed to
    a lane hash on that lane's chip (the witness-engine pinning model).
    `device_floor`: -1 (default) = the adaptive link-aware gate; 0 forces
    the device route (tests / XLA-CPU proxy); > 0 is a fixed payload-byte
    floor. Thread-safe: stats under `_lock`; merge/dispatch/resolve touch
    no shared tables (plans are caller-owned)."""

    def __init__(
        self,
        device_floor: Optional[int] = None,
        device_index: Optional[int] = None,
    ):
        if device_floor is None:
            device_floor = int(os.environ.get("PHANT_ROOT_DEVICE_FLOOR", "-1"))
        self._device_floor = device_floor
        self._device_index = device_index
        self._pinned = None
        self._lock = threading.Lock()
        self.stats = {
            "root_batches": 0,
            "root_requests": 0,
            "device_batches": 0,
            "host_batches": 0,
        }

    # -- routing --------------------------------------------------------------

    def _pinned_device(self):
        if self._device_index is None:
            return None
        if self._pinned is None:
            import jax

            devices = jax.devices()
            self._pinned = devices[self._device_index % len(devices)]
        return self._pinned

    @staticmethod
    def _payload_bytes(plans: Sequence) -> int:
        """Total template bytes across the batch — the shippable payload
        the offload gate weighs (ops/mpt_jax.plan_payload_bytes, the one
        definition the scheduler's byte accounting shares)."""
        from phant_tpu.ops.mpt_jax import plan_payload_bytes

        return sum(plan_payload_bytes(p) for p in plans)

    def _route_device(self, payload: int) -> bool:
        """THE routing predicate (see the module docstring's offload-gate
        story): device iff a device exists and the merged payload clears
        the link-aware break-even — a lone sub-break-even request keeps
        the host walk."""
        from phant_tpu.backend import (
            crypto_backend,
            device_offload_pays,
            jax_device_ok,
        )

        if crypto_backend() != "tpu" or not jax_device_ok():
            return False
        if self._device_floor >= 0:
            return payload >= self._device_floor
        return device_offload_pays(payload)

    # -- merge (the plan-lowering stage) --------------------------------------

    def _merge(self, plans: Sequence) -> Tuple[object, list, Optional[tuple], int]:
        """(merged plan, per-plan out rows, staging lease, payload):
        concatenate the batch's plans into one program on a rung of the
        ladder, over a pooled blob (ops/mpt_jax.merge_plans). The plan
        and the lease are None where the batch is over the top rung."""
        from phant_tpu.ops.mpt_jax import merge_plans
        from phant_tpu.ops.witness_engine import _staging

        taken: list = []

        def lease(size: int) -> np.ndarray:
            key = ("root_blob", size)
            entry = _staging.take(key)
            if entry is None:
                entry = {"blob": np.zeros(size, np.uint8), "dirty": 0}
            taken.append((key, entry))
            return entry["blob"]

        payload = self._payload_bytes(plans)
        merged, outs = merge_plans(plans, lease=lease)
        if merged is None:
            metrics.count("root.plan_rung", rung="over")
            return None, outs, None, payload
        key, entry = taken[0]
        # templates lie head to tail from 0: clear what an earlier, longer
        # merge left beyond them
        if entry["dirty"] > merged.used:
            merged.blob[merged.used : entry["dirty"]] = 0
        entry["dirty"] = merged.used
        return merged, outs, (key, entry), payload

    # -- two-phase protocol (scheduler pipeline shape) ------------------------

    def prefetch_batch(self, plans: Sequence) -> RootPrefetch:
        """STAGE 0 for root batches: run the merge (host memcpy + index
        remap work) off the serving critical path. Identity-advisory —
        pass the SAME plans list to `begin_batch(plans, prefetch=...)`;
        an unused plan must be `release()`d."""
        with metrics.phase("witness_engine.root_prefetch"):
            payload = self._payload_bytes(plans)
            if not self._route_device(payload):
                # host route: a merge would go unused — carry only the
                # payload verdict (begin_batch re-checks and routes host)
                return RootPrefetch(plans, None, None, None, payload)
            merged, outs, lease, payload = self._merge(plans)
            return RootPrefetch(plans, merged, outs, lease, payload)

    def begin_batch(
        self, plans: Sequence, prefetch: Optional[RootPrefetch] = None
    ) -> RootHandle:
        """Pack + dispatch one root batch with no host sync: route by the
        offload gate, merge (or consume the prefetch merge), and enqueue
        the fused device program. Everything that needs the digests waits
        for `resolve_batch`."""
        pf = prefetch
        if pf is not None and pf.plans is not plans:
            pf.release()  # not the batch this merge was computed for
            pf = None
        h = RootHandle()
        h.plans = list(plans)
        with metrics.phase("witness_engine.root_pack"):
            h.payload = pf.payload if pf is not None else self._payload_bytes(plans)
            route = self._route_device(h.payload)
            if route:
                if pf is not None and pf.merged is not None:
                    h.merged, h.outs, h.lease = pf.merged, pf.outs, pf.lease
                    pf.lease = pf.merged = pf.outs = None  # ownership moves
                else:
                    h.merged, h.outs, h.lease, _ = self._merge(plans)
                route = h.merged is not None  # over the ladder: host
            if not route:
                h.backend = "host"
                if pf is not None:
                    pf.release()  # host route: the merge goes unused
        if route:
            with metrics.phase("witness_engine.root_dispatch", rung=h.merged.rung):
                try:
                    with device_host("root", "enqueue"):
                        h.device_out = self._dispatch(h.merged)
                    h.backend = "device"
                except Exception:
                    import logging

                    from phant_tpu.backend import device_fallback

                    device_fallback("root_dispatch")
                    logging.getLogger("phant.root").warning(
                        "device root dispatch failed for %d plans; "
                        "host fallback at resolve",
                        len(plans),
                        exc_info=True,
                    )
                    self._release_lease(h)
                    h.backend = "host"
        return h

    def _dispatch(self, merged):
        """Enqueue the merged program on the (possibly pinned) device —
        upload + kernel launch, ZERO host sync; returns the unresolved
        (Rp, 8) u32 output array."""
        import jax
        import jax.numpy as jnp

        from phant_tpu.ops.mpt_jax import (
            MPT_MAX_CHUNKS,
            STRIP_ROWS,
            _hash_plan_outputs,
        )

        device = self._pinned_device()
        # committed inputs pin the compute with them (mesh lanes)
        put = jnp.asarray if device is None else (lambda a: jax.device_put(a, device))
        args = [
            put(a)
            for a in (
                merged.blob,
                merged.off,
                merged.ln,
                merged.hole_pos,
                merged.hole_child,
                np.int32(merged.n_steps),
                merged.out_rows,
            )
        ]
        out = _hash_plan_outputs(*args, max_chunks=MPT_MAX_CHUNKS)
        note_plan_shape((device, merged.rung))
        metrics.count("root.plan_rung", rung=str(merged.rung))
        metrics.count("root.plan_rows", merged.n_nodes, kind="real")
        metrics.count(
            "root.plan_rows", merged.n_steps * STRIP_ROWS - merged.n_nodes, kind="pad"
        )
        return out

    def resolve_batch(self, handle: RootHandle) -> List[List[bytes]]:
        """Per-plan out-row digests (each plan's storage roots in patch
        order, its post root LAST — `HashPlan.out_rows` order). Device:
        the readback is the honest sync; host: the per-plan CPU mirror
        (execute_plan_outputs_host), byte-identical by construction."""
        if handle.resolved:
            raise RuntimeError("root handle already resolved")
        try:
            with metrics.phase("witness_engine.root_resolve"):
                if handle.backend == "device":
                    with device_host("root", "sync"):
                        arr = np.asarray(handle.device_out, dtype="<u4")  # phantlint: disable=HOSTSYNC — timed root readback is the product
                    flat = [arr[k].tobytes() for k in range(arr.shape[0])]
                    out: List[List[bytes]] = []
                    pos = 0
                    # merged out rows concatenate per plan in order
                    for rows in handle.outs:
                        out.append(flat[pos : pos + len(rows)])
                        pos += len(rows)
                else:
                    from phant_tpu.ops.mpt_jax import execute_plan_outputs_host

                    out = [
                        execute_plan_outputs_host(p) for p in handle.plans
                    ]
        except BaseException:
            self.abandon_batch(handle)
            raise
        handle.resolved = True
        self._release_lease(handle)
        n = len(handle.plans)
        backend = handle.backend or "host"
        with self._lock:
            self.stats["root_batches"] += 1
            self.stats["root_requests"] += n
            self.stats[backend + "_batches"] += 1
        metrics.count("witness_engine.root_batches", backend=backend)
        return out

    def abandon_batch(self, handle: RootHandle) -> None:
        """Release a handle WITHOUT resolving it — the crash path. A
        device lease stays stranded when a dispatch may still be reading
        it (the witness-engine contract: bounded loss on a crash path);
        an undispatched merge lease returns to the pool. Idempotent."""
        if handle.resolved:
            return
        handle.resolved = True
        if handle.device_out is None:
            self._release_lease(handle)
        handle.device_out = None
        handle.plans = []

    @staticmethod
    def _release_lease(handle: RootHandle) -> None:
        if handle.lease is not None:
            from phant_tpu.ops.witness_engine import _staging

            key, entry = handle.lease
            handle.lease = handle.merged = None
            _staging.give(key, entry)

    # -- fused one-call face ---------------------------------------------------

    def root_many(self, plans: Sequence) -> List[List[bytes]]:
        """K requests' out digests in one engine call — begin + resolve
        fused (the depth-1 scheduler path and offline callers)."""
        return self.resolve_batch(self.begin_batch(plans))

    def stats_snapshot(self) -> dict:
        with self._lock:
            return dict(self.stats)


_shared: Optional[RootEngine] = None
_shared_lock = threading.Lock()


def shared_root_engine() -> RootEngine:
    """Process-global root engine (the scheduler default — plans carry no
    cross-request state, so one engine serves any number of schedulers)."""
    global _shared
    with _shared_lock:
        if _shared is None:
            _shared = RootEngine()
        return _shared
