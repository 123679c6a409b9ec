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
(witness_engine._staging), keyed by pow2 size, returned at resolve (or
abandon) exactly like witness pack leases.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from phant_tpu.utils.trace import device_host, metrics


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

    def _merge(self, plans: Sequence) -> Tuple[object, list, tuple, int]:
        """(merged plan, per-plan out rows, staging lease, payload):
        concatenate the batch's plans into one level-aligned program over
        a pooled blob (ops/mpt_jax.merge_plans)."""
        from phant_tpu.crypto.keccak import RATE
        from phant_tpu.ops.mpt_jax import MPT_MAX_CHUNKS, _pow2, merge_plans
        from phant_tpu.ops.witness_engine import _staging

        payload = self._payload_bytes(plans)
        raw = sum(len(p.blob) for p in plans)
        # the SAME pow2 merge_plans sizes its blob with — the pooled
        # lease can never come up short
        need = _pow2(raw + MPT_MAX_CHUNKS * RATE)
        key = ("root_blob", need)
        entry = _staging.take(key)
        if entry is None:
            entry = {"blob": np.zeros(need, np.uint8), "dirty": 0}
        blob = entry["blob"]
        if entry["dirty"] > raw:
            blob[raw : entry["dirty"]] = 0
        entry["dirty"] = raw
        merged, outs = merge_plans(plans, blob_out=blob)
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
            metrics.count("witness_engine.root_plan_stale")
        h = RootHandle()
        h.plans = list(plans)
        with metrics.phase("witness_engine.root_pack"):
            h.payload = pf.payload if pf is not None else self._payload_bytes(plans)
            route = self._route_device(h.payload)
            if route:
                if pf is not None and pf.merged is not None:
                    h.merged, h.outs, h.lease = pf.merged, pf.outs, pf.lease
                    pf.lease = pf.merged = pf.outs = None  # ownership moves
                    metrics.count("witness_engine.root_plan_hits")
                else:
                    h.merged, h.outs, h.lease, _ = self._merge(plans)
            else:
                h.backend = "host"
                if pf is not None:
                    pf.release()  # host route: the merge goes unused
        if route:
            with metrics.phase("witness_engine.root_dispatch"):
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
            _hash_plan_outputs,
            _pow2,
        )

        out_rows = merged.out_rows
        rp = _pow2(len(out_rows))
        padded = np.full(rp, out_rows[-1], np.int32)
        padded[: len(out_rows)] = out_rows
        device = self._pinned_device()
        if device is not None:
            # committed inputs pin the compute with them (mesh lanes)
            blob_d = jax.device_put(merged.blob, device)
            rows_d = jax.device_put(padded, device)
            levels_d = tuple(
                tuple(jax.device_put(a, device) for a in lvl)  # phantlint: disable=JNPHOSTLOOP — bounded per-level metadata upload
                for lvl in merged.levels
            )
        else:
            blob_d = jnp.asarray(merged.blob)
            rows_d = jnp.asarray(padded)
            levels_d = tuple(
                tuple(jnp.asarray(a) for a in lvl) for lvl in merged.levels  # phantlint: disable=JNPHOSTLOOP — bounded per-level metadata upload
            )
        return _hash_plan_outputs(
            blob_d, levels_d, rows_d, max_chunks=MPT_MAX_CHUNKS
        )

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
        metrics.count("witness_engine.root_requests", n)
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
        fused (the depth-1 scheduler path and the offline bench face)."""
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
