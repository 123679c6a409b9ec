"""Coalesced sender recovery across serving requests — the sig lane engine.

The paper's stateless hot loop is THREE batched kernels — witness keccak,
post-state-root recomputation, and batched ecrecover over each block's tx
list. The first two ride the batched/pipelined/mesh-sharded serving path
(the witness lane; PR 11's root lane); until this module, sender recovery
did not: every `engine_executeStatelessPayloadV1` paid
`TxSigner.get_senders_batch` synchronously on its handler thread, and the
per-request PHANT_TPU_MIN_ECRECOVER floor (default 64) means a typical
mainnet block (~8-200 txs, usually below the floor) NEVER reaches the
device kernel under serving traffic, no matter how many requests are
concurrently in flight. This engine closes that gap: each request builds
its signature rows `(signing_hash, r, s, recid)` on its own handler
thread (`TxSigner.signature_rows` — host keccak over RLP, embarrassingly
parallel; invalid signatures ride the placeholder lane exactly like
`recover_senders_async`), and the serving scheduler's sig lane hands
concurrent requests' rows here, where they MERGE into ONE device
ecrecover dispatch: K requests' signatures recover in one kernel launch
instead of K sub-floor native batches, and each request gets back its own
sender slice.

THE OFFLOAD-GATE STORY (single source of truth — signer.TxSigner and
stateless.dispatch_sender_recovery point here): the device ecrecover
kernel only wins once the batch amortizes transfer + dispatch latency, so
the same PHANT_TPU_MIN_ECRECOVER floor that gates the per-request path
gates this engine — but applied to the MERGED row count across the
batch's requests. A lone sub-floor request therefore performs zero
merged-dispatch work and lands on the fused native batch (recover +
keccak + address in one FFI call — today's behavior, byte-identical by
construction), and the round-2 invariant — never slower than cpu
end-to-end — survives. Coalescing is what changes the verdict: K blocks'
concatenated tx lists clear the floor no single block can, the exact
below-break-even-alone / wins-when-batched shape that already
rehabilitated witness keccak and the root lane. `device_floor` >= 0
overrides the floor (0 forces the device — the XLA-CPU proxy/tests knob;
the env twin is PHANT_SIG_DEVICE_FLOOR). The device route runs the
Shamir interleaved ladder (`ops/secp256k1_jax.ecrecover_kernel`).

Protocol: `prefetch_batch` / `begin_batch` / `resolve_batch` /
`abandon_batch` / the fused `sig_many` — deliberately the same names and
semantics as WitnessEngine's two-phase API, so the scheduler's pipeline,
crash paths (handle abandonment), prefetch worker, and mesh lanes drive
this engine through the code path they already drive the witness and
root engines through. The prefetch stage runs the merge LOWERING (row
concatenation + the u256 -> (B,16) u32 limb encode) off the serving
critical path; dispatch enqueues the kernel with ZERO host sync
(HOSTSYNC-scoped); resolve pays the readback. Unlike witness pack blobs
and root merge blobs there is no pooled staging lease: the limb arrays
are a few KB per batch and the limb ENCODE, not the allocation, is the
merge cost — so an abandoned handle strands nothing.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Sequence

from phant_tpu.utils.trace import device_host, metrics

class SigPrefetch:
    """Output of `SigEngine.prefetch_batch`: the merged rows + limb-packed
    device inputs, computed OFF the serving critical path (the
    scheduler's prefetch worker / a mesh lane's prefetch stage).
    Advisory by identity: `begin_batch(rows_list, prefetch=...)` only
    consumes it when `rows_list` is the SAME list object the merge ran
    over. `release()` exists for crash-path symmetry with the witness and
    root plans; there are no pooled leases to return (idempotent no-op
    beyond dropping the arrays)."""

    __slots__ = ("rows_list", "packed", "n_rows")

    def __init__(self, rows_list, packed, n_rows):
        self.rows_list = rows_list
        self.packed = packed  # `pack_signatures`' arrays and rungs, or None
        self.n_rows = n_rows

    def release(self) -> None:
        self.packed = None


class SigHandle:
    """One in-flight sig batch between `begin_batch` and `resolve_batch`.
    Opaque to callers; `resolved` flips once the senders were returned
    (or the handle was abandoned on a crash path)."""

    __slots__ = (
        "rows_list",
        "n_rows",      # merged signature rows across the batch's requests
        "device_out",  # unresolved (digest_words, valid) of each launch
        "backend",     # "device" | "native" | "scalar"
        "resolved",
    )

    def __init__(self):
        for name in self.__slots__:
            setattr(self, name, None)
        self.n_rows = 0
        self.resolved = False


class SigEngine:
    """Cross-request sender-recovery executor (see module docstring).

    `device_index` pins dispatches to one mesh device — the serving pool
    gives each lane its own pinned SigEngine, so sig batches routed to a
    lane recover on that lane's chip (the witness/root-engine pinning
    model). `device_floor`: -1 (default) = the PHANT_TPU_MIN_ECRECOVER
    floor applied to the MERGED row count (resolved ONCE here, never on
    the hot path); 0 forces the device route (tests / XLA-CPU proxy);
    > 0 is a fixed merged-row floor. Thread-safe: stats under `_lock`;
    merge/dispatch/resolve touch no shared tables (rows are
    caller-owned)."""

    def __init__(
        self,
        device_floor: Optional[int] = None,
        device_index: Optional[int] = None,
    ):
        if device_floor is None:
            device_floor = int(os.environ.get("PHANT_SIG_DEVICE_FLOOR", "-1"))
        self._device_floor = device_floor
        # the per-call env re-read this engine replaces (signer.py r14
        # bugfix): the floor is a process-lifetime deployment knob,
        # resolved once per engine
        self._min_device = int(os.environ.get("PHANT_TPU_MIN_ECRECOVER", "64"))
        self._device_index = device_index
        self._pinned = None
        self._lock = threading.Lock()
        self.stats = {
            "sig_batches": 0,
            "sig_requests": 0,
            "sig_rows": 0,
            "device_batches": 0,
            "native_batches": 0,
            "scalar_batches": 0,
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
    def _n_rows(rows_list: Sequence) -> int:
        return sum(r.n for r in rows_list)

    def _route_device(self, n_rows: int) -> bool:
        """THE routing predicate (see the module docstring's offload-gate
        story): device iff a device exists and the MERGED row count
        clears the ecrecover floor — a lone sub-floor request keeps the
        fused native batch. With NO native toolchain a sub-floor batch
        still promotes to the device (the kernel beats scalar Python
        even below the floor — the floor only arbitrates device vs the
        fused NATIVE batch, the same promotion `recover_rows_async`
        applies; without it the lane would be slower than the inline
        path it replaced on toolchain-less TPU deployments)."""
        from phant_tpu.backend import crypto_backend, jax_device_ok

        if n_rows == 0:
            return False
        if crypto_backend() != "tpu" or not jax_device_ok():
            return False
        floor = (
            self._device_floor if self._device_floor >= 0 else self._min_device
        )
        if n_rows >= floor:
            return True
        from phant_tpu.utils.native import load_native

        return load_native() is None

    # -- merge (the row-lowering stage) ---------------------------------------

    @staticmethod
    def _merge(rows_list: Sequence):
        """The device kernel's inputs for the batch's merged rows, padded
        to the launches they go out as: one rung of
        `secp256k1_jax.SIG_LADDER`, or above its top several launches of
        the top rung (`pack_signatures`, the one packing
        `ecrecover_batch_async` uses too). Pure host work: list
        concatenation + the u256 -> limb encode."""
        from phant_tpu.ops.secp256k1_jax import pack_signatures

        es: List[int] = []
        rs: List[int] = []
        ss: List[int] = []
        pars: List[int] = []
        for rows in rows_list:
            es.extend(int.from_bytes(m, "big") for m in rows.msgs)
            rs.extend(rows.rs)
            ss.extend(rows.ss)
            pars.extend(rid & 1 for rid in rows.recids)
        return pack_signatures(es, rs, ss, pars)

    # -- two-phase protocol (scheduler pipeline shape) ------------------------

    def prefetch_batch(self, rows_list: Sequence) -> SigPrefetch:
        """STAGE 0 for sig batches: run the merge (row concat + limb
        encode) off the serving critical path. Identity-advisory — pass
        the SAME rows list to `begin_batch(rows_list, prefetch=...)`."""
        with metrics.phase("witness_engine.sig_prefetch"):
            n_rows = self._n_rows(rows_list)
            if not self._route_device(n_rows):
                # host route: a limb pack would go unused — carry only
                # the row count (begin_batch re-checks and routes host)
                return SigPrefetch(rows_list, None, n_rows)
            return SigPrefetch(rows_list, self._merge(rows_list), n_rows)

    def begin_batch(
        self, rows_list: Sequence, prefetch: Optional[SigPrefetch] = None
    ) -> SigHandle:
        """Pack + dispatch one sig batch with no host sync: route by the
        offload gate, merge (or consume the prefetch merge), and enqueue
        the ecrecover kernel. Everything that needs the senders waits for
        `resolve_batch` (host routes run their fused native batch
        there, off the executor thread)."""
        pf = prefetch
        if pf is not None and pf.rows_list is not rows_list:
            pf.release()  # not the batch this merge was computed for
            pf = None
        h = SigHandle()
        h.rows_list = list(rows_list)
        with metrics.phase("witness_engine.sig_pack"):
            h.n_rows = pf.n_rows if pf is not None else self._n_rows(rows_list)
            route = self._route_device(h.n_rows)
            packed = None
            if route:
                if pf is not None and pf.packed is not None:
                    packed = pf.packed
                    pf.packed = None  # ownership moves
                else:
                    packed = self._merge(rows_list)
            else:
                h.backend = "host"  # native vs scalar classified at resolve
                if pf is not None:
                    pf.release()
        if route:
            with metrics.phase("witness_engine.sig_dispatch", rung=sum(packed[4])):
                try:
                    from phant_tpu.ops.secp256k1_jax import launch_ecrecover

                    with device_host("sig", "enqueue"):
                        h.device_out = launch_ecrecover(
                            packed, h.n_rows, self._pinned_device()
                        )
                    h.backend = "device"
                except Exception:
                    import logging

                    from phant_tpu.backend import device_fallback

                    device_fallback("sig_dispatch")
                    logging.getLogger("phant.sig").warning(
                        "device sig dispatch failed for %d rows; "
                        "native fallback at resolve",
                        h.n_rows,
                        exc_info=True,
                    )
                    h.backend = "host"
        return h

    def resolve_batch(self, handle: SigHandle) -> List[List[Optional[bytes]]]:
        """Per-request sender slices (tx order within each request; None =
        invalid signature — the caller raises with the right per-block
        attribution, `blockchain.chain.apply_body`). Device: the address
        readback is the honest sync; host: the fused native batch over
        the SAME merged rows (one FFI call for K requests — still
        coalesced), or the scalar pure-Python path when no toolchain is
        present. Byte-identical across routes by construction
        (differential-tested)."""
        if handle.resolved:
            raise RuntimeError("sig handle already resolved")
        try:
            with metrics.phase("witness_engine.sig_resolve"):
                if handle.backend == "device":
                    flat = self._resolve_device(handle)
                else:
                    flat = self._resolve_host(handle)
                out: List[List[Optional[bytes]]] = []
                pos = 0
                # merged rows concatenate per request in order; the bad
                # (placeholder-lane) mask re-applies per request
                for rows in handle.rows_list:
                    senders = flat[pos : pos + rows.n]
                    pos += rows.n
                    if rows.bad:
                        senders = [
                            None if i in rows.bad else a
                            for i, a in enumerate(senders)
                        ]
                    out.append(senders)
        except BaseException:
            self.abandon_batch(handle)
            raise
        handle.resolved = True
        n = len(handle.rows_list)
        backend = handle.backend or "native"
        handle.device_out = None
        with self._lock:
            self.stats["sig_batches"] += 1
            self.stats["sig_requests"] += n
            self.stats["sig_rows"] += handle.n_rows
            self.stats[backend + "_batches"] += 1
        metrics.count("witness_engine.sig_batches", backend=backend)
        return out

    @staticmethod
    def _resolve_device(handle: SigHandle) -> List[Optional[bytes]]:
        from phant_tpu.ops.secp256k1_jax import read_ecrecover, senders_of

        with device_host("sig", "sync"):
            digests, valid = read_ecrecover(handle.device_out)
        return senders_of(digests, valid, handle.n_rows)

    @staticmethod
    def _resolve_host(handle: SigHandle) -> List[Optional[bytes]]:
        """The offload-gated host route over the SAME merged rows — one
        fused native batch for K requests, or the scalar fallback. The
        recovery itself is `signer.recover_rows_host`, THE shared
        definition the local `recover_rows_async` path uses too (the
        byte-identity contract rides on there being exactly one). The
        backend classification lands on the handle so batch records and
        the lone-request gate read which path actually ran."""
        from phant_tpu.signer.signer import recover_rows_host

        msgs: List[bytes] = []
        rs: List[int] = []
        ss: List[int] = []
        rids: List[int] = []
        for rows in handle.rows_list:
            msgs.extend(rows.msgs)
            rs.extend(rows.rs)
            ss.extend(rows.ss)
            rids.extend(rows.recids)
        out, handle.backend = recover_rows_host(msgs, rs, ss, rids)
        return out

    def abandon_batch(self, handle: SigHandle) -> None:
        """Release a handle WITHOUT resolving it — the crash path. No
        pooled leases back this engine (see the module docstring), so
        abandonment only retires the handle; an enqueued device dispatch
        completes into garbage-collected arrays. Idempotent."""
        if handle.resolved:
            return
        handle.resolved = True
        handle.device_out = None
        handle.rows_list = []

    # -- fused one-call face ---------------------------------------------------

    def sig_many(self, rows_list: Sequence) -> List[List[Optional[bytes]]]:
        """K requests' sender slices in one engine call — begin + resolve
        fused (the depth-1 scheduler path and offline callers)."""
        return self.resolve_batch(self.begin_batch(rows_list))

    def stats_snapshot(self) -> dict:
        with self._lock:
            return dict(self.stats)


_shared: Optional[SigEngine] = None
_shared_lock = threading.Lock()


def shared_sig_engine() -> SigEngine:
    """Process-global sig engine (the scheduler default — signature rows
    carry no cross-request state, so one engine serves any number of
    schedulers)."""
    global _shared
    with _shared_lock:
        if _shared is None:
            _shared = SigEngine()
        return _shared
