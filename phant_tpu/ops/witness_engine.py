"""Memoized witness-verification engine: hash once, verify forever.

A continuously-validating stateless client sees the same trie nodes over and
over: the upper levels of the state trie change only along the paths the
previous block wrote, so consecutive block witnesses overlap heavily. The
reference client ignores this structure — it recomputes every node hash of
every block from scratch (reference scope: src/mpt/mpt.zig:38-119 recomputes
the root per block; src/crypto/hasher.zig:4-17 hashes one node at a time,
no reuse). This engine is the framework's north-star redesign of that loop:

  * every UNIQUE node byte-string is keccak-hashed exactly once, in large
    batches, on the selected crypto backend (the TPU kernel behind
    `--crypto_backend=tpu`, the native C batch otherwise);
  * digests and the parent->child hash references are interned into integer
    ids, so per-block linked-multiproof verification — "the nodes form a
    connected subtree rooted at the claimed state root" — collapses to a
    vectorized integer join (numpy sort + searchsorted), with no
    cryptography on the hot path at all;
  * the interning survives across blocks/batches, so the steady-state cost
    of validating block N is hashing the handful of nodes block N-1's
    writes actually changed.

Soundness: a digest is only ever computed from the full node bytes by the
(differential-tested) keccak backends, and ref->row resolution uses exact
256-bit digest equality via byte-keyed dicts. Memoization is sound because
keccak is a function; linking a foreign node would need a collision.
Verdict semantics are identical to ops/witness_jax.witness_verify_fused and
mpt/proof.verify_witness_linked (differential-tested in
tests/test_witness_engine.py).

Memory is bounded: `max_nodes` caps the interned set; crossing it drops the
oldest generation of interned nodes (their parents' child links are
re-resolved lazily if the same bytes are ever re-inserted).
"""

from __future__ import annotations

import itertools
import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from phant_tpu.utils.trace import device_host, metrics
from phant_tpu.ops.witness_jax import (
    WITNESS_MAX_CHUNKS,
    _account_storage_root_off,
    _rlp_item_bounds,
    _scan_list_refs,
)

_NO_ROW = np.int64(-1)


class _HostStaging:
    """Reusable host staging buffers, keyed by shape bucket.

    The device hashing path pads both its axes to power-of-two buckets, so
    steady-state batches land on a handful of distinct shapes — yet every
    call used to allocate (and page-zero) a fresh padded blob. This pool
    hands the same arrays back out instead: `take(key)` pops a free entry
    (or returns None, caller allocates), `give(key, entry)` returns one
    for reuse. Entries are dicts of arrays plus whatever dirty-watermark
    the caller tracks; a borrowed entry is owned exclusively by its
    borrower until given back, so pipelined batches in flight never alias
    a buffer (each holds its own lease until its resolve stage)."""

    def __init__(self, max_free_per_key: int = 4):
        self._lock = threading.Lock()
        self._free: Dict[tuple, List[dict]] = {}
        self._max_free = max_free_per_key

    def take(self, key: tuple) -> Optional[dict]:
        with self._lock:
            entries = self._free.get(key)
            if entries:
                return entries.pop()
        return None

    def give(self, key: tuple, entry: dict) -> None:
        with self._lock:
            entries = self._free.setdefault(key, [])
            if len(entries) < self._max_free:
                entries.append(entry)


#: process-global staging pool (shapes are engine-independent)
_staging = _HostStaging()


class BatchHandle:
    """One in-flight verify batch between `begin_batch` (pack + dispatch)
    and `resolve_batch` (readback/hash + commit + linkage join). Opaque to
    callers; `resolved` flips once the verdict has been returned."""

    __slots__ = (
        "kind",         # "ext" | "native" | "python"
        "n_blocks",
        "novel",        # list[bytes] to hash (empty: fully cached batch)
        "n_novel",      # len(novel), preserved after resolve clears the list
        "miss",
        "total",
        "ext_batch",    # ext core: the pyext Batch object
        "rows",         # native/python cores: scan rows
        "novel_idx",    # native core
        "joined",       # native core: pins the packed blob
        "blob",
        "offsets",
        "lens",
        "pack_entry",   # native core: staging entry to return at resolve
        "counts",       # per-block node counts (verdict composition)
        "roots",        # concatenated roots (native) / witness list (python)
        "witnesses",    # python core linkage join
        "device",       # keccak_jax.DeviceDigests when dispatched async
        "resident",     # witness_resident.ResidentBatch on the resident route
        "ref_hint",     # python core: prefetch-decoded bytes -> child refs
        "resolved",
    )

    def __init__(self):
        for name in self.__slots__:
            setattr(self, name, None)
        self.novel = []
        self.resolved = False


class _DepthStats:
    """`cache_hit_rate vs trie_depth` (PHANT_DEPTH_HIST=1): classify every
    witness-node occurrence by its depth under its block's root and by
    novelty, publishing the `witness_engine.depth_hits{depth=}` /
    `depth_misses{depth=}` counter families — the /metrics surface that
    validates the Patricia-trie reuse model (PAPERS.md 2408.14217: node
    reuse is heavy and DEPTH-SKEWED; top-of-trie nodes should hit ~always,
    leaf-level nodes carry the misses) against live traffic, and the
    measurement the resident-table eviction policy leans on.

    Depth needs node digests (parent->child links ARE digests), so the
    helper keeps its own bytes -> (digest, child-ref digests) memo: a
    never-seen node is C-hashed once HERE, and the steady state is pure
    dict lookups plus a per-block BFS from the root. Classification: the
    FIRST occurrence of never-memoized bytes is the MISS; every later
    occurrence — same batch or later — is a hit, matching the engine's
    unique-novel accounting (`cache_misses` = unique novel count, PR 5).
    The memo flushes together with the engine's generation flushes.
    Depth labels are bounded: "0".."6", "7+", and "u" for nodes
    unreachable from the root (an unlinked witness — those blocks fail
    verification anyway)."""

    def __init__(self, max_nodes: int):
        self._memo: Dict[bytes, tuple] = {}
        self._max = max(max_nodes, 1024)
        self._lock = threading.Lock()

    def flush(self) -> None:
        with self._lock:
            self._memo.clear()

    def record(self, witnesses) -> None:
        hits: Dict[str, int] = {}
        misses: Dict[str, int] = {}
        with self._lock:
            memo = self._memo
            fresh: List[bytes] = []
            seen = set()
            for _root, nodes in witnesses:
                for n in nodes:
                    if n not in memo and n not in seen:
                        seen.add(n)
                        fresh.append(n)
            if fresh and len(memo) + len(fresh) > self._max:
                # bounded like the engine tables — and the clear must
                # RE-SCAN: the batch's previously-memoized (hit) nodes
                # are gone too, and the BFS below reads memo[n] for
                # every node, so they must re-enter as fresh (their
                # occurrences count as misses, exactly like an engine
                # generation flush)
                memo.clear()
                fresh = []
                seen = set()
                for _root, nodes in witnesses:
                    for n in nodes:
                        if n not in seen:
                            seen.add(n)
                            fresh.append(n)
            if fresh:
                from phant_tpu.utils.native import load_native

                native = load_native()
                if native is not None:
                    digests = list(native.keccak256_batch_fast(fresh))
                else:
                    from phant_tpu.crypto.keccak import keccak256

                    digests = [keccak256(n) for n in fresh]
                for n, dg in zip(fresh, digests):
                    memo[n] = (dg, tuple(_extract_ref_digests(n)))
            consumed: set = set()  # fresh bytes whose one miss was counted
            for root, nodes in witnesses:
                infos = [memo[n] for n in nodes]
                by_digest: Dict[bytes, list] = {}
                for i, (dg, _refs) in enumerate(infos):
                    by_digest.setdefault(dg, []).append(i)
                depth = [-1] * len(nodes)
                frontier = list(by_digest.get(root, ()))
                for i in frontier:
                    depth[i] = 0
                d = 0
                while frontier:
                    nxt: List[int] = []
                    for i in frontier:
                        for r in infos[i][1]:
                            for j in by_digest.get(r, ()):
                                if depth[j] < 0:
                                    depth[j] = d + 1
                                    nxt.append(j)
                    frontier = nxt
                    d += 1
                for i, n in enumerate(nodes):
                    if depth[i] < 0:
                        lbl = "u"
                    elif depth[i] < 7:
                        lbl = str(depth[i])
                    else:
                        lbl = "7+"
                    if n in seen and n not in consumed:
                        consumed.add(n)
                        tgt = misses
                    else:
                        tgt = hits
                    tgt[lbl] = tgt.get(lbl, 0) + 1
        # registry publishes outside our lock (same discipline as the
        # engine: the metrics lock never nests inside ours)
        for lbl, c in hits.items():
            metrics.count("witness_engine.depth_hits", c, depth=lbl)
        for lbl, c in misses.items():
            metrics.count("witness_engine.depth_misses", c, depth=lbl)


class _PinTracker:
    """Shallow-node classifier behind depth-TIERED eviction (PR 9).

    The PR 8 depth histogram measured what PAPERS.md 2408.14217 predicts:
    cross-block reuse is depth-skewed — depth-0 nodes hit > 90%, depth 1
    > 75%, and the rate falls monotonically toward the leaves. A flat
    generation flush therefore throws away exactly the rows most likely
    to be needed again. This tracker identifies the shallow tier so the
    flush can PIN it across generations, at (near) zero hot-path cost:

      * roots are depth-0 DIGESTS by definition — noted per batch from
        the witness tuples, no hashing;
      * when a batch's novel nodes surface with their digests (every
        commit path already has both), a novel whose digest is a known
        shallow digest is pinned, and its child references (one RLP ref
        scan of that node only) become shallow digests one level deeper.

    Hit nodes cost NOTHING (no per-occurrence work — the deliberate
    contrast with the PHANT_DEPTH_HIST per-batch BFS, which stays an
    opt-in measurement tool). Classification is conservative: a shallow
    node committed before its parent's digest was known is simply not
    pinned until it next churns — a missed pin is a perf miss, never a
    correctness issue (eviction soundness never depended on WHICH rows
    survive).

    Budgets: `budget` bounds the pinned set; at flush time the snapshot
    is shallow-FIRST (per-depth allocation falls out of the live
    classification — all of depth 0, then depth 1, ... until the budget),
    because the measured hit rate is monotone in depth.

    Staleness: pins age out at FLUSH time, never on the hot path. Each
    generation records the root digests it actually served (from the
    same per-batch note_roots); the flush snapshot keeps only pins
    reachable from the last TWO generations' roots through the pinned
    nodes' own child refs (one RLP ref scan per pinned node, flush-time
    cost — two windows because a generation can be arbitrarily short
    under a novel-filler burst, and one root-less window must not kill
    a live pin). Without the prune the budget would saturate with the
    first generations' shallow nodes and a churning trie — the real
    workload — would re-commit an increasingly dead set forever."""

    __slots__ = (
        "pin_depth",
        "budget",
        "_shallow",
        "_pinned",
        "_recent_roots",
        "_prev_roots",
    )

    def __init__(self, pin_depth: int, budget: int):
        self.pin_depth = max(0, pin_depth)
        self.budget = max(1, budget)
        # digest -> min observed depth (only depths <= pin_depth kept)
        self._shallow: Dict[bytes, int] = {}
        # node bytes -> (depth, digest): the pin candidates
        self._pinned: Dict[bytes, Tuple[int, bytes]] = {}
        # root digests served in the current / previous generation: the
        # liveness evidence the flush-time prune walks from. Two windows,
        # not one — a generation can be arbitrarily short (a burst of
        # novel filler flushes back-to-back), and a pin must survive a
        # single root-less window before it counts as dead
        self._recent_roots: set = set()
        self._prev_roots: set = set()

    def _shallow_cap(self) -> int:
        # bounded advisory state: 17 refs/node over the pinned budget,
        # plus root-digest churn headroom
        return max(4096, self.budget * 17)

    def note_roots(self, roots) -> None:
        sh = self._shallow
        if len(sh) > self._shallow_cap():
            # advisory overflow: drop and rebuild from live traffic
            # (pinned entries keep their own digests)
            sh.clear()
        rr = self._recent_roots
        if len(rr) > self._shallow_cap():
            rr.clear()  # same bounded-advisory-state contract as _shallow
        for r in roots:
            if len(r) == 32:
                rr.add(r)
                if sh.get(r, 1) > 0:
                    sh[r] = 0

    def note_novel(self, novel: Sequence[bytes], digests: Sequence[bytes]) -> None:
        """Classify one commit's novel nodes. Runs pin_depth+1 passes so
        a parent and child landing in the same batch classify regardless
        of their order in the novel list (novel lists are tiny in the
        steady state — reuse makes them so)."""
        sh, pinned = self._shallow, self._pinned
        pin_depth, budget = self.pin_depth, self.budget
        for _ in range(pin_depth + 1):
            changed = False
            for nb, dg in zip(novel, digests):
                d = sh.get(dg)
                if d is None or d > pin_depth:
                    continue
                cur = pinned.get(nb)
                if cur is not None and cur[0] <= d:
                    continue
                if cur is None and len(pinned) >= budget:
                    continue  # full: only min-depth updates of existing pins
                pinned[nb] = (d, dg)
                changed = True
                if d < pin_depth and len(sh) < self._shallow_cap():
                    for r in _extract_ref_digests(nb):
                        if sh.get(r, pin_depth + 1) > d + 1:
                            sh[r] = d + 1
            if not changed:
                break

    def pinned_snapshot(self) -> List[Tuple[bytes, bytes, int]]:
        """[(node bytes, digest, depth)] shallow-first within the budget
        (ties keep insertion order — older shallow nodes first). Called
        at FLUSH time, so it first prunes stale pins and opens the next
        generation's liveness window."""
        self._prune_stale()
        items = sorted(self._pinned.items(), key=lambda kv: kv[1][0])
        return [(nb, dg, d) for nb, (d, dg) in items[: self.budget]]

    def _prune_stale(self) -> None:
        """Keep only pins reachable from a root served THIS generation,
        walking child refs through the pinned nodes themselves (depths
        re-derive along the walk). Conservative in the documented
        direction: a live deep pin whose parent never pinned is dropped
        and re-classifies when it next churns — a perf miss, never a
        correctness issue. Runs once per generation flush, never on the
        hot path."""
        pinned = self._pinned
        rr = self._recent_roots | self._prev_roots
        self._prev_roots = self._recent_roots
        self._recent_roots = set()
        if not pinned:
            return
        by_digest = {dg: nb for nb, (_d, dg) in pinned.items()}
        live: Dict[bytes, int] = {}
        frontier = [r for r in rr if r in by_digest]
        for r in frontier:
            live[r] = 0
        depth = 0
        while frontier and depth < self.pin_depth:
            nxt = []
            for dg in frontier:
                for r in _extract_ref_digests(by_digest[dg]):
                    if r in by_digest and r not in live:
                        live[r] = depth + 1
                        nxt.append(r)
            frontier = nxt
            depth += 1
        self._pinned = {
            nb: (live[dg], dg)
            for nb, (_d, dg) in pinned.items()
            if dg in live
        }

    def per_depth(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for _nb, (d, _dg) in self._pinned.items():
            out[d] = out.get(d, 0) + 1
        return out

    def flush(self) -> None:
        self._shallow.clear()
        self._pinned.clear()
        self._recent_roots.clear()
        self._prev_roots.clear()


class PrefetchPlan:
    """Output of `WitnessEngine.prefetch_batch` — everything the PACK
    stage would otherwise compute on the serving critical path: the host
    batch assembly, an ADVISORY novelty pre-scan against the committed
    tables, the decoded child references of the candidate novels, and
    pre-filled staging leases (host pack blob / device dispatch blob).

    Staleness contract: the plan is advisory end to end. begin_batch's
    lock-held scan remains the authoritative commit — a plan whose
    candidate set no longer matches (a concurrent batch committed some
    of them, a generation flushed) is simply dropped, which costs the
    perf win and nothing else. `release()` returns unconsumed staging
    leases to the pool (idempotent; begin_batch calls it, crash paths
    may call it again)."""

    __slots__ = (
        "witnesses",
        "all_nodes",
        "counts",
        "novel",      # candidate-novel bytes (advisory, dedup'd)
        "refs",       # python core: node bytes -> child-ref digests
        "pack_lease",  # native core: (key, entry) from _pack_entry
        "packed",      # native core: (joined, blob, offsets, lens)
        "device_lease",  # device route: filled staging from _stage_device_blob
    )

    def __init__(self):
        for name in self.__slots__:
            setattr(self, name, None)

    def release(self) -> None:
        """Return unconsumed staging leases to the pool (idempotent)."""
        if self.pack_lease is not None:
            key, entry = self.pack_lease
            self.pack_lease = self.packed = None
            _staging.give(key, entry)
        if self.device_lease is not None:
            key, entry = self.device_lease[0], self.device_lease[1]
            self.device_lease = None
            _staging.give(key, entry)


def _extract_ref_digests(node: bytes) -> List[bytes]:
    """The 32-byte child hash references of one RLP trie node (branch
    children, extension child, account-leaf storage root). Malformed nodes
    reference nothing (they can still BE referenced — same contract as the
    device kernel's _extract_ref_positions)."""
    try:
        mv = memoryview(node)
        kind, ps, pe, pos = _rlp_item_bounds(mv, len(node), 0)
        if kind != 1 or pos != len(node):
            return []
        offs: List[int] = []
        _scan_list_refs(mv, ps, pe, offs)
        return [node[o : o + 32] for o in offs]
    except (ValueError, IndexError):  # IndexError: zero-length node bytes
        return []


class WitnessEngine:
    """Cross-block memoized linked-multiproof verifier.

    One instance owns an interning table (digest <-> integer row) plus the
    resolved child-link graph; `verify_batch` verifies whole batches of
    (root, nodes) block witnesses against it.
    """

    def __init__(
        self,
        hasher: Optional[object] = None,
        max_nodes: int = 1 << 20,
        device_batch_floor: int = -1,
        device_index: Optional[int] = None,
        resident: Optional[bool] = None,
        resident_cap: Optional[int] = None,
        depth_hist: Optional[bool] = None,
        tiered_evict: Optional[bool] = None,
        pin_depth: Optional[int] = None,
        pin_budget: Optional[int] = None,
    ):
        """device_batch_floor: minimum novel-batch size that goes to the
        device hasher under `--crypto_backend=tpu`. -1 (default) = adaptive:
        measure the host->device link once and engage the device only when
        the cost model says a batch beats the native path — a ~20 MB/s
        host<->device link never qualifies for byte-dense hashing, a
        ~GB/s one qualifies from a few thousand nodes up. This
        is the mechanism behind round-2's "never slower than cpu" demand:
        the flag routes by measured cost, not by hope.

        device_index: pin this engine's device hashing to ONE mesh device
        (`jax.devices()[device_index]`, resolved lazily so construction
        never imports jax). The mesh serving pool (serving/mesh_exec.py)
        gives each executor its own pinned engine: the engine's intern
        table and its device dispatches stay on the same chip, so
        bucket-affinity routing preserves the cross-block reuse the table
        exists for. A pinned engine never takes the mesh-sharded hashing
        path — sharding across the mesh is the POOL's axis, not one
        engine's.

        resident: route verdicts through a DEVICE-RESIDENT intern table
        (ops/witness_resident.py) — digest/ref rows persist on the chip
        across batches, only truly-novel bytes are uploaded, the linkage
        join runs on device, and the host tables commit from the device
        digests. None (default) = auto: engaged under
        `--crypto_backend=tpu` on a real accelerator (PHANT_RESIDENT=1
        forces it — the XLA-CPU test/proxy path — and =0 disables).
        True/False override the env. The per-batch offload cost model is
        deliberately NOT consulted on this route: residency amortizes
        each upload across every future batch, which is exactly what a
        per-batch model cannot see.

        resident_cap: row bound of the resident table (default
        min(max_nodes, PHANT_RESIDENT_CAP)); it grows toward the bound
        in pow2 generations and flushes with the host generation.

        depth_hist: record the `cache_hit_rate vs trie_depth` histogram
        (witness_engine.depth_{hits,misses}{depth=}) on every batch.
        None = PHANT_DEPTH_HIST (default off: first sight of a node
        costs one extra host hash for the depth memo).

        tiered_evict: depth-TIERED generation eviction (PR 9, default
        ON; PHANT_TIERED_EVICT=0 disables). A generation flush pins the
        shallow tier (depth <= pin_depth, the near-100%-hit rows per
        the PR 8 histogram) by re-committing it into the fresh
        generation with its remembered digests — zero re-hashing —
        while deeper tiers evict generationally; the device-resident
        table re-commits the same set so host and device stay in
        lockstep. Classification is the zero-hot-path-cost _PinTracker
        (roots are depth 0 by definition; novel nodes classify when
        their digests surface at commit). On the ext core, tiering
        routes novel hashing through the Python-visible batch keccak
        instead of the in-C finish_native fast path so digests surface
        — same C hashing, one extra round trip, novel counts go to ~0
        in the steady state.

        pin_depth: deepest tier pinned across flushes (default
        PHANT_PIN_DEPTH=2 — the histogram's near-100%-hit depths).

        pin_budget: pinned-set row bound (default PHANT_PIN_BUDGET or
        max_nodes // 8); at flush time pins allocate shallow-first from
        the live classification until the budget (or the room the
        incoming batch needs) is exhausted."""
        # native C++ core (native/engine.cc): same interning + verdict
        # semantics, ~5-10x the steady-state throughput (no Python dict
        # re-hash of node bytes, no numpy sort in the join). Preferred
        # driver is the CPython extension (native/pyext.cc — feeds the
        # core scattered PyBytes pointers, zero joins); the ctypes+numpy
        # driver is the fallback (PHANT_ENGINE_EXT=0 forces it). The
        # Python tables below stay as the final fallback/differential
        # twin (PHANT_ENGINE_NATIVE=0 forces it; tests run all three).
        self._core = None
        self._ext_core = None
        if os.environ.get("PHANT_ENGINE_NATIVE", "1") == "1":
            from phant_tpu.utils.native import load_engine_ext, load_native

            ext = load_engine_ext()
            if ext is not None:
                self._ext_core = ext.Engine()
            else:
                native = load_native()
                if native is not None:
                    self._core = native.new_engine()
        # node bytes -> row (the memoization key: raw bytes, no hashing
        # needed to test membership)
        self._row_of_bytes: Dict[bytes, int] = {}
        # digest bytes -> refid. EVERY 32-byte digest that appears — as a
        # node's hash or inside a node as a child reference — gets one id,
        # so parent->child linkage resolves at insert time with no pending
        # table (an unresolved-ref table would grow with every off-path
        # sibling digest, ~16x the node count, and those digests never
        # arrive as nodes).
        self._refid_of_digest: Dict[bytes, int] = {}
        self._n_refids = 0
        # growable per-row tables
        cap = 1024
        self._own_refid = np.full(cap, _NO_ROW, np.int64)
        self._child_refids = np.full((cap, 17), _NO_ROW, np.int64)
        self._n_rows = 0
        self._max_nodes = max_nodes
        self._hasher = hasher  # callable: List[bytes] -> List[bytes]
        self._device_batch_floor = device_batch_floor
        # mesh pinning: the target index plus the lazily-resolved jax
        # device handle (write-once from whatever thread first routes to
        # the device; both writers compute the same value, so the benign
        # race needs no lock — and the engine lock must NOT be held across
        # a jax import anyway)
        self._device_index = device_index
        self._pinned = None
        self._lock = threading.Lock()  # Engine API serves from threads
        # pipelined two-phase state (begin_batch/resolve_batch), all
        # guarded by _lock: the in-flight handle count and the deferred-
        # eviction flag (a generation flush must never run while a
        # scanned-but-uncommitted batch holds row ids — the tables it
        # scanned against would vanish under it). _drained signals the
        # count hitting zero, so an over-cap begin under SUSTAINED
        # pipelined load can wait for a flush window instead of deferring
        # forever (tables are append-only and commits re-check membership,
        # so handles may begin/resolve in ANY interleaving — several
        # schedulers can share one engine)
        self._inflight = 0
        self._drained = threading.Condition(self._lock)
        self._evict_pending = False
        # the python twin tables have their OWN deferred flag: on a
        # C-core engine the public intern() fills _row_of_bytes, and its
        # overflow must flush those dicts — not the warm memoized core
        self._evict_pending_py = False
        # device-resident intern table (ops/witness_resident.py): built
        # lazily on the first resident-routed batch, behind its own init
        # lock (construction imports jax — the engine lock must not be
        # held across that)
        self._resident = None
        self._resident_opt = resident
        self._resident_cap = resident_cap
        self._resident_lock = threading.Lock()
        if depth_hist is None:
            depth_hist = os.environ.get("PHANT_DEPTH_HIST", "0") == "1"
        self._depth = _DepthStats(max_nodes) if depth_hist else None
        # depth-tiered eviction (PR 9): the shallow-node pin tracker plus
        # an ADVISORY committed-bytes set for the prefetch pre-scan. Both
        # are engine-lock-guarded at every write; the pre-scan reads the
        # set without the lock (GIL-atomic membership, re-checked by the
        # authoritative pack-time scan).
        if tiered_evict is None:
            tiered_evict = os.environ.get("PHANT_TIERED_EVICT", "1") not in (
                "0",
                "",
            )
        if pin_depth is None:
            pin_depth = int(os.environ.get("PHANT_PIN_DEPTH", "2"))
        if pin_budget is None:
            pin_budget = int(
                os.environ.get("PHANT_PIN_BUDGET", str(max(1, max_nodes // 8)))
            )
        self._pin = _PinTracker(pin_depth, pin_budget) if tiered_evict else None
        # the prefetch pre-scan's lock-free membership probe. The C cores
        # keep their committed bytes in native memory, so this is the only
        # host-side bytes-keyed view of the tables — which is exactly why
        # it must stay LAZY: it duplicates up to max_nodes of node bytes,
        # and an engine that never serves a prefetch consumer (depth-1
        # scheduler, --sched-prefetch 0, offline verify_batch) must not
        # pay that. _advisory_add is a no-op until the first
        # prefetch_batch call activates it (python core: seeded exactly
        # from _row_of_bytes; C cores: warms with subsequent commits — a
        # cold start under-reports hits, a perf miss the authoritative
        # pack-time scan absorbs).
        self._seen_advisory: set = set()
        self._advisory_active = False
        self.stats = {"hashed": 0, "hits": 0, "evictions": 0}

    # -- hashing backends ---------------------------------------------------

    def _hash_batch(
        self, nodes: List[bytes], route_device: Optional[bool] = None
    ) -> List[bytes]:
        with metrics.phase("witness_engine.hash"):
            return self._hash_batch_routed(nodes, route_device)

    def _hash_batch_routed(
        self, nodes: List[bytes], route_device: Optional[bool] = None
    ) -> List[bytes]:
        digests, backend = self._hash_novel(nodes, route_device)
        if backend in ("device", "native"):
            key = backend + "_batches"
            self.stats[key] = self.stats.get(key, 0) + 1
        return digests

    def _hash_novel(
        self, nodes: List[bytes], route_device: Optional[bool] = None
    ) -> Tuple[List[bytes], str]:
        """(digests, backend) with NO stats mutation — the pipelined
        resolve stage hashes outside the engine lock and must account the
        batch counter under it afterwards (a lock-free stats bump here
        would race concurrent callers)."""
        if self._hasher is not None:
            return list(self._hasher(nodes)), "hasher"
        if route_device is None:
            route_device = self._device_route_wanted(nodes)
        if route_device:
            try:
                return (
                    self._device_dispatch(nodes, self._pinned_device()).resolve(),
                    "device",
                )
            except Exception:
                import logging

                from phant_tpu.backend import device_fallback

                device_fallback("witness_hash")
                logging.getLogger("phant.witness").warning(
                    "device keccak failed for %d nodes; native fallback",
                    len(nodes),
                    exc_info=True,
                )
        from phant_tpu.utils.native import load_native

        native = load_native()
        if native is not None:
            return list(native.keccak256_batch_fast(nodes)), "native"
        from phant_tpu.crypto.keccak import keccak256

        return [keccak256(n) for n in nodes], "native"

    def _pinned_device(self):
        """The jax device this engine is pinned to (device_index), or None
        for default placement. Resolved lazily ON the device route — the
        only path that may import jax — and memoized; indexes past the
        device count wrap, so an 8-executor pool degrades gracefully on a
        smaller mesh."""
        if self._device_index is None:
            return None
        if self._pinned is None:
            import jax

            devices = jax.devices()
            self._pinned = devices[self._device_index % len(devices)]
        return self._pinned

    # -- device-resident intern table (ops/witness_resident.py) --------------

    def _resident_wanted(self) -> bool:
        """Route this engine's verdicts through the device-resident
        table? Auto-on under `--crypto_backend=tpu` on a real
        accelerator; PHANT_RESIDENT=1 forces (XLA-CPU tests/proxy), =0
        disables; the constructor arg overrides the env. A hasher
        override always wins — its batches must surface to the host
        hashing route."""
        if self._hasher is not None or self._resident_opt is False:
            return False
        env = os.environ.get("PHANT_RESIDENT", "auto")
        if env in ("0", "off") and self._resident_opt is not True:
            return False
        from phant_tpu.backend import crypto_backend, jax_device_ok

        if crypto_backend() != "tpu" or not jax_device_ok():
            return False
        if self._resident_opt is True or env == "1":
            return True
        import jax

        return jax.default_backend() != "cpu"

    def _resident_table(self):
        """The engine's ResidentTable, built on first use (pinned to the
        engine's device on a mesh lane — one independent table per chip).
        Construction is serialized by `_resident_lock` and happens
        OUTSIDE the engine lock (it imports jax); the handle itself is
        engine-lock-guarded like every other table reference."""
        with self._lock:
            res = self._resident
        if res is not None:
            return res
        with self._resident_lock:
            with self._lock:
                res = self._resident
            if res is not None:
                return res
            from phant_tpu.ops.witness_resident import (
                ResidentTable,
                resident_default_cap,
            )

            table = ResidentTable(
                max_cap=self._resident_cap
                or min(self._max_nodes, resident_default_cap()),
                device=self._pinned_device(),
            )
            with self._lock:
                self._resident = table
            return table

    def prewarm_resident(self) -> int:
        """Where this engine's verdicts go through the resident table,
        build the table (at the cap it is born at) and its programs on
        every rung of their ladders (`ResidentTable.prewarm`): a server's
        boot on an accelerator. Returns the programs built, 0 off the
        resident route."""
        if not self._resident_wanted():
            return 0
        return self._resident_table().prewarm()

    def _resident_dispatch(self, witnesses, novel):
        """Enqueue the resident update + verdict for one batch; None =
        this batch cannot go resident (oversized node, table failure —
        the table is dropped on failure so a lost device degrades to the
        classic route instead of wedging every batch)."""
        try:
            return self._resident_table().dispatch(witnesses, novel)
        except Exception:
            import logging

            from phant_tpu.backend import device_fallback

            device_fallback("witness_resident")
            logging.getLogger("phant.witness").warning(
                "resident dispatch failed; dropping the device table and "
                "falling back to the classic route",
                exc_info=True,
            )
            with self._lock:
                self._resident = None
            return None

    def reset(self) -> None:
        """Release EVERYTHING: host tables (all cores), the python
        twins, the device-resident arrays, and the depth memo. The soak
        uses this between timed passes — constructing a fresh
        engine resets the HOST state, but with residency the old
        engine's device arrays would linger until GC, so pass 2 could
        silently measure a warm resident table (or accumulate device
        memory). Requires an idle pipeline (no in-flight handles)."""
        with self._lock:
            if self._inflight:
                raise RuntimeError("reset() with in-flight batch handles")
            if self._ext_core is not None:
                self._ext_core.flush()
            elif self._core is not None:
                self._core.flush()
            self._row_of_bytes.clear()
            self._refid_of_digest.clear()
            self._n_rows = 0
            self._n_refids = 0
            self._evict_pending = False
            self._evict_pending_py = False
            self._seen_advisory.clear()
            if self._pin is not None:
                self._pin.flush()
            self.stats["resets"] = self.stats.get("resets", 0) + 1
            res, self._resident = self._resident, None
        if res is not None:
            res.flush()  # drop the device arrays deterministically
        if self._depth is not None:
            self._depth.flush()

    def _flush_attached_locked(self, pinned: Sequence[tuple] = ()) -> None:
        """Flush the device-resident table and the depth memo together
        with a host GENERATION flush (caller holds the engine lock with
        an empty pipeline): host and device tables evict in lockstep, so
        they never disagree about what exists. With a tiered flush the
        resident table re-commits the same `pinned` set the host just
        retained — row ids restart together, the open-addressed index is
        rebuilt over exactly the pinned fingerprints, and the two tables
        keep agreeing about what exists. The python-TWIN-only flush
        (`_evict_pending_py`) deliberately does not come here — the core
        (and its resident mirror) stay warm there."""
        if self._resident is not None:
            if pinned:
                self._resident.flush_retaining([nb for nb, _dg, _d in pinned])
            else:
                self._resident.flush()
        if self._depth is not None:
            self._depth.flush()

    @staticmethod
    def _stage_device_blob(nodes: List[bytes]) -> tuple:
        """Lease + fill the pow2-bucketed device staging for one novel
        set: (key, entry, n_nodes) — the host-side half of a device
        dispatch, split out so the PREFETCH stage can run it off the
        serving critical path (PrefetchPlan.device_lease). Raises
        ValueError for a node past the kernel's absorb capacity, same
        contract as the dispatch itself."""
        from phant_tpu.crypto.keccak import RATE
        from phant_tpu.ops.witness_jax import _pow2ceil

        limit = WITNESS_MAX_CHUNKS * RATE
        for n in nodes:
            if len(n) >= limit:
                raise ValueError(
                    f"node of {len(n)}B exceeds device absorb capacity "
                    f"({limit}B); route to the native hasher"
                )
        raw = b"".join(nodes)
        blob_len = _pow2ceil(len(raw) + WITNESS_MAX_CHUNKS * RATE)
        B = _pow2ceil(len(nodes))
        key = ("device_blob", blob_len, B)
        entry = _staging.take(key)
        if entry is None:
            entry = {
                "blob": np.zeros(blob_len, np.uint8),
                "lens": np.zeros(B, np.int32),
                "offsets": np.zeros(B, np.int32),
                "blob_dirty": 0,
                "lens_dirty": 0,
            }
        blob, lens, offsets = entry["blob"], entry["lens"], entry["offsets"]
        # zero only the reused region past this batch's payload (a fresh
        # allocation is already zero; the pool tracks the high-water mark)
        if entry["blob_dirty"] > len(raw):
            blob[len(raw) : entry["blob_dirty"]] = 0
        if entry["lens_dirty"] > len(nodes):
            lens[len(nodes) : entry["lens_dirty"]] = 0
        blob[: len(raw)] = np.frombuffer(raw, np.uint8)
        lens[: len(nodes)] = [len(n) for n in nodes]
        entry["blob_dirty"] = len(raw)
        entry["lens_dirty"] = len(nodes)
        offsets[0] = 0
        np.cumsum(lens[:-1], out=offsets[1:])
        return (key, entry, len(nodes))

    @staticmethod
    def _device_dispatch(nodes: List[bytes], device=None, staged=None):
        """Enqueue one fused device dispatch of the concatenated novel
        bytes WITHOUT any host sync: returns a keccak_jax.DeviceDigests
        handle whose `resolve()` pays the readback. The transfer is the
        novel bytes + 2B/node — the memoized design makes this the ONLY
        recurring h2d traffic of witness verification. Both the node axis
        AND the blob byte axis are padded to power-of-two buckets so
        repeat calls hit a small set of compiled shapes (a ragged blob
        length would recompile per call) — and the padded staging arrays
        themselves are leased from `_staging` keyed by that same bucket,
        so steady-state batches stop reallocating (and page-zeroing) the
        blob every call. The lease returns to the pool on resolve, when
        the device can no longer be reading the buffers.

        `device` pins the dispatch: inputs are device_put-committed to
        that one device (jax places the compute with them) and the
        mesh-sharded route is skipped — a pinned engine is one lane of
        the serving pool's mesh, never a whole-mesh dispatcher.

        `staged` hands in a pre-filled lease from `_stage_device_blob`
        (the prefetch stage's output for exactly these nodes); ownership
        transfers here — the lease returns to the pool on resolve, or
        right away if the enqueue fails."""
        import jax.numpy as jnp

        from phant_tpu.ops.keccak_jax import DeviceDigests
        from phant_tpu.ops.witness_jax import witness_digests

        if staged is None:
            staged = WitnessEngine._stage_device_blob(nodes)
        key, entry, _n = staged
        blob, lens, offsets = entry["blob"], entry["lens"], entry["offsets"]
        B = len(lens)
        import os

        import jax

        sharded = os.environ.get("PHANT_ENGINE_SHARDED", "auto")
        if device is not None:
            # pinned engines never shard: the mesh axis belongs to the
            # serving pool (one pinned engine per device), and a pinned
            # dispatch sharding back across the mesh would defeat the
            # per-device intern-table affinity the pool routes for
            use_sharded = False
        elif sharded == "auto":
            # default ON with >1 REAL accelerator (the production
            # multi-chip topology); the virtual CPU test mesh stays
            # single-device unless explicitly opted in — its 8 "devices"
            # share one core, so sharding there only costs compiles
            use_sharded = (
                len(jax.devices()) > 1
                and jax.default_backend() != "cpu"
            )
        else:
            use_sharded = sharded == "1"
        # dispatch (upload + kernel launch) vs readback (the honest sync)
        # timed separately: the split localizes whether
        # the link or the kernel is eating the batch budget
        try:
            with metrics.phase("keccak.device_dispatch"), device_host(
                "witness", "enqueue"
            ):
                if use_sharded and len(jax.devices()) > 1 and B % len(jax.devices()) == 0:
                    # multi-chip novelty hashing: shard the node axis over
                    # the mesh (default-safe: the sharded compile's cache-
                    # suspension window is lock-serialized, parallel/mesh.py)
                    from phant_tpu.parallel.mesh import (
                        make_mesh,
                        witness_digests_sharded,
                    )

                    out = witness_digests_sharded(
                        make_mesh(),
                        blob,
                        offsets,
                        lens,
                        max_chunks=WITNESS_MAX_CHUNKS,
                    )
                elif device is not None:
                    # committed inputs pin the compute with them: the
                    # upload AND the keccak land on this engine's device
                    out = witness_digests(
                        jax.device_put(blob, device),
                        jax.device_put(offsets, device),
                        jax.device_put(lens, device),
                        max_chunks=WITNESS_MAX_CHUNKS,
                    )
                else:
                    out = witness_digests(
                        jnp.asarray(blob),
                        jnp.asarray(offsets),
                        jnp.asarray(lens),
                        max_chunks=WITNESS_MAX_CHUNKS,
                    )
        except BaseException:
            # a failed enqueue (lost device) must not strand the lease —
            # the caller falls back to the native route and the buffers
            # go back to the pool
            _staging.give(key, entry)
            raise
        return DeviceDigests(
            out, len(nodes), on_resolve=lambda: _staging.give(key, entry)
        )

    @staticmethod
    def _hash_batch_device(nodes: List[bytes]) -> List[bytes]:
        """Synchronous device hashing on the DEFAULT device: dispatch +
        immediate readback (the pipelined path keeps the DeviceDigests
        handle unresolved instead so batch N+1 packs while batch N
        computes; pinned engines pass their device explicitly)."""
        return WitnessEngine._device_dispatch(nodes).resolve()

    @staticmethod
    def _pack_blob(nodes: Sequence[bytes], entry: Optional[dict] = None):
        """(joined, blob u8, offsets u64, lens u32) C-ABI layout of a node
        batch. `joined` must stay referenced while the views are in use.
        With a staging `entry` (from `_pack_entry`), the offsets array is
        a view into a pooled buffer instead of a fresh allocation — the
        caller owns the entry until the views are dead."""
        n = len(nodes)
        joined = b"".join(nodes)
        blob = np.frombuffer(joined, np.uint8)
        lens = np.fromiter(map(len, nodes), np.uint32, n)
        if entry is not None and len(entry["offsets"]) >= n:
            offsets = entry["offsets"][:n]
            offsets[0:1] = 0
        else:
            offsets = np.zeros(n, np.uint64)
        if n > 1:
            np.cumsum(lens[:-1], dtype=np.uint64, out=offsets[1:])
        return joined, blob, offsets, lens

    @staticmethod
    def _pack_entry(n: int) -> Tuple[tuple, dict]:
        """Lease a `_pack_blob` staging entry sized for `n` nodes (pow2
        bucket). Return it with `_staging.give(key, entry)` once the blob
        views are no longer referenced."""
        from phant_tpu.ops.witness_jax import _pow2ceil

        cap = _pow2ceil(max(n, 1))
        key = ("pack_offsets", cap)
        entry = _staging.take(key)
        if entry is None:
            entry = {"offsets": np.zeros(cap, np.uint64)}
        return key, entry

    @staticmethod
    def _refs_for_batch(nodes: List[bytes]) -> Tuple[List[bytes], np.ndarray]:
        """(ref_digests, ref_node): the flat scan-order list of 32-byte
        child references across the whole batch plus each ref's node index
        (non-decreasing — scan order). Batched through the native C scanner
        when available; malformed nodes — which the native scanner rejects
        wholesale — fall back to the per-node Python walk that marks just
        the bad ones ref-less."""
        from phant_tpu.utils.native import load_native

        native = load_native()
        if native is not None:
            raw, blob, offsets, lens = WitnessEngine._pack_blob(nodes)
            try:
                ref_off, ref_node = native.scan_refs(blob, offsets, lens)
            except ValueError:
                pass
            else:
                refs = [raw[o : o + 32] for o in ref_off.tolist()]
                return refs, ref_node.astype(np.int64)
        refs = []
        ref_node_l = []
        for i, nb in enumerate(nodes):
            for r in _extract_ref_digests(nb):
                refs.append(r)
                ref_node_l.append(i)
        return refs, np.asarray(ref_node_l, np.int64)

    # -- interning ----------------------------------------------------------

    def _grow(self, need: int) -> None:
        cap = self._own_refid.shape[0]
        if need <= cap:
            return
        new_cap = cap
        while new_cap < need:
            new_cap *= 2
        o = np.full(new_cap, _NO_ROW, np.int64)
        o[:cap] = self._own_refid
        c = np.full((new_cap, 17), _NO_ROW, np.int64)
        c[:cap] = self._child_refids
        self._own_refid, self._child_refids = o, c

    def _evict_all(self) -> None:
        """Generation flush: drop the whole interned set and start ids over.
        Safe because nothing outside the (just-cleared) dicts holds row or
        ref ids, and every insert fully re-initializes its per-row entries."""
        self.stats["evictions"] += 1
        self._row_of_bytes.clear()
        self._refid_of_digest.clear()
        self._n_rows = 0
        self._n_refids = 0

    def intern(self, nodes: Sequence[bytes]) -> np.ndarray:
        """Public interning entry point — takes the engine lock.

        `verify_batch` reaches the same table through `_intern_locked`
        (it already holds the lock; threading.Lock does not re-enter), so
        direct callers — tests, warm-up loops — get the same mutual
        exclusion the serving path has instead of racing it (phantlint
        LOCK: every `stats`/table touch outside the lock was a finding)."""
        with self._lock:
            return self._intern_locked(nodes)

    def _scan_rows_locked(
        self, nodes: Sequence[bytes]
    ) -> Tuple[np.ndarray, List[bytes], int]:
        """(rows, novel, miss): the pure hit scan — NO table mutation, so
        the pipelined pack stage can run it while earlier batches are
        still uncommitted. rows[i] is a row id, or -2-k pointing into the
        novel first-occurrence list; miss counts every negative entry
        (novel duplicates included), the `hits` complement."""
        # bulk hit scan: one C-level map over the interning dict instead of
        # a Python loop with per-node numpy scalar writes — the steady
        # state is ~all hits, so this IS the verification hot path
        n = len(nodes)
        rows = np.fromiter(
            map(self._row_of_bytes.get, nodes, itertools.repeat(-1)),
            np.int64,
            n,
        )
        miss_idx = np.nonzero(rows < 0)[0]
        novel: List[bytes] = []
        seen_this_call: Dict[bytes, int] = {}
        for i in miss_idx.tolist():
            nb = nodes[i]
            j = seen_this_call.get(nb)
            if j is not None:
                rows[i] = -2 - j  # forward ref into this call's novel list
                continue
            seen_this_call[nb] = len(novel)
            rows[i] = -2 - len(novel)
            novel.append(nb)
        return rows, novel, len(miss_idx)

    def _commit_novel_locked(
        self,
        rows: np.ndarray,
        novel: List[bytes],
        digests: List[bytes],
        ref_hint: Optional[Dict[bytes, list]] = None,
    ) -> None:
        """Insert `novel` (with caller-computed digests), intern every
        digest + child reference, and patch the negative entries of `rows`
        in place. Caller holds `self._lock`.

        Each novel node's digest AND each of its child-reference digests
        are interned to refids immediately, so linkage is fully resolved
        at insert: a parent cached today links to a child that first
        arrives as a node next week, because both map to the same refid.

        A novel entry already present in the table — committed by an
        earlier in-flight pipelined batch between this batch's scan and
        now — reuses the existing row instead of inserting a duplicate."""
        row_of_bytes = self._row_of_bytes
        actual = np.empty(len(novel), np.int64)
        fresh_idx: List[int] = []
        for k, nb in enumerate(novel):
            got = row_of_bytes.get(nb)
            if got is None:
                fresh_idx.append(k)
            else:
                actual[k] = got
        if len(fresh_idx) == len(novel):
            fresh, fresh_digests = novel, digests
        else:
            fresh = [novel[k] for k in fresh_idx]
            fresh_digests = [digests[k] for k in fresh_idx]

        if fresh:
            if ref_hint is not None and all(nb in ref_hint for nb in fresh):
                # prefetch already RLP-decoded these nodes' child refs
                # (content-derived: bytes -> refs can never go stale, it
                # can only go unused when the hint misses a fresh node)
                ref_digests = []
                ref_node_l: List[int] = []
                for i, nb in enumerate(fresh):
                    for r in ref_hint[nb]:
                        ref_digests.append(r)
                        ref_node_l.append(i)
                ref_node = np.asarray(ref_node_l, np.int64)
            else:
                ref_digests, ref_node = self._refs_for_batch(fresh)
            base_row = self._n_rows
            self._n_rows += len(fresh)
            self._grow(self._n_rows)
            self._child_refids[base_row : self._n_rows] = _NO_ROW  # gen reuse

            # per-node child slots FIRST: ref_node is non-decreasing (scan
            # order), so the slot index is the offset from the node's first
            # ref. Refs past the 17-slot cap (branch(16) + account storage
            # root) are dropped BEFORE interning — adversarial deep-embedded
            # RLP must not inflate the digest dict beyond the old
            # 17-per-node bound
            if len(ref_node):
                slots = np.arange(len(ref_node)) - np.searchsorted(
                    ref_node, ref_node
                )
                keep = slots < 17
                if not keep.all():
                    ref_digests = [
                        ref_digests[k] for k in np.nonzero(keep)[0].tolist()
                    ]
                    ref_node = ref_node[keep]
                    slots = slots[keep]

            # bulk refid resolution: ONE C-level map over the digest dict
            # for every digest in the batch (own digests first, then the
            # flat ref list); only genuinely new digests take the Python
            # assignment loop
            all_dig = fresh_digests + ref_digests
            ids = np.fromiter(
                map(self._refid_of_digest.get, all_dig, itertools.repeat(-1)),
                np.int64,
                len(all_dig),
            )
            missing = np.nonzero(ids < 0)[0]
            if len(missing):
                rod = self._refid_of_digest
                rid = self._n_refids
                for k in missing.tolist():
                    dg = all_dig[k]
                    got = rod.get(dg)
                    if got is None:
                        rod[dg] = got = rid
                        rid += 1
                    ids[k] = got
                self._n_refids = rid

            nfresh = len(fresh)
            self._own_refid[base_row : base_row + nfresh] = ids[:nfresh]
            if len(ref_node):
                self._child_refids[base_row + ref_node, slots] = ids[nfresh:]
            for j, nb in enumerate(fresh):
                row_of_bytes[nb] = base_row + j
            if len(fresh_idx) == len(novel):
                actual[:] = base_row + np.arange(nfresh)
            else:
                actual[np.asarray(fresh_idx, np.int64)] = base_row + np.arange(
                    nfresh
                )

        # patch forward refs through the actual-row map
        neg = rows < -1
        if neg.any():
            rows[neg] = actual[-2 - rows[neg]]

    def _intern_locked(self, nodes: Sequence[bytes]) -> np.ndarray:
        """Rows for `nodes`, hashing the never-seen ones in one batch.
        Caller holds `self._lock`."""
        rows, novel, miss = self._scan_rows_locked(nodes)
        hits_before = self.stats["hits"]
        self.stats["hits"] += len(nodes) - miss
        if novel:
            if (
                len(self._row_of_bytes) + len(novel) > self._max_nodes
                and self._row_of_bytes  # an over-cap single batch still runs
            ):
                # NOT _over_cap_locked: this path interns into the PYTHON
                # tables even on an engine whose verify path runs a C core
                # (the public intern() entry), so the flush — immediate or
                # deferred — must clear the python tables specifically;
                # routing it to the core would leave _row_of_bytes full
                # (and recurse forever) while wiping the warm core cache
                if self._inflight:
                    self._evict_pending_py = True
                else:
                    # the pass above is discarded — roll back its hit
                    # tally so the stats RPC doesn't double-count the
                    # re-interned scan
                    self.stats["hits"] = hits_before
                    if self._ext_core is None and self._core is None:
                        # the python tables ARE this engine's verify
                        # core: a real generation flush, tiered like
                        # every other scan site (pins re-commit, room
                        # reserved for this batch's novels)
                        self._evict_now_locked(incoming_novel=len(novel))
                    else:
                        self._evict_all()
                        self._flush_attached_locked()  # generation flush
                    # re-intern into the new generation (lock already held)
                    return self._intern_locked(nodes)
            self._advisory_add(novel)
            digests = self._hash_batch(novel)
            self.stats["hashed"] += len(novel)
            self.stats["novel_bytes"] = self.stats.get("novel_bytes", 0) + sum(
                map(len, novel)
            )
            if self._pin is not None:
                self._pin.note_novel(novel, digests)
            self._commit_novel_locked(rows, novel, digests)
        return rows

    # -- verification -------------------------------------------------------

    def verify_batch(
        self, witnesses: Sequence[Tuple[bytes, Sequence[bytes]]]
    ) -> np.ndarray:
        """(n_blocks,) bool — full linked-multiproof verdict per block.

        Block b verifies iff some node's digest equals root_b AND every node
        is that root or is hash-referenced by another node of block b
        (exactly witness_verify_fused's semantics; references are acyclic
        because a cycle would be a keccak collision).

        Instrumented at BATCH granularity (per-node bookkeeping would be
        measurable overhead on the hot path): cache hit/miss/eviction and
        novel-bytes counters from the stats delta, interned-set gauges, and
        the hash / intern / linkage-join phase split in the registry. The
        delta is captured under the engine lock so concurrent callers can
        never double-count each other's work; the registry publish happens
        after release (the metrics lock never nests inside ours)."""
        if self._resident_wanted():
            # the resident route is inherently two-phase (the verdict is
            # an async device program); the one-call API is begin+resolve
            # fused — verdict semantics stay byte-identical (the host
            # scan is authoritative, differential-tested)
            return self.resolve_batch(self.begin_batch(witnesses))
        if self._depth is not None:
            self._depth.record(witnesses)
        with metrics.phase("witness_engine.verify_batch"):
            with self._lock:
                # eviction-window wait FIRST (it releases the lock, see
                # _pack_handle): only then is the s0 snapshot race-free
                # against a concurrent resolver's already-published stats
                self._await_evict_window_locked()
                if not self._inflight:
                    self._run_deferred_evictions_locked()
                s0 = dict(self.stats)
                verdict = self._verify_batch_locked(witnesses)
                s1 = self.stats
                deltas = [
                    (metric, s1.get(stat_key, 0) - s0.get(stat_key, 0))
                    for stat_key, metric in (
                        ("hits", "witness_engine.cache_hits"),
                        ("hashed", "witness_engine.cache_misses"),
                        ("novel_bytes", "witness_engine.novel_bytes_hashed"),
                    )
                ]
                evict_tiers = self._evictions_by_tier(s0, s1)
                snap = self._stats_snapshot_locked()
        for metric, d in deltas:
            if d:
                # names come from the literal tuple above — all three are
                # in METRIC_HELP; the loop only exists to batch the
                # registry calls outside the engine lock
                metrics.count(metric, d)  # phantlint: disable=METRICNAME — names from the literal tuple above
        for tier, d in evict_tiers:
            metrics.count("witness_engine.evictions", d, tier=tier)
        metrics.gauge_set(
            "witness_engine.interned_digests", snap["interned_digests"]
        )
        return verdict

    # -- pipelined two-phase API (pack / dispatch / resolve) -----------------

    def _advisory_add(self, nodes) -> None:
        """Commit-site hook for the prefetch advisory set: a no-op until
        the first prefetch_batch activates it (no consumer, no copy)."""
        if self._advisory_active:
            self._seen_advisory.update(nodes)

    def _advisory_activate(self) -> None:
        """First prefetch_batch call: start maintaining the advisory set.
        The python core's committed bytes are its _row_of_bytes keys —
        seed exactly (key references, no byte copies). The C cores hold
        bytes natively; they warm with commits from here on."""
        with self._lock:
            if not self._advisory_active:
                if self._ext_core is None and self._core is None:
                    self._seen_advisory.update(self._row_of_bytes)
                self._advisory_active = True

    def prefetch_batch(
        self, witnesses: Sequence[Tuple[bytes, Sequence[bytes]]]
    ) -> PrefetchPlan:
        """STAGE 0 of the 4-stage serving pipeline (PR 9): witness
        decode + advisory novelty pre-scan for a batch that will be
        `begin_batch`'d next — host batch assembly, the candidate-novel
        scan against the advisory committed-bytes set, the candidates'
        child-reference RLP decode (python core), and pre-filled staging
        leases (native pack blob / device dispatch blob). A prefetch
        worker runs this while the previous batch is in dispatch/resolve,
        so the pack stage's critical-path work shrinks to the lock-held
        re-check + commit.

        Read-only against the tables: the advisory set is probed WITHOUT
        the engine lock (GIL-atomic membership reads racing concurrent
        commits benignly). The staleness contract is absolute — the
        pack-time scan under the lock stays the authoritative commit, so
        a stale plan (concurrent commit, generation flush, shed jobs) is
        dropped at a perf cost of zero correctness risk. Pass the SAME
        witnesses list to `begin_batch(witnesses, prefetch=plan)`; an
        unused plan must be `release()`d."""
        with metrics.phase("witness_engine.prefetch"):
            return self._prefetch_plan(witnesses)

    def _prefetch_plan(self, witnesses) -> PrefetchPlan:
        # phantlint: disable=LOCK — double-checked activation: this
        # GIL-atomic read only short-circuits the common case; a stale
        # False costs one _advisory_activate call, which re-checks the
        # flag UNDER the lock before doing anything
        if not self._advisory_active:
            self._advisory_activate()
        plan = PrefetchPlan()
        plan.witnesses = witnesses
        n_blocks = len(witnesses)
        all_nodes: List[bytes] = []
        counts = np.empty(n_blocks, np.int64)
        for b, (_root, nodes) in enumerate(witnesses):
            counts[b] = len(nodes)
            all_nodes.extend(nodes)
        plan.all_nodes = all_nodes
        plan.counts = counts
        # phantlint: disable=LOCK — advisory pre-scan, deliberately
        # lock-free: set membership under the GIL is atomic, a racing
        # commit only makes the answer stale, and stale is re-checked by
        # the authoritative pack-time scan (the staleness contract)
        seen = self._seen_advisory
        novel: List[bytes] = []
        dedup = set()
        for nb in all_nodes:
            if nb not in seen and nb not in dedup:
                dedup.add(nb)
                novel.append(nb)
        plan.novel = novel
        with self._lock:
            ext, core = self._ext_core, self._core
        if ext is None and core is not None:
            # the native core's scan/commit consume the packed C-ABI
            # blob: lease + fill it here, off the serving critical path
            plan.pack_lease = self._pack_entry(len(all_nodes))
            plan.packed = self._pack_blob(all_nodes, plan.pack_lease[1])
        if ext is None and core is None and novel:
            # python core: the commit's child-ref extraction is host-side
            # RLP parsing — decode the candidates here. Content-derived,
            # so a hint can never go stale (only unused).
            refs, ref_node = self._refs_for_batch(novel)
            by_node: Dict[bytes, list] = {nb: [] for nb in novel}
            for r, i in zip(refs, ref_node.tolist()):
                by_node[novel[i]].append(r)
            plan.refs = by_node
        if (
            novel
            and self._hasher is None
            and not self._resident_wanted()
            and not self._native_route_certain()
            and self._device_route_wanted(novel)
        ):
            try:
                plan.device_lease = self._stage_device_blob(novel)
            except ValueError:
                pass  # oversized node: dispatch will route native anyway
        return plan

    def begin_batch(
        self,
        witnesses: Sequence[Tuple[bytes, Sequence[bytes]]],
        prefetch: Optional[PrefetchPlan] = None,
    ) -> BatchHandle:
        """Pack + dispatch one verify batch WITHOUT the device round-trip:
        the engine lock is held only for the intern-table scan (pack), the
        device keccak of the novel nodes is enqueued with no host sync
        (dispatch), and everything that needs the digests — readback,
        commit, linkage join — waits for `resolve_batch`. Batch N+1 can
        therefore pack while batch N computes and batch N-1 resolves (the
        serving scheduler's pipeline, phant_tpu/serving/scheduler.py).

        Handles may be resolved in ANY order (tables are append-only and
        commits re-check membership, so interleavings — including several
        schedulers sharing one engine — stay sound; the serving resolve
        worker happens to be FIFO for per-requester ordering);
        `verify_batch` remains the one-call depth-1 equivalent and may
        interleave freely with in-flight handles.

        `prefetch` consumes a plan from `prefetch_batch` run over the
        SAME witnesses list: pack reuses the plan's assembly + staging
        leases, and when the authoritative scan confirms the plan's
        candidate-novel set the device dispatch reuses its pre-filled
        blob too. A mismatched/stale plan is released and ignored —
        the plan is advisory, this scan is the commit."""
        if self._depth is not None:
            self._depth.record(witnesses)
        plan = prefetch
        if plan is not None and plan.witnesses is not witnesses:
            # not the batch this plan was computed for: drop it whole
            plan.release()
            plan = None
        with metrics.phase("witness_engine.pack"):
            h = self._pack_handle(witnesses, plan)
        used = plan is not None and h.novel == plan.novel
        if plan is not None:
            if used:
                metrics.count("witness_engine.prefetch_plan_hits")
            else:
                metrics.count("witness_engine.prefetch_plan_stale")
            if plan.refs is not None and h.kind == "python":
                # content-derived: valid even under a stale candidate
                # set (the commit only uses it when it covers every
                # fresh node)
                h.ref_hint = plan.refs
        resident = self._resident_wanted()
        # distinct pre-state roots: a wave of one head's copies or of
        # different blocks (the scheduler's sched.batch_blocks beside it)
        attrs = {"blocks": len({root for root, _nodes in witnesses})}
        if resident:
            from phant_tpu.ops.witness_resident import verdict_rows

            attrs["rung"] = verdict_rows([len(nodes) for _root, nodes in witnesses])
        with metrics.phase("witness_engine.dispatch", **attrs):
            if resident:
                # device-resident route: update (novel bytes only) +
                # verdict enqueued with no host sync; the host tables
                # will commit from the device digests at resolve
                h.resident = self._resident_dispatch(witnesses, h.novel)
            if h.resident is None and h.novel and self._hasher is None and (
                not self._native_route_certain()
                and self._device_route_wanted(h.novel)
            ):
                staged = None
                if used and plan.device_lease is not None:
                    # ownership moves to the dispatch (lease returns to
                    # the pool at resolve, or on enqueue failure)
                    staged, plan.device_lease = plan.device_lease, None
                try:
                    h.device = self._device_dispatch(
                        h.novel, self._pinned_device(), staged=staged
                    )
                except Exception:
                    import logging

                    from phant_tpu.backend import device_fallback

                    device_fallback("witness_dispatch")
                    logging.getLogger("phant.witness").warning(
                        "device keccak dispatch failed for %d nodes; "
                        "native fallback at resolve",
                        len(h.novel),
                        exc_info=True,
                    )
        if plan is not None:
            plan.release()  # whatever was not consumed goes back pooled
        return h

    def _pack_handle(
        self, witnesses, plan: Optional[PrefetchPlan] = None
    ) -> BatchHandle:
        h = BatchHandle()
        h.n_blocks = len(witnesses)
        with self._lock:
            # core refs are write-once in __init__; alias them under the
            # lock once so the pre-lock assembly below branches on a
            # consistent snapshot (LOCK discipline)
            ext, core = self._ext_core, self._core
        all_nodes: List[bytes] = []
        if ext is None:
            # host-side batch assembly + blob packing stays OUTSIDE the
            # lock: it touches no engine table, and it is exactly the work
            # the pipeline overlaps with the previous batch's resolve —
            # or, with a prefetch plan, the work ALREADY DONE off the
            # critical path (assembly is content-derived from the same
            # witnesses list, so it is valid even when the plan's novelty
            # pre-scan went stale)
            if plan is not None and plan.all_nodes is not None:
                all_nodes = plan.all_nodes
                h.counts = plan.counts
                if core is not None and plan.packed is not None:
                    # staging ownership moves plan -> handle (the lease
                    # returns to the pool at resolve, like every pack)
                    h.pack_entry = plan.pack_lease
                    h.joined, h.blob, h.offsets, h.lens = plan.packed
                    plan.pack_lease = plan.packed = None
            else:
                counts = np.empty(h.n_blocks, np.int64)
                for b, (_root, nodes) in enumerate(witnesses):
                    counts[b] = len(nodes)
                    all_nodes.extend(nodes)
                h.counts = counts
            if core is not None and h.pack_entry is None:
                h.pack_entry = self._pack_entry(len(all_nodes))
                h.joined, h.blob, h.offsets, h.lens = self._pack_blob(
                    all_nodes, h.pack_entry[1]
                )
        with self._lock:
            # the eviction-window wait RELEASES the lock: the stats
            # snapshot for this batch's delta must come after it, or a
            # concurrent resolver's flush (already published by its own
            # resolve_batch) would be counted into the registry twice
            self._await_evict_window_locked()
            if not self._inflight:
                self._run_deferred_evictions_locked()
            s0 = dict(self.stats)
            if ext is not None:
                h.kind = "ext"
                h.ext_batch, novel, miss, total = ext.scan_begin(witnesses)
                if self._over_cap_locked(len(novel), ext.nodes()):
                    h.ext_batch, novel, miss, total = ext.scan_begin(witnesses)
                h.novel, h.miss, h.total = novel, miss, total
            elif self._core is not None:
                h.kind = "native"
                core = self._core
                rows, novel_idx, miss = core.scan(h.blob, h.offsets, h.lens)
                if self._over_cap_locked(len(novel_idx), core.nodes):
                    rows, novel_idx, miss = core.scan(h.blob, h.offsets, h.lens)
                h.rows, h.novel_idx, h.miss = rows, novel_idx, miss
                h.total = len(all_nodes)
                h.novel = [all_nodes[i] for i in novel_idx.tolist()]
                h.roots = b"".join(root for root, _nodes in witnesses)
            else:
                h.kind = "python"
                rows, novel, miss = self._scan_rows_locked(all_nodes)
                if self._over_cap_locked(len(novel), len(self._row_of_bytes)):
                    rows, novel, miss = self._scan_rows_locked(all_nodes)
                h.rows, h.novel, h.miss = rows, novel, miss
                h.total = len(all_nodes)
                h.witnesses = witnesses
            self.stats["hits"] += h.total - h.miss
            h.n_novel = len(h.novel)
            if self._pin is not None:
                # roots are depth-0 digests by definition (tier tracker)
                self._pin.note_roots([root for root, _nodes in witnesses])
            if h.novel:
                # optimistic advisory update at SCAN time: the commit is
                # coming; an abandoned handle over-approximates, which
                # only costs the prefetch pre-scan accuracy
                self._advisory_add(h.novel)
                self.stats["hashed"] += len(h.novel)
                self.stats["novel_bytes"] = self.stats.get(
                    "novel_bytes", 0
                ) + sum(map(len, h.novel))
            self._inflight += 1
            evict_tiers = self._evictions_by_tier(s0, self.stats)
        # registry publishes after release (the metrics lock never nests
        # inside ours — same discipline as verify_batch)
        for tier, d in evict_tiers:
            metrics.count("witness_engine.evictions", d, tier=tier)
        return h

    def resolve_batch(self, handle: BatchHandle) -> np.ndarray:
        """(n_blocks,) bool verdicts for a handle from `begin_batch`:
        digest readback (device) or novel-node hashing (host — on THIS
        thread, outside the engine lock, so a resolve worker's C keccak
        overlaps the executor's next pack), then commit + linkage join
        under the lock. Verdict semantics are byte-identical to
        `verify_batch` over the same witnesses."""
        with metrics.phase("witness_engine.resolve"):
            verdict, snap = self._resolve_handle(handle)
        res = handle.resident
        if res is not None:
            if res.uploaded_nodes:
                metrics.count(
                    "witness_resident.uploaded_nodes", res.uploaded_nodes
                )
                metrics.count(
                    "witness_resident.uploaded_bytes", res.uploaded_bytes
                )
            if res._table is not None:
                metrics.gauge_set("witness_resident.rows", res._table.rows())
        if handle.total:
            hits = handle.total - handle.miss
            if hits:
                metrics.count("witness_engine.cache_hits", hits)
        metrics.gauge_set(
            "witness_engine.interned_digests", snap["interned_digests"]
        )
        return verdict

    def abandon_batch(self, handle: BatchHandle) -> None:
        """Release a handle WITHOUT committing it — the crash path.
        Dropping a scanned batch is sound (commit is all-or-nothing under
        the lock, so no table state is half-applied); what MUST not leak
        is the pipeline bookkeeping: a stranded in-flight count would
        defer generation flushes forever on a shared engine that outlives
        a dead scheduler, growing the intern tables without bound.
        Idempotent; called by resolve_batch's own pre-commit failure path
        and by the serving scheduler's _die for dispatched-but-unresolved
        handles."""
        if handle.resolved:
            return
        handle.resolved = True
        with self._lock:
            self._release_inflight_locked()
        if handle.pack_entry is not None:
            # the commit that would have consumed the staging views is
            # never coming: the lease goes straight back to the pool.
            # (A device lease stays stranded — the enqueued compute may
            # still be reading its buffers; bounded loss on a crash path.)
            key, entry = handle.pack_entry
            handle.blob = handle.offsets = handle.lens = handle.joined = None
            _staging.give(key, entry)
            handle.pack_entry = None
        if handle.resident is not None:
            # the resident UPDATE was already enqueued and its row
            # assignments stand — that is consistent: the device rows
            # exist, the host prune knows it, and the host core (never
            # committed) simply re-reports those nodes as novel next
            # time, where the prune skips the re-upload. The verdict/
            # digest outputs are dropped unread; the index drop-count
            # scalars go BACK to the table (the stat must not undercount
            # across a crash path).
            dropped = handle.resident.drop_outputs()
            if dropped and handle.resident._table is not None:
                handle.resident._table.return_dropped(dropped)
        handle.novel = []
        handle.witnesses = None
        handle.ext_batch = None

    def _resolve_handle(self, h: BatchHandle):
        if h.resolved:
            raise RuntimeError("batch handle already resolved")
        digests: Optional[List[bytes]] = None
        backend = None
        n_novel = len(h.novel)
        with self._lock:
            # write-once core ref, aliased under the lock (LOCK
            # discipline); the hashing below deliberately runs OUTSIDE it
            ext = self._ext_core
        # host-routed ext batches hash IN C into batch-local digest
        # storage — same zero-Python-round-trip keccak as _verify_ext's
        # finish_native, but split out so it runs WITHOUT the engine lock
        # (GIL released too): the executor's next pack scans the tables
        # concurrently. Any override or open offload gate surfaces the
        # novel list to the Python-visible route instead.
        ext_native_fast = (
            h.resident is None
            and h.kind == "ext"
            and n_novel > 0
            # tiered eviction needs the novel digests at the Python level
            # (the pin tracker classifies on them); route through the
            # batch keccak + finish instead of the in-C finish_native —
            # same C hashing, one extra round trip, and novel counts go
            # to ~0 in the steady state anyway
            # phantlint: disable=LOCK — `_pin` is assigned once in __init__ and never rebound; the tracker's own state only mutates under the engine lock
            and self._pin is None
            and self._native_route_certain()
        )
        verdict_dev = None
        try:
            if h.resident is not None:
                # resident route: the device computed BOTH the verdict
                # and the novel digests the host tables commit from —
                # the host hashes nothing, the readback is 1 B/block +
                # 32 B/core-novel (witness_resident.ResidentBatch)
                verdict_dev, res_digests = h.resident.resolve()
                digests = res_digests or None
                backend = "resident"
            elif h.device is not None:
                digests = h.device.resolve()  # the honest sync (keccak_jax)
                backend = "device"
            elif ext_native_fast:
                backend = "native"
                with metrics.phase("witness_engine.hash"):
                    ext.hash_batch(h.ext_batch)
            elif h.novel:
                with metrics.phase("witness_engine.hash"):
                    digests, backend = self._hash_novel(
                        h.novel, route_device=False
                    )
        except BaseException:
            # readback/hash died BEFORE any commit: release the handle so
            # the pipeline bookkeeping (and deferred evictions) survive
            self.abandon_batch(h)
            raise
        with self._lock:
            s0 = dict(self.stats)
            try:
                if h.kind == "ext":
                    with metrics.phase("witness_engine.linkage_join"):
                        # digests=None: no novels, or hash_batch already
                        # filled the batch-local digests (C side commits
                        # straight from them)
                        raw = self._ext_core.finish_batch(
                            h.ext_batch,
                            b"".join(digests) if digests else None,
                        )
                    verdict = np.frombuffer(raw, np.uint8).astype(bool)
                elif h.kind == "native":
                    if n_novel:
                        self._core.commit(
                            h.blob, h.offsets, h.lens, h.rows, h.novel_idx,
                            b"".join(digests),
                        )
                    if verdict_dev is None:
                        block_offs = np.zeros(h.n_blocks + 1, np.uint64)
                        np.cumsum(h.counts, dtype=np.uint64, out=block_offs[1:])
                        with metrics.phase("witness_engine.linkage_join"):
                            verdict = self._core.verdict(
                                h.rows, block_offs, h.roots
                            )
                else:
                    if n_novel:
                        self._commit_novel_locked(
                            h.rows, h.novel, digests, ref_hint=h.ref_hint
                        )
                    if verdict_dev is None:
                        with metrics.phase("witness_engine.linkage_join"):
                            verdict = self._linkage_join(
                                h.witnesses, h.rows, h.counts, h.n_blocks
                            )
                if self._pin is not None and digests and n_novel:
                    # novel digests surfaced (device / native / resident
                    # readback): classify them for the tiered flush
                    self._pin.note_novel(h.novel, digests)
                if verdict_dev is not None:
                    # the device join IS the verdict on the resident
                    # route (the host join is skipped — the ext core's
                    # fused commit+join is the one place it still runs,
                    # and the two are differential-tested identical)
                    verdict = verdict_dev
                if backend in ("device", "native"):
                    key = backend + "_batches"
                    self.stats[key] = self.stats.get(key, 0) + 1
                elif backend == "resident":
                    self.stats["resident_batches"] = (
                        self.stats.get("resident_batches", 0) + 1
                    )
                    # a resident batch IS a device batch for routing/
                    # record classification (batch_record_from_stats)
                    self.stats["device_batches"] = (
                        self.stats.get("device_batches", 0) + 1
                    )
            finally:
                # a failed commit poisons THIS batch but must not wedge the
                # pipeline bookkeeping (deferred evictions would never run)
                h.resolved = True
                self._release_inflight_locked()
            evict_tiers = self._evictions_by_tier(s0, self.stats)
            snap = self._stats_snapshot_locked()
        for tier, d in evict_tiers:
            # a resolve-drain flush counts like any other (pack publishes
            # its delta the same way — the metric must not undercount)
            metrics.count("witness_engine.evictions", d, tier=tier)
        if n_novel:
            metrics.count("witness_engine.cache_misses", n_novel)
            metrics.count(
                "witness_engine.novel_bytes_hashed", sum(map(len, h.novel))
            )
        if h.pack_entry is not None:
            # the staging offsets buffer is dead only now (commit/verdict
            # consumed the views) — back to the pool for the next batch
            key, entry = h.pack_entry
            h.blob = h.offsets = h.lens = h.joined = None
            _staging.give(key, entry)
            h.pack_entry = None
        h.resolved = True
        h.novel = []
        h.witnesses = None
        h.ext_batch = None
        return verdict, snap

    @staticmethod
    def _evictions_by_tier(s0: dict, s1: dict) -> List[Tuple[str, int]]:
        """(tier, delta) pairs for the `witness_engine.evictions{tier=}`
        metric from a stats delta captured under the engine lock:
        tier="deep" pinned the shallow set and evicted only the deeper
        tiers, tier="full" dropped everything (tiering off, or no pins),
        tier="twin" flushed only the python twin tables of a C-core
        engine (the public intern() overflow path). Publishing happens
        at the caller, outside the lock."""
        out: List[Tuple[str, int]] = []
        tiered = 0
        for tier in ("deep", "full"):
            d = s1.get("evictions_" + tier, 0) - s0.get("evictions_" + tier, 0)
            tiered += d
            if d:
                out.append((tier, d))
        twin = s1.get("evictions", 0) - s0.get("evictions", 0) - tiered
        if twin:
            out.append(("twin", twin))
        return out

    def _release_inflight_locked(self) -> None:
        """Drop one in-flight handle (resolve or abandon). When the
        pipeline empties, run any deferred eviction RIGHT HERE — under
        sustained pipelined load the executor's next begin overlaps this
        resolve, so 'check at the next begin' alone can starve the flush
        indefinitely and grow the tables without bound — and wake begins
        waiting for a flush window."""
        self._inflight -= 1
        if self._inflight == 0:
            self._run_deferred_evictions_locked()
            self._drained.notify_all()

    def _run_deferred_evictions_locked(self) -> None:
        """Any deferred generation flushes, each against ITS tables.
        Caller holds the lock with an empty pipeline."""
        if self._evict_pending:
            self._evict_pending = False
            self._evict_now_locked()
        if self._evict_pending_py:
            # intern() on a C-core engine overfilled the python twin:
            # flush those dicts only, never the warm core cache
            self._evict_pending_py = False
            self._evict_all()

    def _interned_nodes_locked(self) -> int:
        if self._ext_core is not None:
            return self._ext_core.nodes()
        if self._core is not None:
            return self._core.nodes
        return len(self._row_of_bytes)

    def _await_evict_window_locked(self) -> None:
        """Hard ceiling on deferred-eviction overshoot: when the tables
        have grown past 2x max_nodes with a flush still pending, make the
        over-cap begin WAIT (bounded) for the pipeline to drain instead
        of deferring again — a saturated pipeline never has a natural
        idle point, and unbounded deferral would unbound memory. The
        timeout keeps a caller that begins without a concurrent resolver
        (API misuse) degraded-but-alive rather than deadlocked."""
        over_core = (
            self._evict_pending
            and self._interned_nodes_locked() > 2 * self._max_nodes
        )
        over_py = (
            self._evict_pending_py
            and len(self._row_of_bytes) > 2 * self._max_nodes
        )
        if not (self._inflight and (over_core or over_py)):
            return
        import time

        deadline = time.monotonic() + 2.0
        while self._inflight and time.monotonic() < deadline:
            self._drained.wait(0.05)
        # _release_inflight_locked already flushed if the pipe drained

    def _over_cap_locked(self, n_novel: int, n_existing: int) -> bool:
        """THE eviction policy, shared by every scan site (classic verify
        paths and the pipelined pack stage): when this batch's novels
        would cross `max_nodes` over a non-empty table, either flush now
        (pipeline empty — returns True, caller MUST rescan against the
        fresh generation) or defer (`_evict_pending`, handles in flight —
        a flush would strand their scanned row ids; the flush then runs
        at the next pipeline drain, see _release_inflight_locked)."""
        if not (
            n_novel
            and n_existing  # an over-cap single batch still runs
            and n_existing + n_novel > self._max_nodes
        ):
            return False
        if self._inflight:
            self._evict_pending = True
            return False
        self._evict_now_locked(incoming_novel=n_novel)
        return True

    def _evict_now_locked(self, incoming_novel: int = 0) -> None:
        """Generation flush on whichever core is live. Caller holds the
        lock AND has checked `self._inflight == 0` — flushing under an
        outstanding pipelined batch would strand its scanned row ids.

        With tiered eviction (`_pin`), the flush is DEPTH-TIERED: the
        shallow pinned set (depth <= pin_depth, shallow-first within the
        budget) re-commits into the fresh generation with its remembered
        digests — no re-hashing — while everything deeper evicts
        generationally. `incoming_novel` reserves room for the batch
        that triggered the flush, so pins can never crowd out live
        traffic (and a single over-cap batch degrades to the flat
        flush). The tier label rides the evictions metric: tier="deep"
        evicted only the deep tiers, tier="full" dropped everything."""
        pinned: List[tuple] = []
        if self._pin is not None:
            room = self._max_nodes - incoming_novel
            if room > 0:
                pinned = self._pin.pinned_snapshot()[:room]
        self.stats["evictions"] += 1
        tier = "deep" if pinned else "full"
        self.stats["evictions_" + tier] = self.stats.get(
            "evictions_" + tier, 0
        ) + 1
        if self._ext_core is not None:
            self._ext_core.flush()
        elif self._core is not None:
            self._core.flush()
        else:
            self._row_of_bytes.clear()
            self._refid_of_digest.clear()
            self._n_rows = 0
            self._n_refids = 0
        self._seen_advisory.clear()
        if pinned:
            self._recommit_pinned_locked(pinned)
        self.stats["pinned_retained"] = len(pinned)
        self._flush_attached_locked(pinned)

    def _recommit_pinned_locked(self, pinned: Sequence[tuple]) -> None:
        """Insert the pinned shallow set into the just-flushed generation
        with its REMEMBERED digests — the scan/commit protocols every
        core already exposes, fed known digests instead of fresh keccak.
        The ext core runs one throwaway scan_begin/finish_batch pair
        (the verdict of the dummy block is discarded); row/refid spaces
        restart at zero with the pins as the first rows on every core,
        so cross-core parity holds."""
        nodes = [nb for nb, _dg, _d in pinned]
        dmap = {nb: dg for nb, dg, _d in pinned}
        if self._ext_core is not None:
            batch, novel, _miss, _total = self._ext_core.scan_begin(
                [(b"\x00" * 32, nodes)]
            )
            self._ext_core.finish_batch(
                batch, b"".join(dmap[nb] for nb in novel) if novel else None
            )
        elif self._core is not None:
            joined, blob, offsets, lens = self._pack_blob(nodes)
            rows, novel_idx, _miss = self._core.scan(blob, offsets, lens)
            if len(novel_idx):
                self._core.commit(
                    blob,
                    offsets,
                    lens,
                    rows,
                    novel_idx,
                    b"".join(dmap[nodes[i]] for i in novel_idx.tolist()),
                )
            del joined  # kept alive across the ctypes calls above
        else:
            rows, novel, _miss = self._scan_rows_locked(nodes)
            if novel:
                self._commit_novel_locked(
                    rows, novel, [dmap[nb] for nb in novel]
                )
        self._advisory_add(nodes)

    def _verify_batch_locked(
        self, witnesses: Sequence[Tuple[bytes, Sequence[bytes]]]
    ) -> np.ndarray:
        # deferred evictions already ran in verify_batch, BEFORE its
        # stats snapshot (the eviction-window wait releases the lock)
        if self._ext_core is not None:
            return self._verify_ext(witnesses)
        n_blocks = len(witnesses)
        all_nodes: List[bytes] = []
        counts = np.empty(n_blocks, np.int64)
        for b, (_root, nodes) in enumerate(witnesses):
            counts[b] = len(nodes)
            all_nodes.extend(nodes)
        if self._core is not None:
            return self._verify_native(witnesses, all_nodes, counts, n_blocks)
        return self._verify_interned(witnesses, all_nodes, counts, n_blocks)

    def _verify_ext(self, witnesses):
        """Two-call scan/finish protocol against the CPython extension
        driver — no batch assembly on the Python side at all. When the
        hashing route is provably the host (no hasher override, no device
        floor, and the offload gate cannot fire), the novel nodes hash
        inside the extension (finish_native) with zero Python round trip;
        otherwise the novel list comes back here so the backend route
        applies identically to every core."""
        st = self._ext_core
        if self._pin is not None:
            self._pin.note_roots([root for root, _nodes in witnesses])
        with metrics.phase("witness_engine.intern"):
            novel, miss, total = st.scan(witnesses)
        n_novel = len(novel)
        if n_novel:
            if self._over_cap_locked(n_novel, st.nodes()):
                with metrics.phase("witness_engine.intern"):
                    novel, miss, total = st.scan(witnesses)
                n_novel = len(novel)
            self._advisory_add(novel)
            route_device = not self._native_route_certain() and (
                self._device_route_wanted(novel)
            )
            self.stats["novel_bytes"] = self.stats.get("novel_bytes", 0) + sum(
                map(len, novel)
            )
            if not route_device and self._pin is None:
                # the routed hasher for THIS batch is the host: hash inside
                # the extension, zero Python round trip.  (With the Pallas
                # kernel the offload gate is open in principle, so the
                # structural short-circuit alone no longer covers the
                # common native case — the per-batch cost-model verdict
                # does, at the price of one cached link-profile read.)
                self.stats["hashed"] += n_novel
                self.stats["native_batches"] = (
                    self.stats.get("native_batches", 0) + 1
                )
                # finish_native hashes + commits + joins in one C call; it
                # times as "hash" because the novel-node keccak dominates
                with metrics.phase("witness_engine.hash"):
                    verdict = st.finish_native()
            else:
                # device-routed, or tiered eviction needs the digests at
                # the Python level: the batch keccak route (device or
                # native per the cost model) surfaces them
                digests = self._hash_batch(novel, route_device=route_device)
                self.stats["hashed"] += n_novel
                if self._pin is not None:
                    self._pin.note_novel(novel, digests)
                with metrics.phase("witness_engine.linkage_join"):
                    verdict = st.finish(b"".join(digests))
        else:
            with metrics.phase("witness_engine.linkage_join"):
                verdict = st.finish(None)
        self.stats["hits"] += total - miss
        return np.frombuffer(verdict, np.uint8).astype(bool)

    def _device_route_wanted(self, nodes: List[bytes]) -> bool:
        """THE routing predicate: would this batch go to the device?
        Shared by _hash_batch (which acts on it) and _verify_ext (which
        uses it to keep the zero-round-trip finish_native fast path for
        host-routed batches), so the two can never disagree.

        A hasher override returns True — the batch must surface to
        the Python-visible path for the override to apply."""
        from phant_tpu.backend import (
            crypto_backend,
            device_offload_pays,
            jax_device_ok,
        )
        from phant_tpu.crypto.keccak import RATE

        if self._hasher is not None:
            return True
        # backend check FIRST: the adaptive gate probes the device link,
        # which must never happen on the pure-CPU path (a device call
        # that never returns would hang a run that never asked for one)
        if crypto_backend() != "tpu" or not jax_device_ok():
            return False
        # nodes at/over the kernel's absorb capacity (pad byte positions
        # would fall past the gathered chunks) must take the native path —
        # witnesses are untrusted input and the digest must never be
        # silently wrong, matching pack_witness_fused's explicit raise
        if any(len(n) >= WITNESS_MAX_CHUNKS * RATE for n in nodes):
            return False
        if self._device_batch_floor >= 0:
            return len(nodes) >= self._device_batch_floor
        return device_offload_pays(sum(len(n) for n in nodes))

    def _native_route_certain(self) -> bool:
        """True when _hash_batch could only ever pick the native hasher —
        then finish_native may hash in C without consulting the route. Any
        override (injected hasher, device floor) or a cost model that could
        favor the device falls back to the Python-visible path."""
        if self._hasher is not None or self._device_batch_floor >= 0:
            return False
        from phant_tpu.backend import crypto_backend, device_offload_possible

        if crypto_backend() != "tpu":
            return True
        # tpu backend: only safe when the gate is structurally closed
        return not device_offload_possible()

    def _verify_native(self, witnesses, all_nodes, counts, n_blocks):
        """Scan/hash/commit/verdict against the C++ core. The hashing of
        novel nodes stays here so the device/native backend route (and an
        injected hasher) applies identically to both cores."""
        core = self._core
        n = len(all_nodes)
        if self._pin is not None:
            self._pin.note_roots([root for root, _nodes in witnesses])
        # `joined` kept alive across the ctypes calls
        joined, blob, offsets, lens = self._pack_blob(all_nodes)
        with metrics.phase("witness_engine.intern"):
            rows, novel_idx, miss = core.scan(blob, offsets, lens)
        if len(novel_idx):
            if self._over_cap_locked(len(novel_idx), core.nodes):
                with metrics.phase("witness_engine.intern"):
                    rows, novel_idx, miss = core.scan(blob, offsets, lens)
            novel = [all_nodes[i] for i in novel_idx.tolist()]
            self._advisory_add(novel)
            digests = self._hash_batch(novel)
            self.stats["hashed"] += len(novel)
            self.stats["novel_bytes"] = self.stats.get("novel_bytes", 0) + sum(
                map(len, novel)
            )
            if self._pin is not None:
                self._pin.note_novel(novel, digests)
            core.commit(blob, offsets, lens, rows, novel_idx, b"".join(digests))
        self.stats["hits"] += n - miss
        block_offs = np.zeros(n_blocks + 1, np.uint64)
        np.cumsum(counts, dtype=np.uint64, out=block_offs[1:])
        roots = b"".join(root for root, _nodes in witnesses)
        with metrics.phase("witness_engine.linkage_join"):
            return core.verdict(rows, block_offs, roots)

    def _verify_interned(self, witnesses, all_nodes, counts, n_blocks):
        # the intern phase includes the nested witness_engine.hash phase of
        # any novel nodes; linkage-join covers the integer-join verdict
        if self._pin is not None:
            self._pin.note_roots([root for root, _nodes in witnesses])
        with metrics.phase("witness_engine.intern"):
            rows = self._intern_locked(all_nodes)
        with metrics.phase("witness_engine.linkage_join"):
            return self._linkage_join(witnesses, rows, counts, n_blocks)

    def _linkage_join(self, witnesses, rows, counts, n_blocks):
        block_id = np.repeat(np.arange(n_blocks, dtype=np.int64), counts)

        # the root digest resolves through the same refid space; -1 when the
        # digest has never been seen (as a node or a reference)
        root_refid = np.fromiter(
            (self._refid_of_digest.get(root, -1) for root, _n in witnesses),
            np.int64,
            n_blocks,
        )

        # per-(block, refid) edge join, all integer ops: node ok <=> its
        # digest is the block's root, or some node of the same block has a
        # child reference to its digest. 64-bit pairing key =
        # block * stride + refid.
        own = self._own_refid[rows]  # (N,)
        children = self._child_refids[rows]  # (N, 17)
        live = children >= 0
        stride = np.int64(self._n_refids + 1)
        edge_keys = np.unique((block_id[:, None] * stride + children)[live])
        node_keys = block_id * stride + own
        if len(edge_keys):
            idx = np.searchsorted(edge_keys, node_keys)
            referenced = (idx < len(edge_keys)) & (
                edge_keys[np.minimum(idx, len(edge_keys) - 1)] == node_keys
            )
        else:
            referenced = np.zeros(len(node_keys), bool)
        is_root = own == root_refid[block_id]
        ok_node = referenced | is_root

        all_ok = np.ones(n_blocks, bool)
        np.logical_and.at(all_ok, block_id, ok_node)
        # some node of the block must actually hash to the root (a root
        # refid that exists only as a reference is not enough)
        root_present = np.zeros(n_blocks, bool)
        np.logical_or.at(root_present, block_id, is_root)
        return all_ok & root_present & (counts > 0)

    def verify(self, state_root: bytes, nodes: Sequence[bytes]) -> bool:
        """Single-witness convenience wrapper (the Engine API path)."""
        return bool(self.verify_batch([(state_root, list(nodes))])[0])

    def resident_table(self):
        """The live device-resident table, or None (not yet engaged /
        dropped). Tests read its arrays and upload accounting."""
        with self._lock:
            return self._resident

    def stats_snapshot(self) -> dict:
        """Counters + derived cache-effectiveness numbers (the public
        surface behind the phant_witnessEngineStats RPC). Takes the engine
        lock: finish_native releases the GIL mid-commit, so an unlocked
        read could otherwise observe the native tables mid-mutation."""
        with self._lock:
            return self._stats_snapshot_locked()

    def _stats_snapshot_locked(self) -> dict:
        st = dict(self.stats)
        seen = st.get("hashed", 0) + st.get("hits", 0)
        st["hit_rate"] = round(st.get("hits", 0) / seen, 4) if seen else 0.0
        if self._ext_core is not None:
            st["interned_nodes"] = self._ext_core.nodes()
            st["interned_digests"] = self._ext_core.digests()
            st["core"] = "native-ext"
        elif self._core is not None:
            st["interned_nodes"] = self._core.nodes
            st["interned_digests"] = self._core.digests
            st["core"] = "native"
        else:
            st["interned_nodes"] = len(self._row_of_bytes)
            st["interned_digests"] = len(self._refid_of_digest)
            st["core"] = "python"
        if self._device_index is not None:
            # mesh pinning surface: which pool lane this engine is, and —
            # once the device route has resolved it — the actual jax
            # device the hashing lands on
            st["device_index"] = self._device_index
            if self._pinned is not None:
                st["device"] = str(self._pinned)
        if self._pin is not None:
            # depth-tiered eviction (PR 9): the live pin classification —
            # how many shallow rows the next generation flush would
            # retain, per depth (the histogram-derived tier model)
            st["tiered_evict"] = True
            st["pin_depth"] = self._pin.pin_depth
            st["pinned_rows"] = len(self._pin._pinned)
            st["pinned_per_depth"] = {
                str(d): c for d, c in sorted(self._pin.per_depth().items())
            }
        if self._resident is not None:
            # device-resident intern table: rows/generation plus the
            # upload accounting (novel bytes shipped vs pruned) — the
            # steady-state link-independence claim, auditable per lane
            st["resident"] = self._resident.stats_snapshot()
        return st
