"""Device-resident witness intern table: upload novel bytes once, ever.

The memoized engine (ops/witness_engine.py) already hashes each unique
trie node once — but on the TPU route it still pays the link per batch:
novel bytes go up, their digests come back down, and the linkage join
runs on HOST tables, so the chip holds no state and contributes nothing
in the steady state (the ROADMAP "device-resident intern table" gap:
a fast kernel, ~zero end-to-end, because the host<->device link —
not the compute — is on the per-batch critical path).

This module keeps the intern table ON the device, persistent across
batches:

  * **Resident rows** — `digests` (cap, 8) u32, the child-reference
    words `refs` (cap, 128: slots 0..15, a branch's children) and `tail`
    (cap, 9: slot 16, an extension's child or an account leaf's storage
    root, then the liveness of all 17 as a bit mask), one row per unique
    interned node, scattered in place by the update program the moment a
    novel batch is dispatched. The shapes are the ones XLA writes and
    reads a row at a time on the TPU: a (cap, 17, 8) table is kept with
    the row index minor-most and was re-laid out whole, 4.3 GB of
    temporaries at 2^20 rows, by every update (PERF.md section 5, PR 31).
    Rows are assigned by the HOST
    (`slot_of_bytes`, the authoritative commit — exact byte equality,
    no fingerprint trust on the verdict path) and grow in power-of-two
    generations; a generation FLUSH drops everything and is synchronized
    with the owning engine's host-table flushes, so host and device
    tables never disagree about what exists.
  * **Row index** — a hash-bucketed open-addressing table over 64-bit
    digest fingerprints (ops/keccak_jax.index_insert / index_lookup),
    resident next to the rows. The production verdict never needs it
    (host rows are exact); it is the DEVICE-side scan: it resolves
    rows on device from fingerprints alone (8 bytes/node up, nothing
    else), and tests cross-check it against the host dict.
  * **Per-batch traffic** — the truly-novel nodes (the host scan prunes
    anything already resident, including cross-batch pipelined
    duplicates the engine cores re-report) in the ROW FORM: one row of
    680 bytes a node, zero past its length, the row count padded to a
    power of two (`witness_jax.pack_node_rows`: one native memcpy loop),
    with 8 bytes a row of length and slot. A lone block's 1,400 novel
    nodes of 0.47 MB go up as 2,048 rows, 1.39 MB: three times the
    bytes, 0.26 ms more on the link, and in exchange the update program
    moves no single byte (as a blob it moved 2.5 M of them a batch
    through XLA's gather, 25 of its 46 ms) and its jit key holds no byte
    count (`witness_resident.update_rows` / `update_bytes` and
    `lanes.program_shapes{program=update}` on /metrics). Then 4 bytes/node of row ids +
    32 bytes/block of roots up; 1 byte/block of verdicts + 32 bytes per
    CORE-novel digest down (the engine's host tables commit from the
    device digests, so the host hashes nothing on this route). Steady
    state: row ids and roots only — the PAPERS.md 2408.14217 reuse
    analysis is exactly why that is a small fraction of witness bytes.

Verdict semantics are identical to the host engine's linkage join and
the fused kernel (a block verifies iff some node's digest equals its
root AND every node is that root or hash-referenced by a same-block
node); a row the device cannot resolve FAILS its block — residency can
only reject, never silently accept. Differential-tested against all
three engine cores in tests/test_witness_resident.py.

Thread-safety: one lock guards the host bookkeeping and the array
handles; dispatches enqueue under it (async — no device sync inside the
lock) so concurrent engines/schedulers see a consistent row space, and
data dependencies between the update and verdict programs serialize the
device work regardless of thread interleaving. The lock never takes the
engine lock (the engine calls in, never the reverse).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from phant_tpu.utils.trace import device_host, metrics
from phant_tpu.crypto.keccak import RATE
from phant_tpu.ops.witness_jax import WITNESS_MAX_CHUNKS, pack_node_rows
from phant_tpu.utils.rungs import launches, note_launch, note_split, pow2ceil

#: bytes of one node row; a node must leave one free for the keccak pad
_ROW_BYTES = WITNESS_MAX_CHUNKS * RATE

__all__ = [
    "ROW_LADDER",
    "VERDICT_LADDER",
    "ResidentBatch",
    "ResidentTable",
    "resident_default_cap",
    "verdict_rows",
    "verdict_rung",
]

#: THE shape set of `_verdict_impl`: (node rows, blocks) of one launch. The
#: first three rungs are a WAVE's, one index: one, two and four requests of
#: mainnet's usual shape (a lone block of 225 transactions under a 2^20
#: genesis is 1,400-1,580 nodes and sits on the first rung, as it did when
#: the program was keyed on the wave's own powers of two). A wave above
#: the widest of them (`_wave_rung`: the rung of the most blocks) goes out
#: as several launches, cut between blocks: a block's nodes only ever
#: reference each other. The last rung is ONE block's, and no wave is cut
#: at it: a block's verdict is one reachability over all its rows and
#: cannot be cut, and a block at the gas limit is wider than a wave of
#: four usual ones (30M gas of plain transfers between distinct accounts
#: touch 2,857 accounts: about 10,050 nodes under a 2^20 genesis, about
#: 15,800 at mainnet's depth of 7-8). The boot builds it with the others
#: (`ResidentTable.prewarm`), so such a block never waits for a build; a
#: block above it (30M gas of cold SLOADs, 14,285 slots) keeps a shape of
#: its own, counted in `lanes.oversize_launches`.
VERDICT_LADDER: Tuple[Tuple[int, int], ...] = (
    (2048, 1),
    (4096, 2),
    (8192, 4),
    (16384, 1),
)

#: THE shape set of `_update_impl` and `_gather_impl`, in rows: ONE rung, a
#: request's novel nodes (1,400-1,580 under a 2^20 genesis sat on 2,048
#: when the programs were keyed on the batch's own power of two). A wave
#: that brings more goes out as launches of 2,048 (rows are independent;
#: 1.04 ms a launch, my chip run, PR 31), a batch of a few novel nodes (a
#: block altered in one node) pads up to it. Why one: the boot builds every
#: rung before the port answers, and with 64 / 2,048 / 4,096 / 8,192 a warm
#: server on the host-walk configuration came up after 43 s where it had
#: come up at once (`setup_s` +31 % against a bound of 0.25; my chip run,
#: PR 34): `_update_impl` is the one table program that is dear to trace.
ROW_LADDER: Tuple[int, ...] = (2048,)


def _wave_rung() -> Tuple[int, int]:
    """The rung a wave is cut at: the one that holds the most blocks."""
    return max(VERDICT_LADDER, key=lambda rung: (rung[1], rung[0]))


def verdict_rung(n_nodes: int, n_blocks: int) -> Optional[Tuple[int, int]]:
    """The first rung of VERDICT_LADDER that holds a launch of `n_nodes`
    rows in `n_blocks` blocks, or None where none does: one block above
    the lone block's rung (`_verdict_launches` never asks for a wave
    above the wave's)."""
    for rows, blocks in VERDICT_LADDER:
        if rows >= n_nodes and blocks >= n_blocks:
            return rows, blocks
    return None


def _verdict_launches(counts: Sequence[int]) -> List[Tuple[int, int, Tuple[int, int]]]:
    """Cut a wave of blocks of `counts` nodes into launches of whole
    blocks, in order: (first block, one past the last, the launch's shape),
    each as many blocks as the wave's rung holds. One block above that
    rung's rows stands alone: on the lone block's rung where it fits,
    else on its own power of two, the one shape outside the ladder
    (`lanes.oversize_launches`)."""
    top_rows, top_blocks = _wave_rung()
    cuts = []
    first = rows = 0
    for b, n in enumerate(counts):
        if b > first and (b - first == top_blocks or rows + n > top_rows):
            cuts.append((first, b, rows))
            first, rows = b, 0
        rows += n
    cuts.append((first, len(counts), rows))
    return [
        (lo, hi, verdict_rung(r, hi - lo) or (pow2ceil(r), 1)) for lo, hi, r in cuts
    ]


def verdict_rows(counts: Sequence[int]) -> int:
    """The node rows a wave of blocks of `counts` nodes is launched on,
    all its launches together (`rung=` of `phant/witness.dispatch`)."""
    return sum(shape[0] for _lo, _hi, shape in _verdict_launches(counts))


def resident_default_cap() -> int:
    """PHANT_RESIDENT_CAP: hard row bound of a resident table (~632 B of
    HBM per row: digest + 17 ref words + liveness bits + fingerprint + 4
    index buckets, the narrow arrays padded to whole tiles). The default
    fits comfortably in a v5e's 16 GB."""
    return int(os.environ.get("PHANT_RESIDENT_CAP", 1 << 20))


# ---------------------------------------------------------------------------
# device programs (compose keccak + ref extraction + index primitives)
# ---------------------------------------------------------------------------


def _update_impl(digests, refs, tail, index, fps, words, lens, slots, *, max_chunks):
    """Scatter one novel batch into the resident arrays: hash the node
    rows, extract their child references, write rows at the host-assigned
    slots, insert digest fingerprints into the index. Pad rows carry
    slot -1 and drop out of bounds. The batch arrives in the row form
    (`witness_jax.pack_node_rows`): every move of this program has a row
    for its unit, and its cost follows the batch, not the table."""
    import jax.numpy as jnp

    from phant_tpu.ops.keccak_jax import index_insert
    from phant_tpu.ops.witness_jax import node_row_features

    cap = digests.shape[0]
    d, r, rl = node_row_features(words, lens, max_chunks=max_chunks)
    ok = slots >= 0
    tgt = jnp.where(ok, slots, cap)  # out of bounds -> dropped by the mode
    live_bits = jnp.sum(
        rl.astype(jnp.uint32) << jnp.arange(17, dtype=jnp.uint32),
        axis=1,
        dtype=jnp.uint32,
    )
    digests = digests.at[tgt].set(d, mode="drop")
    refs = refs.at[tgt].set(r[:, :16].reshape(-1, 128), mode="drop")
    tail = tail.at[tgt].set(
        jnp.concatenate([r[:, 16], live_bits[:, None]], axis=1), mode="drop"
    )
    fps = fps.at[tgt].set(d[:, :2], mode="drop")
    index, dropped = index_insert(index, d[:, :2], slots, ok)
    return digests, refs, tail, index, fps, dropped


def _rows_at(refs, tail, rc):
    """((B, 17, 8) ref words, (B, 17) liveness) of the resident rows rc."""
    import jax.numpy as jnp

    t = tail[rc]
    r17 = jnp.concatenate([refs[rc].reshape(-1, 16, 8), t[:, None, :8]], axis=1)
    live = ((t[:, 8:9] >> jnp.arange(17, dtype=jnp.uint32)) & 1) != 0
    return r17, live


def _verdict_impl(digests, refs, tail, rows, node_live, block_id, roots):
    """(n_blocks,) bool linked-multiproof verdict from resident rows.

    `node_live` marks real nodes (False = batch padding); a live node
    whose row is unresolved (< 0) fails its block — the device-lookup
    mode can MISS, and a miss must reject, exactly like a witness
    missing that node. Semantics otherwise identical to
    witness_jax.linked_verdict / the host engine join."""
    import jax.numpy as jnp

    from phant_tpu.ops.witness_jax import _referenced

    cap = digests.shape[0]
    n_blocks = roots.shape[0]
    present = node_live & (rows >= 0)
    rc = jnp.clip(rows, 0, cap - 1)
    d = digests[rc]  # (B, 8); garbage for non-present rows, masked below
    r17, live = _rows_at(refs, tail, rc)
    rl = (live & present[:, None]).reshape(-1)
    rb = jnp.broadcast_to(block_id[:, None], (rows.shape[0], 17)).reshape(-1)
    is_root = jnp.all(d == roots[block_id], axis=1) & present
    referenced = _referenced(d, block_id, r17.reshape(-1, 8), rb, rl)
    ok_node = (~node_live) | (present & (is_root | referenced))
    root_hit = (
        jnp.zeros((n_blocks,), jnp.int32)
        .at[block_id]
        .max(is_root.astype(jnp.int32))
    )
    all_ok = (
        jnp.ones((n_blocks,), jnp.int32)
        .at[jnp.where(node_live, block_id, 0)]
        .min(jnp.where(node_live, ok_node, True).astype(jnp.int32))
    )
    return (root_hit > 0) & (all_ok > 0)


def _reindex_impl(fps, n_rows):
    """Fresh index over the first `n_rows` fingerprints (pow2 growth
    rehashes: bucket positions depend on the table size)."""
    import jax.numpy as jnp

    from phant_tpu.ops.keccak_jax import INDEX_EMPTY, index_insert

    cap = fps.shape[0]
    slots = jnp.arange(cap, dtype=jnp.int32)
    index = jnp.full((4 * cap,), INDEX_EMPTY, jnp.int32)
    return index_insert(index, fps, slots, slots < n_rows)


def _gather_impl(digests, slots):
    """(N, 8) digest rows at `slots` (clipped; callers slice real rows)."""
    import jax.numpy as jnp

    return digests[jnp.clip(slots, 0, digests.shape[0] - 1)]


def _lookup_impl(index, fps, q):
    from phant_tpu.ops.keccak_jax import index_lookup

    return index_lookup(index, fps, q)


_JIT_PROGRAMS: dict = {}
_JIT_LOCK = threading.Lock()


def _jit_programs(donate: bool) -> dict:
    """The jitted resident programs, memoized per donation mode (which
    is a per-backend property, so in practice one entry per process)."""
    with _JIT_LOCK:
        fns = _JIT_PROGRAMS.get(donate)
        if fns is None:
            import jax

            fns = _JIT_PROGRAMS[donate] = {
                "update": jax.jit(
                    _update_impl,
                    static_argnames=("max_chunks",),
                    donate_argnums=(0, 1, 2, 3, 4) if donate else (),
                ),
                "verdict": jax.jit(_verdict_impl),
                "reindex": jax.jit(_reindex_impl),
                "gather": jax.jit(_gather_impl),
                "lookup": jax.jit(_lookup_impl),
            }
        return fns


class ResidentBatch:
    """One dispatched resident batch: the verdict bits and (when the
    engine core had novel nodes) their digest rows, both still on
    device. `resolve()` pays the readback — verdicts are 1 byte/block,
    digests 32 bytes per core-novel node; in the steady state that is
    the ONLY downlink traffic of witness verification."""

    __slots__ = (
        "verdict_outs",  # (unresolved verdict bits, blocks) of each launch
        "digest_outs",  # (unresolved digest rows, real rows) of each launch
        "dropped_outs",
        "uploaded_nodes",
        "uploaded_bytes",
        "generation",
        "_table",
        "resolved",
    )

    def __init__(self):
        for name in self.__slots__:
            setattr(self, name, None)
        self.verdict_outs = []
        self.digest_outs = []
        self.dropped_outs = []
        self.resolved = False

    def drop_outputs(self) -> list:
        """Let go of the device outputs unread (an abandoned handle) and
        hand back the drop counts, which the table still has to read."""
        dropped, self.dropped_outs = self.dropped_outs, []
        self.verdict_outs = []
        self.digest_outs = []
        return dropped

    def resolve(self) -> Tuple[np.ndarray, List[bytes]]:
        """(verdicts, core_novel_digests) — the honest sync of the
        resident route."""
        from phant_tpu.ops.keccak_jax import digests_to_bytes

        with metrics.phase("witness_resident.resolve"):
            # the timed verdict readback IS the honest sync (1 B/block):
            # device.host_seconds{lane=witness,op=sync} is the time this
            # thread stands blocked on the chip, and nothing else
            with device_host("witness", "sync"):
                verdicts = np.concatenate(
                    [np.asarray(out)[:n] for out, n in self.verdict_outs]  # phantlint: disable=HOSTSYNC — timed resident verdict readback
                )
                digest_words = [
                    np.asarray(out)[:n] for out, n in self.digest_outs  # phantlint: disable=HOSTSYNC — timed core-commit digest readback
                ]
                dropped = 0
                for out in self.dropped_outs:
                    dropped += int(np.asarray(out))  # phantlint: disable=HOSTSYNC — rides the resolve sync above
            digests: List[bytes] = []
            for words in digest_words:
                digests.extend(digests_to_bytes(words))
        if dropped and self._table is not None:
            self._table.note_index_dropped(dropped)
        self.resolved = True
        self.drop_outputs()  # release the device outputs
        return verdicts.astype(bool), digests


class ResidentTable:
    """The device-resident intern table of ONE engine (or one mesh lane:
    device-pinned engines each own an independent table on their chip).
    """

    def __init__(
        self,
        max_cap: Optional[int] = None,
        start_cap: Optional[int] = None,
        device=None,
    ):
        self._max_cap = pow2ceil(max_cap or resident_default_cap())
        import jax

        on_device = jax.default_backend() != "cpu"
        if start_cap is None:
            # the five programs are keyed on the row space, and every
            # doubling rebuilds them inside a request (PERF.md, fault 0b):
            # on an accelerator the table is born at its cap, 0.6 GB of
            # 16 at 2^20 rows. On the CPU (tests, dry runs) a program is
            # built in seconds and memory is the host's: start small and
            # grow. PHANT_RESIDENT_START_CAP says otherwise on either.
            start_cap = int(
                os.environ.get(
                    "PHANT_RESIDENT_START_CAP",
                    self._max_cap if on_device else 1 << 10,
                )
            )
        self._start_cap = min(pow2ceil(max(start_cap, 64)), self._max_cap)
        self._device = device  # jax device handle or None (default placement)
        self._lock = threading.Lock()
        #: the authoritative commit: exact node bytes -> resident row.
        #: Byte objects are shared references with the engine core's own
        #: dict, so the marginal host memory is dict overhead, not copies.
        self._slot_of_bytes: Dict[bytes, int] = {}
        self._n_rows = 0
        self._cap = 0
        self._arrays = None  # (digests, refs, tail, index, fps)
        self._deferred_dropped: list = []  # reindex drop counts, unread
        self.generation = 0
        self.stats = {
            "uploaded_nodes": 0,
            "uploaded_bytes": 0,
            "pruned_nodes": 0,
            "batches": 0,
            "grows": 0,
            "flushes": 0,
            "index_dropped": 0,
        }
        # jitted programs: PROCESS-level singletons (not per-table — a
        # mesh pool builds one table per lane, and per-table jit wrappers
        # would recompile the same HLO once per lane). Buffer DONATION is
        # enabled on real accelerators so the update rewrites the
        # resident arrays in place instead of copying ~cap*632B per
        # novel batch; the CPU backend does not support donation and
        # would warn per call.
        fns = _jit_programs(on_device)
        self._update_fn = fns["update"]
        self._verdict_fn = fns["verdict"]
        self._reindex_fn = fns["reindex"]
        self._gather_fn = fns["gather"]
        self._lookup_fn = fns["lookup"]

    # -- host bookkeeping ---------------------------------------------------

    def _put(self, x):
        import jax

        if self._device is not None:
            return jax.device_put(x, self._device)
        return jax.device_put(x)

    def _alloc_locked(self, cap: int) -> None:
        from phant_tpu.ops.keccak_jax import INDEX_EMPTY

        self._cap = cap
        self._arrays = (
            self._put(np.zeros((cap, 8), np.uint32)),
            self._put(np.zeros((cap, 128), np.uint32)),
            self._put(np.zeros((cap, 9), np.uint32)),
            self._put(np.full((4 * cap,), INDEX_EMPTY, np.int32)),
            self._put(np.zeros((cap, 2), np.uint32)),
        )

    def _grow_locked(self, need: int) -> None:
        """Double the row space (pow2 generations) up to max_cap. The
        index is rebuilt — bucket positions depend on the table size —
        via one device program; nothing is read back."""
        import jax.numpy as jnp

        if self._arrays is None:
            cap = self._start_cap
            while cap < min(need, self._max_cap):
                cap *= 2
            self._alloc_locked(min(cap, self._max_cap))
            return
        new_cap = self._cap
        while new_cap < need and new_cap < self._max_cap:
            new_cap *= 2
        if new_cap <= self._cap:
            return
        pad = ((0, new_cap - self._cap), (0, 0))
        d, r, t, _idx, fps = self._arrays
        d, r, t, fps = jnp.pad(d, pad), jnp.pad(r, pad), jnp.pad(t, pad), jnp.pad(fps, pad)
        idx, dropped = self._reindex_fn(fps, jnp.int32(self._n_rows))
        self._deferred_dropped.append(dropped)
        self._arrays = (d, r, t, idx, fps)
        self._cap = new_cap
        self.stats["grows"] += 1

    def flush(self) -> None:
        """Generation flush: drop every resident row AND the device
        arrays. Called by the owning engine's generation flush (host and
        device tables evict together) and by `WitnessEngine.reset()`."""
        with self._lock:
            self._flush_locked()

    def _flush_locked(self) -> None:
        self._slot_of_bytes.clear()
        self._n_rows = 0
        self._cap = 0
        self._arrays = None  # releases the device buffers
        self._deferred_dropped = []
        self.generation += 1
        self.stats["flushes"] += 1

    def flush_retaining(self, nodes: Sequence[bytes]) -> None:
        """Depth-TIERED generation flush (PR 9): drop every resident row,
        then re-commit `nodes` — the owning engine's pinned shallow set,
        in ITS snapshot order — into the fresh generation. Rows restart
        at 0..len(nodes)-1 exactly like the host core's pinned re-commit,
        and the open-addressed index is rebuilt over exactly the pinned
        fingerprints, so host and device tables keep agreeing about what
        exists across a tiered flush. The device re-hashes the pinned
        bytes once per flush (the update program already fuses hash +
        ref-extract + scatter + index insert) — flush-time cost, never
        the per-batch hot path. Nodes the kernel cannot absorb, or past
        max_cap, are silently dropped from the device set: the HOST
        keeps them pinned and the prune re-uploads on next use — a perf
        miss, never an inconsistency."""
        with self._lock:
            self._flush_locked()
            keep = [n for n in nodes if len(n) < _ROW_BYTES][: self._max_cap]
            if not keep:
                return
            self._grow_locked(len(keep))
            sob = self._slot_of_bytes
            for j, nb in enumerate(keep):
                sob[nb] = j
            self._n_rows = len(keep)
            self._deferred_dropped.extend(self._update_locked(keep, 0))
            self.stats["retained_rows"] = len(keep)

    def note_index_dropped(self, n: int) -> None:
        with self._lock:
            self.stats["index_dropped"] += n

    def return_dropped(self, outs: list) -> None:
        """Give unread drop-count device scalars back (an ABANDONED
        handle never resolves them): they re-attach to the next
        dispatched batch, so `index_dropped` cannot silently undercount
        across a crash path."""
        with self._lock:
            self._deferred_dropped.extend(outs)

    def rows(self) -> int:
        with self._lock:
            return self._n_rows

    def stats_snapshot(self) -> dict:
        with self._lock:
            st = dict(self.stats)
            st["rows"] = self._n_rows
            st["cap"] = self._cap
            st["generation"] = self.generation
            return st

    def host_rows_of(self, nodes: Sequence[bytes]) -> np.ndarray:
        """(N,) int32 resident rows per the AUTHORITATIVE host map (-1 =
        not resident). Tests cross-check the device index against this."""
        with self._lock:
            return np.fromiter(
                (self._slot_of_bytes.get(n, -1) for n in nodes),
                np.int32,
                len(nodes),
            )

    def arrays(self) -> tuple:
        """The live (digests, refs, tail, index, fps) handles —
        `chip_smoke.py` and tests read them; treat as immutable. A row of
        `refs` (cap, 128) holds the 8 words of ref slots 0..15, a row of
        `tail` (cap, 9) those of slot 16 and, last, the liveness of all 17
        as a bit mask: rows XLA scatters and gathers whole, where the
        (cap, 17, 8) form was re-laid out table-wide in every update."""
        with self._lock:
            if self._arrays is None:
                raise RuntimeError("resident table has no device arrays yet")
            return self._arrays

    def device_lookup(self, fps: np.ndarray) -> np.ndarray:
        """Device-side row resolution from (N, 2) u32 fingerprints — the
        on-device scan (forced sync: a test surface, not the serving
        hot path)."""
        arrays = self.arrays()
        return np.asarray(self._lookup_fn(arrays[3], arrays[4], self._put(fps)))

    # -- the per-batch dispatch ---------------------------------------------

    def dispatch(
        self,
        witnesses: Sequence[Tuple[bytes, Sequence[bytes]]],
        core_novel: Sequence[bytes],
    ) -> Optional[ResidentBatch]:
        """Enqueue one resident verify batch with NO host sync: prune the
        upload against the authoritative host map, assign rows to the
        truly-novel bytes, enqueue the update (hash + ref-extract +
        scatter + index insert) and the verdict program, and hand back
        the unresolved handle. Returns None when this batch cannot go
        resident (a node past the kernel's absorb capacity, or more
        unique nodes than max_cap) — the caller falls back to the
        classic route."""
        with metrics.phase("witness_resident.dispatch"):
            with self._lock:
                return self._dispatch_locked(witnesses, core_novel)

    def _update_locked(self, nodes: List[bytes], base: int) -> list:
        """Enqueue the update program over `nodes` (rows base.. of the
        table) in the row form, one launch a rung of ROW_LADDER (rows are
        independent: above the top rung, several launches of it); returns
        the launches' unread drop counts. Counted at dispatch: what the
        row form uploads against what the nodes hold."""
        dropped, at = [], 0
        rungs = launches(ROW_LADDER, len(nodes))
        for rung in rungs:
            part = nodes[at : at + rung]
            words, lens = pack_node_rows(part, WITNESS_MAX_CHUNKS, pad_rows_to=rung)
            slots = np.full(rung, -1, np.int32)
            slots[: len(part)] = np.arange(
                base + at, base + at + len(part), dtype=np.int32
            )
            n_bytes = int(lens.sum())
            metrics.count("witness_resident.update_rows", len(part), kind="real")
            metrics.count("witness_resident.update_rows", rung - len(part), kind="pad")
            metrics.count("witness_resident.update_bytes", n_bytes, kind="payload")
            metrics.count(
                "witness_resident.update_bytes", words.nbytes - n_bytes, kind="pad"
            )
            dropped.append(self._launch_update(words, lens, slots))
            self.stats["uploaded_bytes"] += n_bytes
            at += rung
        note_split("update", len(rungs))
        self.stats["uploaded_nodes"] += len(nodes)
        return dropped

    def _launch_update(self, words, lens, slots):
        """One launch of the update program; the table's arrays are its
        (donated) outputs. Returns the unread drop count."""
        # device.host_seconds{lane=witness,op=enqueue}: the uploads and
        # launches of this batch (update, verdict, gather), and not the
        # host's numpy work between them
        with device_host("witness", "enqueue"):
            out = self._update_fn(
                *self._arrays,
                self._put(words),
                self._put(lens),
                self._put(slots),
                max_chunks=WITNESS_MAX_CHUNKS,
            )
        self._arrays = out[:5]
        note_launch("update", lens.shape[0], self._device, self._cap)
        return out[5]

    def _launch_verdict(self, rows, block_id, roots_w):
        """One launch of the verdict program over `rows` (resident row of
        each node, -1 = padding) of the blocks `block_id` names."""
        digests, refs, tail = self._arrays[:3]
        with device_host("witness", "enqueue"):
            out = self._verdict_fn(
                digests,
                refs,
                tail,
                self._put(rows),
                self._put(rows >= 0),
                self._put(block_id),
                self._put(roots_w),
            )
        note_launch(
            "verdict", (rows.shape[0], roots_w.shape[0]), self._device, self._cap
        )
        return out

    def _launch_gather(self, slots):
        """One launch of the gather program: the digest rows at `slots`."""
        with device_host("witness", "enqueue"):
            out = self._gather_fn(self._arrays[0], self._put(slots))
        note_launch("gather", slots.shape[0], self._device, self._cap)
        return out

    def prewarm(self) -> int:
        """Build every served program of this table on every rung of its
        ladder, on the table's own arrays at the cap it is born at, so
        that no request ever waits for one: called when a server starts on
        an accelerator (engine_api/server.py), before the port answers.
        Each launch is empty (every slot and row -1: nothing is written,
        no verdict is read). Returns the programs built or loaded."""
        import jax

        with self._lock:
            if self._arrays is None:
                self._grow_locked(0)
            outs = []
            for rung in ROW_LADDER:
                none = np.full(rung, -1, np.int32)
                words = np.zeros((rung, _ROW_BYTES // 4), np.uint32)
                outs.append(self._launch_update(words, np.zeros(rung, np.int32), none))
                outs.append(self._launch_gather(none))
            for rows, blocks in VERDICT_LADDER:
                none = np.full(rows, -1, np.int32)
                outs.append(
                    self._launch_verdict(
                        none, np.zeros(rows, np.int32), np.zeros((blocks, 8), np.uint32)
                    )
                )
        jax.block_until_ready(outs)  # phantlint: disable=HOSTSYNC — boot prewarm: the build is the point
        return len(outs)

    def _dispatch_locked(self, witnesses, core_novel):
        n_blocks = len(witnesses)
        if n_blocks == 0:
            return None
        all_nodes: List[bytes] = []
        counts = np.empty(n_blocks, np.int64)
        for b, (_root, nodes) in enumerate(witnesses):
            counts[b] = len(nodes)
            all_nodes.extend(nodes)
        sob = self._slot_of_bytes
        pruned = sum(1 for n in core_novel if n in sob)

        def scan_candidates() -> Optional[List[bytes]]:
            cand: List[bytes] = []
            seen = set()
            for n in all_nodes:
                if n in sob or n in seen:
                    continue
                if len(n) >= _ROW_BYTES:
                    return None  # device kernel cannot hash this node
                seen.add(n)
                cand.append(n)
            return cand

        cand = scan_candidates()
        if cand is None:
            return None
        if self._n_rows + len(cand) > self._max_cap:
            # the resident generation is full: flush (host flushes are
            # synchronized the other way — engine flush calls ours) and
            # re-treat the whole batch as novel against the new
            # generation. A single batch larger than max_cap can never
            # go resident.
            self._flush_locked()
            cand = scan_candidates()
            if cand is None or len(cand) > self._max_cap:
                return None
        if self._arrays is None or self._n_rows + len(cand) > self._cap:
            self._grow_locked(self._n_rows + len(cand))

        h = ResidentBatch()
        h._table = self
        h.generation = self.generation

        # authoritative commit: assign rows to the truly-novel bytes
        base = self._n_rows
        for j, nb in enumerate(cand):
            sob[nb] = base + j
        self._n_rows = base + len(cand)

        # update program: upload ONLY the pruned novel nodes, laid out
        if cand:
            h.dropped_outs.extend(self._update_locked(cand, base))
        h.dropped_outs.extend(self._deferred_dropped)
        self._deferred_dropped = []

        # verdict program: row ids + roots only (4 B/node + 32 B/block),
        # one launch a rung of VERDICT_LADDER, whole blocks a launch
        rows_of = np.fromiter((sob[nb] for nb in all_nodes), np.int32, len(all_nodes))
        ends = np.cumsum(counts)
        cuts = _verdict_launches(counts.tolist())
        for lo, hi, (np_pad, nb_pad) in cuts:
            n_at, n_end = int(ends[lo] - counts[lo]), int(ends[hi - 1])
            n_nodes = n_end - n_at
            rows = np.full(np_pad, -1, np.int32)
            rows[:n_nodes] = rows_of[n_at:n_end]
            block_id = np.zeros(np_pad, np.int32)
            block_id[:n_nodes] = np.repeat(
                np.arange(hi - lo, dtype=np.int32), counts[lo:hi]
            )
            roots_w = np.zeros((nb_pad, 8), np.uint32)
            for b in range(lo, hi):
                roots_w[b - lo] = np.frombuffer(witnesses[b][0], dtype="<u4")
            metrics.count("witness_resident.verdict_rows", n_nodes, kind="real")
            metrics.count("witness_resident.verdict_rows", np_pad - n_nodes, kind="pad")
            if (np_pad, nb_pad) not in VERDICT_LADDER:
                metrics.count("lanes.oversize_launches", program="verdict")
            h.verdict_outs.append((self._launch_verdict(rows, block_id, roots_w), hi - lo))
        note_split("verdict", len(cuts))

        # core-commit digests: the engine's host tables intern from the
        # DEVICE digests, so the host never hashes on this route
        if core_novel:
            cslots_of = np.fromiter(
                (sob[nb] for nb in core_novel), np.int32, len(core_novel)
            )
            rungs, at = launches(ROW_LADDER, len(core_novel)), 0
            for rung in rungs:
                part = cslots_of[at : at + rung]
                cslots = np.full(rung, -1, np.int32)
                cslots[: len(part)] = part
                h.digest_outs.append((self._launch_gather(cslots), len(part)))
                at += rung
            note_split("gather", len(rungs))

        h.uploaded_nodes = len(cand)
        h.uploaded_bytes = sum(map(len, cand))
        self.stats["pruned_nodes"] += pruned
        self.stats["batches"] += 1
        return h
