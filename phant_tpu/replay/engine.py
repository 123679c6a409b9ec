"""Historical chain replay as a first-class megabatch workload.

`Blockchain.run_blocks` imports one block at a time; the serving stack
(serving/scheduler.py) batches *across concurrent requests*. Catch-up
sync has no concurrent requests — but it holds a whole chain SEGMENT in
hand, and a segment is a better batch than any traffic mix:

  * the segment's full tx list goes through the sig lane as ONE merged
    ecrecover launch (`TxSigner.signature_rows` over K blocks' txs,
    one `sig_async` job — the lane's single-bucket coalescing was built
    for exactly this, and closes the r14 "merge across blocks" open);
  * witnessed fixtures drive all K blocks' linked-multiproof checks
    through the witness lane together, where they coalesce into
    megabatches against per-lane resident intern tables (mesh fan-out:
    a scheduler with `mesh_devices` >= 1 shards them over
    MeshExecutorPool lanes — affinity + spill routing, no replay-side
    special case);
  * deferred-root mode hashes K consecutive block states as ONE vmapped
    device program (replay/lowering.py over `StateDB.flush_root_trie`
    plans) instead of K host walks.

The segment pipeline reuses the scheduler's 4-stage vocabulary —
prefetch (build segment N+1's merged sig rows), pack (submit its
witness megabatch), dispatch (launch its merged ecrecover), resolve
(join + EVM-execute segment N) — with the same failure semantics: a
scheduler death fails IN-FLIGHT work only (`SchedulerDown`, code
-32052), recorded as a stage-named `replay.segment_crash` flight
record, and the segment degrades to the local fused batch over rows
already built (sender recovery always has a correct local fallback, so
the lanes may only ever help). A consensus-invalid block fails exactly
that block (`replay.block_failed`, stage-named) and stops the import at
it — earlier blocks stand, the same contract as `run_blocks`.

Where a segment's seconds go is counted phase by phase (`PHASES`), on
both clocks, on the thread that ran the phase: `replay.phase_cpu_seconds`
and `replay.phase_offcpu_seconds`, one observation a phase a segment,
with `replay.execute_seconds`, `replay.root_seconds{backend=}` and
`replay.ready_wait_seconds` beside the older `replay.*` timers; and every
verdict carries the seconds its block was in the pipeline
(`replay.block_latency_seconds`, `BlockVerdict.latency_s`).
"""

from __future__ import annotations

import contextlib
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

from phant_tpu.blockchain.chain import BlockError
from phant_tpu.obs.flight import flight
from phant_tpu.utils.trace import (
    REQUEST_SECONDS_BUCKETS,
    clock_ns,
    cpu_clock_ns,
    metrics,
)

STAGE_PREFETCH = "prefetch"
STAGE_PACK = "pack"
STAGE_DISPATCH = "dispatch"
STAGE_RESOLVE = "resolve"

#: what a segment's seconds are split into: the lookahead worker's three
#: stages, then the run loop's wait for the worker, its two joins with the
#: lanes, the blocks' execution and their post-state roots
PHASES = (
    "prefetch",
    "pack",
    "dispatch",
    "ready_wait",
    "sig_wait",
    "witness_wait",
    "execute",
    "root",
)

#: default blocks per segment (`--segment` / PHANT_REPLAY_SEGMENT)
DEFAULT_SEGMENT_BLOCKS = 32


def _default_depth() -> int:
    """PHANT_REPLAY_DEPTH: segments in flight (1 = fully inline, no
    prefetch worker; >= 2 = segment N+1's prefetch/pack/dispatch run
    under segment N's EVM execution)."""
    try:
        return max(1, int(os.environ.get("PHANT_REPLAY_DEPTH", "2")))
    except ValueError:
        return 2


@dataclass
class BlockVerdict:
    """Per-block outcome; `error` carries the BlockError text on failure
    (byte-compatible with what serial `run_blocks` raises); `latency_s`
    is the block's seconds in the pipeline, from its segment's hand-over
    to this verdict (`replay.block_latency_seconds`)."""

    index: int
    block_number: int
    ok: bool
    error: Optional[str] = None
    latency_s: Optional[float] = None


@dataclass
class ReplayReport:
    """One `ReplayEngine.run` outcome. `verdicts` covers every block up
    to and including the first failure (import stops there — the
    run_blocks contract); `final_state_root` is the host-walked root of
    the state actually reached."""

    verdicts: List[BlockVerdict] = field(default_factory=list)
    final_state_root: bytes = b""
    segments: int = 0
    blocks_ok: int = 0
    txs: int = 0
    stats: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return bool(self.verdicts) and all(v.ok for v in self.verdicts)


class _Segment:
    __slots__ = (
        "index",
        "start",
        "blocks",
        "witnesses",
        "counts",
        "rows",
        "sig_kind",
        "sig_handle",
        "witness_futs",
        "prepare_error",
        "prepare_stage",
        "handed_ns",
        "clocks",
    )

    def __init__(self, index, start, blocks, witnesses):
        self.index = index
        self.start = start
        self.blocks = blocks
        self.witnesses = witnesses
        self.counts = [len(b.transactions) for b in blocks]
        self.rows = None
        self.sig_kind = None  # "lane" | "local"
        self.sig_handle = None
        self.witness_futs = None  # None | list[Future] | ("local", ...)
        self.prepare_error = None
        self.prepare_stage = None
        self.handed_ns = None  # when the pipeline took the segment up
        self.clocks = {p: [0, 0] for p in PHASES}  # phase -> [wall ns, cpu ns]

    def add(self, phase: str, wall_ns: int, cpu_ns: int) -> None:
        got = self.clocks[phase]
        got[0] += wall_ns
        got[1] += cpu_ns

    @contextlib.contextmanager
    def clock(self, phase: str):
        """Both clocks around one phase of this segment, on the thread
        that runs it."""
        t0, c0 = clock_ns(), cpu_clock_ns()
        try:
            yield
        finally:
            self.add(phase, clock_ns() - t0, cpu_clock_ns() - c0)


class ReplayEngine:
    """Drives a chain through the serving lanes at segment batch shapes.

    `run(chain, blocks, witnesses=None)` imports `blocks` onto `chain`
    (a blockchain whose state is at the parent of `blocks[0]`) and
    returns a ReplayReport. The scheduler is discovered per run
    (serving.active_scheduler); with none installed every stage has a
    local megabatch fallback, so the engine is byte-identical to serial
    `run_blocks` by construction — the differential tests pin it.
    Replay work is tagged tenant `replay` at backfill priority: live
    serving traffic preempts catch-up under the standard QoS weights."""

    def __init__(
        self,
        segment_blocks: int = DEFAULT_SEGMENT_BLOCKS,
        pipeline_depth: Optional[int] = None,
        root_mode: Optional[str] = None,
        tenant: str = "replay",
    ):
        if segment_blocks < 1:
            raise ValueError("segment_blocks must be >= 1")
        self.segment_blocks = segment_blocks
        self.pipeline_depth = (
            pipeline_depth if pipeline_depth is not None else _default_depth()
        )
        if root_mode not in (None, "host", "defer"):
            raise ValueError(f"unknown root_mode {root_mode!r}")
        self.root_mode = root_mode
        self.tenant = tenant
        self._local_witness_engine = None

    # -- stage helpers -------------------------------------------------------

    def _scheduler(self):
        from phant_tpu.serving import active_scheduler

        return active_scheduler()

    def _priority(self):
        from phant_tpu.serving import PRIORITY_BACKFILL

        return PRIORITY_BACKFILL

    def _record_crash(self, seg: _Segment, stage: str, exc: BaseException):
        """Stage-named crash record: the scheduler failed IN-FLIGHT work
        for this segment (its own `sched.executor_crash` record and
        flight dump carry the executor side); the segment degrades to
        local fallbacks and the import continues."""
        metrics.count("replay.lane_fallbacks", stage=stage)
        flight.record(
            "replay.segment_crash",
            segment=seg.index,
            start_block=seg.start,
            stage=stage,
            code=getattr(exc, "code", None),
            error=repr(exc),
        )

    def _prepare(self, signer, seg: _Segment, degraded: bool = False):
        """prefetch + pack + dispatch for one segment. Runs on the
        lookahead worker at depth >= 2 (under the PREVIOUS segment's EVM
        execution) or inline at depth 1. `degraded` skips the scheduler
        lanes entirely (a prior stage already recorded its death)."""
        from phant_tpu.serving.scheduler import SchedulerError

        if seg.handed_ns is None:
            seg.handed_ns = clock_ns()
        txs = [tx for b in seg.blocks for tx in b.transactions]

        # prefetch: the merged signing-hash pass for the whole segment —
        # one SigRows for K blocks (host keccak over RLP, off the
        # critical path at depth >= 2)
        with metrics.phase("replay.prefetch"), seg.clock("prefetch"):
            seg.rows = signer.signature_rows(txs)

        sched = None if degraded else self._scheduler()

        # pack: the segment's witness megabatch — all K blocks'
        # linked-multiproof checks enter the witness lane together and
        # coalesce (mesh schedulers shard them over per-lane resident
        # intern tables)
        if seg.witnesses is not None:
            with metrics.phase("replay.pack"), seg.clock("pack"):
                futs = None
                if sched is not None and sched.accepts_witness():
                    try:
                        futs = [
                            sched.submit_witness(
                                root,
                                nodes,
                                deadline_s=float("inf"),
                                wait_for_space=True,
                                tenant=self.tenant,
                                priority=self._priority(),
                            )
                            for root, nodes in seg.witnesses
                        ]
                    except SchedulerError as exc:
                        self._record_crash(seg, STAGE_PACK, exc)
                        futs = None
                seg.witness_futs = futs  # None -> local verify at resolve

        # dispatch: the merged ecrecover launch. Backlog pacing keeps a
        # deep replay pipeline from monopolizing the admission queue it
        # shares with live traffic (sig_backlog is rows, not jobs).
        with metrics.phase("replay.dispatch"), seg.clock("dispatch"):
            if sched is not None and sched.accepts_sig() and seg.rows.n:
                deadline = time.monotonic() + 0.25
                while (
                    sched.sig_backlog() > 4 * seg.rows.n
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.001)
                try:
                    seg.sig_kind = "lane"
                    seg.sig_handle = sched.sig_async(
                        seg.rows,
                        deadline_s=float("inf"),
                        tenant=self.tenant,
                        priority=self._priority(),
                    )
                    return
                except SchedulerError as exc:
                    self._record_crash(seg, STAGE_DISPATCH, exc)
            seg.sig_kind = "local"
            seg.sig_handle = signer.recover_rows_async(seg.rows)

    def _resolve_senders(self, signer, seg: _Segment):
        """Join the segment's merged recovery; a lane that died in
        flight (-32052) degrades to the local fused batch over the rows
        ALREADY built — in-flight-only failure, no second signing-hash
        pass."""
        from phant_tpu.serving.scheduler import SchedulerError

        try:
            with seg.clock("sig_wait"):
                if seg.sig_kind == "lane":
                    try:
                        senders, _meta = seg.sig_handle()
                        return senders
                    except SchedulerError as exc:
                        self._record_crash(seg, STAGE_RESOLVE, exc)
                        return signer.recover_rows_async(
                            seg.rows, force_cpu=True
                        )()
                try:
                    return seg.sig_handle()
                except Exception:
                    # a dead device surfaces here; pin this call to the CPU
                    return signer.recover_rows_async(seg.rows, force_cpu=True)()
        finally:
            metrics.observe("replay.sig_wait", seg.clocks["sig_wait"][0] / 1e9)

    def _local_witness_verify(self, witnesses) -> List[bool]:
        """No-scheduler (or crashed-lane) fallback: the segment still
        verifies as ONE local megabatch on a private engine."""
        if self._local_witness_engine is None:
            from phant_tpu.ops.witness_engine import WitnessEngine

            self._local_witness_engine = WitnessEngine()
        verdicts = self._local_witness_engine.verify_batch(
            [(root, nodes) for root, nodes in witnesses]
        )
        return [bool(v) for v in verdicts]

    def _resolve_witnesses(self, seg: _Segment) -> Optional[int]:
        """Join the segment's witness verdicts; returns the in-segment
        index of the first failed block, or None when all pass."""
        if seg.witnesses is None:
            return None
        from phant_tpu.serving.scheduler import SchedulerError

        try:
            with seg.clock("witness_wait"):
                if seg.witness_futs is not None:
                    verdicts: List[bool] = []
                    for k, fut in enumerate(seg.witness_futs):
                        try:
                            verdicts.append(bool(fut.result()))
                        except SchedulerError as exc:
                            self._record_crash(seg, STAGE_RESOLVE, exc)
                            verdicts.extend(
                                self._local_witness_verify(seg.witnesses[k:])
                            )
                            break
                else:
                    verdicts = self._local_witness_verify(seg.witnesses)
        finally:
            metrics.observe(
                "replay.witness_wait", seg.clocks["witness_wait"][0] / 1e9
            )
        for k, ok in enumerate(verdicts):
            if not ok:
                return k
        return None

    # -- the run loop --------------------------------------------------------

    def run(self, chain, blocks: Sequence, witnesses=None) -> ReplayReport:
        """Import `blocks` onto `chain` through the segment pipeline.
        `witnesses`: optional per-block (claimed_root, nodes) list
        (fixture.attach_witnesses) verified as segment megabatches."""
        from phant_tpu.replay.lowering import auto_root_mode

        report = ReplayReport()
        if not blocks:
            report.final_state_root = chain.state.state_root()
            return report

        root_mode = self.root_mode
        if root_mode is None:
            root_mode, _why = auto_root_mode()
        verify_roots = chain.verify_state_root
        if root_mode == "defer" and verify_roots:
            # the engine owns root verification at segment granularity;
            # restore the chain's own per-block check on exit
            chain.verify_state_root = False

        metrics.gauge_set("replay.segment_blocks", self.segment_blocks)
        metrics.gauge_set("replay.pipeline_depth", self.pipeline_depth)

        segments = [
            _Segment(
                i // self.segment_blocks,
                i,
                list(blocks[i : i + self.segment_blocks]),
                None if witnesses is None else list(
                    witnesses[i : i + self.segment_blocks]
                ),
            )
            for i in range(0, len(blocks), self.segment_blocks)
        ]
        signer = chain.signer
        stats = {
            "segments": 0,
            "lane_sig_segments": 0,
            "local_sig_segments": 0,
            "lane_witness_segments": 0,
            "witness_blocks": 0,
            "device_root_groups": 0,
            "device_roots": 0,
            "host_roots": 0,
            "root_mode": root_mode,
        }

        stop = threading.Event()
        ready: "queue.Queue" = queue.Queue(
            maxsize=max(1, self.pipeline_depth - 1)
        )
        worker = None
        if self.pipeline_depth >= 2 and len(segments) > 1:

            def _lookahead():
                for seg in segments:
                    if stop.is_set():
                        break
                    try:
                        self._prepare(signer, seg)
                    except BaseException as exc:
                        seg.prepare_error = exc
                        seg.prepare_stage = STAGE_PREFETCH
                    while not stop.is_set():
                        try:
                            ready.put(seg, timeout=0.05)
                            break
                        except queue.Full:
                            continue

            worker = threading.Thread(
                target=_lookahead, name="replay-prefetch", daemon=True
            )
            worker.start()

        try:
            for seg in segments:
                if worker is not None:
                    t0, c0 = clock_ns(), cpu_clock_ns()
                    got = ready.get()
                    assert got is seg  # strictly in order
                    seg.add("ready_wait", clock_ns() - t0, cpu_clock_ns() - c0)
                else:
                    try:
                        self._prepare(signer, seg)
                    except BaseException as exc:
                        seg.prepare_error = exc
                        seg.prepare_stage = STAGE_PREFETCH
                if seg.prepare_error is not None:
                    # lookahead died mid-stage: record it, then rebuild
                    # this segment inline with the lanes bypassed
                    self._record_crash(
                        seg, seg.prepare_stage or STAGE_PREFETCH,
                        seg.prepare_error,
                    )
                    self._prepare(signer, seg, degraded=True)
                done = self._run_segment(
                    chain, seg, report, stats, root_mode, verify_roots
                )
                if not done:
                    break
        finally:
            stop.set()
            if worker is not None:
                while worker.is_alive():
                    try:  # unblock a put-blocked worker
                        ready.get_nowait()
                    except queue.Empty:
                        pass
                    worker.join(timeout=0.05)
            if root_mode == "defer":
                chain.verify_state_root = verify_roots

        report.final_state_root = chain.state.state_root()
        report.blocks_ok = sum(1 for v in report.verdicts if v.ok)
        report.segments = stats["segments"]
        report.stats = stats
        return report

    def _run_block(self, chain, seg: _Segment, block, senders) -> None:
        """`chain.run_block`, with the post-state root it computes inside
        itself (`Blockchain.root_clock`) on the segment's `root` clock and
        the rest of it on `execute`'s."""
        t0, c0 = clock_ns(), cpu_clock_ns()
        r0, rc0 = chain.root_clock
        try:
            chain.run_block(block, senders=senders)
        finally:
            root, root_cpu = chain.root_clock[0] - r0, chain.root_clock[1] - rc0
            seg.add("root", root, root_cpu)
            seg.add("execute", clock_ns() - t0 - root, cpu_clock_ns() - c0 - root_cpu)

    def _book(self, seg: _Segment, root_mode: str) -> None:
        """One observation a phase of the segment's clocks, before
        `replay.blocks` says the segment is done: a reader that watches
        that counter finds the segment's seconds already there."""
        wall = {p: seg.clocks[p][0] / 1e9 for p in PHASES}
        metrics.observe_hist("replay.execute_seconds", wall["execute"])
        metrics.observe_hist(
            "replay.root_seconds",
            wall["root"],
            backend="device" if root_mode == "defer" else "host",
        )
        metrics.observe_hist("replay.ready_wait_seconds", wall["ready_wait"])
        for phase in PHASES:
            metrics.observe_split(
                "replay.phase_cpu_seconds",
                "replay.phase_offcpu_seconds",
                wall[phase],
                seg.clocks[phase][1] / 1e9,
                phase=phase,
            )

    def _verdict(self, seg: _Segment, k: int, done_ns: int, error=None):
        latency = (done_ns - seg.handed_ns) / 1e9
        metrics.observe_hist(
            "replay.block_latency_seconds",
            latency,
            buckets=REQUEST_SECONDS_BUCKETS,
        )
        return BlockVerdict(
            index=seg.start + k,
            block_number=seg.blocks[k].header.block_number,
            ok=error is None,
            error=error,
            latency_s=latency,
        )

    def _run_segment(
        self, chain, seg: _Segment, report, stats, root_mode, verify_roots
    ) -> bool:
        """Resolve + execute one segment; False stops the import (a
        block failed — earlier blocks stand, run_blocks semantics)."""
        t_seg = time.perf_counter()
        bad_witness = self._resolve_witnesses(seg)
        senders = self._resolve_senders(signer=chain.signer, seg=seg)
        stats["segments"] += 1
        stats["lane_sig_segments" if seg.sig_kind == "lane" else
              "local_sig_segments"] += 1
        if seg.witnesses is not None:
            stats["witness_blocks"] += len(seg.witnesses)
            if seg.witness_futs is not None:
                stats["lane_witness_segments"] += 1

        plans: List = []
        fallbacks: List = []
        done_ns: List[int] = []  # when each executed block's verdict stood
        failed: Optional[Tuple[int, str]] = None
        pos = 0
        for k, block in enumerate(seg.blocks):
            n = seg.counts[k]
            if bad_witness is not None and k >= bad_witness:
                failed = (k, "witness verification failed")
                break
            try:
                self._run_block(chain, seg, block, senders[pos : pos + n])
            except BlockError as e:
                failed = (k, str(e))
                break
            pos += n
            report.txs += n
            if root_mode == "defer" and verify_roots:
                from phant_tpu.ops.mpt_jax import build_hash_plan

                with seg.clock("root"):
                    trie = chain.state.flush_root_trie()
                    plan = build_hash_plan(trie)
                    plans.append(plan)
                    # unplannable block: capture the host root NOW (the
                    # trie mutates again next block)
                    fallbacks.append(
                        (lambda r=trie.root_hash(): r) if plan is None else None
                    )
            done_ns.append(clock_ns())
        failed_ns = clock_ns()

        # deferred segment roots: one vmapped device program per
        # structure-sharing run, host walk for the rest
        if root_mode == "defer" and verify_roots and plans:
            from phant_tpu.replay.lowering import (
                lower_segment_plans,
                resolve_segment_roots,
            )

            t0 = time.perf_counter()
            with seg.clock("root"):
                handles = lower_segment_plans(plans)
                roots, rstats = resolve_segment_roots(handles, fallbacks)
            metrics.observe("replay.root_wait", time.perf_counter() - t0)
            if rstats["device_groups"]:
                metrics.count(
                    "replay.root_groups", rstats["device_groups"],
                    backend="device",
                )
            if rstats["host_roots"]:
                metrics.count(
                    "replay.root_groups", rstats["host_roots"], backend="host"
                )
            stats["device_root_groups"] += rstats["device_groups"]
            stats["device_roots"] += rstats["device_roots"]
            stats["host_roots"] += rstats["host_roots"]
            # a deferred root's verdict stands when the roots are read back
            done_ns = [clock_ns()] * len(done_ns)
            for k in range(len(done_ns)):
                header = seg.blocks[k].header
                if roots[k] != header.state_root:
                    failed = (
                        k,
                        f"state root mismatch: {roots[k].hex()} != "
                        f"{header.state_root.hex()}",
                    )
                    failed_ns = done_ns[k]
                    del done_ns[k:]
                    break

        executed = len(done_ns)  # blocks of THIS segment executed OK
        for k in range(executed):
            report.verdicts.append(self._verdict(seg, k, done_ns[k]))
        self._book(seg, root_mode)
        metrics.count("replay.txs", sum(seg.counts[:executed]))
        metrics.count("replay.segments")
        metrics.observe("replay.segment_seconds", time.perf_counter() - t_seg)
        metrics.count("replay.blocks", executed)  # last: see `_book`

        if failed is not None:
            k, err = failed
            block = seg.blocks[k]
            report.verdicts.append(self._verdict(seg, k, failed_ns, error=err))
            # stage-named record: the block failed at the segment's
            # resolve stage (join + execute + root check); earlier
            # blocks stand and the import stops here, exactly like a
            # BlockError out of serial run_blocks
            flight.record(
                "replay.block_failed",
                segment=seg.index,
                block_index=seg.start + k,
                block_number=block.header.block_number,
                stage=STAGE_RESOLVE,
                error=err,
            )
            metrics.count("replay.block_failures")
            return False
        return True


def replay_fixture(
    fix,
    segment_blocks: int = DEFAULT_SEGMENT_BLOCKS,
    pipeline_depth: Optional[int] = None,
    root_mode: Optional[str] = None,
    verify_state_root: bool = True,
    use_witnesses: bool = True,
) -> ReplayReport:
    """Convenience: replay a fixture (fixture.load_fixture) on a fresh
    chain through the segment pipeline."""
    chain = fix.fresh_chain(verify_state_root=verify_state_root)
    eng = ReplayEngine(
        segment_blocks=segment_blocks,
        pipeline_depth=pipeline_depth,
        root_mode=root_mode,
    )
    return eng.run(
        chain,
        fix.blocks,
        witnesses=fix.witnesses if use_witnesses else None,
    )
