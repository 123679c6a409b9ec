"""The replay driver: the segment pipeline as `python -m phant_tpu.replay
<fixture> <argv>` builds it, in this process (which holds the chip), run
over the reference's chain. There is no server and no client: a node that is
behind replays a stored range of blocks with their witnesses as fast as it
can, from a state it holds whole.

`prepare`: FIRST the program is asked whether it can do this at all
(`cannot_replay`): one that does not declare `replay.block_latency_seconds`,
or whose CLI has no `build_engine`, is stopped with a sentence before a
process is started, a genesis built or a device program touched. Then the
chain's process (harness/chainproc_holders.py: the reference's genesis with
its key holders, then its blocks), the native library, the scheduler and the
engine from the CLI's own parser and builder, the program's `StateDB` of the
same genesis (its root must be the reference's before anything runs), each
block put into the program's types as it arrives
(harness/fixture_of_chain.py: nothing is executed), and a warm-up of
`warmup_segments` segments, the last of which may build no program.

`measure`: ONE `ReplayEngine.run` over the rest of the chain, but for the
blocks kept for the probes. The `replay.blocks` counter, which the engine
bumps once a segment after it has booked the segment's seconds, gives the
boundaries; the window opens at boundary `run_in_segments` and closes at the
last boundary inside `--seconds`, or at the boundary `segments_ahead_at_close`
before the run's end where the chain is that short (a pipeline that runs
empty is not what a node that is behind has): `Window`. At every boundary
from the opening on, `metrics.prometheus_text()` is parsed here, in the
process, as a scrape. `completed` is the blocks with an ok verdict between
the edges, `latency_s` those blocks' seconds in the pipeline
(`BlockVerdict.latency_s`, the observations of
`replay.block_latency_seconds`).

The traced stretch (`_trace`) starts the profiler on the running pipeline
and stops it `hold_s` after the first launch read under it; it is judged by
what it wrote (`serve_tenants._held`, imported) and taken again inside the
window where it held nothing, `tries` times at the most. A segment's
signatures are 28 launches of `ecrecover`, 350,000 device events each, so a
stretch holds a launch's head and no more: it proves that the device path ran
and says nothing of the window, and the cell is on the list of no
`device_trace` metric.

`verify`: the configuration's guarantees as comparisons. Every block of the
warm-up and of the measured run has an ok verdict; each run's final root is
the reference's header root of its last block; every segment rode both lanes;
nothing was shed or degraded; nothing was built and no lane shape grew
between the edges; and after the window `tampered_probes` replays of sound
blocks with ONE block altered in one way (`PROBES`) stop AT the altered block
for its own reason with the sound ones before it standing. An altered witness
and an altered stateRoot sit in the middle of a whole segment, so that they
are refused at the timed shape (the segment's merged signature batch, the
verdict's top rung, many blocks of one wave live) and not only in a wave of
two blocks; the other three are a sound block and the altered one.
"""

from __future__ import annotations

import multiprocessing
import os
import shutil
import threading
import time

from drivers import serve_tenants
from drivers.serve import DEGRADATIONS
from harness import chainproc_holders, scrape

#: a program that can be measured here declares this family
LATENCY = "replay.block_latency_seconds"
SHAPES = "phant_lanes_program_shapes"

#: each probe replays sound blocks and ONE altered so
#: (harness/fixture_of_chain.altered): (what, the number it counts into, the
#: words one of which the failed verdict's error has to carry, the words it
#: may not carry, whether it is a WHOLE segment with the altered block in its
#: middle or a sound block and the altered one). Read case-blind.
PROBES = (
    ("signature", "tampered_signature_accepted", (), ("witness", "root", "gas_used"), False),
    ("receipts_root", "tampered_receipts_accepted", ("receipts root",), ("witness",), False),
    ("gas_used", "tampered_receipts_accepted", ("gas_used",), ("witness",), False),
    ("witness", "tampered_witness_accepted", ("witness",), (), True),
    # last: an altered root that IS accepted leaves the chain on a header no sound block follows
    ("state_root", "tampered_root_accepted", ("state root",), ("witness",), True),
)


def probe_shapes(n_probes: int, segment: int) -> tuple:
    """Where each of `n_probes` probes lies in the blocks kept for them:
    ([(probe, first block, blocks, which of them is altered)], blocks
    needed). A probe leaves the chain on the block before its altered one,
    and the next begins there."""
    shapes, at, needed = [], 0, 0
    for j in range(n_probes):
        probe = PROBES[j % len(PROBES)]
        length, k = (segment, segment // 2) if probe[4] else (2, 1)
        shapes.append((probe, at, length, k))
        needed = max(needed, at + length)
        at += k
    return shapes, needed


#: what the log says of a window, ms a block: (name, `_sum` family, labels)
SECONDS = (
    ("phase_cpu", "phant_replay_phase_cpu_seconds_sum", ("phase",)),
    ("phase_offcpu", "phant_replay_phase_offcpu_seconds_sum", ("phase",)),
    ("device_host", "phant_device_host_seconds_sum", ("lane", "op")),
)


def cannot_replay() -> str | None:
    """Why this program cannot be measured in a replay cell, or None. It
    reads two names and starts nothing; the family first, from a module that
    imports nothing of the program: the replay package's import builds the
    native extension in a fresh checkout (12 s), and a program without the
    family is stopped before that."""
    from phant_tpu.utils.trace import METRIC_HELP  # the program's own list of its families

    if LATENCY in METRIC_HELP:
        from phant_tpu.replay import __main__ as cli

        if hasattr(cli, "build_engine") and hasattr(cli, "build_parser"):
            return None
    return (
        f"this program does not declare {LATENCY} or has no builder behind its replay CLI: its "
        "`python -m phant_tpu.replay` cannot be pointed at the chip (no --crypto_backend) and "
        "its `--root auto` plans the whole retained trie once a block, a million nodes at this "
        "genesis (PERF.md section 7, row 2); not measured"
    )


def _blocks_done() -> int:
    from phant_tpu.utils.trace import metrics

    return metrics.snapshot()["counters"].get("replay.blocks", 0)


class Window:
    """Which boundaries of the measured run are the window's edges.
    `boundary(k, t, take)` is told of the run's k-th boundary (1 the first)
    at time `t`; `take()` makes the record of an edge and is called at the
    opening and at every boundary that may yet be the close. Opens at
    boundary `run_in`; closes at the last boundary no later than `seconds`
    after the opening, and no later in the run than `last` (the run's
    segments less those that must still lie ahead at the close)."""

    def __init__(self, run_in: int, last: int, seconds: float):
        if last <= run_in:
            raise ValueError(f"a run that may close at boundary {last} has no window after a run-in of {run_in}")
        self.run_in, self.last, self.seconds = run_in, last, seconds
        self.opened = self.closed = None  # (k, t, record)
        self.done = False

    def boundary(self, k: int, t: float, take) -> None:
        if self.done or k < self.run_in:
            return
        if k == self.run_in:
            self.opened = (k, t, take())
            return
        if t > self.opened[1] + self.seconds:
            self.done = True  # the one before was the close
            return
        self.closed = (k, t, take())
        if k == self.last:
            self.done = True

    @property
    def early(self) -> bool:
        """Did the chain's length close the window, and not `seconds`? True
        where it closed at `last` with room for another segment of the
        width of its own last one."""
        if self.closed is None or self.closed[0] != self.last:
            return False
        k, t, _r = self.closed
        width = (t - self.opened[1]) / (k - self.run_in)
        return t + width <= self.opened[1] + self.seconds


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.traffic = cell.traffic
        self.log = cell.log
        self.procs = []
        self.sched = self.engine = self.chain = self._begun = None
        self.ctx = multiprocessing.get_context("spawn")

    # -- set-up -------------------------------------------------------------

    def prepare(self) -> None:
        self._check()
        # the chain's process first: it is the longest pole of a warm set-up;
        # then the program's state of the genesis on a thread of its own,
        # beside the engine's build (a cold one compiles for minutes)
        self.start_chain(self.cell.seed)
        self._begun = self.cell.seed
        state = threading.Thread(target=self._state, name="replay-genesis")
        state.start()
        try:
            self.start_program()
        finally:
            state.join()
        self.load_chain(self.cell.seed)

    def _check(self) -> None:
        why = cannot_replay()
        if why is not None:
            raise SystemExit(f"{self.cell.entry['name']}: {why}")

    def start_program(self) -> None:
        """The check of the program, the native library, and the scheduler
        and engine as the CLI builds them."""
        cell, t = self.cell, self.traffic
        self._check()
        t0 = time.monotonic()

        from phant_tpu.evm.native_vm import native_available
        from phant_tpu.replay.__main__ import build_engine, build_parser
        from phant_tpu.utils.native import build_native, load_native
        from phant_tpu.utils.trace import metrics

        path = build_native()
        if load_native() is None or not native_available():
            raise RuntimeError(f"the native library {path} does not load")
        self.log(f"setup: native library {path} ({time.monotonic() - t0:.1f}s)")
        t0 = time.monotonic()
        argv = list(cell.config["argv"])
        self.log(f"setup: python -m phant_tpu.replay <fixture> {' '.join(argv)}")
        args = build_parser().parse_args([*argv, "<the reference's chain, made in this run>"])
        self.sched, self.engine = build_engine(args)
        self.use_witnesses = not args.no_witnesses
        stated = (t["segment_blocks"], t["pipeline_depth"], t["root"], t["witnesses"])
        built = (
            self.engine.segment_blocks,
            self.engine.pipeline_depth,
            self.engine.root_mode or "auto",
            self.use_witnesses,
        )
        if built != stated:
            raise RuntimeError(f"the CLI built (segment, depth, root, witnesses) {built}; the traffic states {stated}")
        if self.sched is None or not (self.sched.accepts_witness() and self.sched.accepts_sig()):
            raise RuntimeError("the configuration's argv installs no scheduler whose lanes take a segment")
        self.log(f"setup: scheduler and engine up ({time.monotonic() - t0:.1f}s)")
        self.scrape = lambda: scrape.parse(metrics.prometheus_text())
        self.scrape_boot = self.scrape()

    def start_chain(self, seed: int) -> None:
        """The chain of `seed`, made in a process of its own beside this
        one's set-up."""
        from reference import keccak

        cell, t = self.cell, self.traffic
        keccak.load(cell.build_dir)  # the probes re-derive a transactions root here
        seg = t["segment_blocks"]
        self.seed, self.chain, self._state_error = seed, None, None
        self.ref_blocks, self.blocks, self.witnesses = [], [], []
        self.n_warm = t["warmup_segments"] * seg
        self.n_run = t["chain_blocks"] - self.n_warm - t["probe_blocks"]
        self.probes, needed = probe_shapes(t["tampered_probes"], seg)
        if self.n_run % seg or t["probe_blocks"] < needed:
            raise ValueError(
                f"chain_blocks is not warm-up, whole segments and the probes' blocks ({needed} for "
                f"{t['tampered_probes']} probes)"
            )
        self.chain_wait_s = 0.0
        params = {
            "genesis_log2": cell.config["genesis_accounts"].bit_length() - 1,
            "sender_pool": cell.config["sender_pool"],
            "contracts": cell.config["contracts"],
            **t["chain"],
        }
        self._chain_t0 = time.monotonic()
        self.chain_pipe, far = self.ctx.Pipe()
        p = self.ctx.Process(
            target=chainproc_holders.make,
            args=(far, cell.build_dir, seed, params, t["chain_blocks"]),
            daemon=True,
        )
        p.start()
        self.procs.append(p)
        self._chain_proc = p

    def _state(self) -> None:
        """The reference's genesis as the chain's process sends it, the
        feeder thread for what follows it, and the program's `StateDB` of
        the same accounts, whose root must be the reference's before
        anything runs. An error is kept for `load_chain` to raise."""
        from harness import fixture_of_chain as foc
        from phant_tpu.replay.fixture import ReplayFixture

        cell, t = self.cell, self.traffic
        try:
            kind, genesis, holders = self.chain_pipe.recv()
            if kind != "genesis":
                raise RuntimeError(f"chain process sent {kind} first")
            self.log(
                f"setup: the reference's genesis of {cell.config['genesis_accounts']} accounts "
                f"({time.monotonic() - self._chain_t0:.1f}s after the chain process started)"
            )
            # the blocks arrive while the state is built and the warm-up runs:
            # a thread drains the pipe, so that the chain process never waits
            self._arrived = threading.Condition()
            self._feed_error = None
            self._feeder = threading.Thread(target=self._feed, args=(t["chain_blocks"],), daemon=True)
            self._feeder.start()
            t0 = time.monotonic()
            fix = ReplayFixture(
                chain_id=1,
                genesis=foc.header_of(genesis),
                genesis_accounts=foc.genesis_accounts(holders),
                blocks=[],
            )
            chain = fix.fresh_chain()
            got = chain.state.state_root()
            if got != genesis.state_root:
                raise RuntimeError(
                    f"the program's root of the genesis, {got.hex()}, is not the reference's, "
                    f"{genesis.state_root.hex()}"
                )
            self.log(
                f"setup: the program's StateDB of {len(fix.genesis_accounts)} accounts and its "
                f"retained trie, root as the reference's ({time.monotonic() - t0:.1f}s)"
            )
            self.chain = chain
        except BaseException as e:
            self._state_error = e

    def load_chain(self, seed: int) -> None:
        """The chain of `seed` and the program's state of its genesis (begun
        in `prepare` for the cell's own seed), the warm-up, and the rest of
        the chain."""
        t = self.traffic
        if self._begun != seed:  # not the one `prepare` began: another seed, or the same again
            self.start_chain(seed)
            self._state()
        self._begun = None
        if self._state_error is not None:
            raise RuntimeError("the genesis was not set up") from self._state_error
        self._warm_up()
        t0 = time.monotonic()
        self._await(t["chain_blocks"])
        self._feeder.join()
        self.chain_pipe.close()
        p = self._chain_proc
        p.join()
        self.procs.remove(p)
        sizes = [(len(b.witness), sum(map(len, b.witness))) for b in self.ref_blocks[:4]]
        gas = sorted(b.header.gas_used for b in self.ref_blocks)
        self.log(
            f"setup: chain of {len(self.blocks)} blocks x {len(self.ref_blocks[0].txs)} txs, "
            f"gas used {gas[0]}..{gas[-1]} (waited {time.monotonic() - t0:.1f}s more for it; "
            f"{self.chain_wait_s:.1f}s of this set-up stood waiting for the reference's generator); "
            f"witness (nodes, bytes) of the first blocks: {sizes}"
        )

    def _feed(self, n_blocks: int) -> None:
        """The feeder thread: the reference's blocks as the chain process
        makes them, each put into the program's types here."""
        from harness import fixture_of_chain as foc

        try:
            for _ in range(n_blocks):
                kind, ref_block = self.chain_pipe.recv()
                if kind != "block":
                    raise RuntimeError(f"chain process sent {kind} at {len(self.blocks)}")
                block, witness = foc.block_of(ref_block)
                with self._arrived:
                    self.ref_blocks.append(ref_block)
                    self.blocks.append(block)
                    self.witnesses.append(witness)
                    self._arrived.notify_all()
        except Exception as e:  # the main thread raises it from _await
            with self._arrived:
                self._feed_error = e
                self._arrived.notify_all()

    def _await(self, upto: int) -> None:
        """Until block `upto` of the chain is here. The seconds stood here
        are the reference's generator's, and no part of the program's
        set-up: `chain_wait_s`."""
        t0 = time.monotonic()
        with self._arrived:
            self._arrived.wait_for(lambda: len(self.blocks) >= upto or self._feed_error)
            if self._feed_error:
                raise RuntimeError("the chain process failed") from self._feed_error
        self.chain_wait_s += time.monotonic() - t0

    def _run(self, lo: int, hi: int, on_boundary=None):
        """One `ReplayEngine.run` over blocks lo..hi of the chain, on a
        thread of its own; `on_boundary(k, t)` is called on THIS thread
        when `replay.blocks` is seen to have grown for the k-th time."""
        out = {}

        def work():
            try:
                out["report"] = self.engine.run(
                    self.chain,
                    self.blocks[lo:hi],
                    witnesses=self.witnesses[lo:hi] if self.use_witnesses else None,
                )
            except BaseException as e:  # raised below, on the caller's thread
                out["error"] = e

        seen, k = _blocks_done(), 0
        th = threading.Thread(target=work, name="replay-run")
        th.start()
        while True:
            alive = th.is_alive()
            n = _blocks_done()
            if n != seen:
                seen, k = n, k + 1
                if on_boundary is not None:
                    on_boundary(k, time.monotonic())
            if not alive:
                break
            # a reading copies the whole registry under its lock, beside the
            # pipeline it times: 50 a second put a boundary 20 ms late at the
            # most, of a segment's 2.8 s
            time.sleep(0.02)
        th.join()
        if "error" in out:
            raise RuntimeError("the replay raised") from out["error"]
        return out["report"]

    def _count(self, report) -> None:
        """A run's counts (segments, how many rode each lane) into the
        driver's sums over all its runs."""
        for key, n in report.stats.items():
            if isinstance(n, int):
                self.stats[key] = self.stats.get(key, 0) + n

    def _sound(self, report, lo: int, hi: int, what: str) -> list:
        """What is wrong with a run over blocks lo..hi that should have gone
        through: the verdicts that are not ok, and a final root other than
        the reference's header root of block hi - 1."""
        bad = [v for v in report.verdicts if not v.ok]
        short = (hi - lo) - len(report.verdicts)
        wrong_root = report.final_state_root != self.ref_blocks[hi - 1].header.state_root
        for v in bad[:3]:
            self.log(f"{what}: block {lo + v.index} failed: {v.error}")
        if short and not bad:
            self.log(f"{what}: {short} blocks have no verdict")
        if wrong_root:
            self.log(
                f"{what}: final root {report.final_state_root.hex()} is not the reference's "
                f"{self.ref_blocks[hi - 1].header.state_root.hex()}"
            )
        return [len(bad) + (short if not bad else 0), int(wrong_root)]

    def _warm_up(self) -> None:
        """The first `warmup_segments` segments through the whole pipeline.
        The first builds the programs (and `ecrecover`'s one rung); the
        last may build none."""
        self._await(self.n_warm)
        built, at = [], [self.cell.compiles.count()]

        def boundary(_k, _t):
            at.append(self.cell.compiles.count())
            built.append(at[-1] - at[-2])

        t0 = time.monotonic()
        report = self._run(0, self.n_warm, boundary)
        self.log(
            f"setup: warm-up: {self.n_warm} blocks in {report.segments} segments in "
            f"{time.monotonic() - t0:.1f}s, programs built by segment {built}; root mode "
            f"{report.stats['root_mode']}"
        )
        self.warm = self._sound(report, 0, self.n_warm, "warm-up")
        if any(self.warm):
            raise RuntimeError("the warm-up did not go through: see the lines above")
        self.stats = {}
        self._count(report)
        if built and built[-1]:
            raise RuntimeError(f"the last warm-up segment still built {built[-1]} programs")
        shapes = {
            dict(labels).get("program"): v
            for (n, labels), v in self.scrape().items()
            if n == SHAPES
        }
        self.log(f"setup: lanes.program_shapes after the warm-up: {shapes}")

    # -- the window ---------------------------------------------------------

    def measure(self, seconds: float, trace_dir: str | None) -> dict:
        t, seg = self.traffic, self.traffic["segment_blocks"]
        lo, hi = self.n_warm, self.n_warm + self.n_run
        window = Window(t["run_in_segments"], self.n_run // seg - t["segments_ahead_at_close"], seconds)
        self.stretch, tracer, times = None, None, []

        def take():
            return {"scrape": self.scrape(), "compiles": self.cell.compiles.count()}

        def boundary(k, at):
            nonlocal tracer
            times.append(at)
            window.boundary(k, at, take)
            if k == window.run_in and trace_dir is not None:
                tracer = threading.Thread(target=self._trace, args=(trace_dir, at + seconds))
                tracer.start()

        t_run = time.monotonic()
        report = self._run(lo, hi, boundary)
        if tracer is not None:
            tracer.join()
        self.run_sound = self._sound(report, lo, hi, "measured run")
        self._count(report)
        if window.opened is None or window.closed is None:
            raise RuntimeError(f"the measured run gave no window: boundaries at {times}")
        (k0, t_open, e0), (k1, t_close, e1) = window.opened, window.closed
        self.edges, self.ahead = (e0, e1), self.n_run // seg - k1
        widths = [round(b - a, 3) for a, b in zip(times[k0 - 1 : k1], times[k0:k1])]
        self.log(
            f"window: {(k1 - k0) * seg} blocks in {t_close - t_open:.2f}s between boundaries {k0} "
            f"and {k1} of the measured run (it began {t_open - t_run:.2f}s before the window"
            + ("; the chain ran out: the window closed early" if window.early else "")
            + f"); seconds a segment: {widths}"
        )
        inside = [v for v in report.verdicts if k0 * seg <= v.index < k1 * seg]
        lat = sorted(v.latency_s for v in inside if v.ok)
        if lat:
            self.log(
                "window: a block's ms in the pipeline at each tenth of the blocks, least to most: "
                f"{[round(lat[min(len(lat) - 1, len(lat) * k // 10)] * 1e3) for k in range(11)]}"
            )
        n_blocks = scrape.delta(e0["scrape"], e1["scrape"], "phant_replay_blocks_total")
        grown = {
            name: {
                "/".join(dict(labels)[k] for k in keys): round(
                    (v1 - e0["scrape"].get((n, labels), 0.0)) / max(n_blocks, 1) * 1e3, 2
                )
                for (n, labels), v1 in sorted(e1["scrape"].items(), key=str)
                if n == family
            }
            for name, family, keys in SECONDS
        }
        self.log(f"window: ms a block the program counted between the edges: {grown}")
        gc_pauses = self.cell.gc.between(t_open, t_close) if self.cell.gc else []
        if self.cell.gc:
            full = [round(p[1] * 1e3) for p in gc_pauses if p[2] == 2]
            self.log(f"window: full collections in the process, ms each: {full}")
        compiles = e1["compiles"] - e0["compiles"]
        self.window_blocks = (k1 - k0) * seg
        obs = {
            "latency_s": lat,
            "completed": len(lat),
            "window_s": t_close - t_open,
            "scrape0": e0["scrape"],
            "scrape1": e1["scrape"],
            "compiles": compiles,
            "compiled_names": self.cell.compiles.names_since(e0["compiles"])[:compiles],
            "gc_pauses": gc_pauses,
            "attempted": len(inside),
            "stretch": None,
            "chain_wait_s": self.chain_wait_s,
        }
        if self.stretch is not None:
            a, s1 = self.stretch
            if s1 > t_close:
                self.log("trace: the stretch did not fit into the window: no device reading")
            else:
                # blocks in flight in the stretch, each by the share of its
                # time inside: the window's rate times the stretch's width
                obs["stretch"] = {
                    "window_s": s1 - a,
                    "requests": len(lat) / (t_close - t_open) * (s1 - a),
                    "pace": None,
                    "min_pace": t["trace"]["min_pace"],
                }
        return obs

    def _trace(self, trace_dir: str, close: float) -> None:
        """Attempts from `start_s` after the window's opening on, until one
        has written a device operation: the profiler is started on the
        running pipeline and stopped `hold_s` after the first launch read
        while it is on (or `launch_within_s` after it is on, whatever came).
        An attempt that held nothing has its directory removed before the
        next, so that `trace_reduce.find_xplane` finds the one that held
        something; none is begun that the window has no room for."""
        import jax

        spec = self.traffic["trace"]
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = spec["python_tracer_level"]
        options.host_tracer_level = spec["host_tracer_level"]
        time.sleep(spec["start_s"])
        tries = spec["tries"]
        for k in range(1, tries + 1):
            a = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            s0 = time.monotonic()
            before = serve_tenants._series()
            launch = serve_tenants._watch(s0 + spec["launch_within_s"])
            after = before if launch is None else serve_tenants._series()
            if launch is not None:
                time.sleep(spec["hold_s"])  # its uploads come first: the device starts after them
            s1 = time.monotonic()
            jax.profiler.stop_trace()
            b = time.monotonic()
            # the stretch is counted from the CALL that starts the profiler:
            # the device's events reach back before it is on
            self.stretch = (a, s1)
            held, path, size = serve_tenants._held(trace_dir)
            what = ", ".join(
                f"{key} +{n - before.get(key, (0, 0.0))[0]}"
                for key, (n, _s) in after.items()
                if n != before.get(key, (0, 0.0))[0]
            )
            line = (
                f"trace: attempt {k} of {tries}: the profiler took {(s0 - a) * 1e3:.0f} ms to start; "
                + (
                    f"a launch was read after {(launch - s0) * 1e3:.0f} ms ({what})"
                    if launch is not None
                    else "no launch was read"
                )
                + f" and the profiler stopped {(s1 - s0) * 1e3:.0f} ms after it was on; "
            )
            cost = f"{b - s1:.1f} s to stop, {time.monotonic() - b:.2f} s to judge"
            if held:
                self.log(line + f"held a device operation: {size / 1e6:.1f} MB, {cost}")
                return
            line += f"held nothing: {size / 1e3:.0f} KB, {cost}; "
            need = spec["launch_within_s"] + (s0 - a)
            room = close - time.monotonic()
            if k == tries or room < need:
                why = f"no room for another: {room:.1f} s to the window's close, {need:.1f} s needed"
                self.log(line + ("the last" if k == tries else why))
                self.log(f"trace: {k} attempts, and none held a device operation")
                return
            self.log(line + "trying again")
            if path is not None:
                shutil.rmtree(os.path.dirname(path))

    # -- the comparison -----------------------------------------------------

    def _probe(self) -> tuple:
        """The probes (`probe_shapes`) on the chain where the measured run
        left it, each a replay of the next sound blocks with one of them
        altered in one of PROBES' ways: the sound blocks before it must
        stand, the altered one must fail for its own reason, nothing after it
        may have a verdict, and the state must be the reference's after the
        last sound block. (probes made, per number how many were not refused
        so, sound blocks that did not stand)."""
        from harness import fixture_of_chain as foc

        out = dict.fromkeys((probe[1] for probe in PROBES), 0)
        first, fallen = self.n_warm + self.n_run, 0
        for (what, number, any_of, none_of, _whole), offset, length, k in self.probes:
            at = first + offset
            blocks, witnesses = self.blocks[at : at + length], self.witnesses[at : at + length]
            blocks[k], witnesses[k] = foc.block_of(foc.altered(self.ref_blocks[at + k], what))
            t0 = time.monotonic()
            report = self.engine.run(self.chain, blocks, witnesses=witnesses if self.use_witnesses else None)
            self._count(report)
            v = report.verdicts
            err = (v[k].error or "").lower() if len(v) > k else ""
            refused = len(v) == k + 1 and not v[k].ok
            refused = refused and (not any_of or any(w in err for w in any_of))
            refused = refused and not any(w in err for w in none_of)
            standing = sum(x.ok for x in v[:k])
            if refused and report.final_state_root != self.ref_blocks[at + k - 1].header.state_root:
                standing = min(standing, k - 1)  # nothing of the altered block may be left
            self.log(
                f"probe {what}: blocks {at}..{at + length - 1}, block {at + k} altered: {len(v)} verdicts, "
                f"{standing} of {k} sound blocks stand, the altered one "
                + (f"refused ({v[k].error})" if refused else "NOT refused as such")
                + f" ({time.monotonic() - t0:.1f}s)"
            )
            if standing != k:
                self.log(f"probe {what}: sound blocks did not stand: {[x.error for x in v[:k] if not x.ok]}")
                fallen += k - standing
            if not refused:
                self.log(f"altered {what} not refused as such: {[(x.ok, x.error) for x in v[k : k + 2]]}")
                out[number] += 1
        return len(self.probes), out, fallen

    def verify(self) -> tuple:
        """(comparisons, attempted, failed): each comparison is
        (name, value, limit, "at_most" | "at_least")."""
        n_probes, probes, fallen = self._probe()
        end = self.scrape()
        degraded = sum(scrape.delta(self.scrape_boot, end, fam) for fam in DEGRADATIONS)
        e0, e1 = self.edges
        shapes = scrape.total(e1["scrape"], SHAPES) - scrape.total(e0["scrape"], SHAPES)
        st = self.stats
        rode = {k: st.get(k, 0) for k in ("segments", "lane_sig_segments", "lane_witness_segments", "witness_blocks")}
        self.log(f"segments over the run, probes among them, and how many rode each lane: {rode}")
        local = st["segments"] - st["lane_sig_segments"]
        if self.use_witnesses:
            local += st["segments"] - st["lane_witness_segments"]
        table = {n: v for (n, _labels), v in sorted(end.items(), key=str) if "witness_resident" in n}
        self.log(f"resident intern table at the end of the run: {table}")
        evicted = {str(sorted(labels)): v for (n, labels), v in end.items() if n == "phant_witness_engine_evictions_total"}
        self.log(f"eviction counters at the end of the run: {evicted}")
        blocks_failed = self.warm[0] + self.run_sound[0] + fallen
        comparisons = [
            ("window_blocks", self.window_blocks, 2 * self.traffic["segment_blocks"], "at_least"),
            ("segments_ahead_at_close", self.ahead, self.traffic["segments_ahead_at_close"], "at_least"),
            ("blocks_failed", blocks_failed, 0, "at_most"),
            ("wrong_final_root", self.warm[1] + self.run_sound[1], 0, "at_most"),
            ("local_segments", local, 0, "at_most"),
            ("tampered_probes", n_probes, len(PROBES), "at_least"),
            *((number, count, 0, "at_most") for number, count in probes.items()),
            ("shed_or_degraded", degraded, 0, "at_most"),
            ("compiles_in_window", e1["compiles"] - e0["compiles"], 0, "at_most"),
            ("lane_shapes_grown", shapes, 0, "at_most"),
        ]
        attempted = self.n_run + n_probes
        failed = self.run_sound[0] + fallen + sum(probes.values())
        return comparisons, attempted, failed

    # -- the end ------------------------------------------------------------

    def close(self) -> None:
        if self.sched is not None:
            from phant_tpu import serving

            serving.uninstall(self.sched)
            self.sched.shutdown()
            self.sched = None
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.kill()
                p.join()
        self.procs = []
