"""The serve driver: the Engine API server as a configuration's `argv` builds
it, in this process (which holds the chip), asked over loopback HTTP by the
clients of harness/clients.py (a process of their own).

A driver gives the harness four calls: `prepare` (everything before the
window: set-up), `measure` (one window, optionally with a traced stretch),
`verify` (the comparison with the reference, after the window) and `close`.
"""

from __future__ import annotations

import json
import multiprocessing
import threading
import time
import urllib.request

import numpy as np

from harness import chainproc, clients, scrape

#: Each picked block is posted again with one thing altered, the rest of the
#: body re-derived around it so that nothing else is wrong: (what, the number
#: it counts into, the words one of which the refusal has to carry, the words
#: it may not carry). Read case-blind in the reply's validationError.
PROBES = (
    ("witness", "tampered_witness_accepted", ("witness",), ()),
    ("signature", "tampered_signature_accepted", (), ("witness rejected", "blockhash")),
    ("state_root", "tampered_root_accepted", ("state root",), ("witness", "blockhash")),
    ("receipts_root", "tampered_receipts_accepted", ("receipt",), ("witness", "blockhash")),
    ("gas_used", "tampered_receipts_accepted", ("gas",), ("witness", "blockhash")),
)

#: run-time degradations and refusals: a healthy server has none
DEGRADATIONS = (
    "phant_backend_device_fallbacks_total",
    "phant_replay_lane_fallbacks_total",
    "phant_sched_executor_crashes_total",
    "phant_sched_rejected_total",
)


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.traffic = cell.traffic
        self.log = cell.log
        self.blocks = []  # reference.chain.Block, in chain order
        self.procs = []
        self.server = None

    # -- set-up -------------------------------------------------------------

    def prepare(self) -> None:
        self.start_program()
        self.load_chain(self.cell.seed)

    def start_program(self) -> None:
        """The clients' process, the native library, and the server as the
        CLI builds it."""
        cell = self.cell
        self.ctx = multiprocessing.get_context("spawn")
        self.client_pipe, far = self.ctx.Pipe()
        p = self.ctx.Process(target=clients.serve, args=(far,), daemon=True)
        p.start()
        self.procs.append(p)
        t0 = time.monotonic()

        from phant_tpu.__main__ import build_parser, build_server
        from phant_tpu.evm.native_vm import native_available
        from phant_tpu.utils.native import build_native, load_native

        path = build_native()
        if load_native() is None or not native_available():
            raise RuntimeError(f"the native library {path} does not load")
        self.log(f"setup: native library {path} ({time.monotonic() - t0:.1f}s)")
        t0 = time.monotonic()
        argv = list(cell.config["argv"])
        self.log(f"setup: server: python -m phant_tpu {' '.join(argv)}")
        self.server = build_server(build_parser().parse_args(argv))
        self.server.serve_in_background()
        self.host, self.port = "127.0.0.1", self.server.port
        self.log(f"setup: server up ({time.monotonic() - t0:.1f}s)")
        self.scrape_boot = self.scrape()
        from reference import keccak

        keccak.load(cell.build_dir)

    def load_chain(self, seed: int) -> None:
        """Make the chain of `seed` (in a process of its own, beside the
        warm-up that walks its head), and plan who posts what."""
        cell, t = self.cell, self.traffic
        self.seed, self.blocks = seed, []
        params = {
            "genesis_log2": cell.config["genesis_accounts"].bit_length() - 1,
            "sender_pool": cell.config["sender_pool"],
            "contracts": cell.config["contracts"],
            **t["chain"],
        }
        t0 = time.monotonic()
        self.chain_pipe, far = self.ctx.Pipe()
        p = self.ctx.Process(
            target=chainproc.make,
            args=(far, cell.build_dir, seed, params, t["chain_blocks"]),
            daemon=True,
        )
        p.start()
        self.procs.append(p)

        # the head of the chain is kept for warm-up (a pass takes the next
        # `blocks_per_group` blocks for each group); the rest is the
        # window's, a contiguous range for each group
        groups, w = t["groups"], t["warmup"]
        self.warm_region = w["max_passes"] * groups * w["blocks_per_group"]
        per_group = (t["chain_blocks"] - self.warm_region) // groups
        if per_group < 1:
            raise ValueError("chain_blocks leaves nothing for the window")
        self.ranges = [
            range(self.warm_region + g * per_group, self.warm_region + (g + 1) * per_group)
            for g in range(groups)
        ]
        self.group_of = [c * groups // t["clients"] for c in range(t["clients"])]
        kind, _genesis = self.chain_pipe.recv()
        if kind != "genesis":
            raise RuntimeError(f"chain process sent {kind} first")
        self.log(
            f"setup: genesis of {cell.config['genesis_accounts']} accounts "
            f"({time.monotonic() - t0:.1f}s after the chain process started)"
        )
        # the blocks arrive while warm-up walks the head of the chain: a
        # thread drains the pipe, so that the chain process never waits
        self._arrived = threading.Condition()
        self._fresh, self._feed_error = {}, None
        feeder = threading.Thread(target=self._feed, args=(t["chain_blocks"],), daemon=True)
        feeder.start()
        self._warm_up()
        t0 = time.monotonic()
        self._await(t["chain_blocks"])
        feeder.join()
        self.chain_pipe.close()
        p.join()
        self.procs.remove(p)
        sizes = [(len(b.witness), sum(map(len, b.witness))) for b in self.blocks[:4]]
        gas = sorted(b.header.gas_used for b in self.blocks)
        self.log(
            f"setup: chain of {len(self.blocks)} blocks x {len(self.blocks[0].txs)} txs, "
            f"gas used {gas[0]}..{gas[-1]} (waited {time.monotonic() - t0:.1f}s more for it); "
            f"witness (nodes, bytes) of the first blocks: {sizes}"
        )

    def _feed(self, n_blocks: int) -> None:
        """The feeder thread: blocks and their request bodies, as the chain
        process makes them."""
        try:
            for _ in range(n_blocks):
                kind, block, body = self.chain_pipe.recv()
                if kind != "block":
                    raise RuntimeError(f"chain process sent {kind} at {len(self.blocks)}")
                with self._arrived:
                    self._fresh[len(self.blocks)] = body
                    self.blocks.append(block)
                    self._arrived.notify_all()
        except Exception as e:  # the main thread raises it from _await
            with self._arrived:
                self._feed_error = e
                self._arrived.notify_all()

    def _await(self, upto: int) -> None:
        """Wait until `upto` blocks are here, and hand the request bodies
        that have arrived to the clients."""
        with self._arrived:
            self._arrived.wait_for(lambda: len(self.blocks) >= upto or self._feed_error)
            if self._feed_error:
                raise RuntimeError("the chain process failed") from self._feed_error
            fresh, self._fresh = self._fresh, {}
        if fresh:
            self._bodies(fresh)

    def _bodies(self, bodies: dict) -> None:
        self.client_pipe.send(("bodies", bodies))
        if self.client_pipe.recv() != "ok":
            raise RuntimeError("the clients' process did not take the bodies")

    def _plans(self, warm_pass: int | None) -> list:
        """Per client, the block indices it posts: its group's share of a
        warm-up pass, or (None) its group's whole range of the window."""
        if warm_pass is None:
            return [list(self.ranges[g]) for g in self.group_of]
        n, groups = self.traffic["warmup"]["blocks_per_group"], self.traffic["groups"]
        first = [(warm_pass * groups + g) * n for g in range(groups)]
        return [list(range(first[g], first[g] + n)) for g in self.group_of]

    def _run(self, plans, seconds):
        self.client_pipe.send(("run", self.host, self.port, plans, seconds, self.traffic))
        return self.client_pipe.recv()

    def _warm_up(self) -> None:
        """Walk the first blocks in the cell's own pattern until a whole pass
        builds no program, then send block 0 altered in each of PROBES' ways."""
        w = self.traffic["warmup"]
        for i in range(w["max_passes"]):
            plans = self._plans(i)
            self._await(max(map(max, plans)) + 1)
            before, t0 = self.cell.compiles.count(), time.monotonic()
            _o, _c, _x, records = self._run(plans, None)
            built = self.cell.compiles.count() - before
            bad = self._wrong(records)
            self.log(
                f"setup: warm-up pass {i + 1}: {len(records)} requests in "
                f"{time.monotonic() - t0:.1f}s, {built} programs built, {len(bad)} wrong"
            )
            if bad:
                raise RuntimeError(f"warm-up answers wrong: {bad[:3]}")
            if built == 0 and i + 1 >= w["min_passes"]:
                break
        probes = self._probe([0])
        if any(probes.values()):
            raise RuntimeError(f"warm-up: an altered block was accepted: {probes}")

    # -- the window ---------------------------------------------------------

    def scrape(self) -> dict:
        with urllib.request.urlopen(f"http://{self.host}:{self.port}/metrics", timeout=60) as r:
            return scrape.parse(r.read().decode())

    def measure(self, seconds: float, trace_dir: str | None) -> dict:
        tracer = None
        self.stretch = None
        if trace_dir is not None:
            tracer = threading.Thread(target=self._trace, args=(trace_dir,))
        plans = self._plans(None)
        compiles0 = self.cell.compiles.count()
        scrape0 = self.scrape()
        if tracer is not None:
            tracer.start()
        t_open, t_close, exhausted, records = self._run(plans, seconds)
        scrape1 = self.scrape()
        compiles = self.cell.compiles.count() - compiles0
        if tracer is not None:
            tracer.join()
        self.records = records
        self.log(
            f"window: {len(records)} requests sent in {t_close - t_open:.2f}s"
            + (" (the chain ran out: the window closed early)" if exhausted else "")
        )
        lat = sorted(r[3] - r[2] for r in records)
        if lat:
            self.log(
                "window: latency ms at each tenth of the requests, least to most: "
                f"{[round(lat[min(len(lat) - 1, len(lat) * k // 10)] * 1e3) for k in range(11)]}"
            )
            order = sorted(records, key=lambda r: r[2])
            self.log(
                "window: latency ms in order of sending: "
                f"{[round((r[3] - r[2]) * 1e3) for r in order]}"
            )
        gc_pauses = self.cell.gc.between(t_open, t_close) if self.cell.gc else []
        if self.cell.gc:
            full = [round(p[1] * 1e3) for p in gc_pauses if p[2] == 2]
            self.log(f"window: full collections in the server's process, ms each: {full}")
        self.bad = self._wrong(records)
        wrong = {(r[0], r[1], r[2]) for r in self.bad}
        good = [r for r in records if (r[0], r[1], r[2]) not in wrong]
        obs = {
            "latency_s": [r[3] - r[2] for r in records],
            "completed": sum(1 for r in good if r[3] <= t_close),
            "window_s": t_close - t_open,
            "scrape0": scrape0,
            "scrape1": scrape1,
            "compiles": compiles,
            "compiled_names": self.cell.compiles.names_since(compiles0),
            "gc_pauses": gc_pauses,
            "attempted": len(records),
            "stretch": None,
        }
        if self.stretch is not None:
            obs["stretch"] = self._stretch(good, t_open, t_close)
        return obs

    def _trace(self, trace_dir: str) -> None:
        """The profiler, on for a stretch inside the window. The traffic
        file says when, how long and with which of the profiler's host-side
        tracers (its Python tracer holds a server written in Python back
        fourteenfold). Stopping costs some 200 s for each second traced at
        this cell's pace (a request leaves 409,000 device events), so the
        stretch is short, and it is placed right after a full collection of
        the server's process ends, where there is one within `after_full_gc_s`:
        the next is then seconds away, and the stretch holds requests, not
        the collector's stall (which `gc_pause_ms` reads)."""
        import jax

        spec = self.traffic["trace"]
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = spec["python_tracer_level"]
        options.host_tracer_level = spec["host_tracer_level"]
        time.sleep(spec["start_s"])
        if self.cell.gc is not None:
            seen, give_up = self.cell.gc.full_count(), time.monotonic() + spec["after_full_gc_s"]
            while self.cell.gc.full_count() == seen and time.monotonic() < give_up:
                time.sleep(0.005)
        a = time.monotonic()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        s0 = time.monotonic()
        time.sleep(spec["seconds"])
        s1 = time.monotonic()
        jax.profiler.stop_trace()
        self.stretch = (a, s0, s1, time.monotonic())

    def _stretch(self, good, t_open: float, t_close: float) -> dict | None:
        """The traced stretch against the rest of the window: the requests
        in flight in it, each counted by the share of its time that lies
        inside (a stretch of two or three requests has no whole number of
        them), and its pace: those a second, over the answers a second of
        the window outside the profiler's starting, stretch and stopping."""
        a, s0, s1, b = self.stretch
        self.log(f"trace: the profiler took {s0 - a:.2f}s to start and {b - s1:.2f}s to stop")
        if s1 > t_close:
            self.log("trace: the stretch did not fit into the window: no device reading")
            return None
        touching = [r for r in good if r[3] > s0 and r[2] < s1]
        requests = sum((min(r[3], s1) - max(r[2], s0)) / (r[3] - r[2]) for r in touching)
        rest_s = (t_close - t_open) - (min(b, t_close) - a)
        rest = sum(1 for r in good if r[3] <= t_close and not a <= r[3] <= b)
        pace = None
        if rest and rest_s > 0:
            pace = (requests / (s1 - s0)) / (rest / rest_s)
        self.log(
            f"trace: {requests:.2f} requests in flight in a stretch of {s1 - s0:.2f}s "
            f"(latencies ms {[round((r[3] - r[2]) * 1e3) for r in touching]}), {rest} answered in "
            f"the other {rest_s:.2f}s of the window: pace "
            f"{pace if pace is None else round(pace, 3)} (least admitted "
            f"{self.traffic['trace']['min_pace']})"
        )
        return {
            "window_s": s1 - s0,
            "requests": requests,
            "pace": pace,
            "min_pace": self.traffic["trace"]["min_pace"],
        }

    # -- the comparison -----------------------------------------------------

    def _wrong(self, records) -> list:
        """The records whose answer is not VALID with the reference's root."""
        bad = []
        for rec in records:
            _who, idx, _t0, _t1, code, reply = rec
            want = "0x" + self.blocks[idx].header.state_root.hex()
            try:
                result = json.loads(reply)["result"]
                ok = code == 200 and result["status"] == "VALID" and result["stateRoot"] == want
            except (ValueError, KeyError, TypeError):
                ok = False
            if not ok:
                bad.append(rec)
        return bad

    def _probe(self, indices) -> dict:
        """Post each block of `indices` altered in each of PROBES' ways; per
        number, how many the server did not refuse for the right reason."""
        bodies, plan = {}, []
        for idx in indices:
            for j, (what, _number, _any_of, _none_of) in enumerate(PROBES):
                k = -(idx * len(PROBES) + j) - 1
                bodies[k] = self.blocks[idx].body_altered(what, j + 1)
                plan.append(k)
        self._bodies(bodies)
        _o, _c, _x, records = self._run([plan], None)
        out = dict.fromkeys((number for _w, number, _a, _n in PROBES), 0)
        for _who, k, _t0, _t1, code, reply in records:
            what, number, any_of, none_of = PROBES[(-k - 1) % len(PROBES)]
            try:
                result = json.loads(reply)["result"]
                err = (result.get("validationError") or "").lower()
                refused = code == 200 and result["status"] == "INVALID"
                refused = refused and (not any_of or any(w in err for w in any_of))
                refused = refused and not any(w in err for w in none_of)
            except (ValueError, KeyError, TypeError, AttributeError):
                refused = False
            if not refused:
                self.log(f"altered {what} not refused as such: http {code} {reply[:300]!r}")
                out[number] += 1
        return out

    def verify(self) -> tuple:
        """(comparisons, attempted, failed): each comparison is
        (name, value, limit, "at_most" | "at_least")."""
        records, bad = self.records, self.bad
        http = [r for r in bad if r[4] != 200]
        status, root = [], []
        for r in bad:
            if r[4] != 200:
                continue
            try:
                st = json.loads(r[5])["result"]["status"]
            except (ValueError, KeyError, TypeError):
                st = None
            (root if st == "VALID" else status).append(r)
        for r in bad[:5]:
            self.log(f"wrong answer: client {r[0]} block {r[1]} http {r[4]}: {r[5][:300]!r}")
        served = sorted({r[1] for r in records})
        rng = np.random.default_rng([self.seed, 0xBAD])
        k = min(self.traffic["tampered_probes"], len(served))
        picked = [int(i) for i in rng.choice(served, size=k, replace=False)] if k else []
        probes = self._probe(picked)
        end = self.scrape()
        degraded = sum(
            scrape.delta(self.scrape_boot, end, fam) for fam in DEGRADATIONS
        )
        lanes = {
            lane: {dict(labels).get("backend"): v for (n, labels), v in end.items() if n == fam}
            for lane, fam in (
                ("sig", "phant_witness_engine_sig_batches_total"),
                ("root", "phant_witness_engine_root_batches_total"),
            )
        }
        self.log(f"device lanes over the run, batches by backend: {lanes}")
        table = {n: v for (n, _labels), v in sorted(end.items(), key=str) if "witness_resident" in n}
        self.log(f"resident intern table at the end of the run: {table}")
        n_probes = len(PROBES) * len(picked)
        comparisons = [
            ("window_answers", len(records), 1, "at_least"),
            ("http_errors", len(http), 0, "at_most"),
            ("wrong_status", len(status), 0, "at_most"),
            ("wrong_root", len(root), 0, "at_most"),
            ("tampered_probes", n_probes, len(PROBES), "at_least"),
            *((number, count, 0, "at_most") for number, count in probes.items()),
            ("shed_or_degraded", degraded, 0, "at_most"),
        ]
        attempted = len(records) + n_probes
        failed = len(bad) + sum(probes.values())
        return comparisons, attempted, failed

    # -- the end ------------------------------------------------------------

    def close(self) -> None:
        if self.server is not None:
            self.server.shutdown()
            self.server = None
        try:
            self.client_pipe.send(("quit",))
        except (OSError, AttributeError):
            pass
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.kill()
                p.join()
        self.procs = []
