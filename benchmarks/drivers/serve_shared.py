"""The serve driver for the shared verifier: `drivers/serve_steady.py` with
one more family in its first check, one check after the window, and a
traced stretch placed by the clients' barrier and the program's `sig.rows`.

Several clients post the same head at once, so a launch carries a wave of
one to four requests, and which requests share a wave is up to the
scheduler's 5 ms assembly window. A program whose device programs are keyed
on the wave's own size therefore meets new shapes inside the window for as
long as it runs (PERF.md section 7, fault 0c: 16 clients stood 56 s), and no
warm-up of the clients' reaches them all. The program this cell measures
launches every lane on a rung of a declared ladder and says so on /metrics
(`lanes.program_shapes{program=}`): the resident table's programs on ladders
built in the server's constructor, `ecrecover` on its one rung, which the
first request builds.

A program that does not declare that family is stopped before its server
starts, with a sentence. At server start the family must be on /metrics, as
`serve_steady` asks of `root.plan_shapes`, and in a measuring run (not
`--rehearse`, where the CPU builds a rung when it first meets it) each of
the table's programs must stand on exactly the rungs its ladder declares.
After the window no program's shapes may have grown between the window's
edges, and in a measuring run every program must still stand on exactly its
ladder: else the run ends with an error that names the program and the rung.
These checks read `lanes.program_shapes` and `lanes.launches`; no metric of
the cell but `lane_shapes` does.

What this driver DOES decide, and every `device_trace` metric of the cell
depends on, is where the traced stretch lies (`_trace`): it is placed by the
clients' barrier and the wave's first signature launch, not by the clock. A
wave is four requests' device work inside its first quarter second and next
to none until the root plans at its end, so a stretch of fixed length at a
fixed second held either nothing or more `ecrecover` executions than the
profiler can be stopped after inside a run's time (350,000 device events
each; PERF.md section 3). The stretch runs from the quiet middle of one wave
to just past the next wave's first signature launch, which the driver reads
from the program's `sig.rows` counter (the front end took 121 ms from the
barrier's release to that launch in the one wave that shows it, four
requests parsed under one interpreter lock: too loose a hold for a stop
that has 25 ms of room). It does NOT hold the resident table's programs, which run
behind all of a wave's `ecrecover` launches (PERF.md section 7, x).
"""

from __future__ import annotations

import time

from drivers import serve_steady

SHAPES = "phant_lanes_program_shapes"
LAUNCHES = "phant_lanes_launches_total"

#: families the program exports from server start once it can be steady here
REQUIRED = serve_steady.REQUIRED + (SHAPES,)

#: the programs a server's constructor builds on an accelerator (the table's)
BOOT_BUILT = ("verdict", "gather", "update")


def _by_program(scraped: dict) -> dict:
    return {dict(labels).get("program"): v for (n, labels), v in scraped.items() if n == SHAPES}


def _declared() -> dict:
    """program -> the rungs its ladder declares, as the program says them."""
    from phant_tpu.ops.secp256k1_jax import SIG_LADDER
    from phant_tpu.ops.witness_resident import ROW_LADDER, VERDICT_LADDER

    rows = len(ROW_LADDER)
    return {"ecrecover": len(SIG_LADDER), "verdict": len(VERDICT_LADDER), "gather": rows, "update": rows}


def _new_rungs(before: dict, after: dict) -> list:
    """(program, rung) of the launches counted in `after` and not in `before`."""
    seen = {labels for (n, labels), v in before.items() if n == LAUNCHES and v > 0}
    new = [dict(labels) for (n, labels), v in after.items() if n == LAUNCHES and v > 0 and labels not in seen]
    return sorted((d.get("program"), d.get("rung")) for d in new)


def _signature_launch(until: float) -> float | None:
    """When the program's `sig.rows` first grows from now on (a launch of
    `ecrecover`), or None at `until`."""
    from phant_tpu.utils.trace import metrics

    def rows() -> int:
        counters = metrics.snapshot()["counters"]
        return sum(v for k, v in counters.items() if k.startswith("sig.rows{"))

    seen = rows()
    while (now := time.monotonic()) < until:
        if rows() != seen:
            return now
        time.sleep(0.003)
    return None


def _fresh(f) -> list:
    """The releases the clients have noted in `f` since it was last read."""
    return [float(line) for line in f.readlines() if line.endswith("\n")]


class Driver(serve_steady.Driver):
    def prepare(self) -> None:
        from phant_tpu.utils.trace import METRIC_HELP  # the program's own list of its families

        if "lanes.program_shapes" not in METRIC_HELP:
            raise SystemExit(
                f"{self.cell.entry['name']}: this program does not declare lanes.program_shapes: "
                "its ecrecover and resident-table programs are keyed on the size of the wave "
                "(PERF.md section 7, fault 0c), four clients in step meet new shapes inside "
                "the window, and no warm-up reaches them all; not measured"
            )
        super().prepare()

    def start_program(self) -> None:
        super().start_program()
        name = self.cell.entry["name"]
        missing = [f for f in REQUIRED if not any(n == f for n, _l in self.scrape_boot)]
        if missing:
            raise SystemExit(
                f"{name}: /metrics lacks {missing} at server start: the lanes' programs are "
                "not on ladders built at boot (PERF.md section 7, fault 0c); not measured"
            )
        boot = _by_program(self.scrape_boot)
        self.log(f"setup: lanes.program_shapes at server start: {boot}")
        want = _declared()
        unbuilt = {p: (boot.get(p), want[p]) for p in BOOT_BUILT if boot.get(p) != want[p]}
        if unbuilt and not self.cell.rehearsal:
            raise SystemExit(
                f"{name}: the server's constructor did not build the table's programs on their "
                f"ladders (program: shapes at server start, rungs declared) {unbuilt}; launches "
                f"so far (program, rung): {_new_rungs({}, self.scrape_boot)}; not measured"
            )

    def measure(self, seconds: float, trace_dir) -> dict:
        plain = self.traffic
        if trace_dir is not None:
            # the clients note every barrier's release here (harness/clients/in_step.py)
            self.cell.out_dir.mkdir(parents=True, exist_ok=True)
            path = self.cell.out_dir / "releases"
            path.write_text("")
            self.traffic = {**plain, "releases": str(path)}
        try:
            obs = super().measure(seconds, trace_dir)
        finally:
            self.traffic = plain
        name = self.cell.entry["name"]
        opened, close = _by_program(obs["scrape0"]), _by_program(obs["scrape1"])
        self.log(f"window: lanes.program_shapes at its close: {close}")
        grown = {p: (opened.get(p), n) for p, n in close.items() if n != opened.get(p)}
        if grown:
            raise SystemExit(
                f"{name}: lanes.program_shapes grew between the window's edges (program: from, "
                f"to) {grown}; launches on rungs not met before (program, rung): "
                f"{_new_rungs(obs['scrape0'], obs['scrape1'])}: a program was built while "
                "requests waited; not measured"
            )
        off = {p: (close.get(p), n) for p, n in _declared().items() if close.get(p) != n}
        if off and not self.cell.rehearsal:
            raise SystemExit(
                f"{name}: at the window's close a program stands on other shapes than its "
                f"ladder's (program: shapes, rungs declared) {off}; launches on rungs not met "
                f"at server start (program, rung): {_new_rungs(self.scrape_boot, obs['scrape1'])}; "
                "not measured"
            )
        return obs

    def _trace(self, trace_dir: str) -> None:
        """The profiler, on from the quiet middle of one wave to just past
        the head of the next: `lead_s` after one release of the clients'
        barrier (looked for during `find_release_s`) until `seconds` after
        the first signature launch that follows the next release (looked
        for during `launch_within_s`; or `max_s`, whatever came). The
        stretch holds the root plans of the one wave and, of the other,
        what the device runs of its signature launches in those `seconds`:
        two executions of `ecrecover` or so, as a lone client's stretch
        holds. The clients note each release in a file as it happens, read
        here every 2 ms; the launch is the first growth of the program's
        `sig.rows`, read every 3 ms from the release on."""
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

        with open(self.traffic["releases"]) as f:

            def release(until: float) -> float | None:
                """The first release noted from now on, or None at `until`."""
                _fresh(f)
                while time.monotonic() < until:
                    if noted := _fresh(f):
                        return noted[0]
                    time.sleep(0.002)
                return None

            first = release(time.monotonic() + spec["find_release_s"])
            if first is not None:
                time.sleep(max(0.0, first + spec["lead_s"] - time.monotonic()))
            a = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            s0 = time.monotonic()
            following = release(s0 + spec["max_s"])
            launch = None
            if following is not None:
                launch = _signature_launch(following + spec["launch_within_s"])
                if launch is not None:
                    time.sleep(max(0.0, launch + spec["seconds"] - time.monotonic()))
            s1 = time.monotonic()
            jax.profiler.stop_trace()
        self.stretch = (a, s0, s1, time.monotonic())
        if first is None:
            self.log("trace: no release of the clients' barrier was seen: the stretch is not anchored")
        t0 = first if first is not None else s0
        ms = lambda t: "none" if t is None else round((t - t0) * 1e3)  # noqa: E731
        self.log(
            f"trace: the profiler was on from {ms(s0)} to {ms(s1)} ms after a release of the "
            f"clients' barrier; the next release came at {ms(following)} ms and that wave's "
            f"first signature launch at {ms(launch)} ms"
        )
