"""The serve driver for the verifier several operators share:
`drivers/serve_shared.py` with one more family asked of the program, one
more comparison in `verify()`, a log of where a window's seconds went, and
a traced stretch that ends at the first launch it sees.

The clients of this deployment post in groups, each group under its own
`X-Phant-Tenant` (harness/clients/in_step_tenants.py) and at its own height
of the chain, out of step with the others. So a wave holds DIFFERENT blocks
and the program's per-tenant admission lanes and weighted-fair head pick
(`phant_tpu/serving/qos.py`) have work. The configuration's guarantee that
no tenant is starved is held here: between the window's two scrapes
`sched.tenant_served` must have grown for every tenant the traffic names,
and the least served must have at least `tenants_least_share` of the most.

A program that does not declare `sched.tenant_wait_seconds` cannot say what
a tenant waited, which is what this cell is there to read: it is stopped
before its server starts, with a sentence.

Every window's log says the seconds the program counted between its edges
by critical-path phase, device wait, collector generation and tenant: two
windows of this cell's first six stalled for 4-5 s with all sixteen requests
in flight and nothing said where (PERF.md section 7, z). `blocks_per_s` is
the harness's own: every correct answer by the window's close over the
window's seconds.

The traced stretch (`_trace`). `serve_shared` anchors it on ONE group's
barrier and on the next growth of `sig.rows`; with four groups out of step
that counter grows all through a round, and the stretch would end at a
random point with the device's queue up to fifteen `ecrecover` launches deep
(350,000 device events an execution; the profiler stops in some 15 s + 2.4 s
a millisecond of `ecrecover` it holds). Here the profiler is started on a
QUIET device (no lane has launched for `quiet_s`), and stopped at the first
reading of a launch made while it is on: the stretch holds the head of
whatever the device ran next, which bounds the `ecrecover` in it to the
launch's first execution or two, and it cannot end before an operation ran.
A stretch started at the reading of a launch (the first growth of `sig.rows`,
or a burst of four launches) came too late in one run of three or four: the
reading is some 40 ms behind the launch under sixteen handlers and one
interpreter lock, the profiler takes 45-90 ms to start, and by then a burst
of four is over; the driver of the benchmark refused such a run
(traffic/fanin16.json, `sizes`; PERF.md section 6, PR 36). So the watcher
also shortens the interpreter's switch interval while the profiler is on
(`switch_s`), or its own turn at the lock would come a burst late. Placed
so, the stretch proves the device path ran and says nothing of the window:
the cell is on the list of no `device_trace` metric.
"""

from __future__ import annotations

import sys
import threading
import time

from drivers import serve_shared
from harness import scrape

SERVED = "phant_sched_tenant_served_total"

#: what the log says of a window: (name, histogram's `_sum` family), each
#: series under its label values
SECONDS = (
    ("phase", "phant_critpath_phase_seconds_sum"),
    ("device_host", "phant_device_host_seconds_sum"),
    ("gc_pause", "phant_runtime_gc_pause_seconds_sum"),
    ("tenant_wait", "phant_sched_tenant_wait_seconds_sum"),
)


def _launches() -> int:
    """The launches the program has counted so far, of every lane: each
    program of the table and each rung of `ecrecover` at its dispatch
    (`lanes.launches`), and each upload-and-launch a lane's thread has ended
    (`device.host_seconds{op=enqueue}`; the root lane's plans are counted
    nowhere else). A number that grows with every launch, and no more."""
    from phant_tpu.utils.trace import metrics

    snap = metrics.snapshot()
    return sum(v for k, v in snap["counters"].items() if k.startswith("lanes.launches{")) + sum(
        h["count"]
        for k, h in snap["histograms"].items()
        if k.startswith("device.host_seconds{") and 'op="enqueue"' in k
    )


def _watch(until: float, quiet_s: float | None = None) -> float | None:
    """Reads `_launches` every millisecond or so from now on. With `quiet_s`:
    when that long has passed with no launch. Without: when the first launch
    is read. None at `until`."""
    seen, since = _launches(), time.monotonic()
    while (now := time.monotonic()) < until:
        if (n := _launches()) != seen:
            if quiet_s is None:
                return now
            seen, since = n, now
        elif quiet_s is not None and now - since >= quiet_s:
            return now
        time.sleep(0.001)
    return None


class Driver(serve_shared.Driver):
    def prepare(self) -> None:
        from phant_tpu.utils.trace import METRIC_HELP  # the program's own list of its families

        if "sched.tenant_wait_seconds" not in METRIC_HELP:
            raise SystemExit(
                f"{self.cell.entry['name']}: this program does not declare "
                "sched.tenant_wait_seconds: it cannot say what each tenant's requests waited "
                "for their batch, nor how many different blocks a wave held (sched.batch_blocks), "
                "which this cell exists to read; not measured"
            )
        super().prepare()

    def measure(self, seconds: float, trace_dir) -> dict:
        obs = super().measure(seconds, trace_dir)
        self.edges = obs["scrape0"], obs["scrape1"]
        grown = {
            name: {
                "/".join(v for _k, v in sorted(labels)): round(v1 - self.edges[0].get((n, labels), 0.0), 2)
                for (n, labels), v1 in self.edges[1].items()
                if n == family
            }
            for name, family in SECONDS
        }
        self.log(
            f"window: {obs['completed']} correct answers by its close; seconds the program "
            f"counted between the edges, all requests summed: {grown}"
        )
        return obs

    def verify(self) -> tuple:
        comparisons, attempted, failed = super().verify()
        served = {
            t: scrape.delta(*self.edges, SERVED, {"tenant": t}) for t in self.traffic["tenants"]
        }
        answers = {t: 0 for t in served}
        for who, *_rest in self.records:
            answers[self.traffic["tenants"][self.group_of[who]]] += 1
        self.log(f"tenants: sched.tenant_served grew by {served} in the window; answers {answers}")
        most = max(served.values())
        comparisons += [
            ("tenants_served", sum(1 for n in served.values() if n > 0), len(served), "at_least"),
            (
                "tenant_least_over_most",
                round(min(served.values()) / most, 4) if most else 0.0,
                self.cell.config["tenants_least_share"],
                "at_least",
            ),
        ]
        return comparisons, attempted, failed

    def _trace(self, trace_dir: str) -> None:
        """The profiler, started when no lane has launched for `quiet_s`
        (looked for during `quiet_within_s`, from `start_s` of the window and
        the end of a full collection on) and stopped at the first reading of
        a launch made while it is on and `hold_s` more (or `launch_within_s`
        after it is on, whatever came). From its start to the call that stops it the
        interpreter's switch interval is `switch_s`: the watcher's turn at
        the lock then comes a millisecond or two after the launch and not
        forty, which is what bounds the `ecrecover` the stretch can hold."""
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
        watched = time.monotonic()
        quiet = _watch(watched + spec["quiet_within_s"], spec["quiet_s"])
        usual = sys.getswitchinterval()
        sys.setswitchinterval(spec["switch_s"])
        try:
            a = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            s0 = time.monotonic()
            launch = _watch(s0 + spec["launch_within_s"])
            if launch is not None:
                time.sleep(spec["hold_s"])  # its uploads come first: the device starts after them
            # the usual interval comes back from another thread: this one
            # would lose its turn at the lock between that line and the call
            threading.Timer(0.02, sys.setswitchinterval, (usual,)).start()
            s1 = time.monotonic()
            jax.profiler.stop_trace()
        finally:
            sys.setswitchinterval(usual)
        # the stretch is counted from the CALL that starts the profiler: the
        # device's events reach back before it is on (0.061 s of `ecrecover`
        # in 0.034 s, second call)
        self.stretch = (a, a, s1, time.monotonic())
        said = lambda t, t0: "never" if t is None else f"after {(t - t0) * 1e3:.0f} ms"  # noqa: E731
        self.log(
            f"trace: the device was quiet for {spec['quiet_s']} s {said(quiet, watched)} of the "
            f"watch; the profiler took {(s0 - a) * 1e3:.0f} ms to start; a launch was read "
            f"{said(launch, s0)} and the profiler stopped {(s1 - s0) * 1e3:.0f} ms after it was on"
        )
