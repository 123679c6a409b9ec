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
launch's first execution or two.
A stretch started at the reading of a launch (the first growth of `sig.rows`,
or a burst of four launches) came too late in one run of three or four: the
reading is some 40 ms behind the launch under sixteen handlers and one
interpreter lock, the profiler takes 45-90 ms to start, and by then a burst
of four is over; the driver of the benchmark refused such a run
(traffic/fanin16.json, `sizes`; PERF.md section 6, PR 36). So the watcher
also shortens the interpreter's switch interval while the profiler is on
(`switch_s`), or its own turn at the lock would come a burst late.

That placement CAN end before an operation ran that the tracer saw, and did
in two traced runs of five at PR 39's tree (PERF.md section 6, PRs 40 and
41): a launch read 14 ms after the profiler was on, the stop 20 ms later, 160
KB of host planes. Two placements have now been argued race-free and were
not, so whatever the placement, a stretch is an ATTEMPT: judged by what it
wrote (`_held`) and taken again inside the same window where it held no
device operation, `tries` times at the most (`_trace`). The trigger is PR
36's, unchanged; each attempt's `trace:` line says which of the program's
series the reading that ended it was and, for an upload-and-launch, when it
BEGAN against the profiler's being on (`_series`), so that the runs say
whether the reading was the END of an upload begun while the profiler was
starting (nine traced runs at PR 41 held an operation on the first attempt,
two of them stopped at such an end: what emptied a stretch was not found).
Placed so, the stretch proves the device path ran and says nothing
of the window: the cell is on the list of no `device_trace` metric.
"""

from __future__ import annotations

import os
import shutil
import sys
import threading
import time

from drivers import serve_shared
from harness import scrape, trace_reduce

SERVED = "phant_sched_tenant_served_total"

#: what the log says of a window: (name, histogram's `_sum` family), each
#: series under its label values
SECONDS = (
    ("phase", "phant_critpath_phase_seconds_sum"),
    ("device_host", "phant_device_host_seconds_sum"),
    ("gc_pause", "phant_runtime_gc_pause_seconds_sum"),
    ("tenant_wait", "phant_sched_tenant_wait_seconds_sum"),
)


def _series() -> dict:
    """What the program has counted of its launches so far, of every lane,
    {series: (count, seconds)}: each program of the table and each rung of
    `ecrecover` at its dispatch (`lanes.launches`, no seconds), and each
    upload-and-launch a lane's thread has ENDED, with the seconds it took
    (`device.host_seconds{op=enqueue}`; the root lane's plans are counted
    nowhere else)."""
    from phant_tpu.utils.trace import metrics

    snap = metrics.snapshot()
    out = {k: (v, 0.0) for k, v in snap["counters"].items() if k.startswith("lanes.launches{")}
    for k, h in snap["histograms"].items():
        if k.startswith("device.host_seconds{") and 'op="enqueue"' in k:
            out[k] = (h["count"], h["sum"])
    return out


def _launches() -> int:
    """The launches of `_series` in one number, which grows with every
    launch, and no more."""
    return sum(n for n, _s in _series().values())


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


def _held(trace_dir: str) -> tuple:
    """(does the trace under `trace_dir` hold a device operation, its file or
    None, its bytes): one event on the line "XLA Ops" of a "/device:TPU:<n>"
    plane, which is what `trace_reduce.reduce_planes` asks before it gives
    run.py anything. Decided by the device planes alone, whatever the file's
    size (a stretch with no launch lasts `launch_within_s` and its host
    planes may be of any size): the file is parsed by the profiler's own
    reader, in C++, and of each such line the FIRST event is asked for, not
    the 350,000 one execution of `ecrecover` leaves; reading them all is the
    reducer's, once, after the window."""
    from jax.profiler import ProfileData

    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return False, None, 0
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE and next(iter(line.events), None) is not None:
                    return True, path, os.path.getsize(path)
    return False, path, os.path.getsize(path)


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
        self.seconds = seconds  # `_trace` reckons the window's close from it
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

    def _attempt(self, trace_dir: str, options, spec: dict) -> dict:
        """One placement of the stretch: the profiler, started when no lane
        has launched for `quiet_s` (looked for during `quiet_within_s`) and
        stopped at the first reading of a launch made while it is on and
        `hold_s` more (or `launch_within_s` after it is on, whatever came).
        From its start to the call that stops it the interpreter's switch
        interval is `switch_s`: the watcher's turn at the lock then comes a
        millisecond or two after the launch and not forty, which is what
        bounds the `ecrecover` the stretch can hold."""
        import jax

        watched = time.monotonic()
        quiet = _watch(watched + spec["quiet_within_s"], spec["quiet_s"])
        usual = sys.getswitchinterval()
        sys.setswitchinterval(spec["switch_s"])
        # the usual interval comes back from another thread: this one would
        # lose its turn at the lock between that line and the call that stops
        back = threading.Timer(0.02, sys.setswitchinterval, (usual,))
        try:
            a = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            s0 = time.monotonic()
            before = _series()
            launch = _watch(s0 + spec["launch_within_s"])
            after = before if launch is None else _series()
            if launch is not None:
                time.sleep(spec["hold_s"])  # its uploads come first: the device starts after them
            back.start()
            s1 = time.monotonic()
            jax.profiler.stop_trace()
        finally:
            back.cancel()
            sys.setswitchinterval(usual)
        b = time.monotonic()
        said = lambda t, t0: "never" if t is None else f"after {(t - t0) * 1e3:.0f} ms"  # noqa: E731
        # what the reading was, for the log alone: which series grew under the
        # profiler, and an upload-and-launch by when it BEGAN (its end, which is
        # the reading at the latest, less the seconds the program timed it at)
        was = lambda k: before.get(k, (0, 0.0))  # noqa: E731
        began = lambda k, sec: (launch - (sec - was(k)[1]) - s0) * 1e3  # noqa: E731
        what = ", ".join(
            f"{k} +{n - was(k)[0]}" + (f", begun {began(k, sec):.0f} ms after it was on" if sec else "")
            for k, (n, sec) in after.items()
            if n != was(k)[0]
        )
        what = f" ({what})" if what else ""
        return {
            "span": (watched, a, s1, b),
            "start_s": s0 - a,
            "said": (
                f"the device was quiet for {spec['quiet_s']} s {said(quiet, watched)} of the "
                f"watch; the profiler took {(s0 - a) * 1e3:.0f} ms to start; a launch was read "
                f"{said(launch, s0)}{what} and the profiler stopped {(s1 - s0) * 1e3:.0f} ms "
                "after it was on"
            ),
        }

    def _trace(self, trace_dir: str) -> None:
        """`_attempt`s from `start_s` of the window and the end of a full
        collection on, until one has written a device operation, `tries` at
        the most, and none that the window has no room for (a quiet watch, a
        watch for a launch and the profiler's start before its close). An
        attempt that held nothing stops in half a second; its directory is
        removed before the next, so that `trace_reduce.find_xplane`, which
        takes the last file it finds, finds the one that held something (or
        the last attempt's, where none did: run.py then says that no
        operation ran on a device, and the line has no `busy_s`)."""
        import jax

        spec = self.traffic["trace"]
        close = time.monotonic() + self.seconds
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = spec["python_tracer_level"]
        options.host_tracer_level = spec["host_tracer_level"]
        time.sleep(spec["start_s"])
        if self.cell.gc is not None:
            seen, give_up = self.cell.gc.full_count(), time.monotonic() + spec["after_full_gc_s"]
            while self.cell.gc.full_count() == seen and time.monotonic() < give_up:
                time.sleep(0.005)
        self.attempts, tries = [], spec["tries"]
        for k in range(1, tries + 1):
            got = self._attempt(trace_dir, options, spec)
            self.attempts.append(got["span"])
            _w, a, s1, b = got["span"]
            # the stretch is counted from the CALL that starts the profiler: the
            # device's events reach back before it is on (0.061 s of `ecrecover`
            # in 0.034 s, PR 36's second call)
            self.stretch = (a, a, s1, b)
            held, path, size = _held(trace_dir)
            cost = f"{b - s1:.1f} s to stop, {time.monotonic() - b:.2f} s to judge"
            line = f"trace: attempt {k} of {tries}: {got['said']}; "
            if held:
                self.log(line + f"held a device operation: {size / 1e6:.1f} MB, {cost}")
                return
            line += f"held nothing: {size / 1e3:.0f} KB, {cost}; "
            need = spec["quiet_within_s"] + spec["launch_within_s"] + got["start_s"]
            room = close - time.monotonic()
            if k == tries or room < need:
                why = f"no room for another: {room:.1f} s to the window's close, {need:.1f} s needed"
                self.log(line + ("the last" if k == tries else why))
                self.log(f"trace: {k} attempts, and none held a device operation")
                return
            self.log(line + "trying again")
            if path is not None:
                shutil.rmtree(os.path.dirname(path))

    def _stretch(self, good, t_open: float, t_close: float) -> dict | None:
        """`serve`'s, with the attempts before the one kept taken out of both
        sides of the pace, each from the beginning of its quiet watch to the
        return of its stop, as the kept one's starting and stopping are: what
        was answered in them is not counted, and their seconds are not the
        window's (the opening moved forward by them, which is all `serve`
        reads of it)."""
        spans = [(max(w, t_open), min(b, t_close)) for w, _a, _s1, b in self.attempts[:-1]]
        kept = [r for r in good if not any(w <= r[3] <= b for w, b in spans)]
        return super()._stretch(kept, t_open + sum(max(0.0, b - w) for w, b in spans), t_close)
