"""The serve driver for blocks at the gas limit: `drivers/serve_steady.py`
with the chain of `reference/chain_basefee.py`, a third admission check, one
more comparison in `verify()`, a window that opens on a collected heap, a log
of where its seconds went, and a traced stretch that ends at the first launch
it sees.

One request of this deployment is 1,428 signatures and a witness of some
10,000 nodes, and a block's verdict is one reachability over all its rows:
it cannot be cut as a wave is. A program whose verdict ladder ends under
such a block launches EVERY request on a shape of that request's own, built
inside the first request that carries it (PERF.md section 7, fault 0c: the
program counts such launches in `lanes.oversize_launches`). So, beside
`serve_steady`'s two checks: in a measuring run the first block's witness
must fit a verdict rung the server's constructor built (read from the boot's
own launches on /metrics, before a request is posted, so that a program that
cannot be steady here is stopped soon), and after the warm-up
`lanes.oversize_launches` must stand where it stood at server start. Either
failure ends the run with a sentence. The configuration's guarantee that no
launch leaves a ladder is held over the whole run in `verify()`.

The traced stretch is `serve_tenants`'s, attempt by attempt (PR 41): one
request holds six executions of `ecrecover` in a row, 350,000 device events
each, and the profiler stops in some 15 s + 2.4 s a millisecond of it held,
so a stretch that held a request would not stop inside a run's 360 s. The
profiler is started on a quiet device and stopped at the first launch read
while it is on; an attempt that held no device operation is taken again
inside the window. Placed so, it proves the device path ran and gives the
driver its idle share and `breakdown`; it says nothing of a request, and the
cell is on the list of no `device_trace` metric (the lanes' `*_lane_sync_ms`
carry the device's side, over the whole window).
"""

from __future__ import annotations

import gc

from drivers import serve, serve_steady, serve_tenants
from harness import chainproc_basefee, scrape

LAUNCHES = "phant_lanes_launches_total"
OVERSIZE = "phant_lanes_oversize_launches_total"

#: what the log says of a window, ms a request: (name, histogram's `_sum` family)
SECONDS = (
    ("phase", "phant_critpath_phase_seconds_sum"),
    ("front end", "phant_engine_api_phase_seconds_sum"),
    ("lane stage", "phant_lanes_stage_seconds_sum"),
    ("device_host", "phant_device_host_seconds_sum"),
    ("gc_pause", "phant_runtime_gc_pause_seconds_sum"),
)
REQUESTS = "phant_critpath_requests_total"


class Driver(serve_steady.Driver):
    # the stretch's placement and its retaking are the tenants' cell's, as they are
    _attempt = serve_tenants.Driver._attempt
    _trace = serve_tenants.Driver._trace

    def load_chain(self, seed: int) -> None:
        # `serve.load_chain` starts `chainproc.make`: this deployment's chain
        # process stands in for it while the chain is loaded
        usual, serve.chainproc = serve.chainproc, chainproc_basefee
        try:
            super().load_chain(seed)
        finally:
            serve.chainproc = usual

    def _warm_up(self) -> None:
        name = self.cell.entry["name"]
        self._await(1)
        nodes = len(self.blocks[0].witness)
        built = [
            tuple(map(int, dict(labels)["rung"].split("x")))
            for (n, labels), v in self.scrape_boot.items()
            if n == LAUNCHES and v > 0 and dict(labels).get("program") == "verdict"
        ]
        self.log(f"setup: the first block's witness is {nodes} nodes; verdict rungs built at server start: {sorted(built)}")
        if not self.cell.rehearsal and not any(rows >= nodes for rows, _blocks in built):
            raise SystemExit(
                f"{name}: no verdict rung built at server start holds one block of {nodes} "
                "witness nodes: every request would be launched on a shape outside the "
                "ladder, built inside the first request that carries it (PERF.md section 7, "
                "fault 0c); not measured"
            )
        super()._warm_up()
        grown = scrape.delta(self.scrape_boot, self.scrape(), OVERSIZE)
        if grown:
            raise SystemExit(
                f"{name}: lanes.oversize_launches grew by {grown:.0f} over the warm-up: requests "
                "are launched outside the ladders the server built; not steady, not measured"
            )

    def measure(self, seconds: float, trace_dir) -> dict:
        self.seconds = seconds  # `_trace` reckons the window's close from it
        # The window opens on a collected heap. One request of this traffic
        # allocates three young generations' worth (`serving/collector.py`:
        # 50,000 containers, every third collection a full one that tenures),
        # so the full collection falls at ONE place in every request of a run,
        # and at which (early, on little: 13 ms; late, on a request's whole
        # state: 33 ms) was left to where the harness's own feeder thread, which
        # unpickles the chain in this process during set-up, had left the cycle:
        # windows of one tree differed by 7 % in pace for it (PERF.md section 6,
        # PR 44). Collected here, the cycle starts with the window in every run.
        gc.collect()
        obs = super().measure(seconds, trace_dir)
        s0, s1 = obs["scrape0"], obs["scrape1"]
        n = scrape.delta(s0, s1, REQUESTS)
        if n:
            per_request = {
                name: {
                    "/".join(v for _k, v in sorted(labels)): round((v1 - s0.get((f, labels), 0.0)) / n * 1e3, 2)
                    for (f, labels), v1 in s1.items()
                    if f == family
                }
                for name, family in SECONDS
            }
            self.log(
                f"window: {obs['completed']} correct answers by its close; ms a request the "
                f"program counted between the edges, over its {n:.0f} requests: {per_request}"
            )
        return obs

    def _stretch(self, good, t_open: float, t_close: float) -> dict | None:
        """`serve`'s, with the attempts before the one kept out of both sides
        of the pace, as `serve_tenants._stretch` takes them out."""
        spans = [(max(w, t_open), min(b, t_close)) for w, _a, _s1, b in self.attempts[:-1]]
        kept = [r for r in good if not any(w <= r[3] <= b for w, b in spans)]
        return super()._stretch(kept, t_open + sum(max(0.0, b - w) for w, b in spans), t_close)

    def verify(self) -> tuple:
        comparisons, attempted, failed = super().verify()
        grown = scrape.delta(self.scrape_boot, self.scrape(), OVERSIZE)
        return [*comparisons, ("oversize_launches", grown, 0, "at_most")], attempted, failed
