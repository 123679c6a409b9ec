"""The serve driver for a deployment that has to be steady before it is
measured: `drivers/serve.py` with two admission checks and nothing else. It
reads nothing the metrics depend on.

Right after server start, /metrics must carry every family the
configuration's traffic cannot be steady without (here `root.plan_shapes`:
a program from before the root plan's ladder builds a new root program for
every request, PERF.md section 7, fault 0a, and would spend its warm-up and
its window compiling). After the warm-up, the last pass must have built no
program. Either failure ends the run with an error, soon.
"""

from __future__ import annotations

from drivers import serve

#: families the program exports from server start once it can be steady here
REQUIRED = ("phant_root_plan_shapes",)


class Driver(serve.Driver):
    def __init__(self, cell):
        super().__init__(cell)
        self.built_by_run = []  # programs built during each run of the clients

    def start_program(self) -> None:
        super().start_program()
        missing = [f for f in REQUIRED if not any(n == f for n, _l in self.scrape_boot)]
        if missing:
            raise SystemExit(
                f"{self.cell.entry['name']}: /metrics lacks {missing} at server start: this "
                "program builds a root program for every request (PERF.md section 7, fault "
                "0a) and cannot be steady with the device root lane on; not measured"
            )

    def _run(self, plans, seconds):
        before = self.cell.compiles.count()
        out = super()._run(plans, seconds)
        self.built_by_run.append(self.cell.compiles.count() - before)
        return out

    def _warm_up(self) -> None:
        self.built_by_run = []
        super()._warm_up()
        # serve's warm-up stops at the first pass that builds nothing, or at
        # `max_passes` whatever the last one built; its last run is the probes'
        *passes, _probes = self.built_by_run
        if passes[-1]:
            raise SystemExit(
                f"{self.cell.entry['name']}: the last of {len(passes)} warm-up passes still "
                f"built {passes[-1]} programs (by pass: {passes}): not steady, not measured"
            )
