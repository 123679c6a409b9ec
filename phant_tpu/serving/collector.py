"""What CPython's collector does while an Engine API server is up: tenure.

A serving process keeps a long-lived heap that never dies: the programs
jax traced (one `ecrecover` trace is tens of thousands of nested
objects), the witness engine's host tables, the interpreter's modules.
CPython walks all of it again in every full (generation 2) collection,
which it starts whenever the objects promoted since the last one exceed a
quarter of it: half a second for two million containers, every 12th
request of a lone client, and nothing that walk finds is ever freed.

So while a server is up, whatever survives a full collection is moved to
the permanent generation (`gc.freeze()`), which no collection examines.
The next full collection walks only what was allocated since, and the
quarter it is measured against shrinks with it: a full collection takes
milliseconds. The program is told nothing about warm-up; the first full
collection after the last program was traced tenures it, like every one
before. The young generations are sized to a request meanwhile
(`YOUNG_THRESHOLDS`).

Tenured objects still die by reference count. What stays is cyclic
garbage that forms among them later (an evicted entry that sits in a
cycle, the cycles of a request that was in flight when its objects were
tenured; a transaction's `Evm` and its native session were such a cycle
and held the block's whole state: `Evm.execute_message` takes it apart).
The deep collection bounds it: when no request has been in flight for
`IDLE_S` and the tenured count has grown by `DEEP_GROWTH` since the last
one, everything is handed back, collected and tenured again, at most
once per `DEEP_INTERVAL_S`. A client that follows the head leaves eleven
of every twelve seconds for it; one that syncs without pause gets none
until it pauses.

Lifetime is the server's: `install` with the first, `uninstall` with the
last (`gc.unfreeze()`, the interpreter's thresholds back), so a test or
`chip_smoke.py` that builds a server inside a longer-lived process leaves
that process as it found it.

Counters: `runtime.gc_tenures`, `runtime.gc_tenured_objects`,
`runtime.gc_deep_collections`; a deep collection's seconds are
`runtime.gc_pause_seconds{generation="deep"}`.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Optional

from phant_tpu.utils import trace
from phant_tpu.utils.trace import metrics

#: no top-level span open or closed for this long: nobody is waiting
IDLE_S = 3.0
#: a deep collection is due once the tenured count has grown by this share
DEEP_GROWTH = 0.05
#: and the last one (or the policy's start) is this long ago
DEEP_INTERVAL_S = 300.0
#: under load the permanent generation is counted at most this often
GAUGE_INTERVAL_S = 60.0
#: the collector's thresholds while the policy is in force. Generation 0 is
#: sized to a request: what a request allocates and frees again (a block's
#: decoded witness, some 30,000 containers at once) then never reaches the
#: threshold, where the interpreter's 700 ran 46 young collections a request
#: over objects that were about to die by reference count. What does
#: accumulate is what stays (the engine's rows), and with everything older
#: tenured there is nothing to keep apart in generations 1 and 2: at 0 every
#: third collection is a full one that tenures, so a survivor is walked at
#: most three times and no collection walks more than three thresholds' worth
#: (17-33 ms on the v5e's host, one or two in a 51 s window; PERF.md, PR 27)
YOUNG_THRESHOLDS = (50_000, 0, 0)


class TenurePolicy:
    """One per process while any server is up (`install`). `utils/trace.py`
    calls `full_collection_ended` from its collector callback and `flush`
    where it moves the collector's log into the registry; the HTTP accept
    loop calls `idle_tick` between connections."""

    def __init__(self) -> None:
        # guards the registry's view and the deep collection; never taken
        # inside the collector
        self._lock = threading.Lock()
        self.tenures = 0  # bumped inside the collector, published by flush
        self._published = 0
        self._gauged = 0  # `tenures` when the permanent generation was last counted
        self._gauged_at = float("-inf")
        self._tenured = 0
        self._deep = False  # the collection running now is the deep one
        self._deep_at = time.monotonic()
        self._deep_tenured = 0  # tenured objects left by the last deep collection
        self.thresholds = gc.get_threshold()  # the interpreter's, given back at the end

    def full_collection_ended(self) -> bool:
        """At the `stop` of a generation-2 collection, still inside the
        collector: tenure what survived. Whether that was the deep
        collection (its label in `runtime.gc_pause_seconds`).

        Done here and not at the next span close: the collection is
        complete (CPython 3.12 runs the `stop` callbacks after
        `gc_collect_main` has returned and the generation lists are whole
        again, with its `collecting` flag still up, so no collection can
        nest), every survivor sits in generation 2, and the young
        generations hold only what the callbacks allocated: `gc.freeze()`
        is three list splices and tenures exactly the survivors. Any later
        and a request's short-lived objects allocated in between would be
        tenured with them. It takes no lock: a collection can start under
        an allocation made while this thread holds any of ours."""
        gc.freeze()
        # phantlint: disable=LOCK — no lock inside the collector; one collection runs at a time
        self.tenures += 1
        return self._deep

    def flush(self, idle: bool = False) -> None:
        """Publish the tenures counted inside the collector and, after a
        tenure, the permanent generation's new size. Counting it is a walk
        of it (15 ms a million objects here, a thirtieth of what collecting
        them took) on whichever request closes its span next, so under load
        it is done at most once per `GAUGE_INTERVAL_S`; `idle`: nobody is
        waiting, count now."""
        with self._lock:
            tenures = self.tenures
            n, self._published = tenures - self._published, tenures
            if self._gauged != tenures and (
                idle or time.monotonic() - self._gauged_at >= GAUGE_INTERVAL_S
            ):
                self._count_tenured()
        if n:
            metrics.count("runtime.gc_tenures", n)

    def idle_tick(self) -> None:
        """Between connections: where nobody has waited for `IDLE_S`, run
        the deep collection if it is due."""
        idle = trace.idle_seconds()
        if idle is None or idle < IDLE_S:
            return
        self.flush(idle=True)
        with self._lock:
            if (
                time.monotonic() - self._deep_at >= DEEP_INTERVAL_S
                and self._tenured > self._deep_tenured * (1 + DEEP_GROWTH)
            ):
                self._deep_collect()

    def _count_tenured(self) -> None:
        self._gauged, self._gauged_at = self.tenures, time.monotonic()
        self._tenured = gc.get_freeze_count()
        metrics.gauge_set("runtime.gc_tenured_objects", self._tenured)

    def _deep_collect(self) -> None:
        self._deep = True
        try:
            gc.unfreeze()
            # `_on_gc` times it, and `full_collection_ended` tenures what
            # is left
            gc.collect()
        finally:
            self._deep = False
        self._deep_at = time.monotonic()
        self._count_tenured()
        self._deep_tenured = self._tenured
        metrics.count("runtime.gc_deep_collections")


_lock = threading.Lock()
_installs = 0


def install() -> None:
    """Counted, with `uninstall`: the Engine API server calls this at start.
    The first one installs the collector's callback (`trace.watch_gc`) and
    the policy."""
    global _installs
    with _lock:
        _installs += 1
        if _installs == 1:
            trace.gc_policy = TenurePolicy()
            gc.set_threshold(*YOUNG_THRESHOLDS)
    trace.watch_gc()


def uninstall() -> None:
    """The last one hands the heap back to the collector."""
    global _installs
    policy: Optional[TenurePolicy] = None
    with _lock:
        if _installs == 0:
            return
        _installs -= 1
        if _installs == 0:
            # cleared first: a full collection from here on tenures nothing
            policy, trace.gc_policy = trace.gc_policy, None
            gc.unfreeze()
            gc.set_threshold(*policy.thresholds)
    trace.unwatch_gc()
    if policy is not None:
        policy.flush()
        metrics.gauge_set("runtime.gc_tenured_objects", 0)


def idle_tick() -> None:
    """From the HTTP accept loop, every poll interval and after every
    connection. Nothing where no server installed a policy."""
    policy = trace.gc_policy
    if policy is not None:
        policy.idle_tick()
