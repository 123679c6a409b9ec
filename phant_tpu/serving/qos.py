"""QoS policies for the verification scheduler: tenancy, priority, fairness.

Serving "millions of users" means the scheduler cannot treat every client
as one FIFO stream: a backfill indexer replaying a year of history and a
consensus client pushing the chain head offer wildly different traffic
(PAPERS.md's Patricia-trie reuse analysis makes the per-tenant engine cost
skew concrete — witness node reuse is heavy and tenant-mix dependent), and
under a burst naive FIFO admission lets the cheap-to-submit tenant starve
the latency-critical one. This module holds the three policy pieces the
scheduler composes, each deliberately free of scheduler state so it can be
unit-tested in isolation (tests/test_qos.py):

* **Tenant identity** — `tenant_context`/`current_tenant`: a per-thread
  lane tag, bound by the Engine API server from the `X-Phant-Tenant`
  request header (engine_api/server.py) exactly the way `trace_context`
  binds the trace id. Scheduler submissions made inside the context
  inherit it; everything else lands in `DEFAULT_TENANT` — which is why
  offline callers (verify_many, the spec runner) see byte-identical
  single-tenant behavior.
* **Priority classes** — `PRIORITY_HEAD` (head-of-chain work: the serial
  mutation lane's `engine_newPayload*`/`engine_forkchoiceUpdated`, or a
  witness verification explicitly marked `X-Phant-Priority: head`) and
  `PRIORITY_BACKFILL` (default for `engine_executeStatelessPayloadV1`).
  Head work preempts backfill at dequeue time and, when the global queue
  is full, may EVICT the newest backfill job (never another head job,
  never the serial lane) — the documented shed order.
* **`WeightedFairPicker`** — smooth weighted round-robin over tenant
  lanes (the nginx/LVS SWRR shape): every pick adds each candidate's
  weight to its credit, the highest credit wins and pays back the total.
  Over any window the pick ratio converges to the weight ratio, and a
  tenant that was absent does not bank unbounded credit (credits are
  clamped when a tenant leaves the candidate set), so a returning lane
  cannot monopolize the executor.
* **`AdaptiveWait`** — the batching-wait policy (the inference-serving
  shape PR 3 copied, now closed-loop): an under-full batch waits for
  followers only while the queue is SHALLOW. As queue depth approaches
  one full batch the wait decays linearly to `min_wait_ms` — the backlog
  IS the batch, waiting longer only adds latency — and an idle scheduler
  widens back to `max_wait_ms` so a lone request still gets coalescing
  headroom. Pure function of depth: `wait_ms(depth)`.

Nothing here takes locks; the scheduler calls these under its own `_lock`
(tenant-context reads are thread-local, lock-free by construction).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, Mapping, Optional, Sequence

#: priority classes (lower = more urgent). Head-of-chain work preempts
#: backfill at dequeue and may evict backfill at admission; the reverse
#: never happens.
PRIORITY_HEAD = 0
PRIORITY_BACKFILL = 1

#: the lane every untagged submission lands in — offline callers
#: (verify_many, spec runner) never leave it, which is what keeps
#: single-tenant behavior identical to the pre-QoS scheduler.
DEFAULT_TENANT = "default"

#: the fold-over lane once the scheduler has seen its max distinct
#: tenants: an attacker spraying random X-Phant-Tenant values must not be
#: able to grow per-tenant state (or metric cardinality) without bound.
OVERFLOW_TENANT = "other"

_TENANT_MAXLEN = 64

_tls = threading.local()


def sanitize_tenant(raw: Optional[str]) -> str:
    """Clamp an untrusted tenant tag to a metrics-safe label: charset
    `[A-Za-z0-9_.-]`, bounded length, empty -> DEFAULT_TENANT. Applied at
    the HTTP boundary (the header is attacker-controlled) so everything
    downstream — lane keys, metric labels, flight records — is clean."""
    if not raw:
        return DEFAULT_TENANT
    out = []
    for ch in raw[:_TENANT_MAXLEN]:
        out.append(ch if (ch.isalnum() or ch in "_.-") else "_")
    return "".join(out) or DEFAULT_TENANT


@contextlib.contextmanager
def tenant_context(
    tenant: str, priority: int = PRIORITY_BACKFILL
) -> Iterator[None]:
    """Bind a (tenant, priority) pair to the current thread: scheduler
    submissions made inside inherit it (serving/scheduler.py reads it at
    `_witness_job` build time, same pattern as `trace_context`). Nests;
    the innermost binding wins."""
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append((tenant, priority))
    try:
        yield
    finally:
        stack.pop()


def current_tenant() -> str:
    """The innermost bound tenant, or DEFAULT_TENANT."""
    stack = getattr(_tls, "stack", None)
    return stack[-1][0] if stack else DEFAULT_TENANT


def current_priority() -> int:
    """The innermost bound priority class, or PRIORITY_BACKFILL."""
    stack = getattr(_tls, "stack", None)
    return stack[-1][1] if stack else PRIORITY_BACKFILL


def parse_weights(spec: Optional[str]) -> Dict[str, float]:
    """`"cl:4,indexer:1"` -> {"cl": 4.0, "indexer": 1.0} (the
    `--sched-tenant-weights` / PHANT_SCHED_TENANT_WEIGHTS format).
    Unlisted tenants get weight 1. Malformed entries raise ValueError —
    a typo'd weight flag must fail loudly at startup, not silently
    deweight a tenant."""
    out: Dict[str, float] = {}
    if not spec:
        return out
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        if not name or not w:
            raise ValueError(f"bad tenant weight entry {part!r} (want name:weight)")
        weight = float(w)
        if weight <= 0:
            raise ValueError(f"tenant weight must be > 0: {part!r}")
        out[sanitize_tenant(name)] = weight
    return out


class WeightedFairPicker:
    """Smooth weighted round-robin over a changing candidate set.

    Classic SWRR: each `pick` adds every candidate's weight to its
    credit, the largest credit wins and pays back the candidate total —
    over N picks tenant t is chosen ~ N * w_t / sum(w). Two departures
    from the textbook version, both for a LIVE queue where lanes appear
    and drain:

    * unknown tenants get `default_weight` lazily (a new API key must
      not need a config push to be served);
    * a tenant absent from the candidate set has its banked credit
      clamped to one round's worth, so a lane that idled for an hour
      cannot return and monopolize the executor while it burns saved
      credit (fairness is over offered load, not over wall time).
    """

    def __init__(
        self,
        weights: Optional[Mapping[str, float]] = None,
        default_weight: float = 1.0,
    ):
        self._weights: Dict[str, float] = dict(weights or {})
        self._default = float(default_weight)
        self._credit: Dict[str, float] = {}

    def weight_of(self, tenant: str) -> float:
        return self._weights.get(tenant, self._default)

    def pick(self, candidates: Sequence[str]) -> str:
        """Choose the next tenant among `candidates` (non-empty; order
        does not matter — ties break deterministically by name)."""
        if not candidates:
            raise ValueError("pick() needs at least one candidate")
        if len(candidates) == 1:
            # fast path: the common single-tenant scheduler never pays
            # for credit bookkeeping (and its credit stays clamped below)
            self._credit.pop(candidates[0], None)
            return candidates[0]
        total = 0.0
        for t in candidates:
            w = self.weight_of(t)
            total += w
            self._credit[t] = self._credit.get(t, 0.0) + w
        # absent tenants must not bank credit across rounds
        cand = set(candidates)
        for t in list(self._credit):
            if t not in cand:
                self._credit[t] = min(self._credit[t], self.weight_of(t))
        best = max(sorted(candidates), key=lambda t: self._credit[t])
        self._credit[best] -= total
        return best


class AdaptiveWait:
    """Queue-depth-adaptive batching wait.

    `wait_ms(depth)` is the time an under-full batch should wait for
    followers when `depth` requests are queued BEHIND its head:

        depth 0           -> max_wait_ms   (idle: full coalescing window)
        0 < d < full      -> linear decay  (backlog forming: shrink)
        depth >= full     -> min_wait_ms   (the backlog IS the batch)

    `full_depth` defaults to `max_batch`: once a whole batch is already
    waiting, assembly should grab it and go — extra wait is pure added
    latency, the queue-depth signal every production inference server
    keys its batching timeout on. Monotone non-increasing in depth and
    pure (no internal state), so the scheduler can re-evaluate it every
    assembly pass and the policy stays trivially unit-testable."""

    def __init__(
        self, max_wait_ms: float, min_wait_ms: float = 0.0, full_depth: int = 1
    ):
        if min_wait_ms > max_wait_ms:
            min_wait_ms = max_wait_ms
        self.max_wait_ms = float(max_wait_ms)
        self.min_wait_ms = float(min_wait_ms)
        self.full_depth = max(1, int(full_depth))

    def wait_ms(self, depth: int) -> float:
        if depth <= 0:
            return self.max_wait_ms
        if depth >= self.full_depth:
            return self.min_wait_ms
        frac = 1.0 - depth / self.full_depth
        return self.min_wait_ms + (self.max_wait_ms - self.min_wait_ms) * frac
