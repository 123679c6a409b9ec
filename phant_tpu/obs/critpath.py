"""Per-request critical-path latency attribution (PR 15).

The serving stack emits rich counters and spans (PRs 1/4), but nothing
reconstructs where a REQUEST's wall clock actually went: the
`verify_block` span carries its phase timers and the batch records the
serving lanes attach, yet "queue wait vs dispatch vs resolve vs EVM" had
to be eyeballed per trace line. This module closes that gap with a span
sink that, at every top-level `verify_block` close, TILES the request's
wall clock into an exclusive phase breakdown:

  sig_rows       signature-row build on the handler thread
  queue_wait     the handler's wait before its witness batch's first
                 lane stage began: admission -> executor pickup
  prefetch       the handler's wait under the 4th-stage decode/pre-scan
  pack           ... under begin_batch's lock-held scan
  dispatch       the handler waiting with NO lane stage of its batch
                 running: between two stages (the plan's hand-over, the
                 resolve worker still busy with another lane's batch)
                 and after the last (the batch's completion tail). The
                 seconds a host thread stood blocked on the chip are
                 `device.host_seconds{op=sync}` (utils/trace.py
                 device_host), not this
  resolve        ... under readback + commit + linkage join
  witness_decode witness -> WitnessStateDB materialization, at its
                 measured width. With a scheduler it runs BETWEEN the
                 handler's two waits (to the launch; to the verdict),
                 under the device's work: what the lanes did meanwhile
                 is hidden time and lies in no phase
  sig_wait       the sig-lane join block before EVM execution
  evm            block execution minus the sig join
  root_plan      fused post-root hash-plan build on the handler thread
  root_wait      root-lane queue wait (root batch record)
  post_root      the rest of the post-root phase: merged dispatch +
                 readback + apply, or the host walk

The tiling is HIERARCHICAL and exclusive. The handler's waits for the
witness lane (`stateless.witness_verify`: one interval without a
scheduler, two with one) are cut by the lane stages' MEASURED intervals
(`stages` of the batch record, on the span's clock, PR 26): a stage
claims exactly the part of a wait it overlaps, whatever ran while the
handler was busy elsewhere claims nothing (`tile_wait`). A record
without measured stages (an old one, a hand-made one) keeps the older
rule: the record's `*_ms` numbers clipped into the phase in pipeline
order. Each level's remainder goes to the enclosing catch-all
(`dispatch` inside witness_verify, `evm` inside execute, `post_root`
inside the post-root phase) — so the sub-tilings sum EXACTLY to their
parent phases and the only unattributed residual is real: span overhead
and gaps between phases. That residual is the honesty check: `critpath.unattributed_pct`
(and the coverage twin) gauge the cumulative attributed share, and the
test suite asserts >= 95% on the serving path at pipeline depths 1 AND 2
across all three engine lanes. Everything lands in the
`critpath.phase_seconds{phase=}` histogram family, which the derived
p50/p99 gauges (utils/trace.py prometheus_text) turn into per-phase
quantiles at scrape time. Beside it, for a span that read the thread's CPU
clock (`phases[name]["cpu_ms"]`), the same labels in
`critpath.phase_cpu_seconds` and `critpath.phase_offcpu_seconds`
(`attribute_cpu`): how much of each phase the handler thread ran, and how
much it waited; in a phase that waits for nothing by design, for the
interpreter lock.

SLO exemplars: metrics tell you THAT requests are slow; the exemplar
shows WHY. A request whose wall clock exceeds `--slo-budget-ms`
(PHANT_SLO_BUDGET_MS; 0/unset = off) — or whose single phase exceeds a
per-phase override (PHANT_SLO_BUDGET_MS_<PHASE>, e.g.
PHANT_SLO_BUDGET_MS_QUEUE_WAIT) — is captured as its FULL span tree plus
the breakdown into a dedicated bounded flight ring, served at
`GET /debug/slow` and counted in `obs.slow_captures{trigger=}`.

Near-budget tier (PR 16, closing PR 15's named open): on a healthy
server the violation ring is EMPTY — there is nothing to read when an
operator asks "what do our slowest-but-passing requests look like". A
request that lands in the top `PHANT_SLO_NEAR_PCT` percent of the
budget (wall > budget * (1 - near_pct/100) without blowing it) is
captured at a sampled 1-in-`PHANT_SLO_NEAR_SAMPLE_N` rate with
`trigger=near`; its `over_ms` is NEGATIVE — the remaining headroom.
The sampler's RNG is injectable via `configure(near_rng=...)` so tests
pin the decision sequence.

Config is resolved ONCE from the environment and memoized (the env-read-
per-request pattern is exactly what the PR 14 signer bugfix removed from
the hot path); `refresh_from_env()` re-reads it (the Engine API server
calls it at construction, after the CLI has written its flags into the
env), and `configure()` overrides it directly (tests).
`PHANT_OBS_ATTRIBUTION=0` disables the whole layer.

Thread-safety: the rollup runs on request threads; the cumulative
coverage totals sit under one small lock, the metrics registry and the
slow ring carry their own. The sink must never fail the traced work —
span() already swallows sink exceptions, and the rollup additionally
treats malformed records as zero-valued.
"""

from __future__ import annotations

import os
import random
import threading
from typing import Dict, Optional, Tuple

from phant_tpu.obs.flight import FlightRecorder
from phant_tpu.utils.trace import metrics

#: the closed phase vocabulary (documented above + in METRIC_HELP):
#: `critpath.phase_seconds{phase=}` only ever carries these labels, so
#: the family's cardinality is bounded by construction
PHASES: Tuple[str, ...] = (
    "sig_rows",
    "queue_wait",
    "prefetch",
    "pack",
    "dispatch",
    "resolve",
    "witness_decode",
    "sig_wait",
    "evm",
    "root_plan",
    "root_wait",
    "post_root",
)

#: the dedicated slow-exemplar ring (served at GET /debug/slow): its own
#: recorder so a burst of slow requests cannot wash the main flight ring's
#: scheduler postmortem context away — and vice versa
slow = FlightRecorder(
    capacity=int(os.environ.get("PHANT_SLOW_CAPACITY", "64"))
)


class _Config:
    __slots__ = (
        "enabled",
        "budget_ms",
        "phase_budgets_ms",
        "near_pct",
        "near_sample_n",
    )

    def __init__(
        self,
        enabled: bool,
        budget_ms: float,
        phase_budgets_ms: Dict[str, float],
        near_pct: float,
        near_sample_n: int,
    ):
        self.enabled = enabled
        self.budget_ms = budget_ms
        self.phase_budgets_ms = phase_budgets_ms
        self.near_pct = near_pct
        self.near_sample_n = near_sample_n


def _env_num(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, str(default)) or str(default))
    except ValueError:
        return default


def _config_from_env() -> _Config:
    budget = _env_num("PHANT_SLO_BUDGET_MS", 0.0)
    phase_budgets: Dict[str, float] = {}
    for ph in PHASES:
        raw = os.environ.get(f"PHANT_SLO_BUDGET_MS_{ph.upper()}")
        if not raw:
            continue
        try:
            v = float(raw)
        except ValueError:
            continue
        if v > 0:
            phase_budgets[ph] = v
    return _Config(
        enabled=os.environ.get("PHANT_OBS_ATTRIBUTION", "1") not in ("0", ""),
        budget_ms=budget,
        phase_budgets_ms=phase_budgets,
        near_pct=min(max(_env_num("PHANT_SLO_NEAR_PCT", 0.0), 0.0), 100.0),
        near_sample_n=max(int(_env_num("PHANT_SLO_NEAR_SAMPLE_N", 8.0)), 0),
    )


_cfg: _Config = _config_from_env()
_cfg_lock = threading.Lock()

#: near-budget tier sampler; tests pin it via configure(near_rng=...)
_near_rng = random.Random()


def refresh_from_env() -> None:
    """Re-resolve the memoized config from the environment (the Engine API
    server calls this at construction so `--slo-budget-ms`/env changes
    made before boot take effect; tests call it after monkeypatching)."""
    global _cfg
    with _cfg_lock:
        _cfg = _config_from_env()


def configure(
    enabled: Optional[bool] = None,
    budget_ms: Optional[float] = None,
    phase_budgets_ms: Optional[Dict[str, float]] = None,
    near_pct: Optional[float] = None,
    near_sample_n: Optional[int] = None,
    near_rng: Optional[random.Random] = None,
) -> None:
    """Override the memoized config directly (tests); None leaves a
    field as-is. `near_rng` replaces the near-tier sampler's
    generator (determinism for tests)."""
    global _cfg, _near_rng
    with _cfg_lock:
        _cfg = _Config(
            enabled=_cfg.enabled if enabled is None else enabled,
            budget_ms=_cfg.budget_ms if budget_ms is None else budget_ms,
            phase_budgets_ms=(
                dict(_cfg.phase_budgets_ms)
                if phase_budgets_ms is None
                else dict(phase_budgets_ms)
            ),
            near_pct=_cfg.near_pct if near_pct is None else near_pct,
            near_sample_n=(
                _cfg.near_sample_n
                if near_sample_n is None
                else max(int(near_sample_n), 0)
            ),
        )
        if near_rng is not None:
            _near_rng = near_rng


def enabled() -> bool:
    """Is the attribution layer on?"""
    return _cfg.enabled


def budget_ms() -> float:
    """The wall-clock SLO budget (0 = exemplar capture off)."""
    return _cfg.budget_ms


# cumulative coverage totals (the honesty gauges' numerator/denominator);
# guarded by one small lock — two floats, nothing more
_tot_lock = threading.Lock()
_tot_wall_s = 0.0
_tot_attr_s = 0.0


def totals() -> Tuple[float, float]:
    """(wall_s, attributed_s) cumulative since process start / last reset —
    tests compute coverage over a window from the delta of two calls."""
    with _tot_lock:
        return _tot_wall_s, _tot_attr_s


def reset_totals() -> None:
    global _tot_wall_s, _tot_attr_s
    with _tot_lock:
        _tot_wall_s = 0.0
        _tot_attr_s = 0.0


def _num(v) -> Optional[float]:
    return float(v) if isinstance(v, (int, float)) and v == v else None


#: the witness lane's stages in pipeline order (where two overlap, the
#: earlier one claims the overlap)
_LANE_STAGES: Tuple[str, ...] = ("prefetch", "pack", "resolve")


def _stage_spans(stages) -> Tuple[list, Optional[int]]:
    """([(label, start_ns, end_ns)] of the lane stages in pipeline order,
    the earliest start of ANY stage entry) of a batch record's `stages`;
    a depth-1 batch has one fused `dispatch` entry, which labels nothing
    but still ends the queue wait."""
    spans, first = [], None
    if isinstance(stages, dict):
        for label, se in stages.items():
            if (
                isinstance(se, (list, tuple))
                and len(se) == 2
                and all(isinstance(t, int) for t in se)
                and se[1] >= se[0]
            ):
                first = se[0] if first is None else min(first, se[0])
                if label in _LANE_STAGES:
                    spans.append((label, se[0], se[1]))
    spans.sort(key=lambda sp: _LANE_STAGES.index(sp[0]))
    return spans, first


def tile_wait(t0: int, t1: int, stages) -> list:
    """[(phase, start_ns, end_ns)]: one wait `[t0, t1]` of the handler for
    the witness lane, cut at the lane stages' measured intervals.
    Contiguous and exclusive, so the pieces sum to the wait exactly: a
    stage gets the part of the wait it overlaps, the stretch before the
    batch's first stage is `queue_wait`, and where no stage of the batch
    ran the pipeline or the device owned the request (`dispatch`)."""
    return _tile(t0, t1, *_stage_spans(stages))


def _tile(t0: int, t1: int, spans: list, first: Optional[int]) -> list:
    edges = [t for _l, a, b in spans for t in (a, b)]
    if first is not None:
        edges.append(first)
    cuts = sorted({t0, t1} | {t for t in edges if t0 < t < t1})
    out: list = []
    for a, b in zip(cuts, cuts[1:]):
        label = next((l for l, sa, sb in spans if sa <= a and b <= sb), None)
        if label is None:
            label = "queue_wait" if first is not None and b <= first else "dispatch"
        if out and out[-1][0] == label:
            out[-1] = (label, out[-1][1], b)
        else:
            out.append((label, a, b))
    return out


def attribute(record: dict) -> Tuple[Dict[str, float], float, float]:
    """(breakdown_ms, unattributed_ms, wall_ms) for one top-level
    `verify_block` span record. Pure function of the record — the
    unit-testable core of the rollup.

    Tiling rules (see the module docstring for the phase meanings): the
    handler's waits for the witness lane are cut by the stages' measured
    intervals (`tile_wait`); without them the batch record's `*_ms` are
    clipped into the remaining width of the phase in pipeline order.
    Either way the remainder goes to that level's catch-all — so the
    sub-tilings sum exactly to their parent phases and attributed time
    can never exceed the phases the request actually measured."""
    wall = _num(record.get("duration_ms")) or 0.0
    phases = record.get("phases") or {}

    def ph(name: str) -> float:
        st = phases.get(name)
        if isinstance(st, dict):
            return _num(st.get("total_ms")) or 0.0
        return 0.0

    out: Dict[str, float] = {}

    def put(name: str, v: float) -> None:
        if v > 0.0:
            out[name] = out.get(name, 0.0) + v

    # handler-thread phases, already exclusive by construction
    put("sig_rows", ph("stateless.sig_rows"))
    put("witness_decode", ph("stateless.witness_decode"))

    # witness_verify sub-tiling, from the witness batch record (bare
    # keys — the sig/root lanes prefix theirs). The handler's measured
    # waits cut by the stages' measured intervals: what a stage did while
    # the handler decoded (between its two waits) is in no phase
    waits = [
        (iv[1], iv[2])
        for iv in record.get("intervals") or ()
        if len(iv) == 3
        and iv[0] == "stateless.witness_verify"
        and isinstance(iv[1], int)
        and isinstance(iv[2], int)
    ]
    spans, first = _stage_spans(record.get("stages"))
    if waits and first is not None:
        for t0, t1 in waits:
            for label, a, b in _tile(t0, t1, spans, first):
                put(label, (b - a) / 1e6)
    else:
        # no measured stages (a record from before PR 26, a hand-made
        # one, the path without a scheduler): the batch record's numbers,
        # each clipped to what is left of the phase, the remainder is
        # `dispatch`
        rem = ph("stateless.witness_verify")
        for label, key in (
            ("queue_wait", "queue_wait_ms"),
            ("prefetch", "prefetch_ms"),
            ("pack", "pack_ms"),
            ("resolve", "resolve_ms"),
        ):
            v = _num(record.get(key))
            if v is not None and v > 0.0:
                v = min(v, rem)
                put(label, v)
                rem -= v
        put("dispatch", rem)

    # execute sub-tiling: the sig-lane join block, then EVM proper
    ex = ph("stateless.execute")
    sw = min(ph("sched.sig_wait"), ex)
    put("sig_wait", sw)
    put("evm", ex - sw)

    # post-root sub-tiling: plan build (its own nested phase), the
    # root-lane queue wait (prefixed record key), remainder = the merged
    # dispatch + readback + apply, or the host walk
    pr = ph("stateless.post_root")
    rp = min(ph("stateless.post_root_plan"), pr)
    rw = _num(record.get("root_queue_wait_ms")) or 0.0
    rw = min(max(rw, 0.0), pr - rp)
    put("root_plan", rp)
    put("root_wait", rw)
    put("post_root", pr - rp - rw)

    attributed = sum(out.values())
    unattributed = max(0.0, wall - attributed)
    return out, unattributed, wall


#: each parent phase of the span with the phases `attribute` tiles it into,
#: the catch-all that takes its wall remainder first: the handler's CPU
#: inside the parent goes there, and only what the catch-all's wall cannot
#: hold goes on to the cuts a lane's stage claimed (the handler slept there)
_CPU_TILING: Tuple[Tuple[str, Tuple[str, ...], Tuple[str, ...]], ...] = (
    ("stateless.sig_rows", (), ("sig_rows",)),
    ("stateless.witness_decode", (), ("witness_decode",)),
    (
        "stateless.witness_verify",
        (),
        ("dispatch", "queue_wait", "resolve", "pack", "prefetch"),
    ),
    ("stateless.execute", ("sched.sig_wait",), ("evm",)),
    ("sched.sig_wait", (), ("sig_wait",)),
    ("stateless.post_root", ("stateless.post_root_plan",), ("post_root", "root_wait")),
    ("stateless.post_root_plan", (), ("root_plan",)),
)


def attribute_cpu(
    record: dict, breakdown: Dict[str, float]
) -> Optional[Tuple[Dict[str, float], Dict[str, float]]]:
    """(cpu_ms, over_ms) of the phases of `breakdown` (what `attribute`
    gave for the same record): the CPU the handler thread ran inside each,
    from the `cpu_ms` the span keeps beside each phase's `total_ms`. None
    for a record without them (one from before the second clock, a
    hand-made one). A parent's CPU less its nested phases' own goes where
    its wall remainder goes, so the sub-tilings sum to their parents, and
    no phase is given more CPU than it has wall. What a parent's reading
    holds beyond its phases' wall (a CPU clock that steps by ticks charges
    a whole tick to the phase it fell in) is `over_ms` of the parent's
    catch-all: `rollup` books it against the label's next observations
    (`Metrics.observe_split`)."""
    phases = record.get("phases") or {}

    def cpu(name: str) -> Optional[float]:
        st = phases.get(name)
        return _num(st.get("cpu_ms")) if isinstance(st, dict) else None

    if all(cpu(parent) is None for parent, _n, _l in _CPU_TILING):
        return None
    out: Dict[str, float] = {}
    over: Dict[str, float] = {}
    for parent, nested, labels in _CPU_TILING:
        left = (cpu(parent) or 0.0) - sum(cpu(n) or 0.0 for n in nested)
        for label in labels:
            room = breakdown.get(label, 0.0) - out.get(label, 0.0)
            take = min(max(left, 0.0), max(room, 0.0))
            if take > 0.0:
                out[label] = out.get(label, 0.0) + take
                left -= take
        if left > 0.0:
            over[labels[0]] = left
    return out, over


def _capture_slow(
    record: dict,
    breakdown: Dict[str, float],
    wall_ms: float,
    trigger: str,
    budget: float,
    over_ms: float,
) -> None:
    slow.record(
        "obs.slow_capture",
        trigger=trigger,
        budget_ms=budget,
        wall_ms=wall_ms,
        over_ms=round(over_ms, 3),
        breakdown_ms={k: round(v, 3) for k, v in breakdown.items()},
        span=record,
        trace_id=record.get("trace_id"),
    )
    metrics.count("obs.slow_captures", trigger=trigger)


def rollup(record: dict) -> None:
    """THE span sink (registered by phant_tpu/obs/__init__.py): roll a
    top-level `verify_block` record into the critpath family, update the
    coverage gauges, and capture an SLO exemplar when a budget blew."""
    if record.get("span") != "verify_block":
        return
    cfg = _cfg
    if not cfg.enabled:
        return
    breakdown, unattributed, wall = attribute(record)
    if wall <= 0.0:
        return
    split = attribute_cpu(record, breakdown)
    for label, v in breakdown.items():
        metrics.observe_hist("critpath.phase_seconds", v / 1e3, phase=label)
        if split is not None:
            cpu, over = split
            metrics.observe_split(
                "critpath.phase_cpu_seconds",
                "critpath.phase_offcpu_seconds",
                v / 1e3,
                (cpu.get(label, 0.0) + over.get(label, 0.0)) / 1e3,
                phase=label,
            )
    metrics.observe_hist("critpath.wall_seconds", wall / 1e3)
    metrics.count("critpath.requests")
    global _tot_wall_s, _tot_attr_s
    with _tot_lock:
        _tot_wall_s += wall / 1e3
        # clipped tiling means attributed <= wall by construction; min()
        # keeps a malformed record from ever claiming > 100% coverage
        _tot_attr_s += min(wall - unattributed, wall) / 1e3
        cov = 100.0 * _tot_attr_s / _tot_wall_s if _tot_wall_s > 0 else 0.0
    metrics.gauge_set("critpath.coverage_pct", round(cov, 2))
    metrics.gauge_set("critpath.unattributed_pct", round(100.0 - cov, 2))
    # SLO exemplars: wall budget first (the headline trigger), then the
    # sampled near-budget tier, then the per-phase overrides — ONE
    # capture per request, first trigger wins
    if cfg.budget_ms > 0 and wall > cfg.budget_ms:
        _capture_slow(
            record, breakdown, wall, "wall", cfg.budget_ms, wall - cfg.budget_ms
        )
        return
    if (
        cfg.budget_ms > 0
        and cfg.near_pct > 0
        and wall > cfg.budget_ms * (1.0 - cfg.near_pct / 100.0)
    ):
        n = cfg.near_sample_n
        if n == 1 or (n > 1 and _near_rng.randrange(n) == 0):
            # over_ms is NEGATIVE here: the headroom this near-miss
            # still had under the budget
            _capture_slow(
                record,
                breakdown,
                wall,
                "near",
                cfg.budget_ms,
                wall - cfg.budget_ms,
            )
            return
    for label, limit in cfg.phase_budgets_ms.items():
        v = breakdown.get(label, 0.0)
        if v > limit:
            _capture_slow(record, breakdown, wall, label, limit, v - limit)
            return
