"""Mesh-sharded verification serving (phant_tpu/serving/mesh_exec.py).

Pins the PR's tentpole contract on the virtual 8-device CPU mesh:
bucket-affinity routing is STABLE (a witness shape keeps hitting the same
device's intern table), skewed single-bucket load SPILLS to the
least-loaded lanes (every device participates instead of one chip working
while seven idle), per-device batches produce verdicts identical to the
single-device path (bad witnesses included), a full single-bucket batch
takes the whole-mesh fused megabatch dispatch, one crashing lane takes the
scheduler down WITHOUT leaking any engine's in-flight handles, the serial
mutation lane drains every device lane first, `/healthz` + `/metrics`
carry the per-device surface, the obs watchdog names the stalled device,
and the `--sched-mesh*` CLI flags wire through.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request
from collections import Counter

import numpy as np
import pytest

from phant_tpu.__main__ import build_parser
from phant_tpu.obs.flight import flight
from phant_tpu.ops.witness_engine import WitnessEngine
from phant_tpu.serving import (
    MeshExecutorPool,
    SchedulerConfig,
    SchedulerDown,
    VerificationScheduler,
    affinity_device,
)
from phant_tpu.utils.trace import metrics

from test_serving import _witness_set


def _mesh_sched(n_devices: int, **cfg) -> VerificationScheduler:
    cfg.setdefault("max_batch", 8)
    cfg.setdefault("max_wait_ms", 2.0)
    cfg.setdefault("queue_depth", 4096)
    return VerificationScheduler(
        config=SchedulerConfig(mesh_devices=n_devices, **cfg)
    )


def _same_bucket_witnesses(n: int, seed: int = 5):
    """`n` witnesses that all land in ONE scheduler shape bucket (the
    assembler coalesces per bucket; megabatch and the affinity tests need
    a single-bucket stream)."""
    from phant_tpu.serving.scheduler import _pow2ceil

    pool = _witness_set(max(4 * n, 64), seed=seed)
    by_bucket: dict = {}
    for w in pool:
        by_bucket.setdefault(_pow2ceil(sum(map(len, w[1]))), []).append(w)
    bucket, wits = max(by_bucket.items(), key=lambda kv: len(kv[1]))
    assert len(wits) >= n, f"want {n} same-bucket witnesses, have {len(wits)}"
    return wits[:n]


# ---------------------------------------------------------------------------
# routing policy
# ---------------------------------------------------------------------------


def test_affinity_device_stable_and_spread():
    """The bucket->device map is a pure stable function (same bucket, same
    device — across calls and pool instances) and spreads power-of-two
    buckets across the mesh instead of aliasing them onto one device."""
    buckets = [1 << k for k in range(8, 24)]
    first = [affinity_device(b, 8) for b in buckets]
    again = [affinity_device(b, 8) for b in buckets]
    assert first == again
    assert all(0 <= d < 8 for d in first)
    # 16 consecutive pow2 buckets must not collapse onto one or two homes
    assert len(set(first)) >= 4
    # a 1-lane pool routes everything to lane 0
    assert {affinity_device(b, 1) for b in buckets} == {0}


def test_pool_rejects_bad_config():
    with pytest.raises(ValueError):
        MeshExecutorPool(0)
    with pytest.raises(ValueError):
        MeshExecutorPool(2, dispatch="round-robin")


def test_default_factory_pins_engines_per_device():
    """Each lane's default engine carries its device index — the
    per-device intern-table identity the affinity routing preserves."""
    pool = MeshExecutorPool(4, prewarm=False)
    try:
        engines = pool.engines()
        assert [e.stats_snapshot()["device_index"] for e in engines] == [0, 1, 2, 3]
        assert len({id(e) for e in engines}) == 4  # own tables, not shared
    finally:
        pool.shutdown(5.0)


@pytest.mark.parametrize("fails", [False, True])
def test_prewarm_outcome_is_kept_and_a_failure_counted(monkeypatch, fails):
    """The boot prewarm is never a liveness dependency, but its outcome is
    readable (`prewarm_compiled`) and a failure counts into
    backend.device_fallbacks{site="mesh_prewarm"} — chip_smoke.py --mesh
    requires a compile count above zero and that family at zero."""
    import phant_tpu.parallel.mesh as pmesh
    from phant_tpu import backend

    def prewarm_sharded(_mesh):
        if fails:
            raise ValueError("the compiler refused the sharded kernel")
        return 2

    monkeypatch.setattr(backend, "_CRYPTO_BACKEND", "tpu")
    monkeypatch.setattr(backend, "_DEVICE", ("cpu", "cpu", 8))
    monkeypatch.setattr(pmesh, "prewarm_sharded", prewarm_sharded)
    key = 'backend.device_fallbacks{site="mesh_prewarm"}'
    before = metrics.snapshot()["counters"].get(key, 0)
    pool = MeshExecutorPool(2, prewarm=False)
    try:
        assert pool.prewarm_compiled is None
        pool._prewarm()
        assert pool.prewarm_compiled == (None if fails else 2)
        assert metrics.snapshot()["counters"].get(key, 0) == before + fails
    finally:
        pool.shutdown(5.0)


# ---------------------------------------------------------------------------
# correctness: per-device batches vs the single-device path
# ---------------------------------------------------------------------------


def test_mesh_verify_many_matches_single_device():
    """The whole span through an 8-lane mesh scheduler must be verdict-
    identical to direct single-engine verify_batch — including witnesses
    that must FAIL (wrong root, disconnected node set)."""
    wits = _witness_set(48)
    wits[7] = (b"\x11" * 32, wits[7][1])  # wrong root -> False
    wits[23] = (wits[23][0], wits[23][1][1:])  # dropped root node -> False
    want = np.asarray(WitnessEngine().verify_batch(wits))
    with _mesh_sched(8) as s:
        got = s.verify_many(wits)
        st = s.stats_snapshot()
    assert (got == want).all()
    assert not got[7] and not got[23]
    assert st["mesh"]["devices"] == 8
    assert sum(st["mesh"]["dispatches"]) == st["mesh_batches"]


def test_mesh_one_lane_matches_plain_scheduler():
    """mesh_devices=1 (the A/B control lane) is still verdict-identical
    to the pool-less scheduler over the same traffic."""
    wits = _witness_set(24, seed=11)
    with VerificationScheduler(
        config=SchedulerConfig(max_batch=8, max_wait_ms=2.0, queue_depth=4096)
    ) as plain:
        want = plain.verify_many(wits)
    with _mesh_sched(1) as s:
        got = s.verify_many(wits)
    assert (got == np.asarray(want)).all()


def test_mesh_batch_records_carry_device():
    """verify_traced's batch record (and the flight ring's batch_done)
    must name the device lane that served the batch."""
    wits = _witness_set(4, seed=13)
    with _mesh_sched(4) as s:
        ok, meta = s.verify_traced(*wits[0])
        assert ok
        assert meta is not None and "device" in meta
        assert meta["device"] in range(4)
    done = [
        r for r in flight.records()
        if r.get("kind") == "sched.batch_done" and r.get("device") is not None
    ]
    assert done, "no device-carrying batch_done record in the flight ring"


# ---------------------------------------------------------------------------
# spillover under skewed load
# ---------------------------------------------------------------------------


class _SlowEngine:
    """verify_batch with a floor latency: backs the home lane up so the
    spillover policy has something to spill away from."""

    def __init__(self, delay_s: float = 0.03):
        self._eng = WitnessEngine()
        self._delay = delay_s

    def verify_batch(self, witnesses):
        time.sleep(self._delay)
        return self._eng.verify_batch(witnesses)


def test_spillover_spreads_single_bucket_backlog():
    """A deep single-bucket backlog (everything affinity-routes to ONE
    home lane) must spill: every device ends up dispatching batches, and
    the pool counts the spills."""
    wits = _same_bucket_witnesses(16)
    with VerificationScheduler(
        config=SchedulerConfig(
            max_batch=1,  # batch per request: 16 routed batches
            max_wait_ms=0.1,
            queue_depth=4096,
            mesh_devices=4,
            mesh_spill_depth=1,
            pipeline_depth=1,
            mesh_engine_factory=lambda _i: _SlowEngine(),
        )
    ) as s:
        got = s.verify_many(wits)
        st = s.stats_snapshot()
    assert got.all()
    dispatches = st["mesh"]["dispatches"]
    assert sum(dispatches) == 16
    assert all(d >= 1 for d in dispatches), f"idle lane: {dispatches}"
    assert st["mesh"]["spills"] > 0


# ---------------------------------------------------------------------------
# megabatch: the whole-mesh fused dispatch
# ---------------------------------------------------------------------------


def test_megabatch_full_bucket_takes_whole_mesh_path():
    """megabatch mode + a full single-bucket batch => ONE sharded fused
    kernel call across the mesh, verdict-identical to the engine path
    (corrupted block included), counted in stats and metrics."""
    wits = _same_bucket_witnesses(16)
    wits[5] = (b"\x00" * 32, wits[5][1])  # corrupted: must stay False
    want = np.asarray(WitnessEngine().verify_batch(wits))
    snap0 = metrics.snapshot()["counters"].get("sched.mesh_megabatches", 0)
    with _mesh_sched(
        2,
        max_batch=16,
        max_wait_ms=500.0,
        adaptive_wait=False,
        mesh_dispatch="megabatch",
    ) as s:
        got = s.verify_many(wits)
        st = s.stats_snapshot()
    assert (got == want).all()
    assert not got[5]
    assert st["megabatches"] >= 1
    assert metrics.snapshot()["counters"].get("sched.mesh_megabatches", 0) > snap0


def test_megabatch_oversized_node_unsupported():
    """A batch the fused kernel cannot express (an oversized node) raises
    MegabatchUnsupported from the pool — the scheduler's fallback trigger."""
    from types import SimpleNamespace

    from phant_tpu.crypto.keccak import RATE
    from phant_tpu.ops.witness_jax import WITNESS_MAX_CHUNKS
    from phant_tpu.serving.mesh_exec import MegabatchUnsupported

    pool = MeshExecutorPool(2, dispatch="megabatch", prewarm=False)
    try:
        big = b"\x01" * (WITNESS_MAX_CHUNKS * RATE + 7)
        jobs = [SimpleNamespace(root=b"\x00" * 32, nodes=[big], bucket=1024)]
        with pytest.raises(MegabatchUnsupported):
            pool.run_megabatch(jobs, 1)
    finally:
        pool.shutdown(5.0)


def test_megabatch_non_pow2_mesh_falls_back_to_affinity():
    """A non-power-of-two mesh cannot evenly shard the fused pack: the
    full single-bucket batch must FALL BACK to affinity routing and still
    verify correctly (megabatches stays 0, batches still route)."""
    wits = _same_bucket_witnesses(9)
    want = np.asarray(WitnessEngine().verify_batch(wits))
    with _mesh_sched(
        3,
        max_batch=3,
        max_wait_ms=500.0,
        adaptive_wait=False,
        mesh_dispatch="megabatch",
    ) as s:
        got = s.verify_many(wits)
        st = s.stats_snapshot()
    assert (got == want).all()
    assert st["megabatches"] == 0  # fused path unsupported on 3 lanes
    assert st["mesh_batches"] >= 1  # ...so everything routed by affinity


# ---------------------------------------------------------------------------
# crash path: one lane dies, no engine leaks a handle
# ---------------------------------------------------------------------------


class _SharedEngineProxy:
    """Delegates the two-phase protocol to one shared WitnessEngine (the
    pool supports shared engines by contract); the poisoned variant
    crashes its lane at resolve time."""

    def __init__(self, eng):
        self._eng = eng

    def begin_batch(self, witnesses):
        return self._eng.begin_batch(witnesses)

    def resolve_batch(self, handle):
        return self._eng.resolve_batch(handle)

    def abandon_batch(self, handle):
        return self._eng.abandon_batch(handle)

    def verify_batch(self, witnesses):
        return self._eng.verify_batch(witnesses)

    def stats_snapshot(self):
        return self._eng.stats_snapshot()


class _PoisonedLaneEngine(_SharedEngineProxy):
    def __init__(self, eng, after: int = 1):
        super().__init__(eng)
        self._left = after

    def resolve_batch(self, handle):
        if self._left <= 0:
            # release the handle exactly as a real pre-commit resolve
            # failure would, then die — the LANE is what must clean up
            # everything else
            self._eng.abandon_batch(handle)
            raise RuntimeError("mesh lane exploded at resolve")
        self._left -= 1
        return self._eng.resolve_batch(handle)


def test_lane_crash_fails_fast_and_leaks_no_handles():
    """One lane's resolve crash must (a) mark the scheduler down with
    -32052 fail-fast for everything queued/in-flight, (b) leave the
    SHARED engine with ZERO in-flight handles — every lane abandoned its
    dispatched-but-unresolved work — and (c) name the stage + device in
    the crash record."""
    from phant_tpu.serving.scheduler import _pow2ceil

    wits = _same_bucket_witnesses(24)
    bucket_home = affinity_device(_pow2ceil(sum(map(len, wits[0][1]))), 3)
    shared = WitnessEngine()

    def factory(i):
        if i == bucket_home:
            return _PoisonedLaneEngine(shared, after=1)
        return _SharedEngineProxy(shared)

    sched = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=2,
            max_wait_ms=0.1,
            queue_depth=4096,
            mesh_devices=3,
            mesh_spill_depth=64,  # keep the bucket on its poisoned home
            pipeline_depth=2,
            mesh_engine_factory=factory,
        )
    )
    try:
        futs = [sched.submit_witness(r, n) for r, n in wits]
        results = []
        for f in futs:
            try:
                results.append(bool(f.result(timeout=30)))
            except SchedulerDown:
                results.append("down")
        assert "down" in results, "no future saw the crash"
        # the scheduler is down: healthz surface + fail-fast on new work
        assert sched.state()["executor_alive"] is False
        with pytest.raises(SchedulerDown):
            sched.submit_witness(*wits[0])
        # no leaked leases: every dispatched-but-unresolved handle was
        # abandoned (a leak would pin _inflight and defer evictions
        # forever on an engine that outlives the scheduler)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and shared._inflight:
            time.sleep(0.02)
        assert shared._inflight == 0, f"{shared._inflight} leaked handle(s)"
        crash = [
            r for r in flight.records() if r.get("kind") == "sched.executor_crash"
        ][-1]
        assert crash["stage"] == "resolve"
        assert crash["device"] == bucket_home
    finally:
        sched.shutdown(drain=False, timeout=5.0)


# ---------------------------------------------------------------------------
# serial exclusivity across lanes
# ---------------------------------------------------------------------------


def test_serial_mutation_drains_every_lane_first():
    """A serial job must not run while ANY device lane still holds
    witness work — the global-lock replacement holds across the mesh."""
    wits = _same_bucket_witnesses(12)
    observed = {}
    sched = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=1,
            max_wait_ms=0.1,
            queue_depth=4096,
            mesh_devices=4,
            mesh_spill_depth=1,
            pipeline_depth=1,
            mesh_engine_factory=lambda _i: _SlowEngine(0.02),
        )
    )

    def mutation():
        st = sched._pool.state()["per_device"]
        observed["busy"] = {
            d: (v["queued"], v["inflight"])
            for d, v in st.items()
            if v["queued"] or v["inflight"]
        }
        return "done"

    try:
        futs = [sched.submit_witness(r, n) for r, n in wits]
        serial = sched.submit_serial(mutation)
        assert serial.result(timeout=30) == "done"
        assert observed["busy"] == {}, f"serial ran over busy lanes: {observed}"
        assert all(bool(f.result(timeout=30)) for f in futs)
    finally:
        sched.shutdown(drain=True, timeout=10.0)


# ---------------------------------------------------------------------------
# observability: healthz / metrics / watchdog / CLI
# ---------------------------------------------------------------------------


def _get_json(base, path):
    import urllib.error

    try:
        with urllib.request.urlopen(base + path, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_mesh_healthz_and_metrics_over_http():
    """`--sched-mesh` serving surface: /healthz carries per-device lane
    liveness under scheduler.mesh, and /metrics exports the per-device
    dispatch/queue-depth families after served traffic."""
    from concurrent.futures import ThreadPoolExecutor

    from phant_tpu.engine_api.server import EngineAPIServer
    from test_serving import _post, _stateless_request

    chain, rpc, _root = _stateless_request()
    server = EngineAPIServer(
        chain,
        host="127.0.0.1",
        port=0,
        sched_config=SchedulerConfig(
            max_batch=8, max_wait_ms=5.0, queue_depth=256, mesh_devices=2
        ),
    )
    server.serve_in_background()
    try:
        base = f"http://127.0.0.1:{server.port}"
        code, payload = _get_json(base, "/healthz")
        assert code == 200
        mesh = payload["scheduler"]["mesh"]
        assert mesh["devices"] == 2 and mesh["all_alive"]
        assert set(mesh["per_device"]) == {"0", "1"}
        assert all(v["alive"] for v in mesh["per_device"].values())
        with ThreadPoolExecutor(max_workers=6) as pool:
            replies = list(pool.map(lambda _: _post(base, rpc), range(6)))
        assert all(
            body.get("result", {}).get("status") == "VALID" for _c, body in replies
        )
        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert 'phant_sched_device_dispatch_total{device="' in text
        assert 'phant_sched_device_queue_depth{device="' in text
        assert "phant_sched_mesh_devices" in text
    finally:
        server.shutdown()


class _WedgedBeginEngine:
    """begin_batch wedges long enough for the watchdog to flag the lane."""

    def __init__(self, wedge_s: float):
        self._eng = WitnessEngine()
        self._wedge = wedge_s
        self.wedged = threading.Event()

    def begin_batch(self, witnesses):
        self.wedged.set()
        time.sleep(self._wedge)
        return self._eng.begin_batch(witnesses)

    def resolve_batch(self, handle):
        return self._eng.resolve_batch(handle)

    def abandon_batch(self, handle):
        return self._eng.abandon_batch(handle)


def test_watchdog_stall_names_the_stalled_device():
    """A wedged device call must produce a sched.stall flight record that
    NAMES the device lane (the wedged-device postmortem, per-chip)."""
    wits = _same_bucket_witnesses(2)
    eng = _WedgedBeginEngine(wedge_s=1.6)
    sched = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=2,
            max_wait_ms=0.1,
            queue_depth=64,
            deadline_ms=400.0,  # stall bound: 0.4s from pickup
            mesh_devices=2,
            pipeline_depth=2,
            mesh_engine_factory=lambda _i: eng,
        )
    )
    try:
        fut = sched.submit_witness(*wits[0], deadline_s=30.0)
        assert eng.wedged.wait(10)
        deadline = time.monotonic() + 10
        stall = None
        while time.monotonic() < deadline and stall is None:
            stalls = [
                r for r in flight.records()
                if r.get("kind") == "sched.stall" and r.get("device") is not None
            ]
            stall = stalls[-1] if stalls else None
            time.sleep(0.05)
        assert stall is not None, "watchdog never flagged the wedged lane"
        assert stall["device"] in (0, 1)
        assert stall["stage"] in ("pack", "dispatch", "resolve")
        assert bool(fut.result(timeout=30))
    finally:
        sched.shutdown(drain=True, timeout=10.0)


def test_cli_mesh_flags():
    args = build_parser().parse_args(
        ["--sched-mesh", "4", "--sched-mesh-dispatch", "megabatch",
         "--sched-mesh-spill", "3"]
    )
    assert args.sched_mesh == 4
    assert args.sched_mesh_dispatch == "megabatch"
    assert args.sched_mesh_spill == 3
    cfg = SchedulerConfig(
        mesh_devices=args.sched_mesh,
        mesh_dispatch=args.sched_mesh_dispatch,
        mesh_spill_depth=args.sched_mesh_spill,
    )
    with VerificationScheduler(config=SchedulerConfig()) as probe:
        assert probe.state().get("mesh") is None  # default: no pool
    with VerificationScheduler(config=cfg) as s:
        st = s.state()
        assert st["mesh"]["devices"] == 4
        assert st["mesh"]["dispatch"] == "megabatch"


def test_megabatch_backlog_trigger_fires_below_full_batch():
    """`--sched-megabatch-backlog-k`: a single-bucket batch far below
    max_batch takes the whole-mesh fused path once queued same-bucket
    work reaches mesh width x k — fusion under sustained overload
    without sizing max_batch — and the firing is counted separately
    (stats + metric)."""
    wits = _same_bucket_witnesses(16)
    wits[5] = (b"\x00" * 32, wits[5][1])  # corrupted: must stay False
    want = np.asarray(WitnessEngine().verify_batch(wits))
    snap0 = metrics.snapshot()["counters"].get(
        "sched.megabatch_backlog_triggers", 0
    )
    with _mesh_sched(
        2,
        max_batch=64,  # never filled: only the backlog trigger can fuse
        max_wait_ms=500.0,
        adaptive_wait=False,
        mesh_dispatch="megabatch",
        megabatch_backlog_k=1,
    ) as s:
        got = s.verify_many(wits)
        st = s.stats_snapshot()
    assert (got == want).all()
    assert not got[5]
    assert st["megabatches"] >= 1
    assert st["megabatch_backlog_triggers"] >= 1
    assert (
        metrics.snapshot()["counters"].get("sched.megabatch_backlog_triggers", 0)
        > snap0
    )


def test_megabatch_backlog_trigger_default_off():
    """k=0 (the default) keeps the full-batch-only behavior: the same
    under-full single-bucket stream routes by affinity, zero megabatches."""
    wits = _same_bucket_witnesses(16)
    with _mesh_sched(
        2,
        max_batch=64,
        max_wait_ms=500.0,
        adaptive_wait=False,
        mesh_dispatch="megabatch",
    ) as s:
        got = s.verify_many(wits)
        st = s.stats_snapshot()
    assert got.all()
    assert st["megabatches"] == 0
    assert st["megabatch_backlog_triggers"] == 0


def test_megabatch_backlog_k_cli_flag():
    args = build_parser().parse_args(["--sched-megabatch-backlog-k", "3"])
    assert args.sched_megabatch_backlog_k == 3
