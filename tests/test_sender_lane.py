"""Coalesced sender recovery (PR 14): the differential suite.

The sig lane (signer.TxSigner.signature_rows -> serving sig lane ->
ops/sig_engine.py merged ecrecover dispatch) must be BYTE-IDENTICAL to
the direct `get_senders_batch` / `recover_senders_async(force_cpu)`
oracle on every backend route (device / native / scalar) at pipeline
depths 1 AND 2, with mixed valid/invalid signatures per request (same
`SignatureError` attribution), pre-EIP-155 legacy blocks, a poisoned sig
dispatch failing only in-flight with -32052 plus a stage-named crash
record, mesh lane routing with device-tagged records, deadline shed, and
the lone-request offload gate (native path, zero merged dispatches).
The r14 satellite bugfix — PHANT_TPU_MIN_ECRECOVER resolved once at
TxSigner construction instead of per hot-path call — is pinned here too.
"""

from __future__ import annotations

import os
import time
from dataclasses import replace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from phant_tpu.backend import set_crypto_backend
from phant_tpu.signer.signer import TxSigner
from phant_tpu.types.transaction import FeeMarketTx, LegacyTx

CHAIN_ID = 1
signer = TxSigner(CHAIN_ID)


def _mk_txs(seed: int, n: int = 5, pre155: bool = False, bad_at=()):
    """One block-shaped signed tx list: EIP-155 legacy txs (or pre-155
    when `pre155`), one 1559 tx mixed in, with the `bad_at` indices made
    unrecoverable (inconsistent legacy v / out-of-range y_parity)."""
    txs = []
    for i in range(n):
        if i % 3 == 2 and not pre155:
            tx = FeeMarketTx(
                chain_id_val=CHAIN_ID,
                nonce=i,
                max_priority_fee_per_gas=1,
                max_fee_per_gas=10 + seed,
                gas_limit=21_000,
                to=bytes([0x7E]) * 20,
                value=1 + seed + i,
                data=b"",
                access_list=(),
                y_parity=0,
                r=0,
                s=0,
            )
        else:
            tx = LegacyTx(
                nonce=i,
                gas_price=10 + seed,
                gas_limit=21_000,
                to=bytes([0x7E]) * 20,
                value=1 + seed + i,
                data=b"",
                v=27 if pre155 else 37,
                r=0,
                s=0,
            )
        tx = signer.sign(tx, 0xC0FFEE + seed * 1009 + i)
        if i in bad_at:
            if isinstance(tx, LegacyTx):
                tx = replace(tx, v=99)  # inconsistent with chain id
            else:
                tx = replace(tx, y_parity=7)
        txs.append(tx)
    return txs


def _oracle(txs):
    return signer.recover_senders_async(txs, force_cpu=True)()


def _request_set():
    """(oracle sender lists, SigRows list) — the standard mixed request
    set: plain blocks, a pre-EIP-155 block, and a block with invalid
    signatures. Shared with scripts/soak.py's sender-lane phase."""
    reqs = [
        _mk_txs(0),
        _mk_txs(1, n=7),
        _mk_txs(2, pre155=True),
        _mk_txs(3, bad_at=(1, 3)),
        _mk_txs(4, n=3),
    ]
    return [_oracle(t) for t in reqs], [signer.signature_rows(t) for t in reqs]


@pytest.fixture
def forced_device(monkeypatch):
    """Force the sig lane's device route on the XLA-CPU proxy."""
    monkeypatch.setenv("PHANT_ALLOW_JAX_CPU", "1")
    set_crypto_backend("tpu")
    yield
    set_crypto_backend("cpu")


@pytest.fixture(params=["device", "native", "scalar"])
def sig_route(request, monkeypatch):
    """The three backend routes: forced device (XLA-CPU proxy), the fused
    native batch, and the scalar pure-Python fallback (toolchain absent).
    Yields a factory for route-pinned SigEngines."""
    from phant_tpu.ops.sig_engine import SigEngine

    if request.param == "device":
        monkeypatch.setenv("PHANT_ALLOW_JAX_CPU", "1")
        set_crypto_backend("tpu")
        yield request.param, lambda: SigEngine(device_floor=0)
        set_crypto_backend("cpu")
        return
    if request.param == "scalar":
        import phant_tpu.utils.native as native_mod

        monkeypatch.setattr(native_mod, "load_native", lambda: None)
    yield request.param, SigEngine


# ---------------------------------------------------------------------------
# rows + engine-level identity
# ---------------------------------------------------------------------------


def test_signature_rows_shape_and_bad_mask():
    txs = _mk_txs(9, bad_at=(2,))
    rows = signer.signature_rows(txs)
    assert rows.n == len(txs)
    assert rows.bad == frozenset({2})
    # valid rows carry the real signing hash; bad rows the placeholder
    assert rows.msgs[2] == b"\x01" * 32
    assert all(len(m) == 32 for m in rows.msgs)


def test_engine_identity_per_route(sig_route):
    """Merged dispatch byte-identical to the force-CPU oracle on every
    backend route — invalid-signature and pre-EIP-155 requests
    included — and the backend counter names the route that ran."""
    route, make_engine = sig_route
    oracles, rows_list = _request_set()
    eng = make_engine()
    out = eng.sig_many(rows_list)
    assert out == oracles
    st = eng.stats_snapshot()
    assert st["sig_batches"] == 1 and st["sig_requests"] == len(rows_list)
    assert st[f"{route}_batches"] == 1, st


def test_invalid_signature_attribution_matches_inline():
    """The lane's None-sender positions produce the EXACT error text the
    inline `get_senders_batch` path raises — `apply_body` formats both
    identically, so the serving sig lane keeps SignatureError
    attribution byte-for-byte."""
    from phant_tpu.crypto.secp256k1 import SignatureError
    from phant_tpu.ops.sig_engine import SigEngine

    txs = _mk_txs(5, bad_at=(1,))
    with pytest.raises(SignatureError) as ei:
        signer.get_senders_batch(txs)
    senders = SigEngine().sig_many([signer.signature_rows(txs)])[0]
    bad = [i for i, a in enumerate(senders) if a is None]
    assert bad == [1]
    # chain.apply_body raises BlockError(f"invalid signature: <this>")
    # on BOTH paths — the inline path embeds get_senders_batch's message
    assert f"unrecoverable signature at tx index {bad[0]}" == str(ei.value)


def test_prefetch_merge_consumed_and_stale(forced_device):
    """An identity-matched prefetch merge is consumed by begin_batch; a
    mismatched rows list is dropped stale (released, not consumed)."""
    from phant_tpu.ops.sig_engine import SigEngine

    oracles, rows_list = _request_set()
    eng = SigEngine(device_floor=0)
    pf = eng.prefetch_batch(rows_list)
    assert pf.packed is not None
    h = eng.begin_batch(rows_list, prefetch=pf)
    assert pf.packed is None  # ownership moved
    assert eng.resolve_batch(h) == oracles
    # stale: a different list object is released whole
    pf2 = eng.prefetch_batch([rows_list[0]])
    h2 = eng.begin_batch([rows_list[1]], prefetch=pf2)
    assert pf2.packed is None  # released
    assert eng.resolve_batch(h2) == [oracles[1]]


def test_abandoned_handle_is_dead(forced_device):
    from phant_tpu.ops.sig_engine import SigEngine

    _oracles, rows_list = _request_set()
    eng = SigEngine(device_floor=0)
    h = eng.begin_batch([rows_list[0]])
    eng.abandon_batch(h)
    eng.abandon_batch(h)  # idempotent
    assert h.resolved
    with pytest.raises(RuntimeError):
        eng.resolve_batch(h)


def test_lone_request_gate_native_zero_merged_dispatches(forced_device):
    """THE offload gate (ops/sig_engine.py docstring): a lone request
    below the merged floor performs zero merged-dispatch work and lands
    on the fused native batch — byte-identical by construction."""
    from phant_tpu.ops.sig_engine import SigEngine

    txs = _mk_txs(11)  # 5 txs, far below the production floor
    eng = SigEngine(device_floor=64)  # the production floor, pinned
    out = eng.sig_many([signer.signature_rows(txs)])
    assert out == [_oracle(txs)]
    st = eng.stats_snapshot()
    assert st["device_batches"] == 0, st
    assert st["native_batches"] + st["scalar_batches"] == 1
    # ...and the merged batch of many such requests clears the same gate
    oracles, rows_list = _request_set()
    eng2 = SigEngine(device_floor=20)  # merged rows (25) clear it
    assert eng2.sig_many(rows_list) == oracles
    assert eng2.stats_snapshot()["device_batches"] == 1


def test_no_toolchain_promotes_subfloor_to_device(forced_device, monkeypatch):
    """With NO native toolchain a sub-floor batch still takes the device
    kernel (it beats scalar Python even below the floor — the same
    promotion `recover_rows_async` applies; the floor only arbitrates
    device vs the fused NATIVE batch). Without this the lane would be
    slower than the inline path on toolchain-less TPU deployments."""
    import phant_tpu.utils.native as native_mod

    from phant_tpu.ops.sig_engine import SigEngine

    monkeypatch.setattr(native_mod, "load_native", lambda: None)
    txs = _mk_txs(31)
    eng = SigEngine(device_floor=64)  # 5 rows, far below
    assert eng.sig_many([signer.signature_rows(txs)]) == [_oracle(txs)]
    assert eng.stats_snapshot()["device_batches"] == 1, eng.stats


def test_min_ecrecover_resolved_once(monkeypatch):
    """r14 bugfix pin: the device floor resolves ONCE at TxSigner
    construction (env read off the hot path); the explicit ctor argument
    is the test/engine override and wins over the env."""
    monkeypatch.setenv("PHANT_TPU_MIN_ECRECOVER", "7")
    s = TxSigner(CHAIN_ID)
    assert s._min_device == 7
    monkeypatch.setenv("PHANT_TPU_MIN_ECRECOVER", "123")
    assert s._min_device == 7  # no per-call env re-read
    assert TxSigner(CHAIN_ID)._min_device == 123
    assert TxSigner(CHAIN_ID, min_device_ecrecover=5)._min_device == 5


# ---------------------------------------------------------------------------
# the serving sig lane: differential across routes x depths, coalescing,
# crash semantics, mesh, deadline shed, end-to-end server
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2])
def test_sched_sig_lane_differential(sig_route, depth):
    """Sender byte-identity through the scheduler on every backend route
    at both pipeline depths, with witness traffic interleaved on the
    same scheduler (the lanes must coexist)."""
    import numpy as np

    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    _route, make_engine = sig_route

    class _Wit:
        def verify_batch(self, w):
            return np.ones(len(w), bool)

    oracles, rows_list = _request_set()
    with VerificationScheduler(
        engine=_Wit(),
        config=SchedulerConfig(
            max_batch=16,
            max_wait_ms=20.0,
            pipeline_depth=depth,
            sig_engine_factory=make_engine,
        ),
    ) as s:
        wfuts = [s.submit_witness(b"\x11" * 32, [b"x"]) for _ in range(3)]
        outs = s.sig_many(rows_list)
        assert all(f.result(timeout=30) for f in wfuts)
        st = s.stats_snapshot()
    assert outs == oracles
    assert st["sig_batches"] >= 1
    assert st["sig_requests"] == len(rows_list)


def test_sig_jobs_coalesce_and_meta(forced_device):
    """Concurrent requests' rows coalesce into one merged dispatch (they
    all share the single sig bucket); sig_traced returns the joinable
    batch record (backend, batch_id, merged_rows, queue_wait_ms)."""
    import threading

    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    oracles, rows_list = _request_set()
    with VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8,
            max_wait_ms=200.0,
            sig_engine_factory=lambda: SigEngine(device_floor=0),
        ),
    ) as s:
        results = [None] * len(rows_list)

        def one(i):
            # no deadline: a cold XLA compile on the proxy can exceed
            # the default 30s (the test pins coalescing, not latency)
            results[i] = s.sig_traced(rows_list[i], deadline_s=float("inf"))

        threads = [
            threading.Thread(target=one, args=(i,))
            for i in range(len(rows_list))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        st = s.stats_snapshot()
    metas = []
    for (out, meta), want in zip(results, oracles):
        assert out == want
        assert meta is not None and meta["backend"] == "device"
        assert meta["lane"] == "sig" and "queue_wait_ms" in meta
        assert meta["merged_rows"] >= len(want)
        metas.append(meta)
    # every request shares THE sig bucket: one merged dispatch
    assert st["sig_coalesced"] >= 2
    assert len({m["batch_id"] for m in metas}) == 1
    assert metas[0]["merged_rows"] == sum(r.n for r in rows_list)


def test_poisoned_sig_dispatch_crash():
    """A poisoned sig dispatch fails ONLY in-flight requests with -32052
    and leaves a stage-named crash record; earlier results keep their
    senders."""
    from phant_tpu.obs.flight import flight
    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        SchedulerDown,
        VerificationScheduler,
    )

    class _Poisoned(SigEngine):
        armed = False

        def begin_batch(self, rows_list, prefetch=None):
            if _Poisoned.armed:
                raise RuntimeError("test-induced sig dispatch crash")
            return super().begin_batch(rows_list, prefetch=prefetch)

    _Poisoned.armed = False
    oracles, rows_list = _request_set()
    s = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8,
            max_wait_ms=5.0,
            pipeline_depth=2,
            sig_engine_factory=_Poisoned,
        ),
    )
    try:
        first = [s.submit_sig(rows_list[0]), s.submit_sig(rows_list[1])]
        got = [f.result(timeout=60) for f in first]
        assert got == oracles[:2]
        _Poisoned.armed = True
        second = [s.submit_sig(r) for r in rows_list[2:]]
        for f in second:
            with pytest.raises(SchedulerDown) as ei:
                f.result(timeout=60)
            assert ei.value.code == -32052
        # already-resolved senders survive
        assert [f.result(timeout=1) for f in first] == got
    finally:
        s.shutdown()
    crashes = [
        r for r in flight.records() if r.get("kind") == "sched.executor_crash"
    ]
    assert crashes, "no crash record"
    assert crashes[-1]["stage"] in ("pack", "dispatch", "prefetch")


def test_sig_lane_mesh_dispatch(forced_device):
    """Mesh mode: sig batches route to a device lane (device-tagged
    record) and resolve byte-identical through the lane's own pinned
    SigEngine."""
    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    oracles, rows_list = _request_set()
    with VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8,
            max_wait_ms=20.0,
            pipeline_depth=2,
            mesh_devices=2,
            sig_engine_factory=lambda: SigEngine(device_floor=0),
        ),
    ) as s:
        out0, meta0 = s.sig_traced(rows_list[0], deadline_s=float("inf"))
        out1, meta1 = s.sig_traced(rows_list[3], deadline_s=float("inf"))
        st = s.stats_snapshot()
    assert out0 == oracles[0] and out1 == oracles[3]
    assert meta0 is not None and meta0.get("device") is not None
    assert st["mesh_batches"] >= 1 and st["sig_batches"] >= 1


def test_expired_sig_jobs_shed_without_execution():
    """A sig job whose deadline passes while queued sheds with -32051
    (the witness lane's deadline semantics, inherited wholesale)."""
    import numpy as np

    from phant_tpu.serving.scheduler import (
        DeadlineExpired,
        SchedulerConfig,
        VerificationScheduler,
    )

    _oracles, rows_list = _request_set()

    class _Slow:
        def verify_batch(self, w):
            time.sleep(0.3)
            return np.ones(len(w), bool)

    s = VerificationScheduler(
        engine=_Slow(),
        config=SchedulerConfig(max_batch=4, max_wait_ms=1.0, pipeline_depth=1),
    )
    try:
        # a slow witness batch occupies the executor while the sig job's
        # deadline expires in the queue
        s.submit_witness(b"\x11" * 32, [b"x"])
        f = s.submit_sig(rows_list[0], deadline_s=0.05)
        with pytest.raises(DeadlineExpired):
            f.result(timeout=30)
    finally:
        s.shutdown()


# ---------------------------------------------------------------------------
# the request path: dispatch at decode, join before execution
# ---------------------------------------------------------------------------


def test_dispatch_sender_recovery_lane_and_fallbacks(monkeypatch):
    """dispatch_sender_recovery: engaged under PHANT_BATCHED_SIG=1 with
    an installed scheduler (senders identical, sched.sig_wait recorded,
    sig meta folded under sig_-prefixed span attrs); None without a
    scheduler; degrades to the local fused batch over the
    ALREADY-BUILT rows — same senders — when the scheduler dies after
    dispatch."""
    from phant_tpu import serving
    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )
    from phant_tpu.stateless import dispatch_sender_recovery

    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    txs = _mk_txs(21)
    # no scheduler installed -> no lane
    assert dispatch_sender_recovery(CHAIN_ID, txs) is None
    s = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8, max_wait_ms=5.0, sig_engine_factory=SigEngine
        ),
    )
    serving.install(s)
    try:
        from phant_tpu.utils.trace import span

        with span("verify_block", block=1):
            resolve = dispatch_sender_recovery(CHAIN_ID, txs)
            assert resolve is not None
            assert resolve() == _oracle(txs)
            from phant_tpu.utils.trace import current_span

            sp = current_span()
            assert sp.attrs.get("sig_lane") == "sig"
            assert sp.attrs.get("sig_backend") in ("device", "native", "scalar")
        # lane off -> None (the pre-filter)
        monkeypatch.setenv("PHANT_BATCHED_SIG", "0")
        assert dispatch_sender_recovery(CHAIN_ID, txs) is None
        monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    finally:
        serving.uninstall(s)
        s.shutdown()
    # dispatched, then the scheduler dies -> resolve degrades to None
    s2 = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8, max_wait_ms=500.0, sig_engine_factory=SigEngine
        ),
    )
    serving.install(s2)
    try:
        resolve = dispatch_sender_recovery(CHAIN_ID, txs)
        assert resolve is not None
    finally:
        serving.uninstall(s2)
        s2.shutdown(drain=False)
    # shed after dispatch: the local fallback recovers from the rows
    # already built — the block still gets its senders
    assert resolve() == _oracle(txs)


def test_execute_stateless_routes_senders_through_scheduler(monkeypatch):
    """End-to-end: with PHANT_BATCHED_SIG=1 a real
    engine_executeStatelessPayloadV1 recovers its senders through the
    active scheduler's sig lane (native backend here — the lane itself
    is backend-agnostic) and the reply is unchanged."""
    from test_serving import _post, _stateless_request

    from phant_tpu.engine_api.server import EngineAPIServer
    from phant_tpu.serving import SchedulerConfig

    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    chain, rpc, want_root = _stateless_request()
    server = EngineAPIServer(
        chain,
        host="127.0.0.1",
        port=0,
        sched_config=SchedulerConfig(max_batch=8, max_wait_ms=10.0),
    )
    server.serve_in_background()
    try:
        base = f"http://127.0.0.1:{server.port}"
        code, body = _post(base, rpc)
        assert code == 200 and body["result"]["status"] == "VALID", body
        assert body["result"]["stateRoot"] == want_root
        st = server.scheduler.stats_snapshot()
        assert st["sig_batches"] >= 1, st
    finally:
        server.shutdown()


@pytest.mark.parametrize("op", ["enqueue", "sync"])
def test_device_host_seconds_grows_on_the_sig_lane(forced_device, op):
    """The host's time at the device, measured where it is spent (PR 26):
    `enqueue` around the merged ecrecover's upload and launch, `sync`
    around the sender readback."""
    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.utils.trace import metrics

    key = f'device.host_seconds{{lane="sig",op="{op}"}}'
    before = metrics.snapshot()["histograms"].get(key, {"count": 0, "sum": 0.0})
    oracles, rows_list = _request_set()
    assert SigEngine(device_floor=0).sig_many(rows_list[:1]) == oracles[:1]
    after = metrics.snapshot()["histograms"][key]
    assert after["count"] == before["count"] + 1
    assert after["sum"] > before["sum"]
