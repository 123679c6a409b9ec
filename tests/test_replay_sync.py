"""Historical replay (phant_tpu/replay/): the differential suite.

The segment pipeline must be BYTE-IDENTICAL to serial `run_blocks` —
final state root AND per-block verdicts — on every witness engine core
(ext / ctypes / python), at replay depths 1 and 2, under both the mpt
and binary commitment schemes' witnesses, through a mesh-sharded
scheduler, and with deferred device-batched segment roots. Failure
semantics ride along: a consensus-invalid block mid-segment fails
exactly that block with a stage-named `replay.block_failed` record
(earlier blocks stand — the run_blocks contract), and a scheduler death
mid-replay degrades stage-by-stage (`replay.segment_crash`, -32052,
in-flight-only) without changing a byte of the final state.

The r18 satellite bugfix — `run_blocks` window prefetch routing through
`dispatch_sender_recovery` when the sig lane is installed, rows built
once per WINDOW — is pinned here with an engine-level counter test.
"""

from __future__ import annotations

import os
import pickle
from dataclasses import replace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from phant_tpu import serving
from phant_tpu.obs.flight import flight
from phant_tpu.ops.sig_engine import SigEngine
from phant_tpu.ops.witness_engine import WitnessEngine
from phant_tpu.replay import (
    ReplayEngine,
    attach_witnesses,
    build_synthetic_chain,
    load_fixture,
    save_fixture,
)
from phant_tpu.replay.engine import (
    STAGE_DISPATCH,
    STAGE_PACK,
    STAGE_PREFETCH,
    STAGE_RESOLVE,
)
from phant_tpu.types.block import Block
from phant_tpu.utils.trace import metrics

N_BLOCKS = 12
TXS_PER_BLOCK = 3
SEGMENT = 5  # 12 blocks -> segments of 5/5/2; index 7 is mid-segment
STAGES = (STAGE_PREFETCH, STAGE_PACK, STAGE_DISPATCH, STAGE_RESOLVE)


@pytest.fixture(scope="module")
def built():
    return build_synthetic_chain(N_BLOCKS, TXS_PER_BLOCK)


def _own(built):
    """The module's chain as a fixture a test may edit: its own block list."""
    return replace(built, blocks=list(built.blocks))


@pytest.fixture(scope="module")
def serial_root(built):
    """The serial `run_blocks` oracle: final state root with per-block
    root verification ON (the fixture headers carry the real roots)."""
    fix = _own(built)
    chain = fix.fresh_chain()
    chain.run_blocks(fix.blocks)
    return chain.state.state_root()


@pytest.fixture(scope="module")
def mpt_witnesses(built):
    """Per-block full-state witnesses under the default hexary scheme
    (witness generation is scheme-dependent; roots are not)."""
    fix = attach_witnesses(_own(built))
    return fix.witnesses


def _witnessed(built, mpt_witnesses):
    fix = _own(built)
    fix.witnesses = list(mpt_witnesses)
    fix.scheme = "mpt"
    return fix


def _lane_sched(make_sig=None, engine=None, **cfg):
    cfg.setdefault("max_batch", 16)
    cfg.setdefault("max_wait_ms", 20.0)
    return serving.VerificationScheduler(
        engine=engine if engine is not None else WitnessEngine(),
        config=serving.SchedulerConfig(
            sig_engine_factory=(
                make_sig if make_sig else lambda: SigEngine(device_floor=0)
            ),
            **cfg,
        ),
    )


# -- engine cores (mechanics shared with test_witness_engine.py) ------------


@pytest.fixture(params=["ext", "ctypes", "python"])
def engine_core(request, monkeypatch):
    """All three witness-verification cores behind the witness lane
    (same mechanics as test_witness_engine.py's module fixture)."""
    monkeypatch.setenv(
        "PHANT_ENGINE_NATIVE", "0" if request.param == "python" else "1"
    )
    monkeypatch.setenv(
        "PHANT_ENGINE_EXT", "1" if request.param == "ext" else "0"
    )
    if request.param == "ext":
        from phant_tpu.utils.native import load_engine_ext

        if load_engine_ext() is None:
            pytest.skip("engine extension unavailable")
    elif request.param == "ctypes":
        from phant_tpu.utils.native import load_native

        lib = load_native()
        if lib is None or not lib.has_engine:
            pytest.skip("native engine core unavailable")
    return request.param


# -- the tentpole differential: segment replay == serial run_blocks ---------


@pytest.mark.parametrize("depth", [1, 2])
def test_replay_matches_serial_all_cores(
    built, mpt_witnesses, serial_root, engine_core, depth, monkeypatch
):
    """Final-root + verdict byte-identity vs serial run_blocks, with the
    full lane stack up: witness megabatches on every engine core, ONE
    merged ecrecover per segment, at both replay depths."""
    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    fix = _witnessed(built, mpt_witnesses)
    s = _lane_sched()
    serving.install(s)
    try:
        chain = fix.fresh_chain()
        rep = ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=depth).run(
            chain, fix.blocks, witnesses=fix.witnesses
        )
        st = s.stats_snapshot()
    finally:
        serving.uninstall(s)
        s.shutdown()
    assert rep.ok and rep.blocks_ok == N_BLOCKS
    assert rep.final_state_root == serial_root
    assert [v.index for v in rep.verdicts] == list(range(N_BLOCKS))
    assert [v.block_number for v in rep.verdicts] == [
        b.header.block_number for b in fix.blocks
    ]
    # every segment's sig rows rode the lane as one merged job
    assert rep.stats["lane_sig_segments"] == rep.segments == 3
    assert st["sig_requests"] == rep.segments
    assert st["sig_batches"] >= 1
    # all K blocks' witnesses entered the lane and verified
    assert rep.stats["witness_blocks"] == N_BLOCKS
    assert st["requests"] >= N_BLOCKS


@pytest.mark.parametrize("scheme_name", ["mpt", "binary"])
@pytest.mark.parametrize("depth", [1, 2])
def test_replay_commitment_scheme_matrix(
    built, serial_root, scheme_name, depth, monkeypatch
):
    """Witness generation under mpt AND binary commitments: the lane
    verifies linkage against the scheme's own claimed roots while the
    header chain (and the final state root) stays hexary-identical."""
    monkeypatch.setenv("PHANT_COMMITMENT", scheme_name)
    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    fix = attach_witnesses(_own(built))
    assert fix.scheme == scheme_name
    # the synthetic genesis header doesn't carry its state root; compute it
    hexary_roots = [fix.fresh_state().state_root()] + [
        b.header.state_root for b in fix.blocks[:-1]
    ]
    claimed = [root for root, _nodes in fix.witnesses]
    if scheme_name == "mpt":
        # hexary witnesses commit the PARENT header's state root exactly
        assert claimed == hexary_roots
    else:
        # binary roots are the scheme's own; linkage is vs the claim
        assert claimed != hexary_roots
    s = _lane_sched()
    serving.install(s)
    try:
        chain = fix.fresh_chain()
        rep = ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=depth).run(
            chain, fix.blocks, witnesses=fix.witnesses
        )
    finally:
        serving.uninstall(s)
        s.shutdown()
    assert rep.ok and rep.blocks_ok == N_BLOCKS
    assert rep.final_state_root == serial_root
    assert rep.stats["witness_blocks"] == N_BLOCKS


def test_replay_no_scheduler_local_fallbacks(built, serial_root):
    """With no scheduler installed every stage takes its local megabatch
    fallback — still byte-identical, still one fused batch per segment."""
    fix = _own(built)
    rep = ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=2).run(
        fix.fresh_chain(), fix.blocks
    )
    assert rep.ok and rep.final_state_root == serial_root
    assert rep.stats["local_sig_segments"] == rep.segments == 3


def test_deferred_segment_roots_device_batched(
    built, serial_root, monkeypatch
):
    """PHANT_REPLAY_ROOT=1: per-block host root walks are replaced by
    vmapped device megabatches over structure-sharing plan runs; the
    verdicts and final root stay byte-identical and the chain's own
    per-block check is restored on exit."""
    monkeypatch.setenv("PHANT_REPLAY_ROOT", "1")
    fix = _own(built)
    chain = fix.fresh_chain()
    assert chain.verify_state_root is True
    rep = ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=2).run(
        chain, fix.blocks
    )
    assert chain.verify_state_root is True  # restored
    assert rep.ok and rep.blocks_ok == N_BLOCKS
    assert rep.final_state_root == serial_root
    st = rep.stats
    assert st["device_root_groups"] >= 1 and st["device_roots"] >= 2
    assert st["device_roots"] + st["host_roots"] == N_BLOCKS


def test_deferred_roots_catch_header_mismatch(built, monkeypatch):
    """Deferred mode still VERIFIES: a tampered header state root fails
    exactly that block at the segment boundary."""
    monkeypatch.setenv("PHANT_REPLAY_ROOT", "1")
    fix = _own(built)
    bad = 7
    hdr = replace(fix.blocks[bad].header, state_root=b"\xde" * 32)
    fix.blocks[bad] = Block(
        header=hdr,
        transactions=fix.blocks[bad].transactions,
        withdrawals=fix.blocks[bad].withdrawals,
    )
    rep = ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=1).run(
        fix.fresh_chain(), fix.blocks
    )
    assert not rep.ok and rep.blocks_ok == bad
    assert rep.verdicts[-1].index == bad
    assert "state root mismatch" in rep.verdicts[-1].error


def test_group_segment_plans_runs_and_none_singletons():
    """Lowering unit: None plans are singleton runs and never merge."""
    from phant_tpu.replay.lowering import group_segment_plans

    assert group_segment_plans([]) == []
    assert group_segment_plans([None, None]) == [(0, 1), (1, 2)]
    from phant_tpu.mpt.mpt import Trie
    from phant_tpu.ops.mpt_jax import build_hash_plan

    def trie(v):
        t = Trie()
        for i in range(8):
            t.put(bytes([i]) * 4, (b"%d" % v) * 20 + bytes([i]) * 13)
        return t

    a, b = build_hash_plan(trie(1)), build_hash_plan(trie(2))
    assert a is not None and b is not None
    assert group_segment_plans([a, b, None, a]) == [(0, 2), (2, 3), (3, 4)]


# -- failure semantics ------------------------------------------------------


def test_corrupt_mid_segment_block_fails_only_that_block(
    built, monkeypatch
):
    """A consensus-invalid block mid-segment: replay fails exactly that
    block with the SAME BlockError text serial run_blocks raises,
    earlier blocks stand, and a stage-named `replay.block_failed`
    flight record is emitted."""
    from phant_tpu.blockchain.chain import BlockError

    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    fix = _own(built)
    bad = 7
    bad_tx = replace(
        fix.blocks[bad].transactions[1],
        r=(fix.blocks[bad].transactions[1].r + 1) % 2**256,
    )
    fix.blocks[bad] = Block(
        header=fix.blocks[bad].header,
        transactions=(
            fix.blocks[bad].transactions[0],
            bad_tx,
            *fix.blocks[bad].transactions[2:],
        ),
        withdrawals=fix.blocks[bad].withdrawals,
    )

    serial = fix.fresh_chain()
    with pytest.raises(BlockError) as ei:
        serial.run_blocks(fix.blocks)
    assert serial.parent_header.block_number == bad
    serial_stop_root = serial.state.state_root()

    s = _lane_sched()
    serving.install(s)
    try:
        chain = fix.fresh_chain()
        rep = ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=2).run(
            chain, fix.blocks
        )
    finally:
        serving.uninstall(s)
        s.shutdown()
    assert not rep.ok and rep.blocks_ok == bad
    assert chain.parent_header.block_number == bad
    last = rep.verdicts[-1]
    assert last.index == bad and not last.ok
    assert last.error == str(ei.value)  # byte-identical attribution
    assert rep.final_state_root == serial_stop_root
    recs = [
        r for r in flight.records() if r.get("kind") == "replay.block_failed"
    ]
    assert recs and recs[-1]["block_index"] == bad
    assert recs[-1]["stage"] in STAGES


def test_corrupt_witness_fails_only_that_block(
    built, mpt_witnesses, monkeypatch
):
    """A tampered witness mid-segment fails that block's import (the
    stateless contract: no verified witness, no execution) while every
    earlier block lands."""
    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    fix = _witnessed(built, mpt_witnesses)
    bad = 7
    _root, nodes = fix.witnesses[bad]
    fix.witnesses[bad] = (b"\xbb" * 32, list(nodes))
    s = _lane_sched()
    serving.install(s)
    try:
        chain = fix.fresh_chain()
        rep = ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=2).run(
            chain, fix.blocks, witnesses=fix.witnesses
        )
    finally:
        serving.uninstall(s)
        s.shutdown()
    assert not rep.ok and rep.blocks_ok == bad
    assert chain.parent_header.block_number == bad
    assert rep.verdicts[-1].index == bad
    assert rep.verdicts[-1].error == "witness verification failed"


def test_scheduler_death_mid_replay_degrades_stage_by_stage(
    built, mpt_witnesses, serial_root, monkeypatch
):
    """A poisoned sig dispatch kills the scheduler mid-replay: in-flight
    work fails with -32052, the segment records stage-named
    `replay.segment_crash` and degrades to local fallbacks over rows
    ALREADY built, later segments skip the dead lanes — and the final
    state root does not change by a byte."""

    class _Poisoned(SigEngine):
        armed = True

        def begin_batch(self, rows_list, prefetch=None):
            if _Poisoned.armed:
                raise RuntimeError("test-induced replay sig crash")
            return super().begin_batch(rows_list, prefetch=prefetch)

        def sig_many(self, rows_list):
            if _Poisoned.armed:
                raise RuntimeError("test-induced replay sig crash")
            return super().sig_many(rows_list)

    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    fix = _witnessed(built, mpt_witnesses)
    # by sequence number, not by position: the ring may be full already
    # (whatever ran before in this worker), and then it grows no longer
    before = max((r["seq"] for r in flight.records()), default=0)
    s = _lane_sched(make_sig=_Poisoned, pipeline_depth=2)
    serving.install(s)
    try:
        chain = fix.fresh_chain()
        rep = ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=2).run(
            chain, fix.blocks, witnesses=fix.witnesses
        )
    finally:
        serving.uninstall(s)
        s.shutdown()
        _Poisoned.armed = False
    assert rep.ok and rep.blocks_ok == N_BLOCKS
    assert rep.final_state_root == serial_root
    recs = [r for r in flight.records() if r["seq"] > before]
    crashes = [r for r in recs if r.get("kind") == "replay.segment_crash"]
    assert crashes, "no replay.segment_crash record"
    assert all(c["stage"] in STAGES for c in crashes)
    assert any(c.get("code") == -32052 for c in crashes)
    # the executor side left its own record too
    assert any(r.get("kind") == "sched.executor_crash" for r in recs)


# -- mesh fan-out -----------------------------------------------------------


def test_mesh_sharded_segments(built, mpt_witnesses, serial_root, monkeypatch):
    """A mesh scheduler shards the segment's witness megabatch over
    MeshExecutorPool lanes (per-lane resident engines — no replay-side
    special case) and the result is still byte-identical."""
    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    fix = _witnessed(built, mpt_witnesses)
    s = _lane_sched(
        max_batch=4,
        pipeline_depth=2,
        mesh_devices=2,
        mesh_spill_depth=1,
        mesh_engine_factory=lambda i: WitnessEngine(),
    )
    serving.install(s)
    try:
        chain = fix.fresh_chain()
        rep = ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=2).run(
            chain, fix.blocks, witnesses=fix.witnesses
        )
        st = s.stats_snapshot()
        lanes = s._pool.lane_engines("witness")
    finally:
        serving.uninstall(s)
        s.shutdown()
    assert rep.ok and rep.final_state_root == serial_root
    assert st["mesh_batches"] >= 2
    used = [e for e in lanes if e is not None]
    # max_batch=4 vs 12 witness jobs + spill_depth=1: both lanes serve,
    # each with its own resident engine (distinct intern tables)
    assert len(used) == 2 and used[0] is not used[1]


def test_sig_backlog_counts_rows(built):
    """`sig_backlog` (the replay pacing signal) counts queued sig ROWS
    and drains to zero."""
    import numpy as np

    from phant_tpu.signer.signer import TxSigner

    class _Slow:
        def verify_batch(self, w):
            import time as _t

            _t.sleep(0.3)
            return np.ones(len(w), bool)

    blocks = built.blocks
    signer = TxSigner(1)
    rows = signer.signature_rows(list(blocks[0].transactions))
    s = _lane_sched(engine=_Slow(), pipeline_depth=1, max_wait_ms=1.0)
    try:
        assert s.sig_backlog() == 0
        s.submit_witness(b"\x11" * 32, [b"x"])  # occupy the executor
        f1 = s.submit_sig(rows, deadline_s=float("inf"))
        f2 = s.submit_sig(rows, deadline_s=float("inf"))
        assert s.sig_backlog() in (rows.n, 2 * rows.n)
        f1.result(timeout=60) and f2.result(timeout=60)
        assert s.sig_backlog() == 0
    finally:
        s.shutdown()


# -- the r18 run_blocks bugfix pin ------------------------------------------


def test_run_blocks_windows_ride_sig_lane(built, serial_root, monkeypatch):
    """r18 satellite bugfix: with the sig lane installed, `run_blocks`
    window prefetch routes through `dispatch_sender_recovery` — one
    merged lane job per WINDOW, rows built once per window — instead of
    silently bypassing the lane for the raw device path."""
    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    monkeypatch.setenv("PHANT_TPU_PREFETCH_SIGS", "8")  # 2-block windows
    fix = _own(built)
    total_txs = fix.total_txs
    engines = []

    def make_engine():
        eng = SigEngine(device_floor=0)
        engines.append(eng)
        return eng

    t_before = (
        metrics.snapshot()["timers"].get("stateless.sig_rows", {}).get(
            "count", 0
        )
    )
    s = _lane_sched(make_sig=make_engine)
    serving.install(s)
    try:
        chain = fix.fresh_chain()
        chain.run_blocks(fix.blocks)
        st = s.stats_snapshot()
    finally:
        serving.uninstall(s)
        s.shutdown()
    assert chain.parent_header == fix.blocks[-1].header
    assert chain.state.state_root() == serial_root
    n_windows = 6  # 12 blocks x 4 txs at an 8-sig window floor
    assert st["sig_requests"] == n_windows
    assert sum(e.stats_snapshot()["sig_rows"] for e in engines) == total_txs
    t_after = (
        metrics.snapshot()["timers"].get("stateless.sig_rows", {}).get(
            "count", 0
        )
    )
    # rows are built ONCE per window (the bugfix), not once per block
    assert t_after - t_before == n_windows


# -- fixture file + CLI -----------------------------------------------------


def test_fixture_roundtrip_and_cli(
    built, mpt_witnesses, tmp_path, monkeypatch, capsys
):
    """save/load fixture round trip, then the CLI face end-to-end: scheduler lanes, serial-check identity."""
    fix = _witnessed(built, mpt_witnesses)
    p = tmp_path / "chain.fix"
    save_fixture(str(p), fix)
    back = load_fixture(str(p))
    assert back.scheme == "mpt" and len(back.blocks) == N_BLOCKS
    assert back.witnesses == fix.witnesses

    with open(tmp_path / "junk.fix", "wb") as f:
        pickle.dump({"format": "nope"}, f)
    with pytest.raises(ValueError):
        load_fixture(str(tmp_path / "junk.fix"))

    from phant_tpu.replay.__main__ import main

    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    rc = main(
        [
            str(p),
            "--segment",
            str(SEGMENT),
            "--scheduler",
            "--serial-check",
            "--stats",
        ]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "serial-check: final-state-root identity OK" in out
    assert "replay.blocks" in out
