"""Catch-up replay against the benchmark's plain reference (PR 42): the
deployment `replay-mpt-1chip` at test size.

A chain made by `benchmarks/reference/chain.py` (which imports nothing of
the program) is put into a replay fixture by
`benchmarks/harness/fixture_of_chain.py` (which executes nothing with the
program) and replayed through `ReplayEngine` under the scheduler the CLI
builds: every block's root and the head's are the reference's, a tampered
block fails for its own reason where it stands, the CLI's parser and builder
do what the server's do, `--root auto` keeps the host walk for a state that
retains its trie, every new `replay.*` family grows by what three segments
give, and the benchmark driver's window arithmetic and its check of the
program hold on stand-ins. The cpu crypto backend throughout: what is pinned
is the pipeline and what it says of itself, not a device program.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

from phant_tpu import serving
from phant_tpu.backend import crypto_backend, evm_backend, set_crypto_backend, set_evm_backend
from phant_tpu.replay import ReplayEngine, save_fixture
from phant_tpu.replay import __main__ as cli
from phant_tpu.replay import lowering
from phant_tpu.replay.engine import PHASES
from phant_tpu.utils.trace import METRIC_HELP, _labels_key, metrics

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
SEGMENT, N_BLOCKS, BAD = 32, 96, 40  # three segments; block 40 is mid-segment
PARAMS = dict(genesis_log2=10, sender_pool=300, contracts=4, zipf_s=1.0, transfers_per_block=4,
              calls_per_block=2, cold_recipient_share=0.5, slots_per_contract=64)  # fmt: skip
NEW_FAMILIES = (
    "replay.block_latency_seconds", "replay.execute_seconds", "replay.root_seconds",
    "replay.ready_wait_seconds", "replay.phase_cpu_seconds", "replay.phase_offcpu_seconds",
)  # fmt: skip


def _hist(name: str, **labels) -> tuple:
    """(count, sum) of one histogram series."""
    h = metrics.snapshot()["histograms"].get(_labels_key(name, labels))
    return (h["count"], h["sum"]) if h else (0, 0.0)


def _timer(name: str) -> float:
    t = metrics.snapshot()["timers"].get(name)
    return t["total_s"] if t else 0.0


@pytest.fixture(scope="module")
def bench():
    """The benchmark's directories on the path for this file's tests."""
    sys.path.insert(0, str(BENCH))
    try:
        yield
    finally:
        sys.path.remove(str(BENCH))


@pytest.fixture(scope="module")
def ref(bench, tmp_path_factory):
    """A seeded reference chain of three segments and its replay fixture."""
    from harness import fixture_of_chain as foc
    from harness.chainproc_holders import holders_of
    from reference import keccak
    from reference.chain import Chain

    keccak.load(tmp_path_factory.mktemp("keccak"))
    chain = Chain(4200000042, PARAMS)
    chain.extend(N_BLOCKS)
    fix = foc.fixture_of(chain.genesis, holders_of(chain), chain.blocks)
    return chain, fix, foc


@pytest.fixture(scope="module")
def lanes():
    """The scheduler and engine `python -m phant_tpu.replay <fixture>
    --scheduler` builds, installed for this file's replays; the process's
    backends and environment as they were, afterwards."""
    was = crypto_backend(), evm_backend()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PHANT_BATCHED_SIG", "1")  # build_engine would set it for good
        mp.delenv("PHANT_REPLAY_ROOT", raising=False)
        args = cli.build_parser().parse_args(["chain.fix", "--scheduler"])
        sched, engine = cli.build_engine(args)
        try:
            yield sched, engine, args
        finally:
            serving.uninstall(sched)
            sched.shutdown()
            set_crypto_backend(was[0])
            set_evm_backend(was[1])


def _replay(fix, root_mode, blocks=None, witnesses=None):
    return ReplayEngine(segment_blocks=SEGMENT, pipeline_depth=2, root_mode=root_mode).run(
        fix.fresh_chain(),
        fix.blocks if blocks is None else blocks,
        witnesses=fix.witnesses if witnesses is None else witnesses,
    )


# -- (a) the reference's chain goes through, block by block and at the head ------


def test_the_converter_executes_nothing_and_keeps_every_root(ref):
    chain, fix, _foc = ref
    assert fix.scheme == "mpt" and len(fix.blocks) == len(fix.witnesses) == N_BLOCKS
    assert len(fix.genesis_accounts) == 1 << PARAMS["genesis_log2"]
    assert fix.genesis.hash() == chain.genesis.hash()
    for ours, theirs in zip(fix.blocks, chain.blocks):
        assert ours.header.hash() == theirs.header.hash()
        assert [t.encode() for t in ours.transactions] == [t.encode() for t in theirs.txs]
    assert [w[0] for w in fix.witnesses] == [b.pre_root for b in chain.blocks]
    # the program's own root of the genesis it was handed is the reference's
    assert fix.fresh_state().state_root() == chain.genesis.state_root


@pytest.mark.parametrize("root", ["host", "auto", "defer"])
def test_replay_agrees_with_the_reference_block_by_block_and_at_the_head(ref, lanes, root):
    chain, fix, _foc = ref
    rep = _replay(fix, None if root == "auto" else root)
    assert rep.ok and rep.blocks_ok == N_BLOCKS and rep.segments == 3
    assert [v.index for v in rep.verdicts] == list(range(N_BLOCKS))
    # each block was held to its header's root, and the headers' roots are the reference's
    assert [b.header.state_root for b in fix.blocks] == [b.header.state_root for b in chain.blocks]
    assert rep.final_state_root == chain.head.state_root
    st = rep.stats
    assert st["root_mode"] == ("defer" if root == "defer" else "host")  # auto on the cpu backend
    assert st["lane_sig_segments"] == st["lane_witness_segments"] == 3 and st["local_sig_segments"] == 0
    assert st["witness_blocks"] == N_BLOCKS
    if root == "defer":
        assert st["device_roots"] + st["host_roots"] == N_BLOCKS


# -- (b) a tampered block fails where it stands, for its own reason --------------

TAMPERED = {
    "witness": ("witness",),
    "signature": ("nonce", "signature", "sender", "balance"),
    "state_root": ("state root mismatch",),
    "receipts_root": ("receipts root mismatch",),
    "gas_used": ("gas_used mismatch",),
}


def _tampered(ref, what):
    chain, fix, foc = ref
    blocks, witnesses = list(fix.blocks), list(fix.witnesses)
    blocks[BAD], witnesses[BAD] = foc.block_of(foc.altered(chain.blocks[BAD], what))
    return blocks, witnesses


@pytest.mark.parametrize("what", list(TAMPERED))
def test_a_tampered_block_stops_the_import_at_itself_for_its_own_reason(ref, lanes, what):
    chain, fix, _foc = ref
    rep = _replay(fix, "host", *_tampered(ref, what))
    assert not rep.ok and rep.blocks_ok == BAD
    assert [v.ok for v in rep.verdicts] == [True] * BAD + [False]
    last = rep.verdicts[-1]
    assert last.index == BAD and last.block_number == BAD + 1
    assert any(w in last.error.lower() for w in TAMPERED[what]), last.error
    others = {w for k, ws in TAMPERED.items() if k != what for w in ws}
    assert not any(w in last.error.lower() for w in others), last.error
    # earlier blocks stand, and nothing of the bad one is left
    assert rep.final_state_root == chain.blocks[BAD - 1].header.state_root


def test_a_tampered_state_root_is_caught_under_deferred_roots_too(ref, lanes):
    rep = _replay(ref[1], "defer", *_tampered(ref, "state_root"))
    assert not rep.ok and rep.blocks_ok == BAD
    assert rep.verdicts[-1].index == BAD and "state root mismatch" in rep.verdicts[-1].error


# -- (c) the CLI's parser and builder ---------------------------------------------


def test_the_two_backend_flags_are_the_servers():
    from phant_tpu.__main__ import build_parser as server_parser

    def flag(parser, name):
        (action,) = [a for a in parser._actions if a.dest == name]
        return tuple(action.choices), action.default

    ours, theirs = cli.build_parser(), server_parser()
    for name in ("crypto_backend", "evm_backend"):
        assert flag(ours, name) == flag(theirs, name)
    args = ours.parse_args(["x.fix"])
    assert (args.crypto_backend, args.evm_backend, args.root) == ("cpu", "native", "auto")
    assert (args.segment, args.depth, args.scheduler, args.mesh) == (None, None, False, 0)
    args = ours.parse_args(["x.fix", "--crypto_backend=tpu", "--evm_backend", "python", "--root", "defer"])
    assert (args.crypto_backend, args.evm_backend, args.root) == ("tpu", "python", "defer")


def test_help_names_every_flag(capsys):
    with pytest.raises(SystemExit) as stop:
        cli.build_parser().parse_args(["--help"])
    out = capsys.readouterr().out
    assert stop.value.code == 0
    for name in ("--crypto_backend", "--evm_backend", "--root", "--scheduler", "--mesh", "--serial-check", "--stats"):
        assert name in out


def test_the_builder_sets_the_backends_and_installs_todays_scheduler(lanes):
    sched, engine, args = lanes
    assert (crypto_backend(), evm_backend()) == ("cpu", "native")
    assert serving.active_scheduler() is sched
    cfg = sched.config
    assert (cfg.max_batch, cfg.max_wait_ms, cfg.pipeline_depth, cfg.mesh_devices) == (32, 20.0, 2, 0)
    assert cfg.sig_engine_factory()._device_floor == 0
    assert (engine.segment_blocks, engine.pipeline_depth, engine.root_mode) == (32, 2, None)


def test_the_builder_without_a_scheduler_and_with_the_flags(monkeypatch):
    was = crypto_backend(), evm_backend()
    args = cli.build_parser().parse_args(
        ["x.fix", "--segment", "5", "--depth", "1", "--root", "host", "--evm_backend=python"]
    )
    try:
        sched, engine = cli.build_engine(args)
        assert sched is None and evm_backend() == "python"
        assert (engine.segment_blocks, engine.pipeline_depth, engine.root_mode) == (5, 1, "host")
    finally:
        set_crypto_backend(was[0])
        set_evm_backend(was[1])


@pytest.mark.parametrize(
    "device,env,want",
    [
        (False, None, ("host", False)),
        (True, None, ("host", True)),
        (True, "auto", ("host", True)),
        (True, "device", ("defer", False)),
        (True, "1", ("defer", False)),
        (False, "host", ("host", False)),
    ],
    ids=["no-device", "device", "device-env-auto", "pinned-device", "pinned-1", "pinned-host"],
)
def test_autos_choice(monkeypatch, device, env, want):
    """`auto` keeps the host walk, on a live device too (no planner over
    what a block dirtied exists for a state that retains its trie), and
    says why there; the env pin stays an explicit request."""
    if env is None:
        monkeypatch.delenv("PHANT_REPLAY_ROOT", raising=False)
    else:
        monkeypatch.setenv("PHANT_REPLAY_ROOT", env)
    if env in (None, "auto"):
        monkeypatch.setattr(lowering, "device_roots_wanted", lambda: device)
    mode, why = lowering.auto_root_mode()
    assert (mode, why is not None) == want
    if why:
        assert "retains its trie" in why and "--root defer" in why


def test_the_cli_says_once_that_auto_kept_the_host_walk(ref, tmp_path, monkeypatch, capsys):
    chain, fix, _foc = ref
    path = tmp_path / "chain.fix"
    short = type(fix)(fix.chain_id, fix.genesis, fix.genesis_accounts, fix.blocks[:8], fix.witnesses[:8], "mpt")
    save_fixture(str(path), short)
    monkeypatch.delenv("PHANT_REPLAY_ROOT", raising=False)
    monkeypatch.setenv("PHANT_BATCHED_SIG", "1")
    monkeypatch.setattr(lowering, "device_roots_wanted", lambda: True)  # as on the chip
    was = crypto_backend(), evm_backend()
    assert cli.main([str(path), "--segment", "4", "--stats", "--serial-check"]) == 0
    assert (crypto_backend(), evm_backend()) == was  # a caller's choice is given back
    out = capsys.readouterr().out
    assert out.count("--root auto: host walk") == 1
    assert f"final state root {chain.blocks[7].header.state_root.hex()}" in out
    assert "serial-check: final-state-root identity OK" in out
    for family in NEW_FAMILIES:
        assert f"[replay] {family}" in out, family


# -- (d) what the pipeline says of itself ------------------------------------------


@pytest.fixture(scope="module")
def counted(ref, lanes):
    """One three-segment replay, and the registry before and after it."""

    def read():
        return {
            "latency": _hist("replay.block_latency_seconds"),
            "execute": _hist("replay.execute_seconds"),
            "root": _hist("replay.root_seconds", backend="host"),
            "ready_wait": _hist("replay.ready_wait_seconds"),
            "cpu": {p: _hist("replay.phase_cpu_seconds", phase=p) for p in PHASES},
            "offcpu": {p: _hist("replay.phase_offcpu_seconds", phase=p) for p in PHASES},
            "timers": {p: _timer(f"replay.{p}") for p in ("prefetch", "pack", "dispatch", "sig_wait", "witness_wait")},
        }

    before = read()
    rep = _replay(ref[1], "host")
    return before, read(), rep


def _grew(counted, key, sub=None):
    before, after, _rep = counted
    a, b = (before[key], after[key]) if sub is None else (before[key][sub], after[key][sub])
    return b[0] - a[0], b[1] - a[1]


def test_every_block_has_its_seconds_in_the_pipeline(counted):
    _b, _a, rep = counted
    n, total = _grew(counted, "latency")
    assert n == N_BLOCKS
    lat = [v.latency_s for v in rep.verdicts]
    assert all(x is not None and x > 0 for x in lat)
    assert total == pytest.approx(sum(lat), rel=1e-9)
    # a block waits for the blocks before it in its segment: in order inside a segment
    for s in range(0, N_BLOCKS, SEGMENT):
        assert lat[s : s + SEGMENT] == sorted(lat[s : s + SEGMENT])


@pytest.mark.parametrize("family", ["execute", "root", "ready_wait"])
def test_a_segment_is_one_observation_of_each_family(counted, family):
    n, total = _grew(counted, family)
    assert n == 3 and total >= 0.0
    if family != "ready_wait":
        assert total > 0.0


@pytest.mark.parametrize("phase", PHASES)
def test_a_phases_cpu_and_waiting_are_its_wall(counted, phase):
    """One observation a phase a segment on both families, and the two sum
    to the phase's wall: exactly where the wall is the same reading
    (execute, root, ready_wait, and the two joins, whose older timers are
    given the segment's own clock); where an older timer wraps the same
    stretch from outside (prefetch, pack, dispatch) it can only be the
    longer, by what a thread switch between the two readings takes."""
    before, after, _rep = counted
    n_cpu, cpu = _grew(counted, "cpu", phase)
    n_off, off = _grew(counted, "offcpu", phase)
    assert n_cpu == n_off == 3 and cpu >= 0.0 and off >= 0.0
    if phase in ("execute", "root", "ready_wait"):
        assert cpu + off == pytest.approx(_grew(counted, phase)[1], rel=1e-9, abs=1e-12)
        return
    wall = after["timers"][phase] - before["timers"][phase]
    if phase in ("sig_wait", "witness_wait"):
        assert cpu + off == pytest.approx(wall, rel=1e-9, abs=1e-9)
    else:
        assert cpu + off <= wall + 1e-9 and wall - (cpu + off) < 0.25


def test_deferred_roots_are_booked_under_their_own_backend(ref, lanes):
    was = _hist("replay.root_seconds", backend="device")
    rep = _replay(ref[1], "defer")
    now = _hist("replay.root_seconds", backend="device")
    assert rep.ok and now[0] - was[0] == 3 and now[1] > was[1]


def test_the_new_families_are_declared_and_exported(counted):
    text = metrics.prometheus_text()
    for family in NEW_FAMILIES:
        assert family in METRIC_HELP
        assert "phant_" + family.replace(".", "_") + "_count" in text, family
    for phase in PHASES:
        assert f'phant_replay_phase_cpu_seconds_sum{{phase="{phase}"}}' in text


# -- (e) the benchmark driver's window and its check of the program -----------------


@pytest.fixture()
def driver(bench):
    from drivers import replay

    return replay


@pytest.mark.parametrize(
    "times,seconds,want",
    [
        # boundaries 1..8 a second apart, run-in 2, the run may close at 6
        ([1, 2, 3, 4, 5, 6, 7, 8], 10.0, (2, 6, True)),  # the chain's length closes it
        ([1, 2, 3, 4, 5, 6, 7, 8], 3.5, (2, 5, False)),  # the last boundary inside 3.5 s
        ([1, 2, 3, 4, 5, 6, 7, 8], 4.0, (2, 6, False)),  # a boundary ON the edge is inside
        ([1, 2, 3, 4, 5, 6, 7, 8], 4.5, (2, 6, False)),  # closed at `last`, no room for one more
        ([1, 2, 9, 10, 11, 12, 13, 14], 5.0, (2, None, False)),  # no boundary inside: no window
    ],
    ids=["early", "inside", "on-the-edge", "at-last-by-the-clock", "none-inside"],
)
def test_window_edges_on_stand_in_boundaries(driver, times, seconds, want):
    w = driver.Window(run_in=2, last=6, seconds=seconds)
    taken = []
    for k, t in enumerate(times, start=1):
        w.boundary(k, float(t), lambda k=k: taken.append(k) or {"k": k})
    opened = w.opened[0] if w.opened else None
    closed = w.closed[0] if w.closed else None
    assert (opened, closed, w.early) == want
    if closed is not None:
        assert w.opened[2] == {"k": opened} and w.closed[2] == {"k": closed}
        assert taken == list(range(opened, closed + 1)) and w.done
    assert all(k <= 6 for k in taken)  # nothing is scraped past the last boundary that may close it


def test_a_run_too_short_for_a_window_is_refused(driver):
    with pytest.raises(ValueError):
        driver.Window(run_in=2, last=2, seconds=51.0)


def test_this_program_can_be_measured(driver):
    assert driver.cannot_replay() is None


@pytest.mark.parametrize("lacks", ["family", "builder"])
def test_the_parent_is_stopped_with_a_sentence_before_any_set_up(driver, monkeypatch, lacks):
    """A program from before this PR: no `replay.block_latency_seconds`, no
    builder behind the CLI. `start_program` stops at its first line."""
    if lacks == "family":
        monkeypatch.delitem(METRIC_HELP, "replay.block_latency_seconds")
    else:
        monkeypatch.delattr(cli, "build_engine")
    why = driver.cannot_replay()
    assert why is not None and "not measured" in why and "replay.block_latency_seconds" in why

    class Cell:
        entry = {"name": "replay-mpt-1chip.seg32"}
        traffic, config, log = {}, {}, staticmethod(lambda msg: None)

    d = driver.Driver(Cell())
    with pytest.raises(SystemExit) as stop:
        d.prepare()
    assert str(stop.value).startswith("replay-mpt-1chip.seg32: this program does not declare")
    assert d.procs == [] and d.sched is None  # nothing was started
    d.close()


def test_the_probes_name_the_four_guarantees(driver):
    numbers = {number for _what, number, _any, _none, _whole in driver.PROBES}
    assert numbers == {
        "tampered_witness_accepted", "tampered_signature_accepted",
        "tampered_root_accepted", "tampered_receipts_accepted",
    }  # fmt: skip
    assert sorted(what for what, *_rest in driver.PROBES) == sorted(TAMPERED)
    assert driver.PROBES[-1][0] == "state_root"  # an accepted root leaves no chain to probe on
    # the witness and the root are refused at the timed shape: a whole segment
    assert {what for what, *_rest, whole in driver.PROBES if whole} == {"witness", "state_root"}


def test_where_the_probes_lie(driver):
    """Three waves of two blocks, then two whole segments with the altered
    block in the middle; each probe begins where the one before left the
    chain: on the block before its altered one."""
    shapes, needed = driver.probe_shapes(5, 32)
    assert [(p[0], at, n, k) for p, at, n, k in shapes] == [
        ("signature", 0, 2, 1), ("receipts_root", 1, 2, 1), ("gas_used", 2, 2, 1),
        ("witness", 3, 32, 16), ("state_root", 19, 32, 16),
    ]  # fmt: skip
    assert needed == 51
    assert driver.probe_shapes(3, 32)[1] == 4 and driver.probe_shapes(6, 8)[1] == 3 + 4 + 8
