"""The node that is behind (PR 42): the cell `replay-mpt-1chip.seg32`
through run.py's `Cell`; its entries' lengths and names; each new metric file
against its entry, on a made-up scrape, and nothing from a program without
the families; the driver's window arithmetic and its judge of a traced
stretch on the recorded trace; the control; and a whole `--rehearse` run."""

import argparse
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run
from drivers import replay, serve_tenants
from harness import readers, scrape

BENCH = Path(__file__).resolve().parents[1]
CELL = "replay-mpt-1chip.seg32"
RECORDED = BENCH / "tests" / "data" / "small.xplane.pb"
NEW = (
    "replay_execute_ms", "replay_root_ms", "replay_lane_wait_ms", "replay_ready_wait_ms",
    "replay_prepare_ms", "sig_launches_per_segment", "update_launches_per_segment",
    "replay_lane_cpu_ms", "replay_lane_lock_wait_ms", "replay_device_enqueue_ms", "replay_device_sync_ms",
    "replay_chain_wait_s",
)  # fmt: skip
#: of NEW, the lanes' host side in ms a block (asked for at review), and the one of the harness's own clock
LANES, HARNESS = NEW[7:11], NEW[11:]
#: the accepted metrics whose families the replay process exports and whose divisor makes sense there
JOINED = (
    "intern_hit_pct", "resident_rows", "compiles_in_window", "program_compiles", "sig_pad_pct",
    "lane_shapes", "verdict_pad_pct", "verdict_lone_pct", "process_cpu_pct",
    "gc_pause_ms", "wave_size", "update_launches_per_wave",
)  # fmt: skip
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

BEFORE = """
phant_replay_blocks_total 64
phant_replay_segments_total 2
phant_replay_execute_seconds_sum 1.0
phant_replay_execute_seconds_count 2
phant_replay_root_seconds_sum{backend="host"} 3.0
phant_replay_root_seconds_count{backend="host"} 2
phant_replay_ready_wait_seconds_sum 0.0
phant_replay_ready_wait_seconds_count 2
phant_replay_phase_cpu_seconds_sum{phase="sig_wait"} 0.0
phant_replay_phase_cpu_seconds_sum{phase="witness_wait"} 0.0
phant_replay_phase_cpu_seconds_sum{phase="prefetch"} 0.1
phant_replay_phase_cpu_seconds_sum{phase="pack"} 0.1
phant_replay_phase_cpu_seconds_sum{phase="dispatch"} 0.0
phant_replay_phase_cpu_seconds_sum{phase="execute"} 0.9
phant_replay_phase_offcpu_seconds_sum{phase="sig_wait"} 1.0
phant_replay_phase_offcpu_seconds_sum{phase="witness_wait"} 0.5
phant_replay_phase_offcpu_seconds_sum{phase="prefetch"} 0.0
phant_replay_phase_offcpu_seconds_sum{phase="pack"} 0.0
phant_replay_phase_offcpu_seconds_sum{phase="dispatch"} 0.0
phant_replay_phase_offcpu_seconds_sum{phase="execute"} 0.1
phant_lanes_launches_total{program="ecrecover",rung="256"} 57
phant_lanes_launches_total{program="update",rung="2048"} 48
phant_lanes_launches_total{program="verdict",rung="8192x4"} 16
phant_lanes_stage_cpu_seconds_sum{lane="sig",stage="dispatch"} 1.0
phant_lanes_stage_cpu_seconds_sum{lane="witness",stage="pack"} 1.0
phant_lanes_stage_offcpu_seconds_sum{lane="sig",stage="dispatch"} 0.5
phant_lanes_stage_offcpu_seconds_sum{lane="witness",stage="pack"} 0.5
phant_lanes_stage_offcpu_seconds_sum{lane="witness",stage="resolve"} 2.0
phant_device_host_seconds_sum{lane="sig",op="enqueue"} 2.0
phant_device_host_seconds_sum{lane="witness",op="enqueue"} 2.0
phant_device_host_seconds_sum{lane="sig",op="sync"} 1.0
phant_device_host_seconds_sum{lane="witness",op="sync"} 1.0
"""
AFTER = """
phant_replay_blocks_total 576
phant_replay_segments_total 18
phant_replay_execute_seconds_sum 8.68
phant_replay_execute_seconds_count 18
phant_replay_root_seconds_sum{backend="host"} 28.6
phant_replay_root_seconds_count{backend="host"} 18
phant_replay_ready_wait_seconds_sum 0.0512
phant_replay_ready_wait_seconds_count 18
phant_replay_phase_cpu_seconds_sum{phase="sig_wait"} 0.024
phant_replay_phase_cpu_seconds_sum{phase="witness_wait"} 0.1
phant_replay_phase_cpu_seconds_sum{phase="prefetch"} 0.5
phant_replay_phase_cpu_seconds_sum{phase="pack"} 0.2
phant_replay_phase_cpu_seconds_sum{phase="dispatch"} 0.0
phant_replay_phase_cpu_seconds_sum{phase="execute"} 7.0
phant_replay_phase_offcpu_seconds_sum{phase="sig_wait"} 8.0
phant_replay_phase_offcpu_seconds_sum{phase="witness_wait"} 4.0
phant_replay_phase_offcpu_seconds_sum{phase="prefetch"} 0.1
phant_replay_phase_offcpu_seconds_sum{phase="pack"} 0.024
phant_replay_phase_offcpu_seconds_sum{phase="dispatch"} 0.0
phant_replay_phase_offcpu_seconds_sum{phase="execute"} 1.78
phant_lanes_launches_total{program="ecrecover",rung="256"} 505
phant_lanes_launches_total{program="update",rung="2048"} 416
phant_lanes_launches_total{program="verdict",rung="8192x4"} 144
phant_lanes_stage_cpu_seconds_sum{lane="sig",stage="dispatch"} 9.0
phant_lanes_stage_cpu_seconds_sum{lane="witness",stage="pack"} 11.24
phant_lanes_stage_offcpu_seconds_sum{lane="sig",stage="dispatch"} 1.5
phant_lanes_stage_offcpu_seconds_sum{lane="witness",stage="pack"} 2.06
phant_lanes_stage_offcpu_seconds_sum{lane="witness",stage="resolve"} 12.0
phant_device_host_seconds_sum{lane="sig",op="enqueue"} 18.0
phant_device_host_seconds_sum{lane="witness",op="enqueue"} 19.92
phant_device_host_seconds_sum{lane="sig",op="sync"} 6.12
phant_device_host_seconds_sum{lane="witness",op="sync"} 13.8
"""
#: the parent: launches are counted, the replay families are not there
OLD = """
phant_lanes_launches_total{program="ecrecover",rung="256"} 505
phant_lanes_launches_total{program="update",rung="2048"} 416
"""


def _obs(before: str, after: str) -> dict:
    return {
        "scrape0": scrape.parse(before), "scrape1": scrape.parse(after), "window_s": 45.0,
        "latency_s": [7.5] * 512, "completed": 512, "setup_s": 190.0, "compiles": 0,
        "gc_pauses": [], "trace": None, "rehearsal": False, "stretch": None, "chain_wait_s": 95.5,
    }  # fmt: skip


def _spec(name: str) -> dict:
    return json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())


def _cell(rehearse: bool = False) -> run.Cell:
    args = argparse.Namespace(workload=CELL, seed=1, rehearse=rehearse, trace=1)
    return run.Cell(args, run.load_json(run.ROOT / "BENCHMARK.json"))


# -- the cell's files ------------------------------------------------------------


def test_cell_loads_its_files():
    cell = _cell()
    c, t = cell.config, cell.traffic
    assert c["name"] == "replay-mpt-1chip" and cell.chips == 1 and c["driver"] == "replay"
    assert c["argv"] == ["--scheduler", "--crypto_backend=tpu", "--evm_backend=native"] and "env" not in c
    bare = run.load_json(BENCH / "configs" / "serve-mpt-rootlane-1chip.json")
    for key in ("genesis_accounts", "sender_pool", "contracts", "rehearsal"):
        assert c[key] == bare[key], key
    for key in ("source", "deployment", "assumed", "guarantees", "reference", "admission"):
        assert c[key], key
    assert set(c["reduced"]) == {"genesis_accounts", "gas_used_per_block", "chain_blocks"}
    entry = next(e for e in cell.bench["configs"] if e["name"] == c["name"])
    assert entry["reduced"] == list(c["reduced"]) and entry["file"] == "benchmarks/configs/replay-mpt-1chip.json"
    assert c["chain_blocks"] == t["chain_blocks"]  # the cut the configuration lists is the traffic's number
    lone = run.load_json(BENCH / "traffic" / "lone.json")
    fanin = run.load_json(BENCH / "traffic" / "fanin16.json")
    assert t["chain"] == lone["chain"]
    assert (t["segment_blocks"], t["pipeline_depth"], t["root"], t["witnesses"], t["rate"]) == (32, 2, "auto", True, 1.0)
    assert "mode" not in t and "clients" not in t  # nobody posts anything
    seg = t["segment_blocks"]
    run_blocks = t["chain_blocks"] - t["warmup_segments"] * seg - t["probe_blocks"]
    assert run_blocks % seg == 0 and t["tampered_probes"] == len(replay.PROBES)
    assert t["probe_blocks"] == replay.probe_shapes(t["tampered_probes"], seg)[1] == 51
    # the issue's 704: 64 of warm-up, a run-in, 512 in the window, two segments ahead at the close
    assert t["chain_blocks"] - t["probe_blocks"] == 704
    assert run_blocks // seg - t["run_in_segments"] - t["segments_ahead_at_close"] == 16
    # the resident table fills to its cap inside the measured run, at the fewest novel nodes a block seen (1,545)
    assert (t["chain_blocks"] - t["probe_blocks"]) * 1545 > 1 << 20
    assert t["trace"] == {k: fanin["trace"][k] for k in t["trace"]}  # as fanin16 has them
    assert set(fanin["trace"]) - set(t["trace"]) == {"after_full_gc_s", "quiet_s", "quiet_within_s", "switch_s"}
    assert t["trace"]["tries"] * (t["trace"]["launch_within_s"] + 0.6) < cell.bench["run_seconds"]
    assert [m["name"] for m in cell.metrics("end_to_end", "end_to_end")] == [
        "verify_p50_ms", "verify_p95_ms", "blocks_per_s", "setup_s"
    ]  # fmt: skip
    layer = [m["name"] for m in cell.metrics("per_layer", "layer_metrics")]
    assert set(layer) == set(NEW) | set(JOINED) and layer[-len(NEW) :] == list(NEW)
    assert not any(m["source"] == "device_trace" for m in cell.metrics("per_layer", "layer_metrics"))


def test_the_rehearsal_has_a_window_of_two_segments():
    t = _cell(rehearse=True).traffic
    seg = t["segment_blocks"]
    run_blocks = t["chain_blocks"] - t["warmup_segments"] * seg - t["probe_blocks"]
    assert run_blocks % seg == 0
    assert run_blocks // seg - t["run_in_segments"] - t["segments_ahead_at_close"] >= 2


def test_entries_fit_the_contracts_lengths_and_names():
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    config = next(e for e in bench["configs"] if e["name"] == "replay-mpt-1chip")
    cell = next(e for e in bench["workloads"] if e["name"] == CELL)
    assert bench["configs"][-1] is config and bench["workloads"][-1] is cell  # appended
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for entry in (config, cell, *(m for m in bench["per_layer"] if m["name"] in NEW)):
        for key, value in entry.items():
            if isinstance(value, str):
                assert 1 <= len(value) <= 200 and "\n" not in value and "\t" not in value, (entry["name"], key)
    for name in (config["name"], cell["name"], cell["config"], cell["traffic"], *config["reduced"], *NEW):
        assert NAME.match(name), name
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    assert len(bench["workloads"]) == 5 and all(w["chips"] == 1 for w in bench["workloads"])


def test_each_new_entry_equals_its_file_and_the_cell_joined_the_lists_named():
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW) :] == list(NEW)  # appended, the issue's seven first
    layers = {m["layer"] for m in bench["per_layer"][: -len(NEW)]}
    for name in NEW:
        spec, entry = _spec(name), by_name[name]
        assert spec["name"] == name and spec["what"]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == spec[key], (name, key)
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == ("setup_s" if name in HARNESS else "blocks_per_s")
        assert (BENCH / "harness" / "readers" / f"{spec['read']['kind']}.py").is_file()
    assert {by_name[n]["layer"] for n in NEW[:5]} == {"replay pipeline (replay/engine.py, replay/lowering.py)"}
    assert {by_name[n]["layer"] for n in NEW[5:7]} <= layers  # the kernels' accepted name
    assert {by_name[n]["layer"] for n in LANES} <= layers  # as lane_cpu_ms, lane_lock_wait_ms, device_*_ms have them
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL, name
    on = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert on == set(NEW) | set(JOINED)
    # their divisor is critpath.requests, which a replay never counts: left off (PERF.md section 3)
    assert not {"sig_lane_sync_ms", "witness_lane_sync_ms"} & on


# -- the metric files on a made-up scrape -------------------------------------------


@pytest.mark.parametrize(
    "name,want",
    [
        ("replay_execute_ms", 7.68 / 512 * 1e3),
        ("replay_root_ms", 25.6 / 512 * 1e3),
        ("replay_lane_wait_ms", (0.024 + 0.1 + 7.0 + 3.5) / 512 * 1e3),
        ("replay_ready_wait_ms", 0.0512 / 512 * 1e3),
        ("replay_prepare_ms", (0.4 + 0.1 + 0.0 + 0.1 + 0.024 + 0.0) / 512 * 1e3),
        ("sig_launches_per_segment", 448 / 16),
        ("update_launches_per_segment", 368 / 16),
        ("replay_lane_cpu_ms", (8.0 + 10.24) / 512 * 1e3),
        ("replay_lane_lock_wait_ms", (1.0 + 1.56) / 512 * 1e3),  # pack and dispatch: not the resolve stage's wait
        ("replay_device_enqueue_ms", (16.0 + 17.92) / 512 * 1e3),
        ("replay_device_sync_ms", (5.12 + 12.8) / 512 * 1e3),
        ("replay_chain_wait_s", 95.5),
    ],
)
def test_new_metric_file_reads_its_families(name, want):
    assert readers.read(_spec(name), _obs(BEFORE, AFTER)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_reads_nothing_where_the_families_are_not(name):
    """A program from before the spans: the metric is left out, not 0 and
    not an error; and a driver that gave no such number."""
    obs = _obs(OLD, OLD)
    del obs["chain_wait_s"]
    assert readers.read(_spec(name), obs) is None


@pytest.mark.parametrize("name", ["sig_lane_sync_ms", "witness_lane_sync_ms"])
def test_the_lane_syncs_have_no_divisor_in_a_replay(name):
    seconds = 'phant_device_host_seconds_sum{lane="sig",op="sync"} 9.0\nphant_device_host_seconds_sum{lane="witness",op="sync"} 9.0\n'
    assert readers.read(_spec(name), _obs(BEFORE, AFTER + seconds)) is None


# -- the driver ------------------------------------------------------------------------


def test_window_of_the_cells_own_numbers():
    """20 segments of run, run-in 2, two ahead: at 2.8 s a segment the
    chain closes the window at boundary 18 (512 blocks); at 3.3 s, the 9.7
    blocks a second of a slow run, the clock does, a segment earlier."""
    for width, want in ((2.8, (2, 18, True)), (3.3, (2, 17, False))):
        w = replay.Window(run_in=2, last=18, seconds=51.0)
        for k in range(1, 21):
            w.boundary(k, 100.0 + k * width, lambda k=k: k)
        assert (w.opened[0], w.closed[0], w.early) == want
        assert w.closed[1] - w.opened[1] <= 51.0


def test_held_judges_the_recorded_trace_and_an_empty_directory(tmp_path):
    out = tmp_path / "plugins" / "profile" / "x"
    out.mkdir(parents=True)
    assert serve_tenants._held(str(tmp_path)) == (False, None, 0)
    shutil.copy(RECORDED, out / "t.xplane.pb")
    held, path, size = serve_tenants._held(str(tmp_path))
    assert held is True and path == str(out / "t.xplane.pb") and size == RECORDED.stat().st_size
    assert replay.serve_tenants is serve_tenants  # imported, not copied


def test_the_control_switches_the_root_check_off_and_gives_it_back():
    from controls import replay_skip_root
    from phant_tpu.replay.engine import ReplayEngine

    sound, lines = ReplayEngine.run, []
    undo = replay_skip_root.apply(lines.append)
    try:
        assert ReplayEngine.run is not sound and lines and lines[0].startswith("CONTROL replay_skip_root")
    finally:
        undo()
    assert ReplayEngine.run is sound


# -- the whole path on the CPU -------------------------------------------------------------


STEERED = """
import sys
sys.path.insert(0, {bench!r})
sys.path.insert(1, {root!r})
import run

load = run.load_json


def steered(path):
    out = load(path)
    if path.name == "replay-mpt-1chip.json":  # XLA-CPU ecrecover is 12 s a launch, 29 a segment
        out["argv"] = ["--scheduler", "--crypto_backend=cpu", "--evm_backend=native"]
    return out


run.load_json = steered
if __name__ == "__main__":  # the harness spawns a child that imports this module again
    sys.exit(run.main(sys.argv[1:]))
"""


def _rehearsed(tmp_path, *more) -> dict:
    """The cell's `--rehearse` run steered onto the cpu crypto backend, in
    a process of its own: a replay leaves the process's backends, its
    `PHANT_BATCHED_SIG` and a tenant `replay` in the registry (which
    `controls/starve_tenant.py` would pick as its victim in a later test)."""
    script = tmp_path / "rehearse.py"
    script.write_text(STEERED.format(bench=str(BENCH), root=str(run.ROOT)))
    argv = ["--workload", CELL, "--seed", "4200000078", "--seconds", "30", "--trace", "1", "--rehearse"]
    done = subprocess.run([sys.executable, str(script), *argv, *more], capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_rehearsal_of_the_cell_is_correct(tmp_path):
    r = _rehearsed(tmp_path)
    assert r["correct"] is True and r["failed"] == 0 and r["workload"] == CELL
    assert r["attempted"] == 192 + 5  # the rehearsal's six segments and the probes
    assert r["compared"]["window_blocks"]["value"] >= 64
    assert r["compared"]["segments_ahead_at_close"]["value"] >= 2
    for name in ("replay_execute_ms", "replay_root_ms", "replay_lane_wait_ms", "replay_ready_wait_ms", "replay_prepare_ms"):
        assert r["metrics"][name]["value"] >= 0.0, name
    assert r["metrics"]["compiles_in_window"]["value"] == 0


def test_a_root_taken_on_trust_is_not_correct_and_nothing_else_is_wrong(tmp_path):
    r = _rehearsed(tmp_path, "--control", "replay_skip_root")
    assert r["correct"] is False and r["failed"] == 1
    off = {n for n, c in r["compared"].items() if (c["value"] < c["limit"]) == (c["is"] == "at_least") and c["value"] != c["limit"]}
    assert off == {"tampered_root_accepted"}, r["compared"]
