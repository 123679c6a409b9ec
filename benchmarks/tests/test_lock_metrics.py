"""The lock's load as metrics (PR 38): the seven metric files against their
entries of BENCHMARK.json, the reader kind `sums_per_window_second` and the
files on a made-up scrape, nothing from a program without the families (the
parent of PR 38), and a whole `--rehearse` run of `fanin16` that prints all
seven."""

import json
from pathlib import Path

import pytest

import run
from harness import readers, scrape
from harness.readers import sums_per_window_second

BENCH = Path(__file__).resolve().parents[1]
CELL = "serve-mpt-tenants-1chip.fanin16"
SEVEN = (
    "handler_cpu_ms", "handler_lock_wait_ms", "frontend_lock_wait_ms", "lane_cpu_ms",
    "lane_lock_wait_ms", "interp_demand_pct", "process_cpu_pct",
)  # fmt: skip

#: 100 requests in a window of 10 s
BEFORE = """
phant_critpath_requests_total 100
phant_critpath_phase_cpu_seconds_sum{phase="evm"} 1.0
phant_critpath_phase_cpu_seconds_sum{phase="dispatch"} 0.5
phant_critpath_phase_offcpu_seconds_sum{phase="evm"} 2.0
phant_critpath_phase_offcpu_seconds_sum{phase="root_plan"} 1.0
phant_critpath_phase_offcpu_seconds_sum{phase="dispatch"} 7.0
phant_engine_api_phase_cpu_seconds_sum{phase="json"} 0.5
phant_engine_api_phase_offcpu_seconds_sum{phase="json"} 0.25
phant_engine_api_phase_offcpu_seconds_sum{phase="read"} 3.0
phant_lanes_stage_cpu_seconds_sum{lane="sig",stage="pack"} 0.5
phant_lanes_stage_cpu_seconds_sum{lane="witness",stage="resolve"} 0.5
phant_lanes_stage_offcpu_seconds_sum{lane="sig",stage="pack"} 1.0
phant_lanes_stage_offcpu_seconds_sum{lane="root",stage="dispatch"} 1.0
phant_lanes_stage_offcpu_seconds_sum{lane="witness",stage="resolve"} 5.0
phant_runtime_gc_pause_seconds_sum{generation="0"} 0.25
phant_runtime_process_cpu_seconds{mode="user"} 30.0
phant_runtime_process_cpu_seconds{mode="system"} 10.0
phant_native_unlocked_seconds{site="scan"} 0.5
phant_native_unlocked_seconds{site="hash"} 0.0
"""
AFTER = """
phant_critpath_requests_total 200
phant_critpath_phase_cpu_seconds_sum{phase="evm"} 4.0
phant_critpath_phase_cpu_seconds_sum{phase="dispatch"} 1.5
phant_critpath_phase_offcpu_seconds_sum{phase="evm"} 12.0
phant_critpath_phase_offcpu_seconds_sum{phase="root_plan"} 6.0
phant_critpath_phase_offcpu_seconds_sum{phase="dispatch"} 47.0
phant_engine_api_phase_cpu_seconds_sum{phase="json"} 1.5
phant_engine_api_phase_offcpu_seconds_sum{phase="json"} 0.75
phant_engine_api_phase_offcpu_seconds_sum{phase="read"} 9.0
phant_lanes_stage_cpu_seconds_sum{lane="sig",stage="pack"} 1.0
phant_lanes_stage_cpu_seconds_sum{lane="witness",stage="resolve"} 2.0
phant_lanes_stage_offcpu_seconds_sum{lane="sig",stage="pack"} 2.0
phant_lanes_stage_offcpu_seconds_sum{lane="root",stage="dispatch"} 1.5
phant_lanes_stage_offcpu_seconds_sum{lane="witness",stage="resolve"} 9.0
phant_runtime_gc_pause_seconds_sum{generation="0"} 0.75
phant_runtime_process_cpu_seconds{mode="user"} 45.0
phant_runtime_process_cpu_seconds{mode="system"} 20.0
phant_native_unlocked_seconds{site="scan"} 1.0
phant_native_unlocked_seconds{site="hash"} 0.5
"""
#: the parent of PR 38: it times the same phases by wall and has no second clock
OLD = """
phant_critpath_requests_total 200
phant_critpath_phase_seconds_sum{phase="evm"} 9.0
phant_engine_api_phase_seconds_sum{phase="json"} 1.0
phant_device_host_seconds_sum{lane="sig",op="sync"} 1.0
phant_runtime_gc_pause_seconds_sum{generation="0"} 0.75
"""


def _obs(before: str, after: str) -> dict:
    return {
        "latency_s": [0.5] * 4, "completed": 4, "window_s": 10.0, "setup_s": 1.0,
        "scrape0": scrape.parse(before), "scrape1": scrape.parse(after),
        "compiles": 0, "gc_pauses": [], "trace": None, "rehearsal": False, "stretch": None,
    }  # fmt: skip


def _spec(name: str) -> dict:
    return json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())


def test_the_seven_files_load_and_each_entry_equals_its_file():
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-7:] == list(SEVEN)  # appended, in the issue's order
    for name in SEVEN:
        spec = _spec(name)
        assert spec["name"] == name and spec["what"]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[name][key] == spec[key], (name, key)
        assert set(by_name[name]) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert by_name[name]["workloads"] == cells and len(cells) == 4
        assert (BENCH / "harness" / "readers" / f"{spec['read']['kind']}.py").is_file()
    assert {by_name[n]["source"] for n in SEVEN[:5]} == {"program_span"}
    assert {by_name[n]["source"] for n in SEVEN[5:]} == {"program_counter"}
    layers = {m["layer"] for m in bench["per_layer"][:-7]}
    assert {by_name[n]["layer"] for n in SEVEN[1:]} <= layers  # the accepted layers' own names


@pytest.mark.parametrize(
    "name,want",
    [
        ("handler_cpu_ms", (3.0 + 1.0 + 1.0) / 100 * 1e3),
        ("handler_lock_wait_ms", (10.0 + 5.0) / 100 * 1e3),  # dispatch is a wait by definition: left out
        ("frontend_lock_wait_ms", 0.5 / 100 * 1e3),  # read waits for the socket: left out
        ("lane_cpu_ms", (0.5 + 1.5) / 100 * 1e3),
        ("lane_lock_wait_ms", (1.0 + 0.5) / 100 * 1e3),  # resolve waits for the device: left out
        ("interp_demand_pct", (3.0 + 1.0 + 1.0 + 0.5 + 1.5 + 0.5 - 0.5 - 0.5) / 10 * 100),
        ("process_cpu_pct", 25.0 / 10 * 100),
    ],
)
def test_metric_file_reads_its_families(name, want):
    assert readers.read(_spec(name), _obs(BEFORE, AFTER)) == pytest.approx(want)


@pytest.mark.parametrize("name", SEVEN)
def test_metric_file_reads_nothing_from_the_parent(name):
    """The driver lays these files over the parent's checkout: a program
    without the second clock has none of the families at the window's close,
    and the metric is left out, not reported as 0."""
    assert readers.read(_spec(name), _obs(OLD, OLD)) is None


def test_sums_per_window_second_needs_every_family_and_never_says_zero():
    plus = [{"family": "phant_critpath_phase_cpu_seconds_sum"}, {"family": "phant_lanes_stage_cpu_seconds_sum"}]
    minus = [{"family": "phant_native_unlocked_seconds"}]
    read = sums_per_window_second.read
    assert read(_obs(BEFORE, AFTER), plus=plus, minus=minus) == pytest.approx((4.0 + 2.0 - 1.0) / 10)
    # one family short at the window's close: nothing, whatever the others grew
    short = "\n".join(line for line in AFTER.splitlines() if "native_unlocked" not in line)
    assert read(_obs(BEFORE, short), plus=plus, minus=minus) is None
    assert read(_obs(BEFORE, short), plus=plus) == pytest.approx(0.6)
    # nothing grew (a window without a request): nothing, not a share of 0
    assert read(_obs(AFTER, AFTER), plus=plus, minus=minus, scale=100) is None
    # a `where` picks series; `per` is a count's growth in place of the seconds
    evm = [{"family": "phant_critpath_phase_cpu_seconds_sum", "where": {"phase": "evm"}}]
    per = {"family": "phant_critpath_requests_total"}
    assert read(_obs(BEFORE, AFTER), plus=evm, per=per, scale=1000) == pytest.approx(30.0)
    assert read(_obs(AFTER, AFTER), plus=evm, per=per) is None
    empty = dict(_obs(BEFORE, AFTER), window_s=0.0)
    assert read(empty, plus=evm) is None


def test_rehearsal_of_fanin16_prints_all_seven(capsys, monkeypatch):
    """The whole path on the CPU at a tiny genesis, steered onto the cpu
    crypto backend as test_tenants_cell.py does: sixteen handlers and the
    scheduler's threads under one lock, every new family on /metrics, each
    phase's CPU and wait tiling its wall."""
    load = run.load_json

    def steered(path):
        out = load(path)
        if path.name == "serve-mpt-tenants-1chip.json":
            out["argv"] = ["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0"]
        return out

    monkeypatch.setattr(run, "load_json", steered)
    argv = ["--workload", CELL, "--seed", "3800000011", "--seconds", "8", "--trace", "1", "--rehearse"]
    assert run.main(argv) == 0
    r = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0
    got = {n: r["metrics"][n]["value"] for n in SEVEN}
    assert all(v > 0 for v in got.values()), got
    assert got["handler_lock_wait_ms"] > got["frontend_lock_wait_ms"]
    assert got["process_cpu_pct"] >= got["interp_demand_pct"] * 0.5  # the process holds the spanned threads

    from phant_tpu.utils.trace import metrics

    hists = metrics.snapshot()["histograms"]
    for key, wall in hists.items():
        if key.startswith("critpath.phase_seconds{"):
            label = key[len("critpath.phase_seconds"):]
            cpu, off = hists["critpath.phase_cpu_seconds" + label], hists["critpath.phase_offcpu_seconds" + label]
            assert cpu["count"] == off["count"] == wall["count"], key
            assert cpu["sum"] + off["sum"] == pytest.approx(wall["sum"], rel=0.01), key
