"""The default deployment's cell (PR 28): its configuration, cell and
metric files load through run.py's `Cell`; each new metric reads its family
on a made-up scrape and nothing from a program without it; the admission
checks of drivers/serve_steady.py refuse what cannot be steady; and a whole
`--rehearse` run of the cell comes out correct."""

import argparse
import json
from pathlib import Path

import pytest

import run
from harness import readers, scrape

BENCH = Path(__file__).resolve().parents[1]
CELL = "serve-mpt-rootlane-1chip.lone"
NEW = ("root_plan_ms_per_block", "root_device_pct", "root_pad_pct", "root_plan_shapes",
       "root_plan_host_ms")  # fmt: skip

BEFORE = """
phant_critpath_requests_total 10
phant_critpath_phase_seconds_sum{phase="root_plan"} 0.20
phant_critpath_phase_seconds_sum{phase="post_root"} 0.10
phant_witness_engine_root_batches_total{backend="device"} 10
phant_root_plan_rows_total{kind="real"} 16000
phant_root_plan_rows_total{kind="pad"} 9600
phant_root_plan_shapes 8
"""
AFTER = """
phant_critpath_requests_total 14
phant_critpath_phase_seconds_sum{phase="root_plan"} 0.28
phant_critpath_phase_seconds_sum{phase="post_root"} 0.18
phant_witness_engine_root_batches_total{backend="device"} 13
phant_witness_engine_root_batches_total{backend="host"} 1
phant_root_plan_rows_total{kind="real"} 22000
phant_root_plan_rows_total{kind="pad"} 11600
phant_root_plan_shapes 8
"""
#: the parent: requests and phases are counted, the root lane's families are not there
OLD = "phant_critpath_requests_total 14\nphant_critpath_phase_seconds_sum{phase=\"evm\"} 2.0\n"
TRACE = {"device_s_by_module": {"jit__hash_plan_outputs": 0.012, "jit_ecrecover_kernel": 0.05},
         "busy_s": 0.1, "requests": 2.0, "window_s": 0.43, "pace": 1.0, "min_pace": 0.7}  # fmt: skip


def _obs(before: str, after: str, trace=None) -> dict:
    return {
        "latency_s": [0.2] * 4, "completed": 4, "window_s": 1.0, "setup_s": 1.0,
        "scrape0": scrape.parse(before), "scrape1": scrape.parse(after),
        "compiles": 0, "gc_pauses": [], "trace": trace, "rehearsal": False,
    }  # fmt: skip


def _cell(rehearse: bool = True) -> run.Cell:
    args = argparse.Namespace(workload=CELL, seed=1, rehearse=rehearse, trace=1)
    return run.Cell(args, run.load_json(run.ROOT / "BENCHMARK.json"))


def test_cell_loads_its_files():
    cell = _cell(rehearse=False)
    assert cell.config["name"] == "serve-mpt-rootlane-1chip" and cell.chips == 1
    assert "env" not in cell.config and "env" not in cell.config["reduced"]
    assert sorted(cell.config["reduced"]) == ["gas_used_per_block", "genesis_accounts"]
    assert cell.traffic["name"] == "lone"
    accepted = run.load_json(BENCH / "configs" / "serve-mpt-1chip.json")
    for key in ("argv", "genesis_accounts", "sender_pool", "contracts", "assumed"):
        assert cell.config[key] == accepted[key], key
    layer = {m["name"] for m in cell.metrics("per_layer", "layer_metrics")}
    assert set(NEW) <= layer
    # the accepted lone cell's metrics and the root lane's five: the two cells
    # differ in who computes the post-state root and in nothing else
    accepted_cell = {m["name"] for m in cell.bench["per_layer"] if "serve-mpt-1chip.lone" in m["workloads"]}
    assert layer == accepted_cell | set(NEW) and not accepted_cell & set(NEW)
    assert [m["name"] for m in cell.metrics("end_to_end", "end_to_end")] == [
        "verify_p50_ms", "verify_p95_ms", "blocks_per_s", "setup_s"
    ]  # fmt: skip
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    cells = [w["name"] for w in cell.bench["workloads"]]
    for name in NEW:
        spec = run.load_json(BENCH / "layer_metrics" / f"{name}.json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[name][key] == spec[key], (name, key)
        # its own cell is on the list, and the list is in the cells' order;
        # which later cells joined it is for those cells' own tests to say
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["workloads"] == sorted(by_name[name]["workloads"], key=cells.index)


def test_accepted_cell_reads_none_of_the_new_metrics():
    args = argparse.Namespace(workload="serve-mpt-1chip.lone", seed=1, rehearse=True, trace=1)
    cell = run.Cell(args, run.load_json(run.ROOT / "BENCHMARK.json"))
    assert not set(NEW) & {m["name"] for m in cell.metrics("per_layer", "layer_metrics")}


@pytest.mark.parametrize(
    "name,want",
    [("root_plan_ms_per_block", 6.0), ("root_device_pct", 75.0), ("root_pad_pct", 25.0),
     ("root_plan_shapes", 8.0), ("root_plan_host_ms", 20.0)],
)  # fmt: skip
def test_new_metric_file_reads_its_family(name, want):
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert readers.read(spec, _obs(BEFORE, AFTER, TRACE)) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n in NEW if n != "root_plan_host_ms"])
def test_new_metric_file_reads_nothing_from_the_parent(name):
    """The driver lays these files over the parent's checkout: where the
    program has no such family, or ran no such program, the metric is left
    out and not reported as 0. (`root_plan_host_ms` reads a phase the parent
    has too: 0 there with the lane off.)"""
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    parents_trace = {**TRACE, "device_s_by_module": {"jit_ecrecover_kernel": 0.05}}
    got = readers.read(spec, _obs(OLD, OLD, parents_trace))
    assert got is None or (name == "root_device_pct" and got == 0.0)


class _FakeCompiles:
    def __init__(self):
        self.n = 0

    def count(self):
        return self.n


def _steady_driver(monkeypatch, boot: str, built_by_pass: list):
    from drivers import serve, serve_steady

    cell = _cell()
    cell.compiles = _FakeCompiles()
    d = serve_steady.Driver(cell)
    passes = iter(built_by_pass)

    def start_program(self):
        self.scrape_boot = scrape.parse(boot)

    def run_clients(self, plans, seconds):
        cell.compiles.n += next(passes, 0)
        return 0.0, 0.0, False, []

    def warm_up(self):
        for _ in built_by_pass:
            self._run([], None)
        self._run([], None)  # the probes

    monkeypatch.setattr(serve.Driver, "start_program", start_program)
    monkeypatch.setattr(serve.Driver, "_run", run_clients)
    monkeypatch.setattr(serve.Driver, "_warm_up", warm_up)
    return d


def test_steady_driver_refuses_a_program_without_the_ladder(monkeypatch):
    d = _steady_driver(monkeypatch, OLD, [])
    with pytest.raises(SystemExit, match="fault 0a"):
        d.start_program()


def test_steady_driver_refuses_a_warm_up_that_still_builds(monkeypatch):
    d = _steady_driver(monkeypatch, AFTER, [5, 2, 1, 1])
    d.start_program()
    with pytest.raises(SystemExit, match="not steady"):
        d._warm_up()


def test_steady_driver_admits_a_quiet_last_pass(monkeypatch):
    d = _steady_driver(monkeypatch, AFTER, [5, 0])
    d.start_program()
    d._warm_up()
    assert d.built_by_run == [5, 0, 0]


def test_rehearsal_of_the_cell_is_correct(capsys, monkeypatch):
    """The whole path on the CPU at a tiny genesis, steered onto the cpu
    crypto backend as test_controls.py does (12 s a request otherwise)."""
    load = run.load_json

    def steered(path):
        out = load(path)
        if path.name == "serve-mpt-rootlane-1chip.json":
            out["argv"] = ["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0"]
        return out

    monkeypatch.setattr(run, "load_json", steered)
    argv = ["--workload", CELL, "--seed", "78", "--seconds", "12", "--trace", "1", "--rehearse"]
    assert run.main(argv) == 0
    r = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0 and r["workload"] == CELL
    assert r["metrics"]["root_plan_shapes"]["value"] == 0  # exported from server start
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert "root_plan_ms_per_block" not in r["metrics"]  # no device reading on the CPU
