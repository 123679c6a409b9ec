"""The gas-limit cell (PR 44): its configuration, traffic and metric files
load through run.py's `Cell` and each entry equals its file; the three new
metrics read their families on a made-up scrape and nothing (or no launch)
from a program without them; the driver's third admission check refuses a
program whose verdict ladder ends under one block and a warm-up that
launched outside a ladder; and a whole `--rehearse` run of the cell comes
out correct."""

import argparse
import json
from pathlib import Path

import pytest

import run
from harness import readers, scrape

BENCH = Path(__file__).resolve().parents[1]
CELL = "serve-mpt-gaslimit-1chip.transfers1428"
NEW = ("verdict_oversize_per_wave", "sig_launches_per_wave", "request_body_kb")

BEFORE = """
phant_critpath_requests_total 10
phant_sched_batches_total{lane="witness"} 10
phant_sched_batches_total{lane="sig"} 10
phant_sched_batches_total{lane="root"} 10
phant_lanes_launches_total{program="ecrecover",rung="256"} 60
phant_lanes_launches_total{program="verdict",rung="16384x1"} 11
phant_engine_api_request_body_bytes_total 58000000
"""
AFTER = """
phant_critpath_requests_total 14
phant_sched_batches_total{lane="witness"} 14
phant_sched_batches_total{lane="sig"} 14
phant_sched_batches_total{lane="root"} 14
phant_lanes_launches_total{program="ecrecover",rung="256"} 84
phant_lanes_launches_total{program="verdict",rung="16384x1"} 15
phant_engine_api_request_body_bytes_total 81200000
"""
#: the parent (e003592): no body counter, and every request launched outside the ladder
OLD_BEFORE = """
phant_critpath_requests_total 10
phant_sched_batches_total{lane="witness"} 10
phant_sched_batches_total{lane="sig"} 10
phant_lanes_launches_total{program="ecrecover",rung="256"} 60
phant_lanes_oversize_launches_total{program="verdict"} 10
"""
OLD_AFTER = """
phant_critpath_requests_total 14
phant_sched_batches_total{lane="witness"} 14
phant_sched_batches_total{lane="sig"} 14
phant_lanes_launches_total{program="ecrecover",rung="256"} 84
phant_lanes_oversize_launches_total{program="verdict"} 14
"""
BOOT = """
phant_root_plan_shapes 8
phant_lanes_launches_total{program="verdict",rung="2048x1"} 1
phant_lanes_launches_total{program="verdict",rung="4096x2"} 1
phant_lanes_launches_total{program="verdict",rung="8192x4"} 1
"""
BOOT_WITH_THE_RUNG = BOOT + 'phant_lanes_launches_total{program="verdict",rung="16384x1"} 1\n'


def _obs(before: str, after: str) -> dict:
    return {
        "latency_s": [0.7] * 4, "completed": 4, "window_s": 3.0, "setup_s": 1.0,
        "scrape0": scrape.parse(before), "scrape1": scrape.parse(after),
        "compiles": 0, "gc_pauses": [], "trace": None, "rehearsal": False,
    }  # fmt: skip


def _cell(rehearse: bool = True) -> run.Cell:
    args = argparse.Namespace(workload=CELL, seed=1, rehearse=rehearse, trace=1)
    return run.Cell(args, run.load_json(run.ROOT / "BENCHMARK.json"))


def test_cell_loads_its_files():
    cell = _cell(rehearse=False)
    assert cell.config["name"] == "serve-mpt-gaslimit-1chip" and cell.chips == 1
    assert cell.config["driver"] == "serve_gaslimit" and "env" not in cell.config
    assert list(cell.config["reduced"]) == ["genesis_accounts"]
    assert cell.config["gas_used_per_block"] == 1428 * 21_000 == 29_988_000
    bare = run.load_json(BENCH / "configs" / "serve-mpt-rootlane-1chip.json")
    for key in ("argv", "genesis_accounts", "sender_pool", "contracts"):
        assert cell.config[key] == bare[key], key
    assert cell.config["guarantees"][:3] == bare["guarantees"]
    assert "lanes.oversize_launches" in cell.config["guarantees"][3]
    t = cell.traffic
    assert t["name"] == "transfers1428" and t["mode"] == "closed_loop" and t["clients"] == 1
    assert t["chain"] == {"transfers_per_block": 1428, "calls_per_block": 0, "zipf_s": 0, "cold_recipient_share": 1.0}
    lone = run.load_json(BENCH / "traffic" / "lone.json")
    assert t["warmup"] == lone["warmup"] and t["tampered_probes"] == lone["tampered_probes"] == 3
    assert [m["name"] for m in cell.metrics("end_to_end", "end_to_end")] == [
        "verify_p50_ms", "verify_p95_ms", "blocks_per_s", "setup_s"
    ]  # fmt: skip


def test_each_entry_equals_its_file():
    cell = _cell(rehearse=False)
    entry = next(c for c in cell.bench["configs"] if c["name"] == "serve-mpt-gaslimit-1chip")
    assert entry["file"] == "benchmarks/configs/serve-mpt-gaslimit-1chip.json"
    assert entry["reduced"] == list(cell.config["reduced"]) == ["genesis_accounts"]
    assert cell.config["source"].startswith(entry["source"])
    assert cell.entry == {"name": CELL, "config": entry["name"], "traffic": "transfers1428", "chips": 1, "why": cell.entry["why"]}
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    cells = [w["name"] for w in cell.bench["workloads"]]
    for name in NEW:
        spec = run.load_json(BENCH / "layer_metrics" / f"{name}.json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[name][key] == spec[key], (name, key)
        assert by_name[name]["workloads"] == [CELL]  # no accepted cell's line gains a metric
    layer = {m["name"] for m in cell.metrics("per_layer", "layer_metrics")}
    assert set(NEW) <= layer
    # what the bare command's lone cell reads of spans and counters, and no
    # reading of the traced stretch: it ends at one launch's head
    assert not {m["name"] for m in cell.metrics("per_layer", "layer_metrics") if m["source"] == "device_trace"}
    bare_lone = {
        m["name"] for m in cell.bench["per_layer"]
        if "serve-mpt-rootlane-1chip.lone" in m["workloads"] and m["source"] != "device_trace"
    }  # fmt: skip
    assert bare_lone <= layer
    for name in layer:
        assert by_name[name]["workloads"] == sorted(by_name[name]["workloads"], key=cells.index), name
    ends = {m["name"] for m in cell.bench["end_to_end"]}
    assert {by_name[name]["moves"] for name in layer} <= ends


@pytest.mark.parametrize(
    "name,want",
    [("verdict_oversize_per_wave", 0.0), ("sig_launches_per_wave", 6.0), ("request_body_kb", 5664.0625)],
)  # fmt: skip
def test_new_metric_file_reads_its_family(name, want):
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert readers.read(spec, _obs(BEFORE, AFTER)) == pytest.approx(want)


@pytest.mark.parametrize(
    "name,want", [("verdict_oversize_per_wave", 1.0), ("sig_launches_per_wave", 6.0), ("request_body_kb", None)]
)
def test_new_metric_files_on_the_parents_scrape(name, want):
    """The driver lays these files over the parent's checkout: the counter
    it lacks is left out of the line, the launches it made outside its
    ladder are counted, and nothing raises."""
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert readers.read(spec, _obs(OLD_BEFORE, OLD_AFTER)) == want


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_reads_nothing_from_an_empty_scrape(name):
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert readers.read(spec, _obs("", "")) is None


class _Block:
    def __init__(self, nodes: int):
        self.witness = [b""] * nodes


def _driver(monkeypatch, boot: str, oversize_after_warm_up: int, rehearse: bool = False):
    from drivers import serve_gaslimit, serve_steady

    d = serve_gaslimit.Driver(_cell(rehearse))
    d.scrape_boot = scrape.parse(boot)
    d.blocks = [_Block(10050)]
    after = boot + f'phant_lanes_oversize_launches_total{{program="verdict"}} {oversize_after_warm_up}\n'
    monkeypatch.setattr(serve_gaslimit.Driver, "_await", lambda self, upto: None)
    monkeypatch.setattr(serve_gaslimit.Driver, "scrape", lambda self: scrape.parse(after))
    monkeypatch.setattr(serve_steady.Driver, "_warm_up", lambda self: None)
    return d


def test_the_driver_stops_a_program_whose_ladder_ends_under_one_block(monkeypatch):
    with pytest.raises(SystemExit, match="no verdict rung built at server start holds one block of 10050"):
        _driver(monkeypatch, BOOT, 0)._warm_up()


def test_the_driver_stops_a_warm_up_that_launched_outside_a_ladder(monkeypatch):
    with pytest.raises(SystemExit, match="oversize_launches grew by 7 .* not steady, not measured"):
        _driver(monkeypatch, BOOT_WITH_THE_RUNG, 7)._warm_up()
    # on the CPU nothing is built at server start: the rehearsal is held to the second step alone
    with pytest.raises(SystemExit, match="not steady"):
        _driver(monkeypatch, "phant_root_plan_shapes 0\n", 7, rehearse=True)._warm_up()


def test_the_driver_admits_a_program_that_built_the_rung_and_stayed_on_it(monkeypatch):
    _driver(monkeypatch, BOOT_WITH_THE_RUNG, 0)._warm_up()
    _driver(monkeypatch, "phant_root_plan_shapes 0\n", 0, rehearse=True)._warm_up()


def test_rehearsal_of_the_cell_is_correct(capsys, monkeypatch):
    """The whole path on the CPU at a tiny genesis and 300 transfers a block
    (the rehearsal's own chain), steered onto the cpu crypto backend as the
    neighbours' are (12 s an `ecrecover` launch otherwise)."""
    load = run.load_json

    def steered(path):
        out = load(path)
        if path.name == "serve-mpt-gaslimit-1chip.json":
            out["argv"] = ["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0"]
        return out

    monkeypatch.setattr(run, "load_json", steered)
    argv = ["--workload", CELL, "--seed", "44", "--seconds", "6", "--trace", "1", "--rehearse"]
    assert run.main(argv) == 0
    r = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0 and r["workload"] == CELL
    assert r["compared"]["oversize_launches"] == {"value": 0.0, "limit": 0, "is": "at_most"}
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert r["metrics"]["verdict_oversize_per_wave"]["value"] == 0
    assert r["metrics"]["request_body_kb"]["value"] > 400  # 300 transfers and their witness
    assert "busy_s" not in r["device"]  # no device reading on the CPU
