"""The per-layer metrics that read what the program times and counts itself
(PR 26): the new reader kinds on made-up scrapes, and every new metric file
against a scrape with and without its family."""

import json
from pathlib import Path

import pytest

from harness import readers, scrape

LAYER = Path(__file__).resolve().parents[1] / "layer_metrics"
NEW = ("frontend_ms", "device_enqueue_ms", "device_sync_ms", "program_compiles", "gc_full_ms")

BEFORE = """
phant_jit_compiles_total{thread="serving"} 13
phant_jit_compiles_total{thread="other"} 4
phant_critpath_requests_total 10
phant_engine_api_phase_seconds_sum{phase="read"} 0.010
phant_engine_api_phase_seconds_sum{phase="json"} 0.020
phant_engine_api_phase_seconds_sum{phase="decode"} 0.030
phant_engine_api_phase_seconds_sum{phase="reply"} 0.001
phant_device_host_seconds_sum{lane="witness",op="enqueue"} 0.10
phant_device_host_seconds_sum{lane="sig",op="enqueue"} 0.02
phant_device_host_seconds_sum{lane="witness",op="sync"} 0.30
phant_device_host_seconds_sum{lane="sig",op="sync"} 0.05
phant_runtime_gc_pause_seconds_sum{generation="0"} 0.004
phant_runtime_gc_pause_seconds_sum{generation="2"} 1.0
"""
AFTER = """
phant_jit_compiles_total{thread="serving"} 15
phant_jit_compiles_total{thread="other"} 5
phant_critpath_requests_total 14
phant_engine_api_phase_seconds_sum{phase="read"} 0.014
phant_engine_api_phase_seconds_sum{phase="json"} 0.032
phant_engine_api_phase_seconds_sum{phase="decode"} 0.050
phant_engine_api_phase_seconds_sum{phase="reply"} 0.005
phant_device_host_seconds_sum{lane="witness",op="enqueue"} 0.14
phant_device_host_seconds_sum{lane="sig",op="enqueue"} 0.04
phant_device_host_seconds_sum{lane="witness",op="sync"} 0.34
phant_device_host_seconds_sum{lane="sig",op="sync"} 0.09
phant_runtime_gc_pause_seconds_sum{generation="0"} 0.012
phant_runtime_gc_pause_seconds_sum{generation="2"} 1.6
"""
#: a program from before PR 26: the requests are counted, nothing else is there
OLD = "phant_critpath_requests_total 14\nphant_critpath_phase_seconds_sum{phase=\"evm\"} 2.0\n"


def _obs(before: str, after: str) -> dict:
    return {
        "latency_s": [0.2] * 4, "completed": 4, "window_s": 1.0, "setup_s": 1.0,
        "scrape0": scrape.parse(before), "scrape1": scrape.parse(after),
        "compiles": 3, "gc_pauses": [], "trace": None, "rehearsal": False,
    }


@pytest.mark.parametrize(
    "where,want",
    [(None, 3.0), ({"thread": "serving"}, 2.0), ({"thread": "other"}, 1.0),
     ({"thread": ["serving", "other"]}, 3.0), ({"thread": "absent"}, 0.0)],
)
def test_counter_growth(where, want):
    read = {"kind": "counter_growth", "family": "phant_jit_compiles_total"}
    if where is not None:
        read["where"] = where
    assert readers.read({"read": read}, _obs(BEFORE, AFTER)) == pytest.approx(want)


def test_counter_growth_of_a_family_the_program_lacks_is_nothing():
    read = {"kind": "counter_growth", "family": "phant_jit_compiles_total"}
    assert readers.read({"read": read}, _obs(OLD, OLD)) is None


def test_hist_sum_per_exported_is_hist_sum_per_where_the_family_is():
    params = {
        "family": "phant_device_host_seconds", "where": {"op": "sync"},
        "per": {"family": "phant_critpath_requests_total"}, "scale": 1000,
    }
    obs = _obs(BEFORE, AFTER)
    got = readers.read({"read": {"kind": "hist_sum_per_exported", **params}}, obs)
    assert got == readers.read({"read": {"kind": "hist_sum_per", **params}}, obs)
    assert got == pytest.approx(20.0)


@pytest.mark.parametrize(
    "name,want",
    [("frontend_ms", 10.0), ("device_enqueue_ms", 15.0), ("device_sync_ms", 20.0),
     ("program_compiles", 3.0), ("gc_full_ms", 150.0)],
)
def test_new_metric_file_reads_its_family(name, want):
    spec = json.loads((LAYER / f"{name}.json").read_text())
    assert spec["name"] == name
    assert readers.read(spec, _obs(BEFORE, AFTER)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_reads_nothing_from_the_parent(name):
    """The driver lays these files over the parent's checkout: a program
    without the family must leave the metric out, and not report 0."""
    spec = json.loads((LAYER / f"{name}.json").read_text())
    assert readers.read(spec, _obs(OLD, OLD)) is None


def test_benchmark_json_lists_the_new_metrics_as_their_files_say():
    bench = json.loads((LAYER.parents[1] / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in NEW:
        spec = json.loads((LAYER / f"{name}.json").read_text())
        entry = by_name[name]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == spec[key], (name, key)
        # the cell they were added for is on the list, the list in the cells' order;
        # which later cells joined it is for those cells' own tests to say
        assert "serve-mpt-1chip.lone" in entry["workloads"]
        assert entry["workloads"] == sorted(entry["workloads"], key=cells.index)
