"""The trace reducer on a small trace recorded on a v5e (record_trace.py):
three executions of one program, a 0.2 s pause, one of another."""

from pathlib import Path

import pytest

from harness import trace_reduce as tr

TRACE = str(Path(__file__).with_name("data") / "small.xplane.pb")
TABLE = {"jit_witness_digests": "witness_keccak"}


def test_union_and_cover():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tr.covered([(1, 3), (2, 4), (10, 11)]) == 4
    assert tr.union_arrays([5, 1, 2, 7], [7, 3, 4, 8]) == [(1, 4), (5, 8)]
    assert tr.union_arrays([1, 2, 3], [10, 3, 4]) == [(1, 10)]
    assert tr.union_arrays([], []) == []


def test_program_table_is_a_directory(tmp_path):
    (tmp_path / "a.json").write_text('{"group": "resident", "prefixes": ["jit__update"]}')
    (tmp_path / "b.json").write_text('{"group": "update_v2", "prefixes": ["jit__update_impl_v2"]}')
    table = tr.load_table(str(tmp_path))
    assert tr.program_of("jit__update_impl(12)", table) == "resident"
    assert tr.program_of("jit__update_impl_v2(12)", table) == "update_v2"  # the longer prefix wins
    shipped = tr.load_table(str(Path(__file__).parents[1] / "programs"))
    assert tr.program_of("jit_ecrecover_kernel(77)", shipped) == "ecrecover"


def test_program_names():
    assert tr.program_of("jit_witness_digests_standin(1696)", TABLE) == "witness_keccak"
    assert tr.program_of("jit_other_program(954)", TABLE) == "jit_other_program"


def test_recorded_trace():
    planes = tr.load_planes(TRACE)
    assert list(planes) == [0]
    r = tr.reduce_planes(planes, TABLE)
    assert r["executions"] == 4
    assert r["busy_s"] == pytest.approx(0.001684603, rel=1e-9)
    assert r["device_s_by_program"] == pytest.approx(
        {"witness_keccak": 0.000292462, "jit_other_program": 0.001392141}, rel=1e-9
    )
    assert r["device_s_by_module"] == pytest.approx(
        {"jit_witness_digests_standin": 0.000292462, "jit_other_program": 0.001392141}, rel=1e-9
    )
    # busy time by program adds up to busy time: nothing ran outside a program
    assert sum(r["device_s_by_program"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    gaps = r["idle_s_by_gap"]
    assert gaps["witness_keccak->jit_other_program"] == pytest.approx(0.200978643, rel=1e-9)
    assert max(gaps, key=gaps.get) == "witness_keccak->jit_other_program"


def test_no_device_work_reads_as_nothing():
    assert tr.reduce_planes({0: {"XLA Ops": [], "XLA Modules": []}}, TABLE) is None
    assert tr.reduce_planes({}, TABLE) is None
