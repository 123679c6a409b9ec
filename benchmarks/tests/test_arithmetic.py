"""The scrape parser, the percentile and the reader kinds on fixed inputs."""

import pytest

from harness import readers, scrape
from harness.stats import percentile

TEXT = """
# HELP phant_x_total things
# TYPE phant_x_total counter
phant_x_total 7
phant_h_sum{phase="evm"} 1.5
phant_h_sum{phase="pack"} 0.25
phant_h_sum{le="+Inf",phase="odd \\"quoted\\""} 9
phant_h_count{phase="evm"} 4
phant_hits_total 30
phant_misses_total 10
phant_req_total 5
"""


def test_parse_and_total():
    m = scrape.parse(TEXT)
    assert m[("phant_x_total", frozenset())] == 7
    assert scrape.total(m, "phant_h_sum", {"phase": "evm"}) == 1.5
    assert scrape.total(m, "phant_h_sum", {"phase": ["evm", "pack"]}) == 1.75
    assert scrape.total(m, "phant_h_sum") == 10.75
    assert scrape.total(m, "phant_absent") == 0
    with pytest.raises(ValueError):
        scrape.parse("this is not a metric line at all")


@pytest.mark.parametrize(
    "values,q,want",
    [([1, 2, 3, 4, 5], 50, 3), ([1, 2, 3, 4], 50, 2.5), (list(range(1, 11)), 90, 9.1),
     ([5], 90, 5), ([3, 1, 2], 0, 1), ([3, 1, 2], 100, 3)],
)
def test_percentile(values, q, want):
    assert percentile(values, q) == pytest.approx(want)


def _obs():
    zero = {k: 0.0 for k in scrape.parse(TEXT)}
    return {
        "latency_s": [0.1, 0.2, 0.3, 0.4], "completed": 4, "window_s": 2.0, "setup_s": 12.5,
        "scrape0": zero, "scrape1": scrape.parse(TEXT), "compiles": 0, "trace": None,
        "gc_pauses": [(1.0, 0.4, 2), (1.5, 0.002, 0)],
        "rehearsal": False,
    }


PER = {"family": "phant_req_total"}


@pytest.mark.parametrize(
    "read,want",
    [
        ({"kind": "client_percentile", "q": 50, "scale": 1000}, 250.0),
        ({"kind": "completed_rate"}, 2.0),
        ({"kind": "setup_seconds"}, 12.5),
        ({"kind": "hist_sum_per", "family": "phant_h", "where": {"phase": ["evm", "pack"]}, "per": PER, "scale": 1000}, 350.0),
        ({"kind": "hist_mean", "family": "phant_h", "where": {"phase": "evm"}}, 0.375),
        ({"kind": "counter_share", "part": {"family": "phant_hits_total"},
          "whole": [{"family": "phant_hits_total"}, {"family": "phant_misses_total"}]}, 75.0),
        ({"kind": "client_minus_hist", "family": "phant_h", "where": {"phase": "evm"}, "per": PER, "scale": 1000}, -50.0),
        ({"kind": "compile_count"}, 0.0),
        ({"kind": "gc_pause_per_request", "scale": 1000}, 100.5),
        # nothing to read: no value, never a 0
        ({"kind": "trace_idle_share"}, None),
        ({"kind": "trace_device_time_per_request"}, None),
        ({"kind": "trace_device_time_by_prefix", "prefixes": ["jit_a"]}, None),
        ({"kind": "gauge_last", "family": "phant_x_total"}, 7.0),
        ({"kind": "gauge_last", "family": "phant_absent"}, None),
        ({"kind": "hist_mean", "family": "phant_absent"}, None),
    ],
)
def test_reader_kinds(read, want):
    got = readers.read({"read": read}, _obs())
    assert got == (pytest.approx(want) if want is not None else None)


TRACE_READS = [
    ({"kind": "trace_idle_share"}, 75.0),
    ({"kind": "trace_device_time_per_request", "scale": 1000}, 250.0),
    ({"kind": "trace_device_time_by_prefix", "prefixes": ["jit_a", "jit_b"], "scale": 1000}, 225.0),
]


def _traced_obs(**over):
    obs = _obs()
    obs["trace"] = {
        "busy_s": 1.0, "window_s": 4.0, "requests": 2.0, "pace": 0.9, "min_pace": 0.7,
        "device_s_by_program": {"a": 0.5},
        "device_s_by_module": {"jit_a_impl": 0.3, "jit_b": 0.15, "jit_c": 0.05},
    }
    obs["trace"].update(over)
    return obs


@pytest.mark.parametrize("read,want", TRACE_READS)
def test_trace_readers(read, want):
    assert readers.read({"read": read}, _traced_obs()) == pytest.approx(want)
    # the pace could not be taken (nothing answered outside the stretch): still read
    assert readers.read({"read": read}, _traced_obs(pace=None)) == pytest.approx(want)


@pytest.mark.parametrize("read", [r for r, _w in TRACE_READS])
@pytest.mark.parametrize("why", ["rehearsal", "held_back", "none_answered"])
def test_trace_readers_withheld(read, why):
    obs = _traced_obs(**{"held_back": {"pace": 0.5}, "none_answered": {"requests": 0.0}}.get(why, {}))
    obs["rehearsal"] = why == "rehearsal"
    assert readers.read({"read": read}, obs) is None


def test_prefix_that_matches_no_program_reads_as_nothing():
    read = {"kind": "trace_device_time_by_prefix", "prefixes": ["jit_zz"]}
    assert readers.read({"read": read}, _traced_obs()) is None
