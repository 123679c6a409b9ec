"""Several operators on one verifier (PR 36): the benchmark's
`serve-mpt-tenants-1chip` deployment at test size. Two tenants of two
clients each post different ranges of one reference chain in step, through
the real server and scheduler (the cpu crypto backend: what is pinned here is
tenancy and what the scheduler says of it, not a device program), and the
two families the deployment's cell reads, `sched.tenant_wait_seconds` and
`sched.batch_blocks`, say what they are documented to say. What
`tests/test_qos.py` pins of the head pick itself is not repeated.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

from phant_tpu.serving.qos import OVERFLOW_TENANT
from phant_tpu.serving.scheduler import SchedulerConfig, VerificationScheduler, _first_stage_s
from phant_tpu.utils.trace import METRIC_HELP, _labels_key, metrics

from _witnesses import build_witnesses

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
TENANTS = ["op0", "op1"]
WAIT = "sched.tenant_wait_seconds"


def _counter(name: str, **labels) -> int:
    return metrics.snapshot()["counters"].get(_labels_key(name, labels), 0)


def _hist(name: str, **labels) -> tuple:
    """(count, sum) of one histogram series."""
    h = metrics.snapshot()["histograms"].get(_labels_key(name, labels))
    return (h["count"], h["sum"]) if h else (0, 0.0)


def _wait_tenants() -> set:
    return {k for k in metrics.snapshot()["histograms"] if k.startswith(WAIT + "{")}


# -- the deployment at test size ------------------------------------------------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two tenants x two clients in step over two ranges of a seeded
    reference chain, then one wave of a block beside its own copy with a
    witness byte flipped; what the program counted meanwhile."""
    sys.path.insert(0, str(BENCH))
    try:
        from harness.clients import in_step_tenants
        from reference import keccak
        from reference.chain import Chain, hx

        from phant_tpu.__main__ import build_parser, build_server
        from phant_tpu.crypto import kzg

        public = kzg.public_network()  # a mainnet server names it for the whole process
        keccak.load(tmp_path_factory.mktemp("keccak"))
        chain = Chain(
            3600000036,
            dict(genesis_log2=10, sender_pool=300, contracts=4, zipf_s=1.0, transfers_per_block=4,
                 calls_per_block=2, cold_recipient_share=0.5, slots_per_contract=64),
        )  # fmt: skip
        chain.extend(4)
        bodies = {i: b.body(i + 1) for i, b in enumerate(chain.blocks)}
        bodies[-1] = chain.blocks[3].body_altered("witness", 9)
        traffic = {"think_ms": 0, "tenants": TENANTS}
        before = {
            "served": {t: _counter("sched.tenant_served", tenant=t) for t in TENANTS},
            "wait": {t: _hist(WAIT, tenant=t) for t in TENANTS},
            "blocks": _hist("sched.batch_blocks"),
        }
        # a long assembly wait: clients released together share a wave
        argv = ["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0",
                "--sched-max-wait-ms", "150"]  # fmt: skip
        server = build_server(build_parser().parse_args(argv))
        server.serve_in_background()
        try:
            plans = [[0, 1], [0, 1], [2, 3], [2, 3]]
            *_, records = in_step_tenants.run("127.0.0.1", server.port, bodies, plans, None, traffic)
            middle = _hist("sched.batch_blocks"), _counter("sched.coalesced_requests")
            *_, pair = in_step_tenants.run("127.0.0.1", server.port, bodies, [[3], [-1]], None, traffic)
        finally:
            server.shutdown()
            kzg.set_public_network(public)
        want = {i: hx(b.header.state_root) for i, b in enumerate(chain.blocks)}
        yield {"records": records, "pair": pair, "want": want, "before": before, "middle": middle}
    finally:
        sys.path.remove(str(BENCH))


def _result(reply: bytes) -> dict:
    return json.loads(reply)["result"]


def test_every_answer_of_both_tenants_carries_the_references_root(served):
    records = served["records"]
    plans = [[0, 1]] * 2 + [[2, 3]] * 2
    assert sorted((r[0], r[1]) for r in records) == [(c, i) for c, p in enumerate(plans) for i in p]
    for _who, idx, _t0, _t1, code, reply in records:
        result = _result(reply)
        assert code == 200 and result["status"] == "VALID", (idx, reply[:300])
        assert result["stateRoot"] == served["want"][idx], idx


def test_a_flipped_witness_byte_is_refused_beside_its_sound_sibling(served):
    """Block 3 and its altered copy, posted at once by the two tenants: one
    wave (both were coalesced), one VALID, one refused for the witness."""
    by_body = {idx: (code, _result(reply)) for _who, idx, _t0, _t1, code, reply in served["pair"]}
    assert by_body[3][0] == by_body[-1][0] == 200
    assert by_body[3][1]["status"] == "VALID" and by_body[3][1]["stateRoot"] == served["want"][3]
    assert by_body[-1][1]["status"] == "INVALID"
    assert "witness" in by_body[-1][1]["validationError"].lower()
    assert _counter("sched.coalesced_requests") - served["middle"][1] == 2
    # one pre-state root, two witnesses: a wave of copies
    count, total = _hist("sched.batch_blocks")
    assert (count - served["middle"][0][0], total - served["middle"][0][1]) == (1, 1.0)


@pytest.mark.parametrize("tenant", TENANTS)
def test_each_tenant_is_served_and_its_wait_observed(served, tenant):
    """4 answers a tenant: `sched.tenant_served` grew by them, and
    `sched.tenant_wait_seconds` took one observation a witness job, each
    under the assembly wait's order of size."""
    before = served["before"]
    assert _counter("sched.tenant_served", tenant=tenant) - before["served"][tenant] == 5
    count, total = _hist(WAIT, tenant=tenant)
    count, total = count - before["wait"][tenant][0], total - before["wait"][tenant][1]
    assert count == 5  # two blocks x two clients, and the pair's one
    assert 0.0 <= total / count < 5.0


def test_the_servers_waves_hold_different_blocks(served):
    """Two tenants released together post two different blocks: every
    witness batch is observed once, and some batch held both."""
    before, (middle, _coalesced) = served["before"], served["middle"]
    count, total = middle[0] - before["blocks"][0], middle[1] - before["blocks"][1]
    assert count >= 2 and count <= 8
    assert total > count  # at least one wave of more than one block
    assert total <= 2 * count  # and never more than the two in flight


# -- the two families, at the scheduler ------------------------------------------


class _Engine:
    """A witness engine that says yes."""

    def verify_batch(self, witnesses):
        return [True] * len(witnesses)


@pytest.mark.parametrize(
    "roots,want",
    [("ab", 2), ("aa", 1), ("aba", 2), ("abcd", 4), ("a", 1)],
    ids=["two-blocks", "two-copies", "a-copy-among-two", "four-blocks", "alone"],
)
def test_batch_blocks_counts_the_distinct_blocks_of_a_wave(roots, want):
    """The jobs of one batch (the batch is full when all are in): counted
    by their distinct pre-state roots, as the witness engine's
    `phant/witness.dispatch blocks=` counts them."""
    _root, wits = build_witnesses(n_blocks=1)
    _its_root, nodes = wits[0]
    was = _hist("sched.batch_blocks")
    config = SchedulerConfig(
        max_batch=len(roots), max_wait_ms=2000.0, adaptive_wait=False, pipeline_depth=1
    )
    with VerificationScheduler(engine=_Engine(), config=config) as s:
        futs = [s.submit_witness(r.encode() * 32, nodes) for r in roots]
        assert all(f.result(timeout=30) for f in futs)
    count, total = _hist("sched.batch_blocks")
    assert (count - was[0], total - was[1]) == (1, float(want))


def test_tenant_wait_runs_from_admission_to_the_batchs_first_stage():
    """A job alone in its batch waits out the assembly window before the
    batch is picked: that wait, and not the engine's time, is observed."""

    class Slow(_Engine):
        def verify_batch(self, witnesses):
            time.sleep(0.3)
            return super().verify_batch(witnesses)

    _root, wits = build_witnesses(n_blocks=1)
    was = _hist(WAIT, tenant="waits")
    config = SchedulerConfig(max_batch=4, max_wait_ms=120.0, adaptive_wait=False, pipeline_depth=1)
    with VerificationScheduler(engine=Slow(), config=config) as s:
        assert s.submit_witness(*wits[0], tenant="waits").result(timeout=30)
    count, total = _hist(WAIT, tenant="waits")
    assert count - was[0] == 1
    assert 0.1 <= total - was[1] < 0.3


@pytest.mark.parametrize(
    "record,want",
    [({"stages": {"pack": [7_000_000_000, 8_000_000_000], "prefetch": [5_500_000_000, 6_000_000_000]}}, 5.5),
     ({"stages": {}}, 3.0), ({}, 3.0)],
    ids=["measured-stages", "no-stage", "no-record"],
)  # fmt: skip
def test_first_stage_is_the_earliest_measured_stage_or_the_pick(record, want):
    assert _first_stage_s(record, 3.0) == want


def test_tenant_wait_label_set_stops_growing_at_max_tenants():
    """Sprayed tenant tags fold into the overflow lane before the family is
    observed: `max_tenants` lanes and the fold, however many tags came."""
    _root, wits = build_witnesses(n_blocks=1)
    seen = _wait_tenants()
    config = SchedulerConfig(max_batch=4, max_wait_ms=1.0, max_tenants=3)
    with VerificationScheduler(engine=_Engine(), config=config) as s:
        futs = [s.submit_witness(*wits[0], tenant=f"sprayed-{i}") for i in range(12)]
        assert all(f.result(timeout=30) for f in futs)
    new = _wait_tenants() - seen
    assert len(new) == 4 and _labels_key(WAIT, {"tenant": OVERFLOW_TENANT}) in new | seen
    assert sum(_hist(WAIT, tenant=k.split('"')[1])[0] for k in new) >= 9


def test_the_two_families_are_declared():
    assert WAIT in METRIC_HELP and "sched.batch_blocks" in METRIC_HELP
