"""The verifier four operators share (PR 36): the tenant client mode against
a stub server; the cell's configuration, traffic and metric files through
run.py's `Cell`; the new reader kind and the new metric files on a made-up
scrape, and nothing from a program without the families; what
drivers/serve_tenants.py adds to serve_shared; and a whole `--rehearse` run."""

import argparse
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

import run
from harness import readers, scrape
from harness.clients import in_step, in_step_tenants

BENCH = Path(__file__).resolve().parents[1]
CELL = "serve-mpt-tenants-1chip.fanin16"
HEADS4 = "serve-mpt-shared-1chip.heads4"
NEW = ("tenant_wait_skew_pct", "wave_blocks", "update_launches_per_wave")
BY_LANE = ("sig_lane_sync_ms", "witness_lane_sync_ms", "root_lane_sync_ms")
#: PR 34's seven, which test_shared_cell.py pins to `heads4` alone: this cell joined their lists
SHARED = (
    "wave_size", "coalesced_pct", "sig_coalesced_pct", "sig_pad_pct", "verdict_pad_pct",
    "verdict_lone_pct", "lane_shapes",
)  # fmt: skip

BEFORE = """
phant_sched_batches_total{lane="witness"} 10
phant_sched_batches_total{lane="sig"} 30
phant_sched_batch_blocks_sum 12
phant_sched_batch_blocks_count 10
phant_lanes_launches_total{program="update",rung="2048"} 9
phant_lanes_launches_total{program="verdict",rung="2048x1"} 11
phant_sched_tenant_wait_seconds_sum{tenant="op0"} 1.0
phant_sched_tenant_wait_seconds_count{tenant="op0"} 10
phant_sched_tenant_wait_seconds_sum{tenant="op1"} 1.0
phant_sched_tenant_wait_seconds_count{tenant="op1"} 10
phant_sched_tenant_wait_seconds_sum{tenant="default"} 9.0
phant_sched_tenant_wait_seconds_count{tenant="default"} 3
phant_sched_tenant_served_total{tenant="op0"} 30
phant_sched_tenant_served_total{tenant="op1"} 30
phant_sched_tenant_served_total{tenant="op2"} 30
phant_sched_tenant_served_total{tenant="op3"} 30
phant_sched_tenant_served_total{tenant="default"} 5
phant_critpath_requests_total 100
phant_device_host_seconds_sum{lane="sig",op="sync"} 1.0
phant_device_host_seconds_sum{lane="sig",op="enqueue"} 1.0
phant_device_host_seconds_sum{lane="witness",op="sync"} 1.0
phant_device_host_seconds_sum{lane="root",op="sync"} 1.0
"""
AFTER = """
phant_sched_batches_total{lane="witness"} 30
phant_sched_batches_total{lane="sig"} 90
phant_sched_batch_blocks_sum 42
phant_sched_batch_blocks_count 30
phant_lanes_launches_total{program="update",rung="2048"} 39
phant_lanes_launches_total{program="verdict",rung="2048x1"} 40
phant_sched_tenant_wait_seconds_sum{tenant="op0"} 3.0
phant_sched_tenant_wait_seconds_count{tenant="op0"} 30
phant_sched_tenant_wait_seconds_sum{tenant="op1"} 7.0
phant_sched_tenant_wait_seconds_count{tenant="op1"} 30
phant_sched_tenant_wait_seconds_sum{tenant="default"} 9.0
phant_sched_tenant_wait_seconds_count{tenant="default"} 3
phant_sched_tenant_served_total{tenant="op0"} 130
phant_sched_tenant_served_total{tenant="op1"} 110
phant_sched_tenant_served_total{tenant="op2"} 90
phant_sched_tenant_served_total{tenant="op3"} 105
phant_sched_tenant_served_total{tenant="default"} 20
phant_critpath_requests_total 200
phant_device_host_seconds_sum{lane="sig",op="sync"} 1.5
phant_device_host_seconds_sum{lane="sig",op="enqueue"} 9.0
phant_device_host_seconds_sum{lane="witness",op="sync"} 1.25
phant_device_host_seconds_sum{lane="root",op="sync"} 1.1
"""
#: the parent: batches and launches are counted, the two new families are not there
OLD = """
phant_sched_batches_total{lane="witness"} 30
phant_lanes_launches_total{program="update",rung="2048"} 39
phant_sched_tenant_served_total{tenant="default"} 20
"""


def _obs(before: str, after: str) -> dict:
    return {
        "latency_s": [0.5] * 4, "completed": 4, "window_s": 1.0, "setup_s": 1.0,
        "scrape0": scrape.parse(before), "scrape1": scrape.parse(after),
        "compiles": 0, "gc_pauses": [], "trace": None, "rehearsal": False, "stretch": None,
    }  # fmt: skip


# -- the client mode ----------------------------------------------------------


class _Stub:
    """An HTTP server that answers every POST and keeps (body, tenant header)."""

    def __init__(self):
        seen, lock = [], threading.Lock()

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_POST(self):
                body = self.rfile.read(int(self.headers["Content-Length"]))
                with lock:
                    seen.append((body, self.headers.get("X-Phant-Tenant"), self.headers.get("Content-Type")))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"ok")

            def log_message(self, *a):
                pass

        self.seen = seen
        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever, daemon=True).start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()


@pytest.fixture
def stub():
    s = _Stub()
    yield s
    s.close()


TRAFFIC = {"think_ms": 0, "tenants": ["op0", "op1", "op2", "op3"]}


def test_every_request_carries_the_tenant_of_its_clients_group(stub):
    """Four groups of two clients over four ranges: a body is posted under
    the tenant whose index is that of its plan among the distinct plans."""
    bodies = {i: str(i).encode() for i in range(12)}
    ranges = [[0, 1, 2], [3, 4, 5], [6, 7, 8], [9, 10, 11]]
    plans = [r for r in ranges for _ in range(2)]
    *_, records = in_step_tenants.run("127.0.0.1", stub.port, bodies, plans, None, TRAFFIC)
    assert sorted((r[0], r[1]) for r in records) == sorted((c, i) for c, p in enumerate(plans) for i in p)
    assert all(r[4] == 200 and r[5] == b"ok" for r in records)
    assert len(stub.seen) == 24
    for body, tenant, content_type in stub.seen:
        assert tenant == f"op{int(body) // 3}" and content_type == "application/json"


def test_a_lone_plan_posts_as_the_first_tenant_and_in_step_sends_what_it_sent(stub):
    """The probes' one plan; and `in_step` itself is not touched by a phase
    of this mode: it sends no tenant."""
    *_, alone = in_step_tenants.run("127.0.0.1", stub.port, {0: b"0", 1: b"1"}, [[0, 1]], None, TRAFFIC)
    assert [r[1] for r in alone] == [0, 1] and {t for _b, t, _c in stub.seen} == {"op0"}
    in_step.run("127.0.0.1", stub.port, {2: b"2"}, [[2]], None, TRAFFIC)
    assert stub.seen[-1][:2] == (b"2", None)


def test_the_groups_keep_in_steps_barrier(stub):
    """Body i + 1 of a group is sent after every answer to its body i."""
    bodies = {i: str(i).encode() for i in range(8)}
    plans = [[0, 1, 2, 3]] * 3 + [[4, 5, 6, 7]] * 3
    *_, records = in_step_tenants.run("127.0.0.1", stub.port, bodies, plans, None, TRAFFIC)
    for lo in (0, 4):
        for i in range(lo, lo + 3):
            assert min(r[2] for r in records if r[1] == i + 1) >= max(r[3] for r in records if r[1] == i)


def test_the_groups_start_a_stagger_apart(stub):
    """Group g's first body leaves g x `stagger_ms` after the phase opens;
    a traffic without the key starts them together."""
    bodies = {i: str(i).encode() for i in range(6)}
    plans = [[0, 1], [0, 1], [2, 3], [2, 3], [4, 5]]
    t_open, *_, records = in_step_tenants.run(
        "127.0.0.1", stub.port, bodies, plans, None, {**TRAFFIC, "stagger_ms": 120}
    )
    first = {g: min(r[2] for r in records if r[1] // 2 == g) - t_open for g in range(3)}
    assert first[0] < 0.1 and 0.12 <= first[1] < 0.22 and 0.24 <= first[2] < 0.34
    t_open, *_, records = in_step_tenants.run("127.0.0.1", stub.port, bodies, plans, None, TRAFFIC)
    assert max(r[2] for r in records if r[1] % 2 == 0) - t_open < 0.1


def test_a_failed_connection_is_a_record_under_this_mode_too():
    import socket

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]  # bound, never listening: connections are refused
        *_, records = in_step_tenants.run("127.0.0.1", port, {0: b"x", 1: b"y"}, [[0, 1]] * 2, None, TRAFFIC)
    assert len(records) == 4 and all(r[4] == -1 for r in records)


# -- the cell's files ---------------------------------------------------------


def _cell(rehearse: bool = True) -> run.Cell:
    args = argparse.Namespace(workload=CELL, seed=1, rehearse=rehearse, trace=1)
    return run.Cell(args, run.load_json(run.ROOT / "BENCHMARK.json"))


def test_cell_loads_its_files():
    cell = _cell(rehearse=False)
    assert cell.config["name"] == "serve-mpt-tenants-1chip" and cell.chips == 1
    assert cell.config["driver"] == "serve_tenants" and "env" not in cell.config
    bare = run.load_json(BENCH / "configs" / "serve-mpt-rootlane-1chip.json")
    for key in ("argv", "genesis_accounts", "sender_pool", "contracts", "reduced", "gas_used_per_block", "rehearsal"):
        assert cell.config[key] == bare[key], key
    for key in ("source", "deployment", "assumed", "guarantees", "reference"):
        assert cell.config[key], key
    assert 0.0 < cell.config["tenants_least_share"] <= 1.0
    lone = run.load_json(BENCH / "traffic" / "lone.json")
    t = cell.traffic
    assert (t["mode"], t["clients"], t["groups"], t["think_ms"]) == ("in_step_tenants", 16, 4, 0)
    assert t["tenants"] == ["op0", "op1", "op2", "op3"] and len(t["tenants"]) == t["groups"]
    assert 0 < t["stagger_ms"] * t["groups"] <= 4000  # a round's quarter apart, a round in all
    for key in ("chain", "warmup", "tampered_probes"):
        assert t[key] == lone[key], key
    warm = t["warmup"]["max_passes"] * t["groups"] * t["warmup"]["blocks_per_group"]
    assert (t["chain_blocks"] - warm) // t["groups"] == 48
    own = ("quiet_s", "quiet_within_s", "launch_within_s", "hold_s", "switch_s", "tries")
    assert {k: v for k, v in t["trace"].items() if k not in own} == {
        k: v for k, v in lone["trace"].items() if k != "seconds"
    }
    assert 0.25 <= t["trace"]["quiet_s"]  # ten launches of ecrecover have left the device
    assert t["trace"]["hold_s"] <= 0.025  # one execution of ecrecover at the most
    assert t["trace"]["switch_s"] < 0.005  # under the interpreter's own
    # a stretch that held nothing is taken again, and every attempt the window may need has room in it
    assert 2 <= t["trace"]["tries"] <= 8
    assert t["trace"]["tries"] * (t["trace"]["quiet_within_s"] + t["trace"]["launch_within_s"] + 0.6) < cell.bench["run_seconds"]
    layer = {m["name"] for m in cell.metrics("per_layer", "layer_metrics")}
    heads4 = {m["name"] for m in cell.bench["per_layer"] if HEADS4 in m["workloads"]}
    # every metric `heads4` is on but the capture's: the stretch lies on the head of one launch
    device = {m["name"] for m in cell.bench["per_layer"] if m["source"] == "device_trace"}
    assert layer == (heads4 - device) | set(NEW) | set(BY_LANE) and not layer & device
    assert [m["name"] for m in cell.metrics("end_to_end", "end_to_end")] == [
        "verify_p50_ms", "verify_p95_ms", "blocks_per_s", "setup_s"
    ]  # fmt: skip
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    for name in NEW + BY_LANE + SHARED:
        spec = run.load_json(BENCH / "layer_metrics" / f"{name}.json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by_name[name][key] == spec[key], (name, key)
        assert by_name[name]["workloads"] == ([HEADS4, CELL] if name in SHARED else [CELL])
    for w in cell.bench["workloads"] + cell.bench["configs"]:
        assert all(len(v) <= 200 for v in w.values() if isinstance(v, str)), w["name"]


def test_the_rehearsal_has_a_window_for_every_group():
    t = _cell(rehearse=True).traffic
    warm = t["warmup"]["max_passes"] * t["groups"] * t["warmup"]["blocks_per_group"]
    assert (t["chain_blocks"] - warm) // t["groups"] >= 4


# -- the reader kind and the metric files ---------------------------------------


def test_hist_mean_skew_on_a_canned_scrape():
    """op0 waited 0.1 s a job, op1 0.3 s; `default` did not grow in the
    window and is no tenant of it: (0.3 - 0.1) / 0.2."""
    from harness.readers import hist_mean_skew

    got = hist_mean_skew.read(_obs(BEFORE, AFTER), family="phant_sched_tenant_wait_seconds", label="tenant")
    assert got == pytest.approx(100.0)
    assert hist_mean_skew.read(_obs(AFTER, AFTER), family="phant_sched_tenant_wait_seconds", label="tenant") is None
    one = "\n".join(line for line in AFTER.splitlines() if "op1" not in line)
    assert hist_mean_skew.read(_obs(BEFORE, one), family="phant_sched_tenant_wait_seconds", label="tenant") is None


@pytest.mark.parametrize(
    "name,want",
    [("tenant_wait_skew_pct", 100.0), ("wave_blocks", 1.5), ("update_launches_per_wave", 1.5),
     ("sig_lane_sync_ms", 5.0), ("witness_lane_sync_ms", 2.5), ("root_lane_sync_ms", 1.0)],
)  # fmt: skip
def test_new_metric_file_reads_its_family(name, want):
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert readers.read(spec, _obs(BEFORE, AFTER)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["tenant_wait_skew_pct", "wave_blocks", *BY_LANE])
def test_new_metric_file_reads_nothing_where_the_family_is_not(name):
    """The driver lays these files over the parent's checkout: where the
    program has no such family the metric is left out, not reported as 0.
    (`update_launches_per_wave` and the waits by lane read families the
    parent has too: there they read what the parent counted.)"""
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert readers.read(spec, _obs(OLD, OLD)) is None


# -- what the driver adds ---------------------------------------------------------


def test_driver_stops_a_program_that_does_not_declare_the_family(monkeypatch):
    from drivers import serve_tenants

    from phant_tpu.utils import trace

    monkeypatch.setattr(
        trace, "METRIC_HELP", {k: v for k, v in trace.METRIC_HELP.items() if k != "sched.tenant_wait_seconds"}
    )
    with pytest.raises(SystemExit, match="does not declare sched.tenant_wait_seconds"):
        serve_tenants.Driver(_cell()).prepare()


def _verified(monkeypatch, edges: tuple) -> dict:
    from drivers import serve, serve_tenants

    d = serve_tenants.Driver(_cell())
    d.log = lambda line: None
    d.group_of = [c // 4 for c in range(16)]
    d.records = [(c, 0, 0.0, 1.0, 200, b"") for c in range(16)]
    d.edges = tuple(map(scrape.parse, edges))
    monkeypatch.setattr(serve.Driver, "verify", lambda self: ([("window_answers", 16, 1, "at_least")], 16, 0))
    comparisons, attempted, failed = d.verify()
    assert (attempted, failed) == (16, 0) and comparisons[0][0] == "window_answers"
    return {n: (v, lim, how) for n, v, lim, how in comparisons}


def test_verify_compares_what_each_tenant_was_served(monkeypatch):
    """100, 80, 60 and 75 in the window: four served, least over most 0.6."""
    got = _verified(monkeypatch, (BEFORE, AFTER))
    assert got["tenants_served"] == (4, 4, "at_least")
    assert got["tenant_least_over_most"] == (0.6, 0.5, "at_least")
    assert run.all_within([(n, *rest) for n, rest in got.items()])


def test_a_starved_tenant_is_not_correct(monkeypatch):
    starved = AFTER.replace('served_total{tenant="op2"} 90', 'served_total{tenant="op2"} 30')
    got = _verified(monkeypatch, (BEFORE, starved))
    assert got["tenants_served"][0] == 3 and got["tenant_least_over_most"][0] == 0.0
    assert not run.all_within([(n, *rest) for n, rest in got.items()])
    thin = AFTER.replace('served_total{tenant="op2"} 90', 'served_total{tenant="op2"} 70')
    got = _verified(monkeypatch, (BEFORE, thin))
    assert got["tenants_served"][0] == 4 and got["tenant_least_over_most"][0] == 0.4
    assert not run.all_within([(n, *rest) for n, rest in got.items()])


def test_the_profiler_is_started_on_a_quiet_device_and_stopped_at_a_launch(monkeypatch):
    """A profiler that does nothing; the program counts a launch 0.1 s and
    0.2 s into the watch and one more at 0.6 s: the profiler is started when
    0.25 s have passed with none (0.45 s) and stopped `hold_s` after the
    launch at 0.6 s is read, the stretch counted from the call, the
    interpreter's switch interval short meanwhile and the usual one after;
    with no launch it is stopped `launch_within_s` after it was on. One
    attempt: what a stretch that held nothing is followed by is
    test_traced_attempts.py's; this is the placement against the program's
    own counters."""
    import sys

    import jax

    from drivers import serve_tenants
    from phant_tpu.utils.rungs import note_launch
    from phant_tpu.utils.trace import metrics

    cell = _cell()
    cell.gc = None
    cell.traffic["trace"].update(start_s=0.0, quiet_s=0.25, quiet_within_s=2.0, launch_within_s=0.5, hold_s=0.02, tries=1)
    d = serve_tenants.Driver(cell)
    d.seconds = 30.0
    lines, intervals, usual = [], [], sys.getswitchinterval()
    d.log = lines.append
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: intervals.append(sys.getswitchinterval()))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: intervals.append(sys.getswitchinterval()))

    def root_plan():  # the root lane's launches are counted by its enqueues alone
        metrics.observe_hist("device.host_seconds", 0.001, lane="root", op="enqueue")

    t0 = time.monotonic()
    for at, launch in ((0.1, root_plan), (0.2, lambda: note_launch("update", 2048)), (0.6, root_plan)):
        threading.Timer(at, launch).start()
    d._trace("unused")
    a, s0, s1, b = d.stretch
    assert 0.44 <= a - t0 <= 0.55 and a == s0 <= s1 <= b and 0.61 <= s1 - t0 <= 0.72
    assert intervals == pytest.approx([cell.traffic["trace"]["switch_s"]] * 2) and sys.getswitchinterval() == usual
    assert "quiet for 0.25 s after" in lines[-2] and "a launch was read after" in lines[-2]
    assert 'device.host_seconds{lane="root",op="enqueue"} +1, begun' in lines[-2]
    t0 = time.monotonic()
    d._trace("unused")
    a, s0, s1, b = d.stretch
    assert 0.25 <= a - t0 <= 0.35 and 0.5 <= s1 - a <= 0.6
    assert "a launch was read never" in lines[-2] and sys.getswitchinterval() == usual
    assert lines[-1] == "trace: 1 attempts, and none held a device operation"  # the stand-in writes nothing


def test_completed_is_the_harnesss_own_and_the_log_says_where_the_seconds_went(monkeypatch):
    """`measure` leaves what `blocks_per_s` reads as `serve` counted it
    (every correct answer by the window's close) and logs the seconds the
    program counted between the edges, each series under its label values."""
    from drivers import serve, serve_tenants

    d = serve_tenants.Driver(_cell())
    lines = []
    d.log = lines.append
    obs = {**_obs(BEFORE, AFTER), "completed": 10, "window_s": 10.0}
    monkeypatch.setattr(serve.Driver, "measure", lambda self, s, t: dict(obs))
    got = d.measure(10.0, None)
    assert got["completed"] == 10 and got["window_s"] == 10.0
    assert lines[-1].startswith("window: 10 correct answers by its close; seconds the program counted")
    assert "'tenant_wait': {'op0': 2.0, 'op1': 6.0, 'default': 0.0}" in lines[-1]
    for series in ("'sig/sync': 0.5", "'sig/enqueue': 8.0", "'witness/sync': 0.25", "'root/sync': 0.1"):
        assert series in lines[-1]


def _rehearsed(capsys, monkeypatch, *more) -> dict:
    load = run.load_json

    def steered(path):
        out = load(path)
        if path.name == "serve-mpt-tenants-1chip.json":
            out["argv"] = ["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0"]
        return out

    monkeypatch.setattr(run, "load_json", steered)
    argv = ["--workload", CELL, "--seed", "3600000078", "--seconds", "8", "--trace", "1", "--rehearse"]
    assert run.main(argv + list(more)) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_starved_lane_is_not_correct_and_nothing_else_is_wrong(capsys, monkeypatch):
    """The control `starve_tenant` through the harness: every answer right,
    every tenant served, and the run not correct by the one comparison."""
    from phant_tpu.serving.scheduler import VerificationScheduler

    monkeypatch.setattr(VerificationScheduler, "_admit", VerificationScheduler._admit)
    r = _rehearsed(capsys, monkeypatch, "--control", "starve_tenant")
    assert r["correct"] is False and r["failed"] == 0
    off = {n for n, c in r["compared"].items() if (c["value"] < c["limit"]) == (c["is"] == "at_least") and c["value"] != c["limit"]}
    assert off == {"tenant_least_over_most"}, r["compared"]
    assert r["compared"]["tenants_served"]["value"] == 4


def test_rehearsal_of_the_cell_is_correct(capsys, monkeypatch):
    """The whole path on the CPU at a tiny genesis, steered onto the cpu
    crypto backend as test_controls.py does (12 s a request otherwise):
    sixteen clients of four tenants, every answer of every client compared,
    every tenant served."""
    r = _rehearsed(capsys, monkeypatch)
    assert r["correct"] is True and r["failed"] == 0 and r["workload"] == CELL
    assert r["attempted"] >= 16 + 15 and r["attempted"] % 4 == 15 % 4
    assert r["compared"]["tenants_served"]["value"] == 4
    assert r["compared"]["tenant_least_over_most"]["value"] >= 0.5
    assert 1.0 <= r["metrics"]["wave_blocks"]["value"] <= r["metrics"]["wave_size"]["value"] <= 16.0
    assert r["metrics"]["tenant_wait_skew_pct"]["value"] >= 0.0
    assert r["metrics"]["compiles_in_window"]["value"] == 0
