"""The shared verifier's cell (PR 34): the in-step client mode against a
stub server; the cell's configuration, traffic and metric files through
run.py's `Cell`; each new metric on a made-up scrape and nothing from a
program without its family; the admission checks of drivers/serve_shared.py;
and a whole `--rehearse` run of the cell."""

import argparse
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

import run
from harness import readers, scrape
from harness.clients import closed_loop, in_step

BENCH = Path(__file__).resolve().parents[1]
CELL = "serve-mpt-shared-1chip.heads4"
NEW = (
    "wave_size", "coalesced_pct", "sig_coalesced_pct", "sig_pad_pct", "lane_shapes",
    "verdict_pad_pct", "verdict_lone_pct",
)  # fmt: skip

BEFORE = """
phant_critpath_requests_total 40
phant_sched_batch_size_sum 40
phant_sched_batch_size_count 16
phant_sched_coalesced_requests_total 30
phant_sched_sig_coalesced_total 20
phant_sig_rows_total{kind="real"} 9000
phant_sig_rows_total{kind="pad"} 1240
phant_lanes_program_shapes{program="ecrecover"} 1
phant_lanes_program_shapes{program="verdict"} 3
phant_lanes_program_shapes{program="gather"} 1
phant_lanes_program_shapes{program="update"} 1
phant_lanes_launches_total{program="ecrecover",rung="256"} 1
phant_lanes_launches_total{program="verdict",rung="2048x1"} 11
phant_lanes_launches_total{program="verdict",rung="8192x4"} 6
phant_witness_resident_verdict_rows_total{kind="real"} 50000
phant_witness_resident_verdict_rows_total{kind="pad"} 20000
phant_root_plan_shapes 8
"""
AFTER = """
phant_critpath_requests_total 80
phant_sched_batch_size_sum 80
phant_sched_batch_size_count 32
phant_sched_coalesced_requests_total 60
phant_sched_sig_coalesced_total 30
phant_sig_rows_total{kind="real"} 18000
phant_sig_rows_total{kind="pad"} 4240
phant_lanes_program_shapes{program="ecrecover"} 1
phant_lanes_program_shapes{program="verdict"} 3
phant_lanes_program_shapes{program="gather"} 1
phant_lanes_program_shapes{program="update"} 1
phant_lanes_launches_total{program="ecrecover",rung="256"} 9
phant_lanes_launches_total{program="verdict",rung="2048x1"} 14
phant_lanes_launches_total{program="verdict",rung="4096x2"} 4
phant_lanes_launches_total{program="verdict",rung="8192x4"} 11
phant_witness_resident_verdict_rows_total{kind="real"} 110000
phant_witness_resident_verdict_rows_total{kind="pad"} 60000
phant_root_plan_shapes 8
"""
#: the parent: requests and the scheduler's families are counted, the lanes' are not there
OLD = """
phant_critpath_requests_total 80
phant_sched_batch_size_sum 80
phant_sched_batch_size_count 32
phant_sched_coalesced_requests_total 60
phant_sched_sig_coalesced_total 30
phant_root_plan_shapes 8
"""


def _obs(before: str, after: str) -> dict:
    return {
        "latency_s": [0.5] * 4, "completed": 4, "window_s": 1.0, "setup_s": 1.0,
        "scrape0": scrape.parse(before), "scrape1": scrape.parse(after),
        "compiles": 0, "gc_pauses": [], "trace": None, "rehearsal": False,
    }  # fmt: skip


# -- the client mode ----------------------------------------------------------


class _Stub:
    """An HTTP server that answers every POST after `delay_s(body)` and
    keeps (body, arrived, answered) of each."""

    def __init__(self, delay_s=lambda body: 0.0, idle_timeout_s=None):
        seen, lock = [], threading.Lock()

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            timeout = idle_timeout_s  # the socket's: an idle keep-alive connection is closed

            def do_POST(self):
                t0 = time.monotonic()
                body = self.rfile.read(int(self.headers["Content-Length"]))
                time.sleep(delay_s(body))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                with lock:
                    seen.append((body, t0, time.monotonic()))
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
    made = []

    def make(**kw):
        made.append(_Stub(**kw))
        return made[-1]

    yield make
    for s in made:
        s.close()


TRAFFIC = {"think_ms": 0}


def test_no_client_sends_the_next_body_before_all_have_the_last(stub):
    """Bodies answered after unequal delays: every POST of body i + 1
    arrives after every answer to body i has been read."""
    s = stub(delay_s=lambda body: 0.002 * (int(body) % 3) + 0.001 * (len(body) % 2))
    bodies = {i: str(i).encode() for i in range(12)}
    plans = [list(range(12))] * 4
    t_open, t_close, exhausted, records = in_step.run("127.0.0.1", s.port, bodies, plans, None, TRAFFIC)
    assert len(records) == 48 and t_close is None and not exhausted
    assert sorted(r[0] for r in records) == sorted(list(range(4)) * 12)
    for i in range(11):
        answered = max(r[3] for r in records if r[1] == i)
        assert min(r[2] for r in records if r[1] == i + 1) >= answered
    assert all(r[4] == 200 and r[5] == b"ok" for r in records)


def test_releases_are_noted_where_the_traffic_names_a_file(stub, tmp_path):
    """One line a step of the group, the release's monotonic time: before
    any client has sent the step's body, after every answer to the last."""
    s = stub(delay_s=lambda body: 0.002)
    bodies = {i: str(i).encode() for i in range(5)}
    traffic = {"think_ms": 0, "releases": str(tmp_path / "releases")}
    *_, records = in_step.run("127.0.0.1", s.port, bodies, [list(range(5))] * 3, None, traffic)
    noted = [float(line) for line in Path(traffic["releases"]).read_text().splitlines()]
    assert len(noted) == 5 and noted == sorted(noted)
    for i, at in enumerate(noted):
        assert at <= min(r[2] for r in records if r[1] == i)
        assert i == 0 or at >= max(r[3] for r in records if r[1] == i - 1)


def test_groups_keep_their_own_step_and_probes_run_alone(stub):
    s = stub()
    bodies = {i: str(i).encode() for i in range(8)}
    plans = [[0, 1, 2, 3]] * 2 + [[4, 5, 6, 7]] * 2
    *_, records = in_step.run("127.0.0.1", s.port, bodies, plans, None, TRAFFIC)
    assert sorted((r[0], r[1]) for r in records) == sorted(
        (c, i) for c, plan in enumerate(plans) for i in plan
    )
    *_, alone = in_step.run("127.0.0.1", s.port, bodies, [[0, 1, 2]], None, TRAFFIC)
    assert [r[1] for r in alone] == [0, 1, 2]


@pytest.mark.parametrize("mode", [closed_loop, in_step])
def test_deadline_and_exhausted_chain_as_closed_loop(stub, mode):
    s = stub(delay_s=lambda body: 0.02)
    bodies = {i: str(i).encode() for i in range(200)}
    # the deadline first: nobody starts a body after it, the window is whole
    t_open, t_close, exhausted, records = mode.run(
        "127.0.0.1", s.port, bodies, [list(range(200))] * 2, 0.3, TRAFFIC
    )
    assert not exhausted and t_close == pytest.approx(t_open + 0.3)
    assert 2 <= len(records) < 60 and all(r[2] < t_close for r in records)
    # the chain first: the window closes at the last answer
    t_open, t_close, exhausted, records = mode.run(
        "127.0.0.1", s.port, bodies, [list(range(3))] * 2, 30.0, TRAFFIC
    )
    assert exhausted and len(records) == 6
    assert t_close == max(r[3] for r in records) < t_open + 5


@pytest.mark.parametrize("mode", [closed_loop, in_step])
def test_a_failed_connection_is_a_record_and_not_a_hang(mode):
    import socket

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]  # bound, never listening: connections are refused
        *_, records = mode.run("127.0.0.1", port, {0: b"x", 1: b"y"}, [[0, 1]] * 2, None, TRAFFIC)
    assert len(records) == 4 and all(r[4] == -1 for r in records)


def test_a_connection_dropped_while_idle_at_the_barrier_is_reopened(stub):
    """One client's body takes long; the other's connection idles at the
    barrier, and a server that closes it meanwhile costs a reconnection,
    not an answer."""
    s = stub(delay_s=lambda body: 0.25 if body == b"slow" else 0.0, idle_timeout_s=0.1)
    bodies = {0: b"slow", 1: b"fast", 2: b"slow", 3: b"fast"}
    *_, records = in_step.run("127.0.0.1", s.port, bodies, [[0, 2], [1, 3]], None, TRAFFIC)
    assert sorted((r[0], r[1], r[4]) for r in records) == [(0, 0, 200), (0, 2, 200), (1, 1, 200), (1, 3, 200)]


# -- the cell's files ---------------------------------------------------------


def _cell(rehearse: bool = True) -> run.Cell:
    args = argparse.Namespace(workload=CELL, seed=1, rehearse=rehearse, trace=1)
    return run.Cell(args, run.load_json(run.ROOT / "BENCHMARK.json"))


def test_cell_loads_its_files():
    cell = _cell(rehearse=False)
    assert cell.config["name"] == "serve-mpt-shared-1chip" and cell.chips == 1
    assert cell.config["driver"] == "serve_shared" and "env" not in cell.config
    bare = run.load_json(BENCH / "configs" / "serve-mpt-rootlane-1chip.json")
    for key in ("argv", "genesis_accounts", "sender_pool", "contracts", "reduced", "gas_used_per_block"):
        assert cell.config[key] == bare[key], key
    lone = run.load_json(BENCH / "traffic" / "lone.json")
    t = cell.traffic
    assert (t["mode"], t["clients"], t["groups"], t["think_ms"]) == ("in_step", 4, 1, 0)
    for key in ("chain", "chain_blocks", "warmup", "tampered_probes"):
        assert t[key] == lone[key], key
    # the stretch is placed by the clients' barrier (serve_shared._trace):
    # lone's keys but `seconds`, and those that say where a wave puts it
    assert {k: t["trace"][k] for k in lone["trace"] if k != "seconds"} == {
        k: v for k, v in lone["trace"].items() if k != "seconds"
    }
    assert set(t["trace"]) - set(lone["trace"]) == {"find_release_s", "lead_s", "max_s", "launch_within_s"}
    assert t["trace"]["seconds"] <= 0.075  # three executions of ecrecover at the most
    layer = {m["name"] for m in cell.metrics("per_layer", "layer_metrics")}
    assert set(NEW) <= layer
    rootlane = {m["name"] for m in cell.bench["per_layer"] if "serve-mpt-rootlane-1chip.lone" in m["workloads"]}
    # the stretch holds a wave's root plans by construction, and of the
    # next wave's head whatever 0.015 s after its first signature launch is seen
    # hold: nothing promises a whole `ecrecover` or a table program in it
    assert layer == (rootlane | set(NEW)) - {"resident_table_ms_per_block", "ecrecover_ms_per_block"}
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


@pytest.mark.parametrize(
    "name,want",
    [("wave_size", 2.5), ("coalesced_pct", 75.0), ("sig_coalesced_pct", 25.0),
     ("sig_pad_pct", 25.0), ("lane_shapes", 6.0), ("verdict_pad_pct", 40.0), ("verdict_lone_pct", 25.0)],
)  # fmt: skip
def test_new_metric_file_reads_its_family(name, want):
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert readers.read(spec, _obs(BEFORE, AFTER)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["sig_pad_pct", "lane_shapes", "verdict_pad_pct", "verdict_lone_pct"])
def test_new_metric_file_reads_nothing_from_the_parent(name):
    """The driver lays these files over the parent's checkout: where the
    program has no such family the metric is left out and not reported as
    0. (The other three read families the parent has too.)"""
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert readers.read(spec, _obs(OLD, OLD)) is None


# -- the driver's checks --------------------------------------------------------


class _FakeCompiles:
    def count(self):
        return 0

    def names_since(self, _n):
        return []


def _shared_driver(monkeypatch, boot: str, edges: tuple, rehearse: bool):
    from drivers import serve, serve_shared

    cell = _cell(rehearse=rehearse)
    cell.compiles = _FakeCompiles()
    d = serve_shared.Driver(cell)
    monkeypatch.setattr(
        serve.Driver, "start_program", lambda self: setattr(self, "scrape_boot", scrape.parse(boot))
    )
    monkeypatch.setattr(serve.Driver, "measure", lambda self, s, t: _obs(*edges))
    return d


def test_shared_driver_stops_a_program_that_does_not_declare_the_family(monkeypatch):
    from drivers import serve_shared

    from phant_tpu.utils import trace

    monkeypatch.setattr(
        trace, "METRIC_HELP", {k: v for k, v in trace.METRIC_HELP.items() if not k.startswith("lanes.")}
    )
    with pytest.raises(SystemExit, match="fault 0c"):
        serve_shared.Driver(_cell()).prepare()


def test_shared_driver_refuses_a_server_without_the_family(monkeypatch):
    d = _shared_driver(monkeypatch, OLD, (OLD, OLD), rehearse=False)
    with pytest.raises(SystemExit, match="lacks .*phant_lanes_program_shapes"):
        d.start_program()


def test_shared_driver_admits_shapes_that_stand_still(monkeypatch):
    d = _shared_driver(monkeypatch, BEFORE, (BEFORE, AFTER), rehearse=False)
    d.start_program()
    assert d.measure(1.0, None)["completed"] == 4


GROWN = AFTER.replace('program_shapes{program="verdict"} 3', 'program_shapes{program="verdict"} 4') + (
    'phant_lanes_launches_total{program="verdict",rung="16384x1"} 1\n'
)


def test_shared_driver_asks_the_boot_for_the_tables_ladders(monkeypatch):
    """In a measuring run the table's programs stand on exactly their
    ladders' rungs at server start (the constructor built them);
    `ecrecover` need not (the first request builds its one rung); a
    rehearsal on the CPU, where nothing is built at boot, is let through."""
    half = BEFORE.replace('program_shapes{program="verdict"} 3', 'program_shapes{program="verdict"} 1')
    d = _shared_driver(monkeypatch, half, (BEFORE, AFTER), rehearse=False)
    with pytest.raises(SystemExit, match=r"constructor did not build.*'verdict': \(1.0, 3\)"):
        d.start_program()
    cold = BEFORE.replace('program_shapes{program="ecrecover"} 1', 'program_shapes{program="ecrecover"} 0')
    d = _shared_driver(monkeypatch, cold, (BEFORE, AFTER), rehearse=False)
    lines = []
    d.log = lines.append
    d.start_program()
    assert "'verdict': 3.0" in lines[-1] and "'ecrecover': 0.0" in lines[-1]
    assert d.measure(1.0, None)["completed"] == 4
    d = _shared_driver(monkeypatch, half, (BEFORE, AFTER), rehearse=True)
    d.start_program()


def test_shared_driver_names_a_shape_met_inside_the_window(monkeypatch):
    d = _shared_driver(monkeypatch, BEFORE, (BEFORE, GROWN), rehearse=False)
    d.start_program()
    with pytest.raises(SystemExit, match=r"between the window's edges.*verdict.*16384x1"):
        d.measure(1.0, None)


def test_shared_driver_holds_every_program_to_its_ladder_at_the_close(monkeypatch):
    """Shapes met in the warm-up, none in the window: an error in a
    measuring run, where every program must stand on exactly its ladder's
    rungs at the window's close, whoever built them; on the CPU, where a
    rehearsal builds a rung when it first meets it, only growth inside the
    window counts."""
    wide = lambda text: text.replace(  # noqa: E731
        'program_shapes{program="ecrecover"} 1', 'program_shapes{program="ecrecover"} 2'
    ) + 'phant_lanes_launches_total{program="ecrecover",rung="512"} 1\n'
    d = _shared_driver(monkeypatch, BEFORE, (wide(BEFORE), wide(AFTER)), rehearse=False)
    d.start_program()
    with pytest.raises(SystemExit, match=r"other shapes than its ladder's.*'ecrecover': \(2.0, 1\).*512"):
        d.measure(1.0, None)
    cpu = "".join(
        line.rsplit(" ", 1)[0] + " 0\n" if "program_shapes" in line else line + "\n"
        for line in BEFORE.strip().splitlines()
    )
    d = _shared_driver(monkeypatch, cpu, (wide(BEFORE), wide(AFTER)), rehearse=True)
    d.start_program()
    assert d.measure(1.0, None)["completed"] == 4


def test_the_stretch_is_placed_by_the_clients_barrier(monkeypatch, tmp_path):
    """Made-up releases every 0.5 s, noted as `in_step` notes them, a
    signature launch on the program's counter 0.07 s after each, and a
    profiler that does nothing: the stretch opens `lead_s` after a release
    and closes `seconds` after the launch that follows the next one."""
    import jax

    from drivers import serve_shared
    from phant_tpu.utils.trace import metrics

    cell = _cell()
    cell.gc = None
    cell.traffic["trace"].update(
        start_s=0.0, find_release_s=1.5, lead_s=0.2, max_s=0.6, launch_within_s=0.3, seconds=0.03
    )
    cell.traffic["releases"] = str(tmp_path / "releases")
    d = serve_shared.Driver(cell)
    lines = []
    d.log = lines.append
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    group = in_step._Group(1, None, threading.Event(), open(cell.traffic["releases"], "a"))
    heads, stop = [], threading.Event()

    def waves():
        while not stop.is_set():
            group.barrier.wait()
            time.sleep(0.07)
            heads.append(time.monotonic())
            metrics.count("sig.rows", 225, kind="real")
            time.sleep(0.43)

    t = threading.Thread(target=waves, daemon=True)
    t.start()
    time.sleep(0.05)  # the watch begins inside a wave: its own release is not taken for one
    try:
        d._trace("unused")
    finally:
        stop.set()
        t.join()
    a, s0, s1, b = d.stretch
    anchor = max(h for h in heads if h < a) - 0.07  # the release of the wave the stretch opened in
    following = min(h for h in heads if h > s0)  # the next wave's launch
    assert 0.19 <= a - anchor <= 0.3 and a <= s0 <= s1 <= b
    assert 0.025 <= s1 - following <= 0.08
    (line,) = [ln for ln in lines if ln.startswith("trace: the profiler was on")]
    assert "the next release came at" in line and "first signature launch at" in line and "none" not in line


def test_a_traced_window_tells_the_clients_where_to_note_releases(monkeypatch):
    """`measure` names the file in the traffic the clients are sent, for
    the traced window alone."""
    from drivers import serve

    d = _shared_driver(monkeypatch, BEFORE, (BEFORE, AFTER), rehearse=False)
    sent = []
    monkeypatch.setattr(
        serve.Driver, "measure", lambda self, s, t: sent.append(self.traffic.get("releases")) or _obs(BEFORE, AFTER)
    )
    d.start_program()
    d.measure(1.0, None)
    d.measure(1.0, "somewhere")
    assert sent[0] is None and Path(sent[1]).read_text() == "" and "releases" not in d.traffic


def test_rehearsal_of_the_cell_is_correct(capsys, monkeypatch):
    """The whole path on the CPU at a tiny genesis, steered onto the cpu
    crypto backend as test_controls.py does (12 s a request otherwise):
    four clients in step, every answer of every client compared."""
    load = run.load_json

    def steered(path):
        out = load(path)
        if path.name == "serve-mpt-shared-1chip.json":
            out["argv"] = ["--crypto_backend=cpu", "--evm_backend=native", "--engine_api_port", "0"]
        return out

    monkeypatch.setattr(run, "load_json", steered)
    argv = ["--workload", CELL, "--seed", "3400000078", "--seconds", "8", "--trace", "1", "--rehearse"]
    assert run.main(argv) == 0
    r = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0 and r["workload"] == CELL
    assert r["attempted"] >= 8 + 15 and r["attempted"] % 4 == 15 % 4
    assert 1.0 <= r["metrics"]["wave_size"]["value"] <= 4.0
    assert "coalesced_pct" in r["metrics"] and "sig_coalesced_pct" in r["metrics"]
    assert r["metrics"]["lane_shapes"]["value"] == 0  # exported from server start; cpu backend
    assert r["metrics"]["compiles_in_window"]["value"] == 0
