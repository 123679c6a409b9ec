"""`fanin16`'s traced stretch is taken again where it held nothing (PR 41):
`drivers/serve_tenants.py` `_trace`, `_attempt`, `_held` and `_stretch` against
a stand-in profiler that writes what a case tells it to and a stand-in for
the program's launch counters. No server, no chip; each case under a second.

The tier-1 command collects `tests/` only, and a benchmark PR may add no
file there: these are run by hand with the rest of `benchmarks/tests`."""

import argparse
import os
import shutil
import sys
import time
from pathlib import Path

import pytest

import run
from drivers import serve, serve_tenants
from harness import trace_reduce

CELL = "serve-mpt-tenants-1chip.fanin16"
RECORDED = Path(__file__).with_name("data") / "small.xplane.pb"  # a v5e's, with operations


def _msg(field: int, payload: bytes) -> bytes:
    """One length-delimited field of a protobuf message."""
    n, size = len(payload), b""
    while n > 0x7F:
        size, n = size + bytes([n & 0x7F | 0x80]), n >> 7
    return bytes([field << 3 | 2]) + size + bytes([n]) + payload


def _xspace(*planes: tuple) -> bytes:
    """An XSpace of (plane name, line name) pairs, every line without an event."""
    return b"".join(_msg(1, _msg(2, name) + _msg(3, _msg(2, line))) for name, line in planes)


#: what the profiler leaves where nothing ran on the device: host planes alone
HOST_ONLY = _xspace((b"/host:CPU", b"main/1"), (b"/host:metadata", b"x"))
#: a device plane whose "XLA Ops" line has no event
IDLE_DEVICE = _xspace((b"/device:TPU:0", b"XLA Ops"), (b"/host:CPU", b"main/1"))
#: host planes alone again, of 6 MB: the size of a trace says nothing of what it holds
BIG_HOST_ONLY = _xspace((b"/host:CPU", b"x" * (6 << 20)), (b"/host:metadata", b"x"))


class Profiler:
    """Stands in for `jax.profiler`: each `stop_trace` writes the next of
    `writes` ("ops": the recorded trace; "host": host planes only; an
    exception is raised) as the real one does, under plugins/profile/<n>/."""

    def __init__(self, monkeypatch, writes):
        import jax

        self.writes, self.starts, self.stops, self.intervals, self.dir = list(writes), [], [], [], None
        monkeypatch.setattr(jax.profiler, "start_trace", self.start)
        monkeypatch.setattr(jax.profiler, "stop_trace", self.stop)

    def start(self, trace_dir, profiler_options=None):
        self.dir = trace_dir
        self.starts.append(time.monotonic())
        self.intervals.append(sys.getswitchinterval())

    def stop(self):
        self.intervals.append(sys.getswitchinterval())
        what = self.writes[len(self.stops)]
        self.stops.append(time.monotonic())
        if isinstance(what, Exception):
            raise what
        out = Path(self.dir) / "plugins" / "profile" / f"at_{len(self.stops)}"
        out.mkdir(parents=True)
        if what == "ops":
            shutil.copy(RECORDED, out / "t.xplane.pb")
        else:
            (out / "t.xplane.pb").write_bytes(HOST_ONLY)


def _driver(monkeypatch, writes, *, seconds=30.0, launches=(), **trace):
    """A driver of the cell with quick trace parameters, the profiler above,
    and the program's series counting one dispatch at each of `launches`
    (seconds from now)."""
    args = argparse.Namespace(workload=CELL, seed=1, rehearse=True, trace=1)
    cell = run.Cell(args, run.load_json(run.ROOT / "BENCHMARK.json"))
    cell.gc = None
    quick = dict(start_s=0.0, quiet_s=0.02, quiet_within_s=0.2, launch_within_s=0.05, hold_s=0.0)
    cell.traffic["trace"].update({**quick, **trace})
    d = serve_tenants.Driver(cell)
    d.lines, d.seconds = [], seconds
    d.log = d.lines.append
    t0 = time.monotonic()
    monkeypatch.setattr(
        serve_tenants,
        "_series",
        lambda: {"lanes.launches{x}": (sum(1 for at in launches if time.monotonic() - t0 >= at), 0.0)},
    )
    return d, Profiler(monkeypatch, writes)


def test_a_first_attempt_that_held_something_is_the_only_one(monkeypatch, tmp_path):
    d, prof = _driver(monkeypatch, ["ops"])
    d._trace(str(tmp_path))
    assert (len(prof.starts), len(prof.stops)) == (1, 1) and len(d.attempts) == 1
    assert len(d.lines) == 1 and d.lines[0].startswith("trace: attempt 1 of 4: the device was quiet")
    assert "held a device operation" in d.lines[0]
    a, s0, s1, b = d.stretch
    assert a == s0 <= prof.starts[0] <= s1 <= prof.stops[0] <= b


def test_an_empty_attempt_is_taken_again_and_its_directory_is_gone(monkeypatch, tmp_path):
    d, prof = _driver(monkeypatch, ["host", "ops"])
    d._trace(str(tmp_path))
    assert (len(prof.starts), len(prof.stops)) == (2, 2)
    left = sorted(p.name for p in (tmp_path / "plugins" / "profile").iterdir())
    assert left == ["at_2"]
    assert trace_reduce.find_xplane(str(tmp_path)).endswith("at_2/t.xplane.pb")
    assert trace_reduce.reduce_file(trace_reduce.find_xplane(str(tmp_path)), str(run.HERE / "programs")) is not None
    (w1, a1, s1, b1), (w2, a2, s2, b2) = d.attempts
    assert w1 <= a1 <= s1 <= b1 <= w2 <= a2 <= s2 <= b2
    assert d.stretch == (a2, a2, s2, b2)  # the one that held something
    assert "attempt 1 of 4" in d.lines[0] and "held nothing: 0 KB" in d.lines[0] and d.lines[0].endswith("trying again")
    assert "attempt 2 of 4" in d.lines[1] and "held a device operation" in d.lines[1] and len(d.lines) == 2


def test_every_attempt_empty_is_said_and_nothing_is_made_up(monkeypatch, tmp_path):
    d, prof = _driver(monkeypatch, ["host"] * 4)
    d._trace(str(tmp_path))  # no exception
    assert len(prof.starts) == len(prof.stops) == 4 == len(d.attempts)
    assert d.lines[-1] == "trace: 4 attempts, and none held a device operation"
    assert d.lines[-2].endswith("the last") and "attempt 4 of 4" in d.lines[-2]
    # the last attempt's file is what run.py finds, and it reads as nothing: no
    # `busy_s`, no `breakdown`, `obs["trace"]` left None
    assert trace_reduce.find_xplane(str(tmp_path)).endswith("at_4/t.xplane.pb")
    assert trace_reduce.reduce_file(trace_reduce.find_xplane(str(tmp_path)), str(run.HERE / "programs")) is None
    assert d.stretch[0] == d.attempts[-1][1]


def test_no_attempt_is_begun_that_the_window_has_no_room_for(monkeypatch, tmp_path):
    """0.2 s of quiet watch + 0.05 s of watch for a launch + the start: a
    window of 0.3 s has room for the first attempt alone."""
    d, prof = _driver(monkeypatch, ["host"] * 4, seconds=0.3)
    d._trace(str(tmp_path))
    assert len(prof.starts) == 1
    assert "no room for another" in d.lines[0] and "s to the window's close" in d.lines[0]
    assert d.lines[1] == "trace: 1 attempts, and none held a device operation"
    assert trace_reduce.find_xplane(str(tmp_path))  # kept: run.py reads it and says so


def test_the_switch_interval_is_the_usual_one_after_every_attempt(monkeypatch, tmp_path):
    usual = sys.getswitchinterval()
    d, prof = _driver(monkeypatch, ["host", "host", "ops"])
    seen = []
    attempt = d._attempt

    def watched(*a):
        seen.append(sys.getswitchinterval())
        got = attempt(*a)
        seen.append(sys.getswitchinterval())
        return got

    d._attempt = watched
    d._trace(str(tmp_path))
    short = d.traffic["trace"]["switch_s"]
    assert prof.intervals == pytest.approx([short] * 6) and seen == pytest.approx([usual] * 6)
    d, prof = _driver(monkeypatch, ["host", RuntimeError("the profiler failed to stop")])
    with pytest.raises(RuntimeError, match="failed to stop"):
        d._trace(str(tmp_path / "second"))
    assert len(prof.starts) == 2 and sys.getswitchinterval() == usual
    time.sleep(0.03)  # the thread that gives the interval back early was called off, or has run
    assert sys.getswitchinterval() == usual


def test_the_stop_comes_at_a_launch_read_under_the_profiler(monkeypatch, tmp_path):
    """A dispatch 0.1 s from now: quiet from the start (0.02 s), the profiler
    on, the launch read, the stop right after; then with none in sight the
    stop comes `launch_within_s` after the profiler was on."""
    d, prof = _driver(monkeypatch, ["ops", "ops"], launches=(0.1,), launch_within_s=0.3)
    t0 = time.monotonic()
    d._trace(str(tmp_path))
    assert 0.02 <= prof.starts[0] - t0 < 0.1 <= prof.stops[0] - t0 < 0.3
    assert "a launch was read after" in d.lines[0] and "(lanes.launches{x} +1)" in d.lines[0]
    d._trace(str(tmp_path / "second"))
    assert 0.3 <= prof.stops[1] - prof.starts[1] and "a launch was read never" in d.lines[-1]


@pytest.mark.parametrize(
    "content, held",
    [("ops", True), (HOST_ONLY, False), (IDLE_DEVICE, False), (BIG_HOST_ONLY, False), (None, False)],
    ids=["recorded_operations", "host_planes_only", "device_plane_without_events", "host_planes_of_6_MB", "no_file"],
)
def test_held_judges_a_stretch_by_its_device_planes_alone(tmp_path, content, held):
    out = tmp_path / "plugins" / "profile" / "x"
    out.mkdir(parents=True)
    if content == "ops":
        shutil.copy(RECORDED, out / "t.xplane.pb")
    elif content is not None:
        (out / "t.xplane.pb").write_bytes(content)
    got, path, size = serve_tenants._held(str(tmp_path))
    assert got is held
    assert (path, size) == ((None, 0) if content is None else (str(out / "t.xplane.pb"), os.path.getsize(path)))
    if content is not None:  # the judge and the reducer agree
        assert (trace_reduce.reduce_file(path, str(run.HERE / "programs")) is not None) is held


def test_the_log_says_which_series_the_reading_was_and_when_an_upload_began(monkeypatch, tmp_path):
    """The trigger is PR 36's: the END of an upload-and-launch ends the
    stretch like any launch. One of 0.2 s whose end is read some 0.1 s after
    the profiler was on began 0.1 s BEFORE it was on, and the attempt's line
    says so, which is what the runs on the chip are read for (ISSUE 41, point
    2); `_launches` is the same series in one number."""
    enqueue = 'device.host_seconds{lane="sig",op="enqueue"}'
    d, prof = _driver(monkeypatch, ["host", "ops"], launch_within_s=0.5)
    t0 = time.monotonic()
    ended = lambda: time.monotonic() - t0 >= 0.12  # noqa: E731
    monkeypatch.setattr(
        serve_tenants, "_series", lambda: {enqueue: (3 + ended(), 1.0 + 0.2 * ended()), "lanes.launches{x}": (7, 0.0)}
    )
    assert serve_tenants._launches() == 10
    d.traffic["trace"]["tries"] = 1
    d._trace(str(tmp_path))
    assert 0.12 <= prof.stops[0] - t0 < 0.3  # stopped at that reading, not `launch_within_s` after
    said = d.lines[0]
    assert f"({enqueue} +1, begun -" in said and "ms after it was on)" in said
    begun = float(said.split("begun ")[1].split(" ms")[0])
    assert begun == pytest.approx((0.12 - 0.2 - (prof.starts[0] - t0)) * 1e3, abs=25)


def test_earlier_attempts_are_out_of_both_sides_of_the_pace(monkeypatch):
    """A window of 10 s, one answer every 0.1 s. An empty attempt spans
    2.0-3.0 s (watch to stop), the kept one starts at 4.0, stretch to 4.2,
    stop returned at 5.0. The rest of the window is 10 - 1 (kept) - 1
    (earlier) = 8 s with the answers that fell in neither: the pace reads 1
    where the earlier attempt is taken out, and as if 10 more answers had
    come in 1 s more where it is not."""
    d, _prof = _driver(monkeypatch, [])
    good = [(0, i, i / 10 - 0.1, i / 10, 200, b"") for i in range(1, 101)]
    d.attempts = [(2.0, 2.3, 2.35, 3.0), (3.0, 4.0, 4.2, 5.0)]
    d.stretch = (4.0, 4.0, 4.2, 5.0)
    got = d._stretch(good, 0.0, 10.0)
    rest = [r for r in good if not 2.0 <= r[3] <= 3.0 and not 4.0 <= r[3] <= 5.0]
    assert got["window_s"] == pytest.approx(0.2) and got["requests"] == pytest.approx(2.0)
    assert got["pace"] == pytest.approx((2.0 / 0.2) / (len(rest) / 8.0))
    d.attempts = d.attempts[1:]
    alone = serve.Driver._stretch(d, good, 0.0, 10.0)
    assert d._stretch(good, 0.0, 10.0) == alone and alone["pace"] != got["pace"]
