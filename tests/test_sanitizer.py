"""phantsan (phant_tpu/analysis/sanitizer.py): lockset race detection.

Each test enables the sanitizer, builds its own fixture classes (so the
proxied locks are constructed AFTER enable()), runs real threads, and
drains the report buffer before tearing down — reports must never leak
into the conftest sessionfinish check that fails sanitized sessions on
undrained races.
"""

from __future__ import annotations

import threading

import pytest

from phant_tpu.analysis import sanitizer


@pytest.fixture()
def san():
    """Enable around one test, then restore EXACTLY the prior state: under
    a PHANT_SANITIZE=1 session the sanitizer is already live session-wide
    (conftest), and tearing it down here would silently de-sanitize every
    later test."""
    was_enabled = sanitizer.enabled()
    before = set(sanitizer.registered_classes())
    sanitizer.enable()
    yield sanitizer
    for cls in sanitizer.registered_classes():
        if cls not in before:
            sanitizer.unregister(cls)
    if not was_enabled:
        sanitizer.disable()
    sanitizer.drain_reports()


def _run_threads(*targets):
    threads = [threading.Thread(target=t) for t in targets]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def test_racy_counter_produces_two_stack_report(san):
    class Racy:
        def __init__(self):
            self.count = 0

        def bump(self):
            for _ in range(2000):
                self.count += 1  # read-modify-write, no lock

    san.register_shared_class(Racy)
    obj = Racy()
    _run_threads(obj.bump, obj.bump)

    reports = san.drain_reports()
    assert reports, "two lockless writer threads must produce a race report"
    r = reports[0]
    assert r.attr == "count" and r.cls_name == "Racy"
    # a race is a PAIR of accesses: both halves carry a stack ending in
    # the racing line
    assert r.first_stack and r.second_stack
    text = r.format()
    assert "data race on `Racy.count`" in text
    assert text.count("access") >= 2
    assert "bump" in "".join(r.second_stack)


def test_locked_counter_is_clean(san):
    class Locked:
        def __init__(self):
            self._lock = threading.Lock()  # proxy: enable() ran first
            self.count = 0

        def bump(self):
            for _ in range(2000):
                with self._lock:
                    self.count += 1

    san.register_shared_class(Locked)
    obj = Locked()
    _run_threads(obj.bump, obj.bump)
    assert san.drain_reports() == []


def test_single_thread_never_reports(san):
    class Solo:
        def __init__(self):
            self.x = 0

    san.register_shared_class(Solo)
    obj = Solo()
    for _ in range(100):
        obj.x += 1  # exclusive state: no checking, no reports
    assert san.drain_reports() == []


def test_condition_over_proxy_lock_works(san):
    """threading.Condition built over the proxied Lock must wait/notify
    correctly — the proxy's _release_save/_acquire_restore protocol is
    what the whole serving scheduler runs on under PHANT_SANITIZE=1."""

    class Chan:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition(self._lock)
            self.value = None

        def put(self, v):
            with self._lock:
                self.value = v
                self._cond.notify_all()

        def get(self):
            with self._lock:
                while self.value is None:
                    self._cond.wait(timeout=5)
                return self.value

    san.register_shared_class(Chan)
    ch = Chan()
    out = []

    def consumer():
        out.append(ch.get())

    t = threading.Thread(target=consumer)
    t.start()
    ch.put(41)
    t.join(timeout=10)
    assert out == [41]
    assert san.drain_reports() == []


def test_reader_writer_without_common_lock_reports(san):
    """Writer holds lock A, reader holds lock B: every access IS locked,
    but no single lock covers both — the lockset intersection is empty
    and phantsan reports it (the classic Eraser case a 'was a lock held?'
    checker misses)."""

    class Split:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
            self.v = 0

    san.register_shared_class(Split)
    obj = Split()

    def writer():
        for _ in range(500):
            with obj._a:
                obj.v += 1

    def reader():
        got = 0
        for _ in range(500):
            with obj._b:
                got = obj.v
        return got

    _run_threads(writer, reader)
    reports = san.drain_reports()
    assert any(r.attr == "v" for r in reports), [r.attr for r in reports]


def test_default_shared_classes_register(san):
    targets = san.register_default_shared_classes()
    names = {t.__name__ for t in targets}
    assert {
        "VerificationScheduler",
        "FlightRecorder",
        "Metrics",
    } <= names


def test_disable_restores_real_locks(san):
    assert threading.Lock is not None
    san.disable()
    lock = threading.Lock()
    assert not isinstance(lock, sanitizer._LockProxy)
    san.enable()  # fixture teardown expects enabled state to unwind
    assert isinstance(threading.Lock(), sanitizer._LockProxy)
