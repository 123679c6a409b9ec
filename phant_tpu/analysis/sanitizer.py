"""phantsan: an Eraser-style lockset race sanitizer for the serving path.

The static rules (LOCK/LOCKORDER/LOCKBLOCK/THREADSHARE) under-approximate
by construction: sharing through containers, callbacks, or dynamically
chosen locks is invisible to a lexical analysis.  phantsan is the dynamic
backstop — the classic lockset algorithm (Savage et al., "Eraser: A
Dynamic Data Race Detector for Multithreaded Programs", TOCS 1997)
adapted to Python attributes:

  * `enable()` replaces `threading.Lock`/`threading.RLock` with proxy
    factories whose acquire/release maintain a thread-local *held set*.
    `threading.Condition()` and `queue.Queue` pick the proxies up
    automatically (they construct their locks through the patched
    names); Condition-over-proxy works because the proxy implements the
    `_release_save`/`_acquire_restore`/`_is_owned` protocol.
  * `register_shared_class(cls)` instruments `cls.__setattr__` and
    `cls.__getattribute__` to run each instance-attribute access through
    the per-field state machine:

        virgin -> exclusive (single thread; no checking — init is free)
               -> shared (second thread reads)
               -> shared-modified (second thread involved + any write)

    From the first second-thread access on, the field's *candidate
    lockset* is intersected with the locks held at each access.  An empty
    lockset in the shared-modified state is a race: no single lock
    protected every access.  The report carries TWO stacks — the previous
    access and the current one — because a race is a pair of accesses,
    and the previous one is usually the half you didn't think about.

Scope and under-approximation: only attribute REBINDING is tracked
(`self.x = v`, `self.x += v`); in-place mutation of a dict/list held in
an attribute looks like a read.  The GIL makes individual accesses
atomic, so what phantsan reports are not torn words but *atomicity
races*: check-then-act and read-modify-write interleavings — exactly the
class the GIL does NOT prevent.

Everything here must work while `threading.Lock` is patched, so the
module's own bookkeeping locks are captured at import time.
"""

from __future__ import annotations

import os
import sys
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

# real ctors, captured before enable() can patch them: the sanitizer's own
# infrastructure must never run through its own proxies
_REAL_LOCK = threading.Lock
_REAL_RLOCK = threading.RLock

_STACK_LIMIT = 16

# field states
_EXCLUSIVE = "exclusive"
_SHARED = "shared"
_SHARED_MOD = "shared-modified"

_tls = threading.local()


def _held() -> set:
    h = getattr(_tls, "held", None)
    if h is None:
        h = set()
        _tls.held = h
    return h


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------


@dataclass
class RaceReport:
    cls_name: str
    attr: str
    first_thread: str
    first_op: str  # "read" | "write"
    first_stack: List[str]
    second_thread: str
    second_op: str
    second_stack: List[str]

    def format(self) -> str:
        lines = [
            f"phantsan: data race on `{self.cls_name}.{self.attr}` — no "
            "single lock protects every access (empty lockset in the "
            "shared-modified state)",
            f"  access 1: {self.first_op} by thread {self.first_thread}",
        ]
        lines += [
            "    " + l for fr in self.first_stack for l in fr.rstrip().splitlines()
        ]
        lines.append(
            f"  access 2: {self.second_op} by thread {self.second_thread}"
        )
        lines += [
            "    " + l for fr in self.second_stack for l in fr.rstrip().splitlines()
        ]
        return "\n".join(lines)


_reports: List[RaceReport] = []
_reports_lock = _REAL_LOCK()


def reports() -> List[RaceReport]:
    with _reports_lock:
        return list(_reports)


def drain_reports() -> List[RaceReport]:
    """Return accumulated reports and clear the buffer.  Test harnesses
    fail the session on a non-empty drain; the deliberately-racy fixture
    drains its own reports so they never leak into the session check."""
    with _reports_lock:
        out = list(_reports)
        del _reports[:]
    return out


def _record(report: RaceReport) -> None:
    with _reports_lock:
        _reports.append(report)


# ---------------------------------------------------------------------------
# lock proxies
# ---------------------------------------------------------------------------


class _LockProxy:
    """Wraps a real lock; acquire/release maintain the thread-local held
    set.  Implements the `_release_save`/`_acquire_restore`/`_is_owned`
    protocol so `threading.Condition(proxy)` waits correctly (Condition
    prefers those when present, and the proxy always presents them,
    falling back to plain acquire/release for non-reentrant inners)."""

    def __init__(self, inner, reentrant: bool):
        self._phantsan_inner = inner
        self._phantsan_reentrant = reentrant
        self._phantsan_count = 0  # recursion depth, mutated lock-in-hand

    # -- core protocol ---------------------------------------------------

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._phantsan_inner.acquire(blocking, timeout)
        if got:
            self._phantsan_count += 1
            _held().add(self)
        return got

    def release(self) -> None:
        self._phantsan_inner.release()
        self._phantsan_count -= 1
        if self._phantsan_count <= 0:
            self._phantsan_count = 0
            _held().discard(self)

    def locked(self) -> bool:
        return self._phantsan_inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self) -> str:
        return f"<phantsan {type(self._phantsan_inner).__name__} proxy>"

    def _at_fork_reinit(self) -> None:
        # os.fork support (concurrent.futures registers this): the child
        # starts with the lock free and no recursion
        self._phantsan_inner._at_fork_reinit()
        self._phantsan_count = 0

    def __getattr__(self, name):
        # anything the proxy doesn't reimplement delegates to the real
        # lock (only fires for names not found on the proxy class)
        return getattr(self._phantsan_inner, name)

    # -- Condition protocol ----------------------------------------------

    def _release_save(self):
        count = self._phantsan_count
        self._phantsan_count = 0
        _held().discard(self)
        inner = self._phantsan_inner
        if hasattr(inner, "_release_save"):
            return (count, inner._release_save())
        inner.release()
        return (count, None)

    def _acquire_restore(self, state) -> None:
        count, inner_state = state
        inner = self._phantsan_inner
        if hasattr(inner, "_acquire_restore"):
            inner._acquire_restore(inner_state)
        else:
            inner.acquire()
        self._phantsan_count = count
        _held().add(self)

    def _is_owned(self) -> bool:
        inner = self._phantsan_inner
        if hasattr(inner, "_is_owned"):
            return inner._is_owned()
        # plain-Lock fallback, mirroring threading.Condition's own
        if inner.acquire(False):
            inner.release()
            return False
        return True


def _proxy_lock():
    return _LockProxy(_REAL_LOCK(), reentrant=False)


def _proxy_rlock():
    return _LockProxy(_REAL_RLOCK(), reentrant=True)


# ---------------------------------------------------------------------------
# enable / disable
# ---------------------------------------------------------------------------

_enabled = False
_state_lock = _REAL_LOCK()  # guards the enable/disable toggle itself


def enabled() -> bool:
    return _enabled


def enable() -> None:
    """Patch `threading.Lock`/`threading.RLock` to the proxy factories.
    Must run BEFORE the shared objects under test are constructed: a lock
    created earlier is a plain lock, invisible to the held-set, and every
    access under it looks unprotected (false races)."""
    global _enabled
    with _state_lock:
        if _enabled:
            return
        threading.Lock = _proxy_lock
        threading.RLock = _proxy_rlock
        _enabled = True


def disable() -> None:
    global _enabled
    with _state_lock:
        if not _enabled:
            return
        threading.Lock = _REAL_LOCK
        threading.RLock = _REAL_RLOCK
        _enabled = False


# ---------------------------------------------------------------------------
# attribute tracking
# ---------------------------------------------------------------------------


@dataclass
class _FieldState:
    first_tid: int
    state: str = _EXCLUSIVE
    lockset: Optional[set] = None  # None = universe (not yet shared)
    last_thread: str = ""
    last_op: str = ""
    last_stack: List[str] = field(default_factory=list)
    reported: bool = False


def _capture_stack() -> List[str]:
    """Frame-walk stack capture: traceback.extract_stack touches linecache
    (source file I/O) on every call, which is ruinous at one capture per
    tracked attribute access — this walks sys._getframe and formats
    `File "...", line N, in fn` lines only, no source text."""
    out: List[str] = []
    f = sys._getframe(3)  # skip _capture_stack, _track, and the wrapper
    depth = 0
    while f is not None and depth < _STACK_LIMIT:
        code = f.f_code
        out.append(
            f'  File "{code.co_filename}", line {f.f_lineno}, '
            f"in {code.co_name}\n"
        )
        f = f.f_back
        depth += 1
    out.reverse()
    return out


def _track(obj: Any, name: str, op: str) -> None:
    if not _enabled:
        return
    if getattr(_tls, "in_tracker", False):
        return
    _tls.in_tracker = True
    try:
        try:
            d = object.__getattribute__(obj, "__dict__")
        except AttributeError:
            return  # __slots__ class: nowhere to hang field state
        fields = d.get("_phantsan_fields")
        if fields is None:
            fields = d["_phantsan_fields"] = {}
            d["_phantsan_fields_lock"] = _REAL_LOCK()
        tid = threading.get_ident()
        tname = threading.current_thread().name
        with d["_phantsan_fields_lock"]:
            st = fields.get(name)
            if st is None:
                fields[name] = _FieldState(
                    first_tid=tid,
                    last_thread=tname,
                    last_op=op,
                    last_stack=_capture_stack(),
                )
                return
            if st.state == _EXCLUSIVE and tid == st.first_tid:
                st.last_thread, st.last_op = tname, op
                st.last_stack = _capture_stack()
                return
            # a second thread is involved: lockset checking is live
            held_now = set(_held())
            if st.lockset is None:
                st.lockset = held_now
            else:
                st.lockset &= held_now
            if op == "write" or st.state == _SHARED_MOD:
                st.state = _SHARED_MOD
            else:
                st.state = _SHARED
            if st.state == _SHARED_MOD and not st.lockset and not st.reported:
                st.reported = True
                _record(
                    RaceReport(
                        cls_name=type(obj).__name__,
                        attr=name,
                        first_thread=st.last_thread,
                        first_op=st.last_op,
                        first_stack=st.last_stack,
                        second_thread=tname,
                        second_op=op,
                        second_stack=_capture_stack(),
                    )
                )
            st.last_thread, st.last_op = tname, op
            st.last_stack = _capture_stack()
    finally:
        _tls.in_tracker = False


_registered: Dict[type, Tuple[Callable, Callable]] = {}


def register_shared_class(cls: type) -> type:
    """Instrument `cls` so every instance-attribute access runs the
    lockset state machine.  Reads are tracked only for names already in
    the instance `__dict__` (method lookups and class attributes are
    noise, not shared state); dunders and `_phantsan*` bookkeeping are
    skipped.  Idempotent; usable as a decorator."""
    if cls in _registered:
        return cls
    orig_setattr = cls.__setattr__
    orig_getattribute = cls.__getattribute__

    def san_setattr(self, name, value):
        orig_setattr(self, name, value)
        if not name.startswith("_phantsan") and not name.startswith("__"):
            _track(self, name, "write")

    def san_getattribute(self, name):
        value = orig_getattribute(self, name)
        if not name.startswith("_phantsan") and not name.startswith("__"):
            try:
                in_dict = name in object.__getattribute__(self, "__dict__")
            except AttributeError:
                in_dict = False
            if in_dict:
                _track(self, name, "read")
        return value

    cls.__setattr__ = san_setattr
    cls.__getattribute__ = san_getattribute
    _registered[cls] = (orig_setattr, orig_getattribute)
    return cls


def unregister(cls: type) -> None:
    pair = _registered.pop(cls, None)
    if pair is not None:
        cls.__setattr__, cls.__getattribute__ = pair


def registered_classes() -> List[type]:
    return list(_registered)


def unregister_all() -> None:
    for cls in list(_registered):
        unregister(cls)


def register_default_shared_classes() -> List[type]:
    """Register the serving path's shared singletons and engines — the
    objects every Engine API handler thread, scheduler worker, and obs
    poller touches concurrently.  Imports lazily: callers enable the
    sanitizer first, so the classes' locks are built as proxies."""
    from phant_tpu.obs.flight import FlightRecorder
    from phant_tpu.serving.scheduler import VerificationScheduler
    from phant_tpu.utils.trace import Metrics

    targets = [VerificationScheduler, FlightRecorder, Metrics]
    for cls in targets:
        register_shared_class(cls)
    return targets


def wanted() -> bool:
    """True when the environment opts into sanitized runs."""
    return os.environ.get("PHANT_SANITIZE") == "1"
