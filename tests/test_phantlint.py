"""phantlint (phant_tpu/analysis): per-rule true/false-positive fixtures,
suppression + baseline round trips, and the self-check gate over the real
tree (zero non-baselined findings — enforced from inside tier-1).

Pure-ast tests: no jax import, no kernel compiles; the whole file runs in
a couple of seconds.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from phant_tpu.analysis import Analyzer, default_rules, save_baseline
from phant_tpu.analysis.rules.dtype import DTypeRule
from phant_tpu.analysis.rules.hostsync import HostSyncRule
from phant_tpu.analysis.rules.jithygiene import JitHygieneRule
from phant_tpu.analysis.rules.lock import LockRule
from phant_tpu.analysis.rules.metricname import MetricNameRule

REPO = Path(__file__).resolve().parent.parent


def run_fixture(tmp_path, monkeypatch, files, rules, baseline=None):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for rel, src in files.items():
        (pkg / rel).write_text(src)
    monkeypatch.chdir(tmp_path)
    return Analyzer([pkg], rules, baseline=baseline).run()


# ---------------------------------------------------------------------------
# HOSTSYNC
# ---------------------------------------------------------------------------

HOT_SRC = '''
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def kernel(x):
    return x + 1

def main():
    out = kernel(jnp.zeros((4,), jnp.uint32))
    n = int(out)
    v = out.item()
    host = np.asarray(out)
    fine = np.asarray([1, 2, 3])
    return helper(out), n, v, host, fine

def helper(y):
    return y

def cold():
    out = kernel(jnp.zeros((4,), jnp.uint32))
    return int(out)
'''


def test_hostsync_flags_syncs_only_in_hot_scope(tmp_path, monkeypatch):
    res = run_fixture(
        tmp_path,
        monkeypatch,
        {"hot.py": HOT_SRC},
        [HostSyncRule(entries=("pkg.hot.main",))],
    )
    msgs = [f.message for f in res.new]
    assert len(res.new) == 3, msgs
    assert all(f.context == "pkg.hot.main" for f in res.new)
    assert any(".item()" in m for m in msgs)
    assert any("int(out)" in m for m in msgs)
    assert any("np.asarray(out)" in m for m in msgs)
    # cold() has the same int(out) but is not reachable from main
    assert not any(f.context == "pkg.hot.cold" for f in res.new)


def test_hostsync_taint_flows_through_assignments(tmp_path, monkeypatch):
    src = HOT_SRC + '''
def chained():
    a = kernel(jnp.zeros((4,), jnp.uint32))
    b = a * 2
    c, d = b, 7
    return bool(c)
'''
    res = run_fixture(
        tmp_path,
        monkeypatch,
        {"hot.py": src},
        [HostSyncRule(entries=("pkg.hot.chained",))],
    )
    assert len(res.new) == 1
    assert "bool(c)" in res.new[0].message


STORED_ATTR_SRC = '''
import jax
import jax.numpy as jnp

@jax.jit
def kernel(x):
    return x + 1

class Worker:
    def work(self):
        out = kernel(jnp.zeros((4,), jnp.uint32))
        return out.item()

class Caller:
    def __init__(self):
        self.helper = Worker()

    def go(self):
        return self.helper.work()

def via_var():
    c = Caller()
    return c.helper.work()
'''


def test_hostsync_resolves_stored_attribute_calls(tmp_path, monkeypatch):
    """`self.helper = Worker()` must make `self.helper.work()` resolve into
    Worker.work — with the METHOD as the entry (not the class), so the
    constructor-marker blanket cannot paper over a missing attribute edge
    (the `self.signer.…` gap from ROADMAP open item (b))."""
    res = run_fixture(
        tmp_path,
        monkeypatch,
        {"attrs.py": STORED_ATTR_SRC},
        [HostSyncRule(entries=("pkg.attrs.Caller.go",))],
    )
    assert len(res.new) == 1, [f.message for f in res.new]
    assert res.new[0].context == "pkg.attrs.Worker.work"
    assert ".item()" in res.new[0].message


def test_hostsync_resolves_var_attribute_calls(tmp_path, monkeypatch):
    """`c = Caller(); c.helper.work()` resolves through the constructor-
    typed local AND the stored attribute in one chain."""
    res = run_fixture(
        tmp_path,
        monkeypatch,
        {"attrs.py": STORED_ATTR_SRC},
        [HostSyncRule(entries=("pkg.attrs.via_var",))],
    )
    assert any(f.context == "pkg.attrs.Worker.work" for f in res.new), [
        f.message for f in res.new
    ]


def test_hostsync_disable_comment_suppresses(tmp_path, monkeypatch):
    src = HOT_SRC.replace(
        "    v = out.item()",
        "    v = out.item()  # phantlint: disable=HOSTSYNC — test escape",
    )
    res = run_fixture(
        tmp_path,
        monkeypatch,
        {"hot.py": src},
        [HostSyncRule(entries=("pkg.hot.main",))],
    )
    assert len(res.new) == 2
    assert res.suppressed == 1
    assert not any(".item()" in f.message for f in res.new)


# ---------------------------------------------------------------------------
# DTYPE
# ---------------------------------------------------------------------------

LANE_SRC = '''
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def k(x):
    y = x ^ 0x80000000
    z = x ^ jnp.uint32(0x80000000)
    w = x / 2
    q = x // 2
    s = x.at[0].set(0xFFFFFFFF)
    t = x.at[0].set(np.uint32(0xFFFFFFFF))
    return y, z, w, q, s, t

def pack(n):
    a = np.zeros(n)
    b = np.zeros(n, np.uint32)
    c = np.arange(n)
    d = np.arange(n, dtype=np.int32)
    return a, b, c, d

def host_bigint(v):
    # host-side bigint math is fine — not a lane function
    return (v * 0x100000000) % (2**256 - 977)
'''


def test_dtype_rule_lane_and_creator_checks(tmp_path, monkeypatch):
    res = run_fixture(
        tmp_path, monkeypatch, {"lane.py": LANE_SRC}, [DTypeRule(modules=("pkg.lane",))]
    )
    msgs = [f.message for f in res.new]
    big_lit = [m for m in msgs if "0x80000000" in m and "jnp.uint32" in m]
    assert len(big_lit) == 1, msgs  # the uncast one; the cast one is clean
    assert sum("0xffffffff" in m for m in msgs) == 1, msgs  # uncast .set()
    assert sum("true division" in m for m in msgs) == 1, msgs  # `/` not `//`
    assert sum("without an explicit" in m for m in msgs) == 2, msgs  # a, c
    assert not any("host_bigint" in (f.context or "") for f in res.new)


def test_dtype_rule_out_of_scope_module_is_ignored(tmp_path, monkeypatch):
    res = run_fixture(
        tmp_path,
        monkeypatch,
        {"other.py": LANE_SRC},
        [DTypeRule(modules=("pkg.lane",))],
    )
    assert res.new == []


# ---------------------------------------------------------------------------
# JITHYGIENE
# ---------------------------------------------------------------------------

JIT_SRC = '''
import functools
import jax
import jax.numpy as jnp

TABLE = [1, 2, 3]
FROZEN = (1, 2, 3)

@functools.partial(jax.jit, static_argnames=("m",))
def uses_table(x, *, m):
    for i in range(m):
        x = x + TABLE[i]
    return x

@functools.partial(jax.jit, static_argnames=("zz",))
def bad_static(x):
    return x

@jax.jit
def bad_range(x, n):
    for _ in range(n):
        x = x + 1
    return x

@jax.jit
def bad_default(x, opts=[]):
    return x + len(opts)

@jax.jit
def ok_shape(x):
    return x.reshape(x.shape[0] * 2) + FROZEN[0]
'''


def test_jithygiene_rule(tmp_path, monkeypatch):
    res = run_fixture(tmp_path, monkeypatch, {"jj.py": JIT_SRC}, [JitHygieneRule()])
    msgs = [f.message for f in res.new]
    assert any("static_argnames='zz'" in m for m in msgs), msgs
    assert any("mutable default" in m for m in msgs), msgs
    assert any("`n`" in m and "range() bound" in m for m in msgs), msgs
    assert any("mutable `TABLE`" in m for m in msgs), msgs
    # statics used in range() are fine; tuple constants are fine;
    # .shape reads are static
    assert not any("`m`" in m for m in msgs), msgs
    assert not any("FROZEN" in m for m in msgs), msgs
    assert not any(f.context == "pkg.jj.ok_shape" for f in res.new), msgs


# ---------------------------------------------------------------------------
# LOCK
# ---------------------------------------------------------------------------

LOCK_SRC = '''
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self.stats = {}
        self.stats["init"] = 0  # __init__ is exempt

    def locked_op(self):
        with self._lock:
            self.stats["a"] = 1
            self._private()

    def _helper_locked(self):
        self.stats["b"] = 2

    def _private(self):
        self.stats["c"] = 3

    def racy(self):
        self.stats["d"] = 4
        return self.stats

    def racy_in_except(self):
        try:
            pass
        except Exception:
            self.stats["e"] = 5  # unlocked touch hiding in an error path

_MEMO = None
_MEMO2 = None
_m_lock = threading.Lock()

def get_memo():
    global _MEMO
    if _MEMO is None:
        _MEMO = object()
    return _MEMO

def get_memo2():
    global _MEMO2
    if _MEMO2 is None:
        with _m_lock:
            if _MEMO2 is None:
                _MEMO2 = object()
    return _MEMO2

def set_config(v):
    global _MEMO  # unconditional setter, no lazy-init test: not flagged
    _MEMO = v
'''


def test_lock_rule_guarded_attr_and_lazy_init(tmp_path, monkeypatch):
    res = run_fixture(tmp_path, monkeypatch, {"eng.py": LOCK_SRC}, [LockRule()])
    contexts = sorted(f.context for f in res.new)
    # racy() touches guarded stats unlocked -> two findings (store + return)
    assert all("racy" in c or "get_memo" in c for c in contexts), contexts
    assert any("Engine.racy" in c for c in contexts)
    # except-handler bodies are scanned too (error paths hide races)
    assert any("Engine.racy_in_except" in c for c in contexts), contexts
    assert any(c == "pkg.eng.get_memo" for c in contexts)
    # locked helper conventions + locked lazy init + plain setter are clean
    assert not any("_helper_locked" in c for c in contexts)
    assert not any("_private" in c for c in contexts)
    assert not any("get_memo2" in c for c in contexts)
    assert not any("set_config" in c for c in contexts)


def test_lock_rule_outer_alias_handler_idiom(tmp_path, monkeypatch):
    src = '''
import threading

class Server:
    def __init__(self, chain):
        self.chain = chain
        self._lock = threading.Lock()
        outer = self

        class Handler:
            def handle(self):
                with outer._lock:
                    outer.chain.run()

            def racy_handle(self):
                outer.chain.run()
'''
    res = run_fixture(tmp_path, monkeypatch, {"srv.py": src}, [LockRule()])
    assert len(res.new) == 1, [f.message for f in res.new]
    assert "racy_handle" in res.new[0].context


# ---------------------------------------------------------------------------
# METRICNAME
# ---------------------------------------------------------------------------

TRACEY_SRC = '''
METRIC_HELP = {
    "good.metric": "a fine metric",
    "dead.metric": "never emitted anywhere",
}

class _M:
    def count(self, name, delta=1, **labels): ...
    def phase(self, name): ...

metrics = _M()

def phase(name):
    return metrics.phase(name)
'''

APP_SRC = '''
from pkg.tracey import metrics, phase

def go(n):
    metrics.count("good.metric")
    metrics.count("missing.metric")
    metrics.count("Bad-Name")
    metrics.count(n)
    metrics.count(name=n)
    with phase("good.metric"):
        pass
'''


def test_baseline_does_not_mask_second_identical_finding(tmp_path, monkeypatch):
    """Fingerprints are occurrence-indexed: grandfathering one `int(out)`
    must not swallow a SECOND identical sync added later to the same
    function."""
    one = HOT_SRC  # main() has exactly one int(out)
    rules = [HostSyncRule(entries=("pkg.hot.main",))]
    res = run_fixture(tmp_path, monkeypatch, {"hot.py": one}, rules)
    baseline = tmp_path / "baseline.json"
    save_baseline(baseline, res.findings)
    two = one.replace("    n = int(out)", "    n = int(out)\n    n2 = int(out)")
    res2 = run_fixture(tmp_path, monkeypatch, {"hot.py": two}, rules, baseline)
    assert len(res2.new) == 1, [f.render() for f in res2.new]
    assert "int(out)" in res2.new[0].message


def test_lock_rule_sees_match_case_bodies(tmp_path, monkeypatch):
    if sys.version_info < (3, 10):
        pytest.skip("match statements need Python 3.10+")
    src = '''
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self.stats = {}

    def locked_op(self):
        with self._lock:
            self.stats["a"] = 1

    def dispatch(self, kind):
        match kind:
            case "x":
                self.stats["b"] = 2
'''
    res = run_fixture(tmp_path, monkeypatch, {"eng.py": src}, [LockRule()])
    assert len(res.new) == 1, [f.render() for f in res.new]
    assert "dispatch" in res.new[0].context


def test_metricname_rule(tmp_path, monkeypatch):
    res = run_fixture(
        tmp_path,
        monkeypatch,
        {"tracey.py": TRACEY_SRC, "app.py": APP_SRC},
        [MetricNameRule()],
    )
    msgs = [f.message for f in res.new]
    assert any("'missing.metric' has no METRIC_HELP" in m for m in msgs), msgs
    assert any("'Bad-Name' is not [a-z0-9_.]+" in m for m in msgs), msgs
    # both the positional AND the keyword-passed dynamic name are M1
    assert sum("non-literal metric name" in m for m in msgs) == 2, msgs
    assert any("'dead.metric' is never emitted" in m for m in msgs), msgs
    assert not any("'good.metric'" in m for m in msgs), msgs


def test_metricname_rule_holds_both_names_of_observe_split(tmp_path, monkeypatch):
    """`observe_split` names two families (a phase's CPU and the rest of
    its wall): each is held to the catalog, and a dynamic one is M1."""
    app = '''
from pkg.tracey import metrics

def go(n):
    metrics.observe_split("good.metric", "good.metric", 1.0, 0.5)
    metrics.observe_split("good.metric", "missing.offcpu", 1.0, 0.5)
    metrics.observe_split(n, "good.metric", 1.0, 0.5)
    metrics.observe_split(*n)
'''
    tracey = TRACEY_SRC.replace('"dead.metric": "never emitted anywhere",', "")
    res = run_fixture(
        tmp_path, monkeypatch, {"tracey.py": tracey, "app.py": app}, [MetricNameRule()]
    )
    msgs = [f.message for f in res.new]
    assert len(msgs) == 3, msgs
    assert sum("'missing.offcpu' has no METRIC_HELP" in m for m in msgs) == 1, msgs
    assert sum("non-literal metric name" in m for m in msgs) == 2, msgs


# ---------------------------------------------------------------------------
# SPANNAME
# ---------------------------------------------------------------------------

SPAN_TRACEY_SRC = '''
import contextlib

SPAN_HELP = {
    "good.span": "a fine span",
    "good.event": "a fine flight-event kind",
    "dead.span": "never emitted anywhere",
}

@contextlib.contextmanager
def span(name, **attrs):
    yield None
'''

SPAN_FLIGHT_SRC = '''
class FlightRecorder:
    def record(self, kind, **fields):
        self.record(kind)  # internal pass-through: not a registry site

flight = FlightRecorder()
'''

SPAN_APP_SRC = '''
from pkg.tracey import span
from pkg.flight import flight

def go(n):
    with span("good.span", block=n):
        pass
    with span("missing.span"):
        pass
    with span("Bad-Span"):
        pass
    with span(n):
        pass
    flight.record("good.event", detail=1)
    flight.record("missing.event")
    flight.record(kind=n)
'''


def test_spanname_rule(tmp_path, monkeypatch):
    """SPANNAME holds span()/flight.record() names to the METRICNAME
    discipline against SPAN_HELP: literal, [a-z0-9_.]+, cataloged, no
    dead entries."""
    from phant_tpu.analysis.rules.spanname import SpanNameRule

    res = run_fixture(
        tmp_path,
        monkeypatch,
        {
            "tracey.py": SPAN_TRACEY_SRC,
            "flight.py": SPAN_FLIGHT_SRC,
            "app.py": SPAN_APP_SRC,
        },
        [SpanNameRule()],
    )
    msgs = [f.message for f in res.new]
    assert any("'missing.span' has no SPAN_HELP" in m for m in msgs), msgs
    assert any("'missing.event' has no SPAN_HELP" in m for m in msgs), msgs
    assert any("'Bad-Span' is not [a-z0-9_.]+" in m for m in msgs), msgs
    # the dynamic span name AND the keyword-passed dynamic kind are S1
    assert sum("non-literal span/event name" in m for m in msgs) == 2, msgs
    assert any("'dead.span' is never emitted" in m for m in msgs), msgs
    # cataloged names and the recorder's internal pass-through stay quiet
    assert not any("'good.span'" in m or "'good.event'" in m for m in msgs), msgs


def test_spanname_mutation_uncataloged_span_fails_cli(tmp_path, monkeypatch):
    """Acceptance-style mutation: renaming a cataloged span at its emit
    site makes the SPANNAME gate red twice over (uncataloged emit + dead
    catalog entry) — the trace vocabulary cannot silently fork."""
    from phant_tpu.analysis.rules.spanname import SpanNameRule

    mutated = SPAN_APP_SRC.replace('span("good.span", block=n)', 'span("good.spam", block=n)')
    res = run_fixture(
        tmp_path,
        monkeypatch,
        {
            "tracey.py": SPAN_TRACEY_SRC,
            "flight.py": SPAN_FLIGHT_SRC,
            "app.py": mutated,
        },
        [SpanNameRule()],
    )
    msgs = [f.message for f in res.new]
    assert any("'good.spam' has no SPAN_HELP" in m for m in msgs), msgs
    assert any("'good.span' is never emitted" in m for m in msgs), msgs


# ---------------------------------------------------------------------------
# JNPHOSTLOOP
# ---------------------------------------------------------------------------

JNP_LOOP_SRC = '''
import jax
import jax.numpy as jnp

@jax.jit
def kernel(x):
    out = helper(x)
    for _ in range(4):
        out = jnp.sin(out)  # traced: the loop unrolls at trace time
    return out

def helper(x):
    out = x
    for _ in range(3):
        out = jnp.add(out, 1)  # exempt: reachable from the jitted kernel
    return out

def hot_loop(items):
    out = []
    for it in items:
        out.append(jnp.asarray(it))
    return out

def busy_wait(ready, x):
    while not ready():
        x = jnp.abs(x)
    return x

def fine(items):
    arr = jnp.asarray(items)  # no loop around it
    total = 0
    for it in items:
        total += len(it)  # loop without jnp
    return arr, total

def iter_expr_runs_once(x, n):
    out = []
    for row in jnp.split(x, n):  # the iterable evaluates ONCE: fine
        out.append(len(row))
    else:
        out.append(jnp.size(x))  # else clause runs once too: fine
    return out

def comp_loop(items):
    return [jnp.asarray(it) for it in items]  # per-element dispatch

def comp_iter_once(x, n):
    return [len(row) for row in jnp.split(x, n)]  # iterable once: fine

def annotated(items):
    out = []
    for it in items:
        out.append(jnp.asarray(it))  # phantlint: disable=JNPHOSTLOOP — deliberate per-iteration probe
    return out
'''


def test_jnphostloop_flags_host_loops_only(tmp_path, monkeypatch):
    from phant_tpu.analysis.rules.jnphostloop import JnpHostLoopRule

    res = run_fixture(
        tmp_path, monkeypatch, {"loops.py": JNP_LOOP_SRC}, [JnpHostLoopRule()]
    )
    ctxs = sorted(f.context for f in res.new)
    assert ctxs == [
        "pkg.loops.busy_wait",
        "pkg.loops.comp_loop",
        "pkg.loops.hot_loop",
    ], [f.render() for f in res.new]
    msgs = {f.context: f.message for f in res.new}
    assert "for loop" in msgs["pkg.loops.hot_loop"]
    assert "while loop" in msgs["pkg.loops.busy_wait"]
    assert "comprehension loop" in msgs["pkg.loops.comp_loop"]
    # jitted function, jit-reachable helper, loop-free call, loop without
    # jnp: all quiet; the annotated loop is suppressed (counted, not new)
    assert res.suppressed >= 1


def test_jnphostloop_resolves_from_jax_import_alias(tmp_path, monkeypatch):
    from phant_tpu.analysis.rules.jnphostloop import JnpHostLoopRule

    src = '''
from jax import numpy as jn

def spin(items):
    out = []
    for it in items:
        out.append(jn.asarray(it))
    return out
'''
    res = run_fixture(
        tmp_path, monkeypatch, {"alias.py": src}, [JnpHostLoopRule()]
    )
    assert len(res.new) == 1 and "jn.asarray" in res.new[0].message


def test_jnp_in_host_loop_mutation_turns_gate_red(mutated_tree, monkeypatch):
    """Acceptance mutation: introducing a per-iteration jnp call into a
    host loop on the pipeline path makes the gate red with a JNPHOSTLOOP
    finding at the loop's call site. The anchor is the prefetch stage's
    per-witness assembly loop (_prefetch_plan) — the first occurrence of
    the pattern, and exactly where a stray device call would re-serialize
    the 4th stage."""
    p = mutated_tree / "phant_tpu" / "ops" / "witness_engine.py"
    src = p.read_text()
    mutated = src.replace(
        "        for b, (_root, nodes) in enumerate(witnesses):\n"
        "            counts[b] = len(nodes)\n",
        "        import jax.numpy as jnp\n"
        "        for b, (_root, nodes) in enumerate(witnesses):\n"
        "            counts[b] = jnp.asarray(len(nodes))\n",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [f for f in res.new if f.rule == "JNPHOSTLOOP"]
    assert hits, [f.render() for f in res.new]
    assert "witness_engine" in hits[0].path
    assert "jnp.asarray" in hits[0].message


# ---------------------------------------------------------------------------
# baseline round trip
# ---------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path, monkeypatch):
    rules = [LockRule()]
    res = run_fixture(tmp_path, monkeypatch, {"eng.py": LOCK_SRC}, rules)
    assert res.new, "fixture must produce findings"
    baseline = tmp_path / "baseline.json"
    save_baseline(baseline, res.findings)
    # rerun against the written baseline: everything grandfathered
    res2 = run_fixture(tmp_path, monkeypatch, {"eng.py": LOCK_SRC}, rules, baseline)
    assert res2.new == []
    assert res2.baselined == len(res.findings)
    # baselines key on fingerprints, not line numbers: shifting code down
    # must not resurrect findings
    shifted = "# a new leading comment\n\n" + LOCK_SRC
    res3 = run_fixture(tmp_path, monkeypatch, {"eng.py": shifted}, rules, baseline)
    assert res3.new == []
    # a NEW finding is not masked by the old baseline
    grown = LOCK_SRC + '''
def another_racy(e):
    global _MEMO3
    if _MEMO3 is None:
        _MEMO3 = 1
    return _MEMO3
_MEMO3 = None
'''
    res4 = run_fixture(tmp_path, monkeypatch, {"eng.py": grown}, rules, baseline)
    assert len(res4.new) == 1
    assert "another_racy" in res4.new[0].context
    # fingerprints are cwd-independent: the same baseline matches when the
    # tool runs from a completely different working directory
    (tmp_path / "pkg" / "eng.py").write_text(LOCK_SRC)  # back to the
    # baselined source — res4 left the grown variant on disk
    monkeypatch.chdir("/")
    res5 = Analyzer([tmp_path / "pkg"], rules, baseline=baseline).run()
    assert res5.new == []
    assert res5.baselined == len(res.findings)


# ---------------------------------------------------------------------------
# the real tree: self-check gate + mutation detection
# ---------------------------------------------------------------------------


def _analyze_repo_tree(root: Path, monkeypatch):
    monkeypatch.chdir(root)
    return Analyzer(
        [root / "phant_tpu"],
        default_rules(),
        baseline=root / "scripts" / "phantlint_baseline.json",
    ).run()


def test_phantlint_runs_clean_over_phant_tpu(monkeypatch):
    """THE gate: zero non-baselined findings over the real package — and
    the committed baseline itself stays empty (fix or annotate, don't
    grandfather)."""
    res = _analyze_repo_tree(REPO, monkeypatch)
    assert res.new == [], "\n".join(f.render() for f in res.new)
    committed = json.loads(
        (REPO / "scripts" / "phantlint_baseline.json").read_text()
    )
    assert committed["findings"] == []


@pytest.fixture()
def mutated_tree(tmp_path):
    root = tmp_path / "repo"
    (root / "scripts").mkdir(parents=True)
    shutil.copytree(
        REPO / "phant_tpu",
        root / "phant_tpu",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(
        REPO / "scripts" / "phantlint_baseline.json",
        root / "scripts" / "phantlint_baseline.json",
    )
    return root


def test_reintroduced_item_in_verify_batch_is_caught(mutated_tree, monkeypatch):
    p = mutated_tree / "phant_tpu" / "ops" / "witness_engine.py"
    src = p.read_text()
    mutated = src.replace(
        "        return verdict\n",
        "        _n = verdict.sum().item()\n        return verdict\n",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [f for f in res.new if f.rule == "HOSTSYNC" and ".item()" in f.message]
    assert hits, [f.render() for f in res.new]
    assert "witness_engine" in hits[0].path


def test_mesh_exec_is_in_hostsync_scope(mutated_tree, monkeypatch):
    """The mesh serving hot path (PR 7) is HOSTSYNC-scoped: the pool's
    entries are in DEFAULT_ENTRIES, and a stray `.item()` reintroduced
    into a lane's executor loop turns the gate red."""
    from phant_tpu.analysis.rules.hostsync import DEFAULT_ENTRIES

    assert (
        "phant_tpu.serving.mesh_exec.MeshExecutorPool._run_executor"
        in DEFAULT_ENTRIES
    )
    assert (
        "phant_tpu.serving.mesh_exec.MeshExecutorPool.run_megabatch"
        in DEFAULT_ENTRIES
    )
    p = mutated_tree / "phant_tpu" / "serving" / "mesh_exec.py"
    src = p.read_text()
    mutated = src.replace(
        "                        verdicts = eng2.resolve_batch(handle)\n",
        "                        verdicts = eng2.resolve_batch(handle)\n"
        "                        _n = verdicts.sum().item()\n",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [f for f in res.new if f.rule == "HOSTSYNC" and ".item()" in f.message]
    assert hits, [f.render() for f in res.new]
    assert any("mesh_exec" in f.path for f in hits)


def test_dropped_uint32_cast_is_caught(mutated_tree, monkeypatch):
    kj = mutated_tree / "phant_tpu" / "ops" / "keccak_jax.py"
    src = kj.read_text()
    mutated = src.replace(
        "new_lo[i] = lo[i] ^ words[:, c, 2 * i]",
        "new_lo[i] = lo[i] ^ words[:, c, 2 * i] ^ 0x80000000",
    )
    assert mutated != src
    kj.write_text(mutated)
    sj = mutated_tree / "phant_tpu" / "ops" / "secp256k1_jax.py"
    src = sj.read_text()
    mutated = src.replace(
        "words.at[:, 0, 33].set(jnp.uint32(0x80000000))",
        "words.at[:, 0, 33].set(0x80000000)",
    )
    assert mutated != src
    sj.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    dtype_hits = [f for f in res.new if f.rule == "DTYPE"]
    assert len(dtype_hits) >= 2, [f.render() for f in res.new]
    assert any("keccak_jax" in f.path for f in dtype_hits)
    assert any("secp256k1_jax" in f.path for f in dtype_hits)


# ---------------------------------------------------------------------------
# CLI + shim
# ---------------------------------------------------------------------------


def test_cli_exit_codes_and_json(mutated_tree, monkeypatch):
    p = mutated_tree / "phant_tpu" / "ops" / "witness_engine.py"
    p.write_text(
        p.read_text().replace(
            "        return verdict\n",
            "        _n = verdict.sum().item()\n        return verdict\n",
            1,
        )
    )
    cmd = [
        sys.executable,
        str(REPO / "scripts" / "phantlint.py"),
        "phant_tpu",
        "--baseline",
        "scripts/phantlint_baseline.json",
        "--format=json",
    ]
    proc = subprocess.run(
        cmd, cwd=mutated_tree, capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    assert any(f["rule"] == "HOSTSYNC" for f in payload["new"])
    # clean tree -> rc 0
    proc2 = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "phantlint.py"),
            "phant_tpu",
            "--baseline",
            "scripts/phantlint_baseline.json",
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr


def test_metrics_lint_shim_stays_green():
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "metrics_lint.py")],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[metrics-lint] ok" in proc.stdout


def test_resident_dispatch_is_in_hostsync_scope(mutated_tree, monkeypatch):
    """The resident-table hot path (PR 8) is HOSTSYNC-scoped: the whole
    point of the route is zero host syncs at dispatch, so a reintroduced
    readback in the resident scan/assign/enqueue path must turn the gate
    red."""
    from phant_tpu.analysis.rules.hostsync import DEFAULT_ENTRIES

    assert (
        "phant_tpu.ops.witness_resident.ResidentTable.dispatch"
        in DEFAULT_ENTRIES
    )
    assert (
        "phant_tpu.ops.witness_engine.WitnessEngine.begin_batch"
        in DEFAULT_ENTRIES
    )
    p = mutated_tree / "phant_tpu" / "ops" / "witness_resident.py"
    src = p.read_text()
    mutated = src.replace(
        "        h.uploaded_nodes = len(cand)\n",
        "        _sync = h.verdict_out.sum().item()\n"
        "        h.uploaded_nodes = len(cand)\n",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [
        f
        for f in res.new
        if f.rule == "HOSTSYNC"
        and ".item()" in f.message
        and "witness_resident" in f.path
    ]
    assert hits, [f.render() for f in res.new]


def test_root_engine_is_in_hostsync_scope(mutated_tree, monkeypatch):
    """The batched post-root hot path (PR 11) is HOSTSYNC-scoped: plan
    lowering (the prefetch merge) and the root_many dispatch exist to
    enqueue the merged program with zero host syncs, so a reintroduced
    `.item()` in the level-merge loop must turn the gate red."""
    from phant_tpu.analysis.rules.hostsync import DEFAULT_ENTRIES

    assert (
        "phant_tpu.ops.root_engine.RootEngine.prefetch_batch"
        in DEFAULT_ENTRIES
    )
    assert "phant_tpu.ops.root_engine.RootEngine.root_many" in DEFAULT_ENTRIES
    p = mutated_tree / "phant_tpu" / "ops" / "root_engine.py"
    src = p.read_text()
    mutated = src.replace(
        "        merged, outs = merge_plans(plans, lease=lease)\n",
        "        merged, outs = merge_plans(plans, lease=lease)\n"
        "        _sync = merged.blob.sum().item()\n",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [
        f
        for f in res.new
        if f.rule == "HOSTSYNC"
        and ".item()" in f.message
        and "root_engine" in f.path
    ]
    assert hits, [f.render() for f in res.new]


def test_prefetch_prescan_is_in_hostsync_scope(mutated_tree, monkeypatch):
    """The PR 9 prefetch stage is HOSTSYNC-scoped: the 4th pipeline
    stage exists to take work OFF the serving critical path, so a
    reintroduced device-scalar pull in the pre-scan (or anything it
    reaches) must turn the gate red."""
    from phant_tpu.analysis.rules.hostsync import DEFAULT_ENTRIES

    assert (
        "phant_tpu.ops.witness_engine.WitnessEngine.prefetch_batch"
        in DEFAULT_ENTRIES
    )
    assert (
        "phant_tpu.serving.scheduler.VerificationScheduler._prefetch_run"
        in DEFAULT_ENTRIES
    )
    p = mutated_tree / "phant_tpu" / "ops" / "witness_engine.py"
    src = p.read_text()
    mutated = src.replace(
        "        plan.novel = novel\n",
        "        _sync = counts.sum().item()\n        plan.novel = novel\n",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [
        f
        for f in res.new
        if f.rule == "HOSTSYNC"
        and ".item()" in f.message
        and "witness_engine" in f.path
    ]
    assert hits, [f.render() for f in res.new]


def test_binary_commitment_pack_loop_is_in_hostsync_scope(
    mutated_tree, monkeypatch
):
    """The binary commitment backend's hot paths (PR 12) are
    HOSTSYNC-scoped: the witness pack loop (full-subtree node
    collection) and the proof-path walk are in DEFAULT_ENTRIES, and a
    reintroduced `.item()` inside the pack loop turns the gate red while
    the committed baseline stays EMPTY."""
    from phant_tpu.analysis.rules.hostsync import DEFAULT_ENTRIES

    assert (
        "phant_tpu.commitment.binary.BinaryScheme.collect_nodes"
        in DEFAULT_ENTRIES
    )
    assert (
        "phant_tpu.commitment.binary.BinaryScheme.proof_nodes"
        in DEFAULT_ENTRIES
    )
    p = mutated_tree / "phant_tpu" / "commitment" / "binary.py"
    src = p.read_text()
    mutated = src.replace(
        "            nodes[trie.node_encoding(node)[1]] = None\n",
        "            nodes[trie.node_encoding(node)[1]] = None\n"
        "            _n = node.digest.sum().item()\n",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [
        f for f in res.new if f.rule == "HOSTSYNC" and ".item()" in f.message
    ]
    assert hits, [f.render() for f in res.new]
    assert any("commitment" in f.path for f in hits)


def test_sig_engine_is_in_hostsync_scope(mutated_tree, monkeypatch):
    """The sig lane's hot path (PR 14) is HOSTSYNC-scoped: the merge the
    prefetch stage runs and the sig_many dispatch path are in
    DEFAULT_ENTRIES, and a stray `.item()` reintroduced into the merge
    loop turns the gate red (the resolve stage's honest sender readback
    stays annotated)."""
    from phant_tpu.analysis.rules.hostsync import DEFAULT_ENTRIES

    assert (
        "phant_tpu.ops.sig_engine.SigEngine.prefetch_batch" in DEFAULT_ENTRIES
    )
    assert "phant_tpu.ops.sig_engine.SigEngine.sig_many" in DEFAULT_ENTRIES
    p = mutated_tree / "phant_tpu" / "ops" / "sig_engine.py"
    src = p.read_text()
    mutated = src.replace(
        "        return pack_signatures(es, rs, ss, pars)\n",
        "        _n = pack_signatures(es, rs, ss, pars)[3].sum().item()\n"
        "        return pack_signatures(es, rs, ss, pars)\n",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [f for f in res.new if f.rule == "HOSTSYNC" and ".item()" in f.message]
    assert hits, [f.render() for f in res.new]
    assert any("sig_engine" in f.path for f in hits)


def test_lane_stage_brackets_are_in_hostsync_scope(mutated_tree, monkeypatch):
    """The lanes' measured stages (PR 26) are HOSTSYNC-scoped: the
    pipeline handoff (the bracket around the no-sync begin_batch) and the
    resolve worker are in DEFAULT_ENTRIES, and a stray `.item()`
    reintroduced next to the bracket turns the gate red — observability
    must never put a device sync on the serving hot path."""
    from phant_tpu.analysis.rules.hostsync import DEFAULT_ENTRIES

    assert (
        "phant_tpu.serving.scheduler.VerificationScheduler._pipeline_handoff"
        in DEFAULT_ENTRIES
    )
    assert (
        "phant_tpu.serving.scheduler.VerificationScheduler._resolve_run"
        in DEFAULT_ENTRIES
    )
    p = mutated_tree / "phant_tpu" / "serving" / "scheduler.py"
    src = p.read_text()
    mutated = src.replace(
        "                handle = engine.begin_batch(payload)\n        pipe_item = {\n",
        "                handle = engine.begin_batch(payload)\n"
        "        _n = handle.total.item()\n"
        "        pipe_item = {\n",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [f for f in res.new if f.rule == "HOSTSYNC" and ".item()" in f.message]
    assert hits, [f.render() for f in res.new]
    assert any("scheduler" in f.path for f in hits)


def test_replay_lowering_is_in_hostsync_scope(mutated_tree, monkeypatch):
    """The replay pipeline's prefetch-stage lowering (PR 18) is
    HOSTSYNC-scoped: `lower_segment_plans` groups a segment's root plans
    and enqueues the vmapped megabatch with ZERO host sync, and a stray
    `.item()` reintroduced next to the blob stack turns the gate red
    while the committed baseline stays EMPTY (the resolve stage's honest
    per-root readback lives in resolve_segment_roots, off the list)."""
    from phant_tpu.analysis.rules.hostsync import DEFAULT_ENTRIES

    assert (
        "phant_tpu.replay.lowering.lower_segment_plans" in DEFAULT_ENTRIES
    )
    p = mutated_tree / "phant_tpu" / "replay" / "lowering.py"
    src = p.read_text()
    mutated = src.replace(
        "            blobs = jnp.asarray(",
        "            _n = jnp.asarray(run[0].blob).sum().item()\n"
        "            blobs = jnp.asarray(",
        1,
    )
    assert mutated != src
    p.write_text(mutated)
    res = _analyze_repo_tree(mutated_tree, monkeypatch)
    hits = [f for f in res.new if f.rule == "HOSTSYNC" and ".item()" in f.message]
    assert hits, [f.render() for f in res.new]
    assert any("replay" in f.path for f in hits)


# ---------------------------------------------------------------------------
# Concurrency analysis v2: LOCKORDER / LOCKBLOCK / THREADSHARE + LOCK L2
# ---------------------------------------------------------------------------

from phant_tpu.analysis.rules.lockblock import LockBlockRule
from phant_tpu.analysis.rules.lockorder import LockOrderRule
from phant_tpu.analysis.rules.threadshare import ThreadShareRule

DEADLOCK_SRC = '''
import threading

_A = threading.Lock()
_B = threading.Lock()

def takes_b():
    with _B:
        pass

def ab_path():
    with _A:
        takes_b()   # interprocedural edge A -> B

def ba_path():
    with _B:
        with _A:    # lexical edge B -> A: closes the cycle
            pass

def consistent():
    with _A:
        with _B:    # same order as ab_path: no NEW cycle
            pass
'''


def test_lockorder_flags_ab_ba_cycle(tmp_path, monkeypatch):
    res = run_fixture(
        tmp_path, monkeypatch, {"dl.py": DEADLOCK_SRC}, [LockOrderRule()]
    )
    msgs = [f.message for f in res.new]
    assert len(msgs) == 1, msgs  # one finding per cycle, not per edge
    assert "lock-order cycle" in msgs[0]
    assert "pkg.dl._A" in msgs[0] and "pkg.dl._B" in msgs[0]
    # both witness directions are in the report
    assert "ab_path" in msgs[0] and "ba_path" in msgs[0]


def test_lockorder_self_reacquire_and_instance_conflation(tmp_path, monkeypatch):
    src = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._rlock = threading.RLock()

    def deadlocks_itself(self):
        with self._lock:
            with self._lock:   # non-reentrant: single-thread deadlock
                pass

    def reentrant_ok(self):
        with self._rlock:
            with self._rlock:  # RLock: legal by design
                pass

    def sibling_call(self, other):
        with self._lock:
            other.touch()      # same STATIC id, different instance: skip

    def touch(self):
        with self._lock:
            pass
'''
    res = run_fixture(tmp_path, monkeypatch, {"box.py": src}, [LockOrderRule()])
    msgs = [f.message for f in res.new]
    assert len(msgs) == 1, msgs
    assert "re-acquiring non-reentrant lock" in msgs[0]
    assert "deadlocks" in msgs[0]


BLOCKING_SRC = '''
import queue
import subprocess
import threading
import time

class Lane:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._q = queue.SimpleQueue()
        self._fut = None

    def convoy(self):
        with self._lock:
            return self._fut.result()   # blocks every waiter on _lock

    def drains_queue(self):
        with self._lock:
            return self._q.get()        # typed receiver: queue get under lock

    def waits_ok(self):
        with self._lock:
            self._cond.wait()           # Condition.wait RELEASES the lock

    def indirect(self):
        with self._lock:
            self._helper()              # closure blocks: flagged at this call

    def _helper(self):
        time.sleep(0.1)

    def callee_decided(self):
        with self._lock:
            build()     # build() guards its own blocking op: NOT re-flagged

    def clean(self):
        with self._lock:
            self._fut = None
        return self._q.get()            # outside the lock: fine

_b_lock = threading.Lock()

def build():
    with _b_lock:
        subprocess.run(["true"])        # guarded at its own site: one finding
'''


def test_lockblock_direct_and_interprocedural(tmp_path, monkeypatch):
    res = run_fixture(
        tmp_path, monkeypatch, {"lane.py": BLOCKING_SRC}, [LockBlockRule()]
    )
    by_ctx = {}
    for f in res.new:
        by_ctx.setdefault(f.context, []).append(f.message)
    assert any("Future.result()" in m for m in by_ctx.get("pkg.lane.Lane.convoy", [])), by_ctx
    assert any("queue get()" in m for m in by_ctx.get("pkg.lane.Lane.drains_queue", []))
    # interprocedural: the lock-held call names the inner blocking op
    assert any(
        "time.sleep()" in m and "_helper" in m
        for m in by_ctx.get("pkg.lane.Lane.indirect", [])
    ), by_ctx
    # the guarded subprocess.run is build()'s single finding...
    assert any("subprocess.run()" in m for m in by_ctx.get("pkg.lane.build", []))
    # ...and is NOT propagated to the caller holding another lock
    assert "pkg.lane.Lane.callee_decided" not in by_ctx, by_ctx
    # Condition.wait and the unlocked get are clean
    assert "pkg.lane.Lane.waits_ok" not in by_ctx
    assert "pkg.lane.Lane.clean" not in by_ctx


THREADSHARE_SRC = '''
import threading

class Worker:
    def __init__(self):
        self.state = 0
        self._t = None

    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        self.state += 1      # visible to spawner AND worker, no lock

class LockedWorker:
    def __init__(self):
        self._lock = threading.Lock()
        self.state = 0

    def start(self):
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        with self._lock:
            self.state += 1

# phantlint: immutable — counters only move forward, torn reads benign
class WaivedWorker:
    def __init__(self):
        self.state = 0

    def start(self):
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        self.state += 1

class Registry:
    def __init__(self):
        self.items = {}

    def add(self, k, v):
        self.items = {**self.items, k: v}

REG = Registry()   # module-level singleton: every importing thread shares it

class Unshared:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1          # never crosses a thread: not flagged
'''


def test_threadshare_flags_lockless_shared_classes(tmp_path, monkeypatch):
    res = run_fixture(
        tmp_path, monkeypatch, {"ws.py": THREADSHARE_SRC}, [ThreadShareRule()]
    )
    ctxs = sorted(f.context for f in res.new)
    assert ctxs == ["pkg.ws.Registry", "pkg.ws.Worker"], ctxs
    reg = next(f for f in res.new if f.context == "pkg.ws.Registry")
    assert "module-level singleton" in reg.message
    wrk = next(f for f in res.new if f.context == "pkg.ws.Worker")
    assert "threading.Thread" in wrk.message and "state" in wrk.message


def test_lock_l2_resolves_real_lock_objects(tmp_path, monkeypatch):
    # Pre-tightening, ANY context manager whose dotted name contained
    # "lock" suppressed the lazy-init finding. Now only a resolvable
    # threading.Lock/RLock object does.
    src = '''
import contextlib
import threading

_REAL = threading.Lock()
_MEMO = None
_MEMO2 = None

@contextlib.contextmanager
def lockdown():
    yield   # named like a lock; is not one

def racy_memo():
    global _MEMO
    if _MEMO is None:
        with lockdown():
            _MEMO = object()
    return _MEMO

def safe_memo():
    global _MEMO2
    if _MEMO2 is None:
        with _REAL:
            _MEMO2 = object()
    return _MEMO2
'''
    res = run_fixture(tmp_path, monkeypatch, {"memo.py": src}, [LockRule()])
    ctxs = [f.context for f in res.new]
    assert ctxs == ["pkg.memo.racy_memo"], ctxs


def test_flightrecorder_dump_capacity_regression(tmp_path, monkeypatch):
    # The original (pre-PR-16) FlightRecorder.dump read `self.capacity`
    # outside `self._lock` while resize() rebuilt the ring and wrote
    # capacity under it — a dump racing a resize could stamp the payload
    # with a capacity the ring never had. LOCK must keep flagging the
    # shape so it cannot come back.
    src = '''
import threading
from collections import deque

class FlightRecorder:
    def __init__(self, capacity=512):
        self._lock = threading.Lock()
        self.capacity = capacity
        self._ring = deque(maxlen=capacity)
        self._dump_seq = 0

    def resize(self, capacity):
        with self._lock:
            self.capacity = capacity
            self._ring = deque(self._ring, maxlen=capacity)

    def dump(self, reason):
        payload = {
            "reason": reason,
            "capacity": self.capacity,   # racy read: resize() writes under _lock
        }
        with self._lock:
            self._dump_seq += 1
        return payload
'''
    res = run_fixture(tmp_path, monkeypatch, {"fr.py": src}, [LockRule()])
    hits = [f for f in res.new if "capacity" in f.message]
    assert hits, [f.message for f in res.new]
    assert any(f.context == "pkg.fr.FlightRecorder.dump" for f in hits)
