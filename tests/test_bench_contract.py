"""Driver contract for bench.py: it must print exactly ONE parseable JSON
line with the agreed schema, quickly, on CPU, with every section surviving.

Runs bench.py in a subprocess at tiny shapes (the wall-clock knob the
driver cannot pass itself) and checks the schema — the two prior rounds
each shipped a bench/driver-contract regression in the final commit, so
this is pinned by a test.
"""

import json
import os
import subprocess
import sys

import pytest


def _bench_env():
    """os.environ minus the knobs that must not leak into the bench
    subprocess: conftest's in-process kernel switches
    (PHANT_TPU_MIN_ECRECOVER=1 would route the replay's sender
    recovery through the GLV device ladder, whose XLA-CPU compile alone
    blows the watchdog — the bench's PRODUCTION routing is exactly what
    this contract test is supposed to exercise)."""
    env = dict(os.environ)
    for knob in (
        "PHANT_TPU_FORCE_TRIE",
        "PHANT_TPU_MIN_TRIE",
        "PHANT_TPU_MIN_ECRECOVER",
    ):
        env.pop(knob, None)
    return env


@pytest.mark.slow
def test_bench_prints_one_json_line_with_schema(tmp_path):
    env = _bench_env()
    env.update(
        JAX_PLATFORMS="cpu",
        # isolated single-writer compile cache: conftest globally disables
        # the shared one for pytest (concurrent corruption -> jax segfault),
        # but an uncached bench subprocess recompiles for minutes
        PHANT_NO_COMPILE_CACHE="0",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        PHANT_BENCH_WARM="8",
        PHANT_BENCH_BLOCKS="16",
        PHANT_BENCH_TRIE="1024",
        PHANT_REPLAY_BLOCKS="12",
        PHANT_BENCH_KECCAK_N="2048",
        PHANT_BENCH_SR_ACCOUNTS="256",
        PHANT_BENCH_ECRECOVER="0",  # the jax-cpu ladder is minutes-slow
        PHANT_BENCH_PROBE_RETRIES="0",
    )
    out = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True,
        text=True,
        timeout=1200,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    json_lines = [
        ln for ln in out.stdout.splitlines() if ln.startswith("{")
    ]
    assert len(json_lines) == 1, out.stdout[-2000:]
    rec = json.loads(json_lines[0])
    assert rec["metric"] == "block_witness_verifications_per_sec"
    assert rec["unit"] == "blocks/s"
    assert rec["value"] > 0
    assert rec["vs_baseline"] > 0
    detail = rec["detail"]
    assert detail["timing"] == "forced-readback"
    for key in (
        "cpu_baseline_blocks_per_sec",
        "engine_cpu_blocks_per_sec",
        "replay_cpu_blocks_per_sec",
        "state_root_cpu_p50_ms",
        "keccak_hashes_per_sec",
    ):
        assert key in detail, (key, detail)
    # a CPU bench writes no host number under a device name: the replay's
    # device variant is refused, and the artifact names the refusal
    assert "replay_tpu_blocks_per_sec" not in detail
    assert "needs a TPU" in detail["replay_device_refused"]


@pytest.mark.slow
def test_bench_underruns_external_timeout_with_skipped_budget(tmp_path):
    """The BENCH_r05 postmortem pin (parsed: null, rc=124). The driver
    wraps bench in a SHELL under `timeout -k`, and in round 5 its window
    (~1800s) undercut bench's internal 2400s deadline — the partial-emit
    path could never fire before the external kill. The contract now: the
    internal wall budget (PHANT_BENCH_GLOBAL_TIMEOUT, default 1500) stays
    BELOW the driver window, sections that no longer fit are skipped with
    a `skipped_budget` annotation, and the run exits 0 with ONE parseable
    JSON line long before the external timeout — exercised here with the
    exact driver shape (shell wrapper + `timeout -k`) at a deliberately
    short internal budget."""
    env = _bench_env()
    env.update(
        JAX_PLATFORMS="cpu",
        PHANT_NO_COMPILE_CACHE="0",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        PHANT_BENCH_WARM="8",
        PHANT_BENCH_BLOCKS="16",
        PHANT_BENCH_TRIE="1024",
        PHANT_BENCH_KECCAK_N="2048",
        PHANT_BENCH_ONLY="engine,keccak",
        # internal budget far below the external window, and below the
        # reserve (60s) so every section must take the skip path
        PHANT_BENCH_GLOBAL_TIMEOUT="45",
        PHANT_BENCH_PROBE_RETRIES="0",
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        ["timeout", "-k", "5", "120", "sh", "-c", f"{sys.executable} bench.py"],
        capture_output=True,
        text=True,
        timeout=180,
        env=env,
        cwd=repo,
    )
    # rc 0: bench finished ITSELF — the external timeout (which r05 proved
    # can strand the artifact) never fired
    assert out.returncode == 0, (out.returncode, out.stderr[-2000:])
    json_lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, out.stdout[-2000:]
    rec = json.loads(json_lines[0])
    assert rec["metric"] == "block_witness_verifications_per_sec"
    skipped = rec["detail"].get("skipped_budget")
    assert skipped and "engine" in skipped, rec["detail"]


@pytest.mark.slow
def test_bench_global_deadline_always_prints_json(tmp_path):
    """A device call that never returns must still yield the driver a JSON line: force the
    global deadline to fire almost immediately and check the fallback."""
    env = _bench_env()
    env.update(
        JAX_PLATFORMS="cpu",
        PHANT_NO_COMPILE_CACHE="0",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        PHANT_BENCH_WARM="8",
        PHANT_BENCH_BLOCKS="16",
        PHANT_BENCH_TRIE="1024",
        PHANT_BENCH_GLOBAL_TIMEOUT="3",
        PHANT_BENCH_PROBE_RETRIES="0",
    )
    out = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    json_lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert len(json_lines) == 1, out.stdout[-2000:]
    rec = json.loads(json_lines[0])
    assert rec["detail"].get("global_deadline_hit_s") == 3.0
