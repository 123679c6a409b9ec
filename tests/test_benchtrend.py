"""Regression sentinel (scripts/benchtrend.py): section alignment across
rounds, noise-aware flagging, dead-artifact detection, report-only mode.

Pure-python over synthetic artifacts in a tmp dir; the CLI contract (exit
codes) is pinned via subprocess exactly as the driver/check.sh consume it.
"""

from __future__ import annotations

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location(
    "benchtrend", REPO / "scripts" / "benchtrend.py"
)
benchtrend = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(benchtrend)


def _write_round(d: Path, n: int, detail: dict, value: float = 100.0, rc: int = 0):
    rec = {
        "n": n,
        "rc": rc,
        "parsed": {
            "metric": "block_witness_verifications_per_sec",
            "value": value,
            "unit": "blocks/s",
            "vs_baseline": 1.0,
            "detail": detail,
        },
    }
    (d / f"BENCH_r{n:02d}.json").write_text(json.dumps(rec))


def _write_dead_round(d: Path, n: int, rc: int = 124):
    (d / f"BENCH_r{n:02d}.json").write_text(
        json.dumps({"n": n, "rc": rc, "tail": "...", "parsed": None})
    )


def test_stable_series_not_flagged(tmp_path):
    for n, v in enumerate([100.0, 105.0, 98.0, 102.0], start=1):
        _write_round(tmp_path, n, {"engine_cpu_blocks_per_sec": v * 10}, value=v)
    rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert flags == [], flags
    verdicts = {r["metric"]: r["verdict"] for r in rows}
    assert verdicts["value"] == "ok"
    assert verdicts["engine_cpu_blocks_per_sec"] == "ok"


def test_real_regression_flagged_beyond_noise(tmp_path):
    # stable history (spread well under the 40% floor), then a 3x collapse
    for n, v in enumerate([1000.0, 1050.0, 980.0], start=1):
        _write_round(tmp_path, n, {"engine_cpu_blocks_per_sec": v})
    _write_round(tmp_path, 4, {"engine_cpu_blocks_per_sec": 300.0})
    rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert any("engine_cpu_blocks_per_sec" in f for f in flags), flags


def test_noisy_series_raises_the_bar(tmp_path):
    # history itself swings 3x (the shared-box reality: CHANGES PR 2
    # measured 4752->9436 between identical runs) — the same 60% drop that
    # flags a stable metric must NOT flag here
    for n, v in enumerate([3000.0, 9000.0, 5000.0], start=1):
        _write_round(tmp_path, n, {"engine_cpu_blocks_per_sec": v})
    _write_round(tmp_path, 4, {"engine_cpu_blocks_per_sec": 2000.0})
    rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert flags == [], flags


def test_lower_is_better_direction(tmp_path):
    for n, v in enumerate([10.0, 10.5, 9.8], start=1):
        _write_round(tmp_path, n, {"state_root_cpu_p50_ms": v})
    _write_round(tmp_path, 4, {"state_root_cpu_p50_ms": 30.0})  # 3x slower
    rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert any("state_root_cpu_p50_ms" in f for f in flags), flags
    # and an IMPROVEMENT (lower) must not flag
    _write_round(tmp_path, 4, {"state_root_cpu_p50_ms": 3.0})
    _rows, flags2 = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert flags2 == [], flags2


def test_dead_artifact_is_flagged_and_table_falls_back(tmp_path):
    """The BENCH_r05 shape: latest round has parsed=null. It must flag as
    an artifact failure, while the metric table still evaluates the newest
    round WITH data (so the trend stays readable)."""
    for n, v in enumerate([1000.0, 1020.0, 990.0], start=1):
        _write_round(tmp_path, n, {"engine_cpu_blocks_per_sec": v})
    _write_dead_round(tmp_path, 4)
    rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert any("no parseable artifact" in f and "BENCH_r04" in f for f in flags), flags
    row = next(r for r in rows if r["metric"] == "engine_cpu_blocks_per_sec")
    assert row["verdict"] == "ok" and row["latest"] == 990.0


def test_acked_dead_artifact_reports_but_does_not_flag(tmp_path):
    """The BENCH_ACK graduation contract: a root-caused dead round stops
    failing strict mode forever — via the committed BENCH_ACK file or
    --ack — but it still shows in the table as an `acked` row, and a NEW
    dead round is NOT covered by an old ack."""
    for n, v in enumerate([1000.0, 1020.0, 990.0], start=1):
        _write_round(tmp_path, n, {"engine_cpu_blocks_per_sec": v})
    _write_dead_round(tmp_path, 4)
    # file form, with comments
    (tmp_path / "BENCH_ACK").write_text(
        "# known-dead artifacts\nBENCH_r04  # driver timeout, fixed\n"
    )
    rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert flags == [], flags
    row = next(r for r in rows if r["metric"] == "artifact_health")
    assert row["verdict"] == "acked" and "BENCH_r04" in str(row["latest"])
    # a NEW dead round still flags despite the old ack
    _write_dead_round(tmp_path, 5)
    _rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert any("BENCH_r05" in f for f in flags), flags
    # --ack covers it without touching the file (and exits 0 strictly)
    _rows, flags = benchtrend.analyze(
        str(tmp_path), threshold=0.4, min_prior=2, acks=("BENCH_r05",)
    )
    assert flags == [], flags
    strict = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "benchtrend.py"),
            "--dir", str(tmp_path),
            "--ack", "BENCH_r05",
        ],
        capture_output=True,
        text=True,
    )
    assert strict.returncode == 0, strict.stdout
    assert "acked" in strict.stdout


def test_committed_tree_is_strict_green(tmp_path):
    """check.sh runs benchtrend WITHOUT --report-only: the committed
    tree must be strict-green or the gate is red on arrival. The tree
    carries no bench history today (PR 24 removed the pre-PR-5 records),
    and an empty history is green."""
    real = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "benchtrend.py")],
        capture_output=True,
        text=True,
    )
    assert real.returncode == 0, real.stdout


def test_acked_multichip_round_does_not_flag(tmp_path):
    _write_round(tmp_path, 1, {"engine_cpu_blocks_per_sec": 1.0})
    (tmp_path / "MULTICHIP_r01.json").write_text(
        json.dumps({"n_devices": 8, "rc": 0, "ok": True, "skipped": False})
    )
    (tmp_path / "MULTICHIP_r02.json").write_text(
        json.dumps({"n_devices": 8, "rc": 124, "ok": False, "skipped": False})
    )
    (tmp_path / "BENCH_ACK").write_text("MULTICHIP_r02\n")
    rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert flags == [], flags
    row = next(r for r in rows if r["metric"] == "multichip_ok")
    assert row["verdict"] == "ok"


def test_multichip_health_row(tmp_path):
    _write_round(tmp_path, 1, {"engine_cpu_blocks_per_sec": 1.0})
    (tmp_path / "MULTICHIP_r01.json").write_text(
        json.dumps({"n_devices": 8, "rc": 0, "ok": True, "skipped": False})
    )
    (tmp_path / "MULTICHIP_r02.json").write_text(
        json.dumps({"n_devices": 8, "rc": 124, "ok": False, "skipped": False})
    )
    _rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert any("MULTICHIP_r02" in f for f in flags), flags


def test_multichip_skipped_round_neither_flags_nor_shows_regressed(tmp_path):
    """Row verdict and strict-mode flag must agree: a SKIPPED multichip
    round (no second chip that round) is not a regression in either."""
    _write_round(tmp_path, 1, {"engine_cpu_blocks_per_sec": 1.0})
    (tmp_path / "MULTICHIP_r01.json").write_text(
        json.dumps({"n_devices": 8, "rc": 0, "ok": True, "skipped": False})
    )
    (tmp_path / "MULTICHIP_r02.json").write_text(
        json.dumps({"n_devices": 0, "rc": 0, "ok": False, "skipped": True})
    )
    rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert flags == [], flags
    row = next(r for r in rows if r["metric"] == "multichip_ok")
    assert row["verdict"] == "ok"


def test_cli_exit_codes(tmp_path):
    """Strict mode exits 1 on a flag; --report-only always exits 0 (the
    check.sh contract)."""
    for n, v in enumerate([1000.0, 1020.0, 990.0], start=1):
        _write_round(tmp_path, n, {"engine_cpu_blocks_per_sec": v})
    _write_round(tmp_path, 4, {"engine_cpu_blocks_per_sec": 100.0})
    strict = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "benchtrend.py"), "--dir", str(tmp_path)],
        capture_output=True,
        text=True,
    )
    assert strict.returncode == 1, strict.stdout
    assert "REGRESSED" in strict.stdout
    report = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "benchtrend.py"),
            "--dir",
            str(tmp_path),
            "--report-only",
        ],
        capture_output=True,
        text=True,
    )
    assert report.returncode == 0, report.stdout
    # and the committed tree (no artifacts today) is handled end to end
    real = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "benchtrend.py"), "--report-only"],
        capture_output=True,
        text=True,
    )
    assert real.returncode == 0, real.stdout


def test_serving_load_key_directions():
    """Round-6 `serving_load` section keys: goodput/capacity (`_rps`) is
    higher-is-better, latency percentiles are lower-is-better (with or
    without the `_ms` unit suffix), verdict/rate keys are informational."""
    d = benchtrend._direction
    assert d("serving_load_peak_tput_rps") == "up"
    assert d("serving_load_capacity_rps") == "up"
    assert d("serving_load_p50_ms") == "down"
    assert d("serving_load_p99_ms") == "down"
    assert d("serving_load_p999_ms") == "down"
    assert d("serving_load_head_p99_overload_ms") == "down"
    assert d("some_section_p99") == "down"  # unit-less percentile variant
    assert d("serving_load_shed_rate_overload") is None
    assert d("serving_load_serial_sheds") is None
    assert d("serving_load_adaptive_adjustments") is None
    assert d("serving_load_starved_tenants") is None


def test_serving_mesh_key_directions():
    """Round-7 `serving_mesh` section keys: per-device-count throughput
    (`_blocks_per_sec`) and the scaling ratio (`_speedup`) are
    higher-is-better; device-count and batch-shape echoes are
    informational — a config change must not read as a regression."""
    d = benchtrend._direction
    assert d("serving_mesh_d1_blocks_per_sec") == "up"
    assert d("serving_mesh_d8_blocks_per_sec") == "up"
    assert d("serving_mesh_d8_steady_blocks_per_sec") == "up"
    assert d("serving_mesh_speedup") == "up"
    assert d("serving_mesh_devices") is None
    assert d("serving_mesh_best_devices") is None
    assert d("serving_mesh_batch") is None


def test_serving_mesh_scaling_regression_flags(tmp_path):
    """A collapsed mesh speedup (scaling broke) must flag from the
    committed rounds onward."""
    for n, speedup in enumerate([1.8, 1.9, 1.75], start=1):
        _write_round(tmp_path, n, {"serving_mesh_speedup": speedup})
    _write_round(tmp_path, 4, {"serving_mesh_speedup": 0.6})
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any("serving_mesh_speedup" in f for f in flags)


def test_serving_load_latency_regression_flags(tmp_path):
    """A p999 blowup (the tail the QoS layer exists to bound) must flag
    from round 6 onward; a goodput collapse likewise."""
    for n, (p999, rps) in enumerate(
        [(900.0, 100.0), (950.0, 104.0), (880.0, 98.0)], start=1
    ):
        _write_round(
            tmp_path,
            n,
            {"serving_load_p999_ms": p999, "serving_load_peak_tput_rps": rps},
        )
    _write_round(
        tmp_path,
        4,
        {"serving_load_p999_ms": 4000.0, "serving_load_peak_tput_rps": 20.0},
    )
    _rows, flags = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert any("serving_load_p999_ms" in f for f in flags), flags
    assert any("serving_load_peak_tput_rps" in f for f in flags), flags
    # improvements in either direction must not flag
    _write_round(
        tmp_path,
        4,
        {"serving_load_p999_ms": 400.0, "serving_load_peak_tput_rps": 300.0},
    )
    _rows, flags2 = benchtrend.analyze(str(tmp_path), threshold=0.4, min_prior=2)
    assert flags2 == [], flags2


def test_json_output_parses(tmp_path):
    _write_round(tmp_path, 1, {"engine_cpu_blocks_per_sec": 1000.0})
    _write_round(tmp_path, 2, {"engine_cpu_blocks_per_sec": 1010.0})
    out = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "benchtrend.py"),
            "--dir",
            str(tmp_path),
            "--json",
        ],
        capture_output=True,
        text=True,
    )
    rec = json.loads(out.stdout)
    assert "rows" in rec and "flags" in rec


def test_witness_resident_key_directions():
    """Round-8 `witness_resident` section keys: the slope-timed chained
    rates (`_slope_blocks_per_sec` — THE headline metric on real
    accelerators) and the slope/baseline ratio are higher-is-better;
    byte-accounting and shape echoes are informational. Pinned so a
    direction-suffix rework cannot silently drop the headline metric."""
    d = benchtrend._direction
    assert d("witness_fused_resident_slope_blocks_per_sec") == "up"
    assert d("witness_resident_first_blocks_per_sec") == "up"
    assert d("witness_resident_steady_blocks_per_sec") == "up"
    assert d("witness_resident_slope_vs_baseline") == "up"
    assert d("witness_resident_local_projection_blocks_per_sec") == "up"
    # echoes/accounting: never flagged as perf regressions
    assert d("witness_resident_blocks") is None
    assert d("resident_novel_bytes_per_block_steady") is None
    assert d("resident_rows") is None
    assert d("witness_bytes_per_block") is None


def test_witness_resident_slope_regression_flags(tmp_path):
    """A collapsed resident slope rate must flag from the committed
    rounds onward (it is the artifact's headline on real hardware)."""
    for n, rate in enumerate([5200.0, 5400.0, 5100.0], start=1):
        _write_round(
            tmp_path, n, {"witness_fused_resident_slope_blocks_per_sec": rate}
        )
    _write_round(
        tmp_path, 4, {"witness_fused_resident_slope_blocks_per_sec": 900.0}
    )
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any(
        "witness_fused_resident_slope_blocks_per_sec" in f for f in flags
    )


def test_witness_stream_key_directions():
    """Round-9 `witness_stream` section keys: the prefetch-on/off serving
    rates trend via the `_per_sec` suffix, the steady-state hit rates
    and the hidden-decode fraction are higher-is-better (a shrinking hit
    rate is the tiered-eviction win regressing; a shrinking hidden
    fraction means the prefetch decode fell back onto the critical
    path), and shape echoes stay informational. Pinned so a
    direction-suffix rework cannot silently un-gate the PR 9 claims."""
    d = benchtrend._direction
    assert d("witness_stream_prefetch_on_blocks_per_sec") == "up"
    assert d("witness_stream_prefetch_off_blocks_per_sec") == "up"
    assert d("witness_stream_tiered_hit_rate") == "up"
    assert d("witness_stream_flat_hit_rate") == "up"
    assert d("witness_stream_prefetch_hidden_pct") == "up"
    # echoes/accounting: never flagged as perf regressions
    assert d("witness_stream_blocks") is None
    assert d("witness_stream_prefetch_overlap_pct") is None
    assert d("witness_stream_noise_aa_pct") is None
    assert d("witness_stream_cap") is None


def test_witness_stream_hit_rate_regression_flags(tmp_path):
    """A collapsed tiered steady-state hit rate must flag: it is the
    eviction-policy acceptance number (flat-flush behavior creeping
    back would show exactly this signature)."""
    for n, rate in enumerate([0.97, 0.96, 0.97], start=1):
        _write_round(tmp_path, n, {"witness_stream_tiered_hit_rate": rate})
    _write_round(tmp_path, 4, {"witness_stream_tiered_hit_rate": 0.41})
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any("witness_stream_tiered_hit_rate" in f for f in flags)


def test_post_root_key_directions():
    """Round-11 `post_root` section keys: the batched-vs-host median
    paired speedup is higher-is-better (shrinking = the coalesced root
    dispatch regressing toward the host walk), the batched/host root
    rates trend via `_per_sec`, and the A/A noise bar + the lone-request
    parity echo (asserted in-section, not trend-gated) stay
    informational. Pinned so a suffix rework cannot un-gate the PR 11
    claim."""
    d = benchtrend._direction
    assert d("post_root_coalesce_speedup_pct") == "up"
    assert d("post_root_batched_roots_per_sec") == "up"
    assert d("post_root_host_roots_per_sec") == "up"
    assert d("post_root_coalesce_noise_aa_pct") is None
    assert d("post_root_noise_aa_pct") is None
    assert d("post_root_single_parity_pct") is None
    assert d("post_root_batched_vs_host_pct") is None
    assert d("post_root_requests") is None


def test_post_root_speedup_regression_flags(tmp_path):
    """A collapsed coalescing speedup must flag: per-request dispatches
    creeping back onto the request path show exactly this signature."""
    for n, s in enumerate([206.0, 198.0, 210.0], start=1):
        _write_round(tmp_path, n, {"post_root_coalesce_speedup_pct": s})
    _write_round(tmp_path, 4, {"post_root_coalesce_speedup_pct": 12.0})
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any("post_root_coalesce_speedup_pct" in f for f in flags)


def test_commitment_compare_key_directions():
    """Round-12 `commitment_compare` section keys: the binary backend's
    DETERMINISTIC witness-byte savings margin (`_savings_vs_mpt_pct`)
    gates UP and the per-scheme witness bytes per block gate DOWN —
    deliberately overriding the generic `_per_block` info suffix, which
    exists for workload-shape echoes, because these keys ARE the
    section's committed witness-size claim (2504.14069). The noisy
    near-zero throughput margin, shape echoes and node counts stay
    informational."""
    d = benchtrend._direction
    assert d("commitment_binary_witness_savings_vs_mpt_pct") == "up"
    # the throughput margin is parity-within-noise on the proxy box with
    # a near-zero baseline (relative-delta math would flag every in-noise
    # sign flip) — informational; the _blocks_per_sec keys gate the real
    # throughput claims
    assert d("commitment_binary_throughput_vs_mpt_pct") is None
    assert d("commitment_mpt_witness_bytes_per_block") == "down"
    assert d("commitment_binary_witness_bytes_per_block") == "down"
    assert d("commitment_mpt_blocks_per_sec") == "up"
    assert d("commitment_binary_steady_blocks_per_sec") == "up"
    assert d("commitment_mpt_nodes_per_block") is None
    assert d("commitment_compare_blocks") is None
    assert d("commitment_compare_accounts") is None
    # the override is scoped: non-commitment `_bytes_per_block` keys keep
    # their info-suffix behavior (the engine section's workload echo)
    assert d("witness_bytes_per_block") is None


def test_commitment_witness_bloat_flags(tmp_path):
    """A fattened binary witness encoding must flag: the scheme's whole
    reason to exist is the witness-size margin."""
    for n, v in enumerate([5980.0, 6010.0, 5955.0], start=1):
        _write_round(tmp_path, n, {"commitment_binary_witness_bytes_per_block": v})
    _write_round(tmp_path, 4, {"commitment_binary_witness_bytes_per_block": 16000.0})
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any("commitment_binary_witness_bytes_per_block" in f for f in flags)


def test_commitment_savings_collapse_flags(tmp_path):
    """A collapsed savings-vs-mpt margin must flag (the binary backend
    regressing toward — or past — the hexary baseline)."""
    for n, v in enumerate([11.0, 11.4, 10.8], start=1):
        _write_round(
            tmp_path, n, {"commitment_binary_witness_savings_vs_mpt_pct": v}
        )
    _write_round(
        tmp_path, 4, {"commitment_binary_witness_savings_vs_mpt_pct": 0.5}
    )
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any(
        "commitment_binary_witness_savings_vs_mpt_pct" in f for f in flags
    )


def test_sender_lane_key_directions():
    """Round-14 `sender_lane` section keys: the coalescing speedup
    (`_speedup_pct`) and the hidden-fraction audit (`_hidden_pct`) gate
    UP, the merged/native sender rates trend via `_per_sec`, and the A/A
    noise bar, the honest batched-vs-native proxy echo (NEGATIVE on the
    shared-core box — the measured case for the merged offload gate),
    and the shape echoes stay informational. Pinned so a suffix rework
    cannot un-gate the PR 14 claim."""
    d = benchtrend._direction
    assert d("sender_lane_coalesce_speedup_pct") == "up"
    assert d("sender_lane_hidden_pct") == "up"
    assert d("sender_lane_merged_senders_per_sec") == "up"
    assert d("sender_lane_native_senders_per_sec") == "up"
    assert d("sender_lane_coalesce_noise_aa_pct") is None
    assert d("sender_lane_batched_vs_native_pct") is None
    assert d("sender_lane_merged_rows_per_dispatch") is None
    assert d("sender_lane_requests") is None


def test_sender_lane_speedup_regression_flags(tmp_path):
    """A collapsed sig-lane coalescing speedup must flag: per-request
    ecrecover dispatches creeping back onto the serving path show
    exactly this signature."""
    for n, s in enumerate([330.0, 345.0, 338.0], start=1):
        _write_round(tmp_path, n, {"sender_lane_coalesce_speedup_pct": s})
    _write_round(tmp_path, 4, {"sender_lane_coalesce_speedup_pct": 15.0})
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any("sender_lane_coalesce_speedup_pct" in f for f in flags)


def test_obs_overhead_key_directions():
    """Round-15 `obs_overhead` section keys: the attribution-on/off
    median paired overhead gates DOWN (growth = the observability layer
    eating serving throughput) and the critical-path coverage gates UP
    (shrinking = the phase tiling stopped covering a real cost); the
    on/off serving rates trend via `_per_sec`, the A/A noise bar and
    shape echoes stay informational. Pinned so a key rework cannot
    un-gate the PR 15 claims."""
    d = benchtrend._direction
    assert d("obs_overhead_pct") == "down"
    assert d("obs_overhead_coverage_pct") == "up"
    assert d("obs_overhead_on_blocks_per_sec") == "up"
    assert d("obs_overhead_off_blocks_per_sec") == "up"
    assert d("obs_overhead_noise_aa_pct") is None
    assert d("obs_overhead_blocks") is None
    assert d("obs_overhead_pairs") is None
    assert d("obs_overhead_verdict_identity") is None


def test_obs_overhead_regression_flags(tmp_path):
    """Attribution overhead blowing past its noise history must flag —
    the committed claim is 'within the A/A bar', and a 10x growth is the
    layer silently landing on the serving hot path. A collapsed
    coverage flags too (the honesty gauge's trend twin)."""
    for n, (o, c) in enumerate(
        [(2.9, 99.9), (3.1, 99.8), (2.7, 99.9)], start=1
    ):
        _write_round(
            tmp_path,
            n,
            {"obs_overhead_pct": o, "obs_overhead_coverage_pct": c},
        )
    _write_round(
        tmp_path,
        4,
        {"obs_overhead_pct": 31.0, "obs_overhead_coverage_pct": 48.0},
    )
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any("obs_overhead_pct" in f for f in flags)
    assert any("obs_overhead_coverage_pct" in f for f in flags)


def test_timeline_overhead_key_directions():
    """Round-16 `timeline_overhead` section keys: the recorder-on/off
    median paired overhead gates DOWN (growth = the tail-sampled
    timeline layer eating serving throughput); the on/off serving rates
    trend via `_per_sec`; the A/A noise bar and the kept/offered
    reconciliation echoes (asserted in-section, not trend-gated) stay
    informational. Pinned so a key rework cannot un-gate the PR 16
    claim."""
    d = benchtrend._direction
    assert d("timeline_overhead_pct") == "down"
    assert d("timeline_overhead_on_blocks_per_sec") == "up"
    assert d("timeline_overhead_off_blocks_per_sec") == "up"
    assert d("timeline_overhead_noise_aa_pct") is None
    assert d("timeline_overhead_kept") is None
    assert d("timeline_overhead_sampled_out") is None
    assert d("timeline_overhead_offered") is None
    assert d("timeline_overhead_reconciled") is None
    assert d("timeline_overhead_sample_n") is None
    assert d("timeline_overhead_verdict_identity") is None


def test_timeline_overhead_blowup_flags(tmp_path):
    """Timeline overhead blowing past its noise history must flag — the
    committed claim is 'within the A/A bar', and a 10x growth is the
    recorder silently landing on the serving hot path."""
    for n, o in enumerate([1.9, 2.2, 1.7], start=1):
        _write_round(tmp_path, n, {"timeline_overhead_pct": o})
    _write_round(tmp_path, 4, {"timeline_overhead_pct": 24.0})
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any("timeline_overhead_pct" in f for f in flags)


def test_replay_sync_key_directions():
    """Round-18 `replay_sync` section keys: the catch-up throughput
    headline and its serial run_blocks echo gate UP via `_per_sec`, and
    the paired segment-vs-serial margin gates UP via `_speedup_pct`
    (shrinking = per-block dispatch overhead creeping back into the
    segment path); the A/A noise bar and the workload-shape echoes stay
    informational. Pinned so a key rework cannot un-gate the PR 18
    claims."""
    d = benchtrend._direction
    assert d("replay_sync_blocks_per_sec") == "up"
    assert d("replay_sync_serial_blocks_per_sec") == "up"
    assert d("replay_sync_segment_speedup_pct") == "up"
    assert d("replay_sync_noise_aa_pct") is None
    assert d("replay_sync_blocks") is None
    assert d("replay_sync_txs_per_block") is None
    assert d("replay_sync_segment_size") is None
    assert d("replay_sync_pairs") is None
    assert d("replay_sync_identity") is None


def test_replay_sync_throughput_collapse_flags(tmp_path):
    """A collapsed replay throughput must flag from a stable history —
    catch-up regressing to a crawl is exactly the failure the megabatch
    segment path exists to prevent — and so must the segment-vs-serial
    margin going negative (the segment path landing SLOWER than the
    serial loop it amortizes)."""
    for n, (bps, sp) in enumerate(
        [(290.0, 2.1), (301.0, 1.8), (296.0, 2.3)], start=1
    ):
        _write_round(
            tmp_path,
            n,
            {
                "replay_sync_blocks_per_sec": bps,
                "replay_sync_segment_speedup_pct": sp,
            },
        )
    _write_round(
        tmp_path,
        4,
        {
            "replay_sync_blocks_per_sec": 70.0,
            "replay_sync_segment_speedup_pct": -9.0,
        },
    )
    rows, flags = benchtrend.analyze(str(tmp_path), 0.4, 2)
    assert any("replay_sync_blocks_per_sec" in f for f in flags)
    assert any("replay_sync_segment_speedup_pct" in f for f in flags)
