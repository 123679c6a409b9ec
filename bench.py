"""Benchmark: mainnet-shaped block-witness verification throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

TIMING IS SYNC-HONEST: `jax.Array.block_until_ready()` returning early
at large shapes once turned device timings into dispatch-rate
measurements, so every timed region here ends in a forced host readback
(`np.asarray` of the real result), and the measured host<->device link
characteristics (upload MB/s, round-trip latency) are reported in
`detail` so the numbers can be interpreted.

ONE PROCESS PER CHIP: the parent process never initializes jax against
the device. Every device-touching section runs in a child subprocess
(`bench.py --section <name>`) with its own wall-clock budget — a child
hung inside the jax C runtime is simply SIGKILLed, costing its section
and nothing else. Whether a chip is there is a child probe's answer
(detail.tpu_probe_attempts records every attempt with timestamps);
JAX_PLATFORMS=cpu asks for a CPU bench outright. Device sections run
FIRST (before CPU baselines spend the global budget), each emits its
result fragment the moment it finishes. Datasets (witness chain, replay
chain) are built once outside any watchdog and cached on disk under
build/bench_cache keyed by shape params, so repeat runs spend their
window on transfers and compute, not setup.
Set PHANT_BENCH_ONLY=engine,ecrecover,... to run a subset section by
section.

Headline workload (BASELINE.md config #3/#5 shaped): a chain of blocks
over an EVOLVING 65536-leaf state trie (each block reads ~32 accounts —
hot/cold skewed like mainnet — writes 8, and ships a pre-state multiproof
witness incl. storage subtrees). Every witness is FULLY verified: every
node keccak256-hashed AND the parent->child hash linkage checked, so the
witness must form a connected subtree rooted at the block's expected state
root. Verifiers measured on the SAME span:

  * cpu_baseline — the reference-equivalent cold path: per block, batch-
    keccak every node (native C), scan child refs, check connectivity.
    No cross-block reuse, exactly the reference's recompute-per-block
    design (src/crypto/hasher.zig:4-17, src/mpt/mpt.zig:38-119).
  * headline value — the framework path (`--crypto_backend=tpu`): the
    memoized WitnessEngine (phant_tpu/ops/witness_engine.py), novel-node
    hashing batched on device, linkage as vectorized integer joins.
  * engine-cpu (detail) — the same engine hashing on native C: isolates
    architecture-vs-chip contribution honestly.
  * engine_cached_ceiling (detail) — the engine with every span node
    already interned: the zero-novel-work steady state (pure linkage).
  * sched_verify_many (detail) — the same span through the continuous-
    batching scheduler's offline verify_many (phant_tpu/serving/): the
    IDENTICAL admission/assembly/executor code the Engine API serves
    with, plus the mean assembled batch size; sched_depth1/sched_depth2
    are the native-route pipeline-depth parity pair (the CPU path is
    intern-table bound, so depth 2 must track depth 1).
  * serving_load (CPU section) — the QoS acceptance harness
    (scripts/loadgen.py): an OPEN-LOOP Poisson generator with bursts, a
    10:1 backfill:head tenant mix, and slow-loris clients against a real
    EngineAPIServer on an ephemeral port; emits the saturation curve
    (throughput vs offered load at 3 points), p50/p99/p999 latency,
    head-of-chain p99 under overload, shed rate, and the server-side
    no-starvation / zero-serial-shed / adaptive-wait verdicts
    (serving_load_* keys; scripts/benchtrend.py knows their directions).
  * serving_mesh (CPU section) — mesh-sharded serving dispatch
    (`--sched-mesh`, phant_tpu/serving/mesh_exec.py): witness throughput
    vs device count through the scheduler's per-device executor pool
    (bucket-affinity routing + spillover), first-pass (hash-bound) and
    steady-state (linkage-bound) rates per point, per-device dispatch
    counters + a lanes-active participation verdict, and verdict
    identity to the single-device path. On this box the virtual mesh scales over
    HOST cores (the honest floor); the ICI device model is the MULTICHIP
    artifact.
  * engine_pipeline (device section) — the PR 5 tentpole's A/B: the
    device-routed engine through the scheduler at pipeline depth 1 vs 2
    (pack of batch N+1 overlapping device compute + digest resolve of
    batch N), paired interleaved runs; `pipeline_overlap_pct` is the
    median paired speedup and `pipeline_noise_aa_pct` the A/A (d1 vs d1)
    noise bar measured the same way. XLA-CPU is the device proxy on
    CPU-only runs.
  * witness_resident (device section) — the device-RESIDENT intern
    table (ops/witness_resident.py): engine-route first/steady rates
    with truly-novel-bytes-per-block accounting (steady must sit well
    below witness bytes/block), verdict identity to the host route, and
    `witness_fused_resident_slope_blocks_per_sec` — the RTT-insensitive
    slope-timed chained rate that becomes the artifact's value /
    vs_baseline on a real accelerator (the >=10x driver capture).
  * witness_stream (device section) — streaming witness ingestion
    (round 9): (a) the 4-stage pipeline's prefetch A/B through the
    scheduler at depth 2 (median paired overlap vs the A/A noise bar,
    plus `witness_stream_prefetch_hidden_pct` — the fraction of the
    decode + pre-scan the executor never waited for, from the phase
    metrics); (b) the over-cap replay A/B of flat-flush vs depth-tiered
    eviction (steady-state hit rates, verdict identity asserted
    in-section). XLA-CPU is the device proxy on CPU-only runs.
  * post_root (device section) — batched post-state-root recomputation
    (round 11, ops/root_engine.py): roots-byte-identity across every
    mutation class (corrupt/dirty-delete included) asserted in-section,
    the coalescing speedup (one MERGED dispatch vs K per-request
    dispatches, median paired vs its A/A bar — the committed claim),
    the honest batched-vs-host number (negative on the XLA-CPU proxy;
    the case for the offload gate), and the lone-request parity echo.
  * obs_overhead (CPU section) — critical-path attribution overhead
    (round 15, obs/critpath.py + obs/busy.py): the depth-2 serving path
    with the attribution layer ON vs OFF (median paired delta vs the
    same-statistic A/A noise bar — acceptance is overhead WITHIN the
    bar), verdict identity asserted per leg, and the in-section
    critical-path coverage assert (attributed phases >= 95% of wall
    clock — the residual gauge's honesty check).
  * sanitizer_overhead (CPU section) — phantsan lockset-sanitizer cost
    (round 17, analysis/sanitizer.py): the depth-2 serving path with
    PHANT_SANITIZE-style instrumentation ON vs OFF (median paired delta
    vs the same-statistic A/A noise bar). The overhead is the committed
    price of the opt-in sanitized gate, NOT expected to sit within the
    bar; in-section acceptance is verdict identity, ZERO race reports on
    the pinned-clean scheduler, and the positive control (a deliberately
    racy class must yield a two-stack report — the sanitizer works).
  * sender_lane (device section) — coalesced sender recovery (round 14,
    ops/sig_engine.py): sender byte-identity vs direct get_senders_batch
    asserted in-section (invalid-signature and pre-EIP-155 blocks
    included), the coalescing speedup (ONE merged ecrecover dispatch vs
    K per-request dispatches, median paired vs its A/A bar — the
    committed claim), the honest batched-vs-native number (negative on
    the XLA-CPU proxy; the case for the merged offload gate), the
    hidden-fraction audit (recovery resolved before the execute phase
    needed it), and the lone-request gate (native path, zero merged
    dispatches).

The cold fused device kernel (everything incl. RLP ref parsing on device,
ops/witness_jax.py witness_verify_fused) is timed honestly per batch, and
additionally with device-RESIDENT witness bytes (upload once, repeated
verify dispatches, pipelined) — the rate with upload out of the loop,
since over a slow host<->device link upload dominates end-to-end.

Secondary metrics in "detail": state-root recompute p50 (BASELINE.md
metric #2; single root AND the K-roots-per-dispatch batched variant with
an explicit routing verdict), a 1000-block mainnet replay through the
full run_block path as four separately-budgeted sections (config #5;
reference hot loop src/blockchain/blockchain.zig:61-205), batched
ecrecover (config #4; the GLV half-width ladder at B=1024 on device),
and the keccak microbench (config #2).

Platform selection is recorded: a run whose probe finds no chip is a CPU
bench with every probe attempt in detail.tpu_probe_attempts
(PHANT_BENCH_REQUIRE_TPU=1 hard-fails instead).

WALL-CLOCK BUDGET (round-5 postmortem): BENCH_r05 shipped `parsed: null`
because the budgets were INVERTED — the internal global deadline defaulted
to 2400s while the driver killed the run at ~1764s elapsed (the r05 tail:
late-probe retries stop at "636s of global budget left" = 2400-636), so
the internal partial-emit deadline could never fire, and the pre-PR3 code
had no SIGTERM handler to catch the external kill. The driver's `timeout`
also wraps a SHELL (`if [ -f bench.py ]; then ...`), and `timeout -k`
escalates to SIGKILL after a short grace — the only robust contract is to
finish FIRST. The bench therefore (a) defaults its internal budget to
1500s, comfortably under the observed driver window, (b) checks the
remaining budget BEFORE each section and skips what no longer fits —
annotated in detail.skipped_budget — instead of starting work the deadline
will destroy, and (c) on SIGTERM/SIGINT emits the partial artifact BEFORE
reaping children. tests/test_bench_contract.py pins the contract by
running bench under a deliberately short shell-wrapped external timeout.

PHASE ATTRIBUTION (detail.metrics): the process metrics registry
(phant_tpu/utils/trace.py) is RESET before each section and snapshotted
after it, so every artifact carries per-section phase attribution instead
of a bare throughput number. Schema:

    detail.metrics = {
      "<section>": {                # CPU sections: "engine", "keccak", ...;
                                    # device children: "<section>_device";
                                    # inline device: "<section>_device_inline"
        "counters":   {name[{labels}]: int, ...},
        "gauges":     {name[{labels}]: float, ...},
        "histograms": {name: {"buckets": [...], "counts": [...],
                              "sum": float, "count": int}, ...},
        "timers":     {name: {"count", "total_s", "mean_s",
                              "min_s", "max_s"}, ...},
      }, ...
    }

The engine section's timers carry the hash-vs-intern-vs-linkage-join
split of WitnessEngine.verify_batch (witness_engine.hash /
witness_engine.intern / witness_engine.linkage_join) plus the
keccak.device_dispatch / keccak.host_readback transfer split on device
runs — the attribution benchmarking-oriented related work uses to locate
the hashing bottleneck. Device-child sections embed their snapshot in
their fragment line under the distinct `<section>_device` key; the parent
deep-merges the `metrics` key, so the CPU and device runs of one section
never clobber each other's attribution.
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import time

import numpy as np

# keccak absorb capacity of the witness kernels: 5 rate-chunks = 680B,
# covering every RLP trie-node size (mirrors ops/witness_jax.py
# WITNESS_MAX_CHUNKS without importing jax into the parent process)
MAX_CHUNKS = 5

_CACHE_SCHEMA = 4  # bump to invalidate build/bench_cache pickles


# ---------------------------------------------------------------------------
# datasets (CPU-only construction; disk-cached so repeat runs spend their
# window on the chip, not on host-side setup)
# ---------------------------------------------------------------------------


def _cache_path(name: str) -> str:
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "build", "bench_cache")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, name)


def _cached(name: str, builder):
    path = _cache_path(f"{name}_v{_CACHE_SCHEMA}.pkl")
    if os.path.exists(path):
        try:
            with open(path, "rb") as f:
                return pickle.load(f)
        except Exception:
            pass  # corrupt/stale cache: rebuild
    obj = builder()
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        pickle.dump(obj, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)
    return obj


def build_witnesses(
    n_blocks: int,
    accounts_per_block: int,
    trie_size: int,
    storage_slots: int = 0,
    storage_reads_per_block: int = 0,
):
    """Synthetic state trie + per-block multiproof witnesses at
    mainnet-like shapes: `trie_size` accounts give real path depth
    (65536 leaves ~= 5-6 nodes/account incl. ~532B branch nodes), and
    witnesses optionally carry storage-subtree proofs hash-linked through
    account leaves (the leaf's storage-root field commits them)."""
    from phant_tpu import rlp
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.mpt.mpt import Trie
    from phant_tpu.mpt.proof import generate_proof

    rng = np.random.default_rng(7)
    storage = Trie()
    storage_keys = []
    for _ in range(storage_slots):
        sk = keccak256(rng.bytes(32))
        storage.put(sk, rlp.encode(rlp.encode_uint(int.from_bytes(rng.bytes(25), "big") + 1)))
        storage_keys.append(sk)
    sroot = storage.root_hash() if storage_slots else None

    trie = Trie()
    keys = []
    for i in range(trie_size):
        addr = rng.bytes(20)
        key = keccak256(addr)
        leaf = rlp.encode(
            [
                rlp.encode_uint(int(rng.integers(0, 1000))),
                rlp.encode_uint(int(rng.integers(0, 10**18))),
                sroot if (sroot is not None and i % 4 == 0) else rng.bytes(32),
                rng.bytes(32),
            ]
        )
        trie.put(key, leaf)
        keys.append(key)
    root = trie.root_hash()

    witnesses = []
    for _ in range(n_blocks):
        idx = rng.choice(len(keys), size=accounts_per_block, replace=False)
        if storage_keys:
            # ensure a storage-root-committing account anchors the storage
            # subtree (otherwise its nodes would be unlinked in the witness)
            idx[0] = int(rng.integers(0, trie_size // 4)) * 4
        nodes: dict = {}
        for i in idx:
            for n in generate_proof(trie, keys[i]):
                nodes[n] = None
        if storage_reads_per_block and storage_keys:
            sidx = rng.choice(
                len(storage_keys), size=storage_reads_per_block, replace=False
            )
            for i in sidx:
                for n in generate_proof(storage, storage_keys[i]):
                    nodes[n] = None
        witnesses.append((root, list(nodes.keys())))
    return witnesses


def build_witness_chain(
    n_blocks: int,
    trie_size: int = 65536,
    hot_set: int = 4096,
    reads: int = 32,
    writes: int = 8,
    storage_slots: int = 0,
    storage_reads_per_block: int = 8,
    seed: int = 7,
):
    """A chain of pre-state witnesses over an EVOLVING trie.

    Each block reads `reads` accounts (75% from a `hot_set`-sized hot set,
    25% uniform — mainnet access is heavily skewed) and writes `writes` of
    them (balance bump), so consecutive witnesses share every node except
    the ones the previous block's writes actually changed. Storage-subtree
    proofs ride along anchored through a committing account leaf, as in
    build_witnesses."""
    from phant_tpu import rlp
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.mpt.mpt import Trie
    from phant_tpu.mpt.proof import generate_proof

    rng = np.random.default_rng(seed)
    storage = Trie()
    storage_keys = []
    for _ in range(storage_slots):
        sk = keccak256(rng.bytes(32))
        storage.put(sk, rlp.encode(rlp.encode_uint(int.from_bytes(rng.bytes(25), "big") + 1)))
        storage_keys.append(sk)
    sroot = storage.root_hash() if storage_slots else None

    def leaf_for(i: int, balance: int) -> bytes:
        return rlp.encode(
            [
                rlp.encode_uint(i % 997),
                rlp.encode_uint(balance),
                sroot if (sroot is not None and i % 4 == 0) else bytes(code_salts[i][:32]),
                bytes(code_salts[i][32:]),
            ]
        )

    code_salts = [rng.bytes(64) for _ in range(trie_size)]
    balances = rng.integers(1, 10**12, size=trie_size).astype(object)
    trie = Trie()
    keys = []
    for i in range(trie_size):
        key = keccak256(rng.bytes(20))
        trie.put(key, leaf_for(i, int(balances[i])))
        keys.append(key)

    chain = []
    hot_set = min(hot_set, trie_size)
    for _b in range(n_blocks):
        hot = rng.choice(hot_set, size=(reads * 3) // 4, replace=False)
        cold = rng.choice(trie_size, size=reads - len(hot), replace=False)
        touched = np.unique(np.concatenate([hot, cold]))
        root = trie.root_hash()
        nodes: dict = {}
        if storage_keys:
            # ensure a storage-root-committing account anchors the subtree
            anchor = int(rng.integers(0, min(hot_set, trie_size) // 4)) * 4
            touched = np.unique(np.append(touched, anchor))
        for i in touched:
            for n in generate_proof(trie, keys[int(i)]):
                nodes[n] = None
        if storage_keys and storage_reads_per_block:
            sidx = rng.choice(
                len(storage_keys), size=storage_reads_per_block, replace=False
            )
            for i in sidx:
                for n in generate_proof(storage, storage_keys[int(i)]):
                    nodes[n] = None
        chain.append((root, list(nodes.keys())))
        # apply the block's writes: next block's witness re-ships exactly
        # the changed paths
        for i in rng.choice(min(hot_set, trie_size), size=writes, replace=False):
            balances[i] = int(balances[i]) + 1
            trie.put(keys[int(i)], leaf_for(int(i), int(balances[i])))
    return chain


def _witness_chain() -> tuple:
    """(warm, span) witness chain at the env-selected shapes, disk-cached."""
    warm_blocks = int(os.environ.get("PHANT_BENCH_WARM", "256"))
    span_blocks = int(os.environ.get("PHANT_BENCH_BLOCKS", "256"))
    trie_size = int(os.environ.get("PHANT_BENCH_TRIE", "65536"))
    reads = int(os.environ.get("PHANT_BENCH_ACCOUNTS", "32"))
    key = f"wchain_{warm_blocks + span_blocks}_{trie_size}_{reads}"
    chain = _cached(
        key,
        lambda: build_witness_chain(
            warm_blocks + span_blocks,
            trie_size=trie_size,
            reads=reads,
            writes=8,
            storage_slots=4096,
            storage_reads_per_block=8,
        ),
    )
    return chain[:warm_blocks], chain[warm_blocks:]


def _build_replay_chain(n_blocks: int, txs_per_block: int):
    """The synthetic mainnet-shaped replay chain
    (phant_tpu/replay/fixture.py build_synthetic_chain) as a PICKLABLE
    tuple (genesis, blocks, genesis_accounts, total_txs, n_calls) — the
    disk cache moves chain construction out of every future bench run's
    budget entirely."""
    from phant_tpu.replay.fixture import build_synthetic_chain

    fix = build_synthetic_chain(n_blocks, txs_per_block)
    n_calls = max(txs_per_block // 2, 1)
    return (
        fix.genesis,
        fix.blocks,
        fix.genesis_accounts,
        txs_per_block + n_calls,
        n_calls,
    )


def _replay_chain() -> tuple:
    """Disk-cached replay chain at the env-selected shapes. Construction
    executes every block with the best available EVM backend (builder) —
    expensive, hence the cache; if a stale cache fails to replay, callers
    delete the file and rebuild."""
    from phant_tpu.backend import set_evm_backend
    from phant_tpu.evm.native_vm import native_available

    n_blocks = int(os.environ.get("PHANT_REPLAY_BLOCKS", "1000"))
    txs_per_block = int(os.environ.get("PHANT_REPLAY_TXS", "8"))
    key = f"rchain_{n_blocks}_{txs_per_block}"

    def build():
        if native_available():
            set_evm_backend("native")
        try:
            return _build_replay_chain(n_blocks, txs_per_block)
        finally:
            set_evm_backend("python")

    return _cached(key, build)


# ---------------------------------------------------------------------------
# watchdogs / partial-result plumbing
# ---------------------------------------------------------------------------


class _SectionTimeout(Exception):
    pass


class _watchdog:
    """SIGALRM guard around bench sections (in-process stalls only; a call
    hung inside the jax C runtime never returns to the interpreter, which
    is why device sections additionally run in killable subprocesses)."""

    def __init__(self, seconds: int | None = None):
        self.seconds = seconds or int(
            os.environ.get("PHANT_BENCH_SECTION_TIMEOUT", "480")
        )

    def __enter__(self):
        import signal

        def fire(_sig, _frm):
            raise _SectionTimeout(f"section exceeded {self.seconds}s")

        self._old = signal.signal(signal.SIGALRM, fire)
        signal.alarm(self.seconds)
        return self

    def __exit__(self, *exc):
        import signal

        signal.alarm(0)
        signal.signal(signal.SIGALRM, self._old)
        return False


_PARTIAL = {"detail": {}}  # progressively filled; the global deadline prints it
_CHILDREN: list = []  # live child Popen handles, killed on forced exit

#: self-imposed wall budget (seconds). MUST stay below the driver's external
#: timeout (observed ~1800s in round 5): the artifact only exists if bench
#: finishes and prints before the outside world kills it (see module
#: docstring, WALL-CLOCK BUDGET).
_GLOBAL_BUDGET = float(os.environ.get("PHANT_BENCH_GLOBAL_TIMEOUT", "1500"))

#: wall-clock held back for the final JSON emit (and the last child reap)
_BUDGET_RESERVE = float(os.environ.get("PHANT_BENCH_BUDGET_RESERVE", "60"))


def _skip_budget(detail: dict, name: str) -> None:
    """Annotate a section the budget no longer fits: the artifact says
    SKIPPED loudly instead of silently lacking the keys."""
    skipped = detail.setdefault("skipped_budget", [])
    if name not in skipped:
        skipped.append(name)
    _log(f"section {name} SKIPPED (wall budget exhausted)")


def _pin_jax_cpu() -> None:
    """Force jax onto the host CPU for inline (non-child) device sections
    (the jax_platforms config outranks the JAX_PLATFORMS env var)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from phant_tpu.ops._cache import enable_compilation_cache

    enable_compilation_cache()


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _chip_efficiency(detail: dict) -> dict:
    """detail.efficiency (VERDICT r4 #9): per-kernel achieved rate against
    an explicit chip roofline, plus the device-seconds already captured,
    so "is the kernel fast or just correct" is answerable from the
    artifact alone.

    Roofline constants (v5e-1, public spec sheet): HBM bandwidth 819 GB/s
    (the keccak kernel reads each payload byte exactly once from HBM, so
    input bytes/s / 819e9 bounds any keccak kernel); the ALU bound is not
    quoted because the measured kernel is far from both and the HBM bound
    is the tighter audit anchor at these arithmetic intensities."""
    HBM_BPS = 819e9
    out: dict = {}
    mbps = detail.get("keccak_pallas_resident_mbps")
    if mbps:
        from phant_tpu.backend import NATIVE_HASH_BPS

        # the minimum host->device upload bandwidth at which shipping
        # novel bytes to this kernel beats hashing them natively
        # (asymptotic, RTT amortized): 1/up < 1/native - 1/device
        inv = 1 / NATIVE_HASH_BPS - 1 / (mbps * 1e6)
        out["keccak"] = {
            "achieved_input_mbps": mbps,
            "hbm_roofline_mbps": HBM_BPS / 1e6,
            "fraction_of_hbm_roofline": round(mbps * 1e6 / HBM_BPS, 4),
            "device_seconds": detail.get("keccak_device_seconds"),
            "offload_crossover_upload_mbps": (
                round(1 / inv / 1e6, 1) if inv > 0 else None
            ),
        }
    rate = detail.get("ecrecover_per_sec")
    if rate:
        # ~2.3M u32 lane-ops per recovery: 256 ladder steps x ~9k ops
        # (double + mixed add + exceptional double on 16x16-bit limbs)
        out["ecrecover"] = {
            "achieved_per_sec": rate,
            "u32_ops_per_sec_est": round(rate * 2.3e6),
            "device_seconds": detail.get("ecrecover_device_seconds"),
        }
    etpu = detail.get("engine_tpu_blocks_per_sec")
    if etpu:
        out["witness_engine"] = {
            "achieved_blocks_per_sec": etpu,
            "cached_linkage_ceiling_blocks_per_sec": detail.get(
                "engine_cached_ceiling_blocks_per_sec"
            ),
            "device_seconds": detail.get("engine_device_seconds"),
            "note": "steady state is host-routed unless the measured link "
            "beats native hashing (see routing + link_* keys)",
        }
    return out


def _emit_final() -> None:
    # the deadline/signal paths call this from a SECOND thread while the
    # main thread may still be inserting keys — serialize a private copy,
    # or json.dumps can die mid-iteration and strand the artifact (the
    # parsed:null failure this function exists to prevent)
    import copy

    live = _PARTIAL.get("detail", {})
    for _ in range(3):
        try:
            detail = copy.deepcopy(live)
            break
        except RuntimeError:  # dict mutated mid-copy: racing main thread
            continue
    else:
        detail = dict(live)  # best effort: top-level snapshot
    eff = _chip_efficiency(detail)
    if eff:
        detail["efficiency"] = eff
    print(
        json.dumps(
            {
                "metric": "block_witness_verifications_per_sec",
                "value": _PARTIAL.get("value", 0.0),
                "unit": "blocks/s",
                "vs_baseline": _PARTIAL.get("vs_baseline", 0.0),
                "detail": detail,
            },
            default=str,
        ),
        flush=True,
    )


def _arm_global_deadline() -> None:
    """Daemon thread: if the whole bench exceeds the wall budget
    (PHANT_BENCH_GLOBAL_TIMEOUT, default 1500s — deliberately BELOW the
    driver's external timeout), print the JSON line from everything
    measured so far, kill any live children, and exit. The driver must
    ALWAYS receive one JSON line; the per-section budget checks normally
    finish the run long before this backstop fires."""
    import threading

    deadline = _GLOBAL_BUDGET

    def fire():
        _PARTIAL["detail"]["global_deadline_hit_s"] = deadline
        # emit FIRST: the artifact must exist even if a child reap hangs
        _emit_final()
        for p in _CHILDREN:
            try:
                p.kill()
            except Exception:
                pass
        os._exit(0)

    t = threading.Timer(deadline, fire)
    t.daemon = True
    t.start()


def _native_hasher():
    """Native C batched keccak as a WitnessEngine hasher (None if no lib)."""
    from phant_tpu.utils.native import load_native

    native = load_native()
    if native is None:
        return None
    return lambda nodes: native.keccak256_batch_fast(nodes)


def _link_profile() -> dict:
    """Measured device-link characteristics (upload MB/s, round-trip ms) —
    the SAME measurement the adaptive offload routing uses
    (phant_tpu/backend.py device_link_profile)."""
    try:
        from phant_tpu.backend import device_link_profile

        up_bps, rtt = device_link_profile()
        out = {
            "link_upload_mbps": round(up_bps / 1e6, 1),
            "link_roundtrip_ms": round(rtt * 1e3, 1),
        }
        if up_bps >= 50e9:
            # the probe hit the sanity clamp: a loopback relay ACKs the
            # upload at memory speed and streams to the chip behind the
            # (measured) round trip, so RTT is the honest link cost here
            out["link_upload_note"] = "clamped: relay-buffered upload"
        return out
    except Exception as e:
        return {"link_probe_error": repr(e)[:120]}


def verify_cpu(witnesses, fast_keccak: bool = False) -> int:
    """CPU baseline: FULL linked verification per block on the native path —
    batch keccak every node, scan child refs (C++ RLP scanner), and check
    that every node is the root or hash-referenced by a same-block node
    (equivalent to subtree connectivity: hash references are acyclic).
    Returns the number of verified blocks.

    Hashing is the SCALAR batch by default — the reference-equivalent
    baseline (the reference hashes one node at a time through Zig std,
    src/crypto/hasher.zig:4-17; SURVEY.md pins the north-star ratio to the
    'Zig-CPU baseline'). fast_keccak=True swaps in the framework's 8-way
    AVX-512 batch so the artifact also records what the same full-recompute
    architecture does with our SIMD primitive (transparency row)."""
    from phant_tpu.utils.native import load_native

    native = load_native()
    if native is None:  # no toolchain: slower pure-Python full check
        from phant_tpu.mpt.proof import verify_witness_linked

        return sum(bool(verify_witness_linked(r, n)) for r, n in witnesses)

    hash_batch = (
        native.keccak256_batch_fast if fast_keccak else native.keccak256_batch
    )
    ok = 0
    for root, nodes in witnesses:
        digests = hash_batch(nodes)
        raw = b"".join(nodes)
        lens = np.asarray([len(n) for n in nodes], np.uint32)
        offsets = np.zeros(len(nodes), np.uint64)
        if len(nodes) > 1:
            offsets[1:] = np.cumsum(lens[:-1])
        blob = np.frombuffer(raw, np.uint8)
        ref_off, _ref_node = native.scan_refs(blob, offsets, lens)
        refset = {raw[o : o + 32] for o in ref_off.tolist()}
        if root in set(digests) and all(
            d == root or d in refset for d in digests
        ):
            ok += 1
    return ok


def _run_engine(warm, span, hasher=None, backend=None, eng_batch=None,
                reps=None):
    """Warm on the prefix, then time the span (verdicts are host numpy —
    the digest readbacks inside intern() make this sync-honest). The
    first-pass rate can only be measured once per engine (the span is
    memoized afterwards), so the measurement repeats on FRESH engines and
    keeps the best pass — single-shot timings on a shared box swing ±25%.
    Returns (span_seconds, novel_hashed, stats, engine)."""
    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.ops.witness_engine import WitnessEngine

    b = eng_batch or int(os.environ.get("PHANT_BENCH_ENGINE_BATCH", "256"))
    if reps is None:
        reps = int(os.environ.get("PHANT_BENCH_ENGINE_REPS", "5"))
    if backend:
        set_crypto_backend(backend)
    try:
        best = float("inf")
        engines = []
        for _ in range(max(reps, 1)):
            eng = WitnessEngine(hasher=hasher)
            engines.append(eng)
            for i in range(0, len(warm), b):
                assert eng.verify_batch(warm[i : i + b]).all()
            warm_hashed = eng.stats["hashed"]
            t0 = time.perf_counter()
            for i in range(0, len(span), b):
                assert eng.verify_batch(span[i : i + b]).all()
            dt = time.perf_counter() - t0
            if dt < best:
                best = dt
                novel = eng.stats["hashed"] - warm_hashed
                stats, engine = dict(eng.stats), eng
        # explicit reset of the non-returned engines: constructing a
        # fresh engine per rep re-seeds the HOST tables, but with a
        # device-resident table the previous rep's device arrays would
        # linger until GC — pass N+1 would time against a box holding N
        # warm resident tables' worth of device memory (and a shared
        # process-level table would silently measure WARM). reset()
        # drops host tables AND the device arrays deterministically.
        for e in engines:
            if e is not engine:
                e.reset()
        return best, novel, stats, engine
    finally:
        if backend:
            set_crypto_backend("cpu")


# ---------------------------------------------------------------------------
# sections — each returns a flat dict fragment merged into detail.
# *_cpu sections never touch jax; *_device sections are run in a child
# subprocess when a real accelerator is expected (parent pins itself to
# jax-cpu, so on a CPU-only run they execute inline as the XLA-CPU path).
# ---------------------------------------------------------------------------


def sec_engine_cpu() -> dict:
    warm, span = _witness_chain()
    n_blocks = len(span)
    node_lists = [nodes for _root, nodes in span]

    verify_cpu(span[:4])  # warm the native lib
    # best-of-3, matching the engine measurement (single passes on a
    # shared box swing ±25%; the RATIO must not ride that noise)
    cpu_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        ok_cpu = verify_cpu(span)
        cpu_s = min(cpu_s, time.perf_counter() - t0)
        assert ok_cpu == n_blocks
    cpu_rate = n_blocks / cpu_s
    # transparency: the same full-recompute baseline with OUR SIMD keccak
    fastk_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        assert verify_cpu(span, fast_keccak=True) == n_blocks
        fastk_s = min(fastk_s, time.perf_counter() - t0)

    # engine on native C hashing (architecture-only contribution)
    ecpu_s, novel, _st, eng = _run_engine(warm, span)
    # fully-cached ceiling: every span node already interned -> the
    # steady-state linkage-only rate (zero cryptography on the hot path)
    t0 = time.perf_counter()
    b = int(os.environ.get("PHANT_BENCH_ENGINE_BATCH", "256"))
    for i in range(0, len(span), b):
        assert eng.verify_batch(span[i : i + b]).all()
    cached_s = time.perf_counter() - t0

    # serving parity: the SAME span through the continuous-batching
    # scheduler's verify_many (phant_tpu/serving/) — identical batching
    # code to the Engine API path, so the artifact records what the
    # admission/assembly layer costs on top of raw verify_batch and what
    # batch sizes the assembler actually forms
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    eng_s = WitnessEngine()
    for i in range(0, len(warm), b):
        assert eng_s.verify_batch(warm[i : i + b]).all()
    with VerificationScheduler(
        engine=eng_s,
        config=SchedulerConfig(max_batch=b, max_wait_ms=2.0, queue_depth=4096),
    ) as sched:
        t0 = time.perf_counter()
        assert sched.verify_many(span).all()
        sched_s = time.perf_counter() - t0
        sched_stats = sched.stats_snapshot()

    # pipeline-depth parity on the native route (no jax): the CPU path is
    # intern-table bound (scan/commit serialize on the engine lock), so
    # depth 2 must track depth 1 within noise — the overlap WIN is
    # measured on the device-routed engine_pipeline section, where the
    # novel-node compute actually leaves the host. Interleaved best-of.
    def _sched_span(depth: int) -> float:
        eng_p = WitnessEngine()
        for i in range(0, len(warm), b):
            assert eng_p.verify_batch(warm[i : i + b]).all()
        with VerificationScheduler(
            engine=eng_p,
            config=SchedulerConfig(
                max_batch=b, max_wait_ms=50.0, queue_depth=4096,
                pipeline_depth=depth,
            ),
        ) as sp:
            t0 = time.perf_counter()
            assert sp.verify_many(span).all()
            return time.perf_counter() - t0

    pd1 = pd2 = float("inf")
    for _ in range(2):
        pd1 = min(pd1, _sched_span(1))
        pd2 = min(pd2, _sched_span(2))

    return {
        "sched_verify_many_blocks_per_sec": round(n_blocks / sched_s, 2),
        "sched_mean_batch": sched_stats["mean_batch"],
        "sched_batches": sched_stats["batches"],
        "sched_depth1_blocks_per_sec": round(n_blocks / pd1, 2),
        "sched_depth2_blocks_per_sec": round(n_blocks / pd2, 2),
        "cpu_baseline_blocks_per_sec": round(cpu_rate, 2),
        "cpu_baseline_fastkeccak_blocks_per_sec": round(n_blocks / fastk_s, 2),
        "engine_cpu_blocks_per_sec": round(n_blocks / ecpu_s, 2),
        "engine_cached_ceiling_blocks_per_sec": round(n_blocks / cached_s, 2),
        "novel_nodes_per_block": round(novel / n_blocks, 1) if novel else None,
        "nodes_per_block": round(sum(len(n) for n in node_lists) / n_blocks, 1),
        "witness_bytes_per_block": round(
            sum(len(n) for nl in node_lists for n in nl) / n_blocks
        ),
        "verification": "linked-multiproof-memoized",
    }


def sec_engine_device() -> dict:
    import jax
    import jax.numpy as jnp

    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.ops.witness_jax import (
        WITNESS_MAX_CHUNKS,
        pack_witness_fused,
        roots_to_words,
        witness_verify_fused,
    )

    # the parent avoids importing jax, so it carries its own copy of the
    # chunk capacity; a retune of the kernel must fail loudly here, not
    # silently measure a different shape than production routes
    assert WITNESS_MAX_CHUNKS == MAX_CHUNKS, (WITNESS_MAX_CHUNKS, MAX_CHUNKS)
    warm, span = _witness_chain()
    n_blocks = len(span)
    node_lists = [nodes for _root, nodes in span]
    out: dict = {"backend": jax.devices()[0].platform}

    # the product path: --crypto_backend=tpu with adaptive link-aware
    # routing (ships a novel batch to the chip only when the measured link
    # says it beats the native hasher)
    edev_s, novel, rstats, _e = _run_engine(warm, span, backend="tpu")
    out["engine_tpu_blocks_per_sec"] = round(n_blocks / edev_s, 2)
    out["routing"] = {
        "device_batches": rstats.get("device_batches", 0),
        "native_batches": rstats.get("native_batches", 0),
    }
    _bank(out)
    # transparency: the device FORCED on every novel batch
    try:
        efrc_s, _n, _s, _e2 = _run_engine(
            warm, span, hasher=WitnessEngine._hash_batch_device,
            eng_batch=256, reps=1,  # transparency row only; minutes-slow
        )
        out["engine_tpu_forced_blocks_per_sec"] = round(n_blocks / efrc_s, 2)
        _bank({"engine_tpu_forced_blocks_per_sec": out["engine_tpu_forced_blocks_per_sec"]})
    except Exception as e:
        out["engine_tpu_forced_error"] = repr(e)[:160]

    # cold fused device kernel (no memoization), honest end-to-end sync
    _, meta0 = pack_witness_fused(node_lists, MAX_CHUNKS)
    pad_nodes = meta0.shape[1]
    roots_d = jnp.asarray(roots_to_words([r for r, _ in span]))

    def dispatch():
        blob, meta16 = pack_witness_fused(
            node_lists, MAX_CHUNKS, pad_nodes_to=pad_nodes
        )
        return witness_verify_fused(
            jnp.asarray(blob),
            jnp.asarray(meta16),
            roots_d,
            max_chunks=MAX_CHUNKS,
            n_blocks=n_blocks,
        )

    ok0 = int(np.asarray(dispatch()).sum())  # compile + check
    assert ok0 == n_blocks
    cold_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        ok_dev = int(np.asarray(dispatch()).sum())  # forced sync
        cold_s = min(cold_s, time.perf_counter() - t0)
        assert ok_dev == n_blocks, f"device {ok_dev}/{n_blocks}"
    out["device_cold_blocks_per_sec"] = round(n_blocks / cold_s, 2)
    _bank({"device_cold_blocks_per_sec": out["device_cold_blocks_per_sec"]})

    # device-RESIDENT witness bytes: upload once, repeated verify
    # dispatches — the rate with upload out of the loop (upload
    # dominates end-to-end on a slow link). Pipelined at depth 4 to amortize
    # the readback round trip; the final np.asarray of every verdict is
    # the honest sync.
    blob, meta16 = pack_witness_fused(node_lists, MAX_CHUNKS, pad_nodes_to=pad_nodes)
    blob_d, meta_d = jnp.asarray(blob), jnp.asarray(meta16)
    fn = lambda: witness_verify_fused(
        blob_d, meta_d, roots_d, max_chunks=MAX_CHUNKS, n_blocks=n_blocks
    )
    assert int(np.asarray(fn()).sum()) == n_blocks  # warm
    depth = 4
    res_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        outs = [fn() for _ in range(depth)]
        oks = [int(np.asarray(o).sum()) for o in outs]  # forced sync, all
        res_s = min(res_s, time.perf_counter() - t0)
        assert all(ok == n_blocks for ok in oks)
    out["device_resident_blocks_per_sec"] = round(n_blocks * depth / res_s, 2)
    out.update(_link_profile())
    return out


def sec_state_root_cpu() -> dict:
    """BASELINE.md metric #2, host side: recompute every node digest of a
    mainnet-block-sized account trie (the reference recomputes roots from
    scratch per block, src/mpt/mpt.zig:38-45 — and skips the state root
    entirely, src/blockchain/blockchain.zig:83-85)."""
    from phant_tpu.ops.mpt_jax import build_hash_plan, execute_plan_host

    trie, expected, _n = _state_root_trie()
    plan = build_hash_plan(trie)
    assert plan is not None
    assert execute_plan_host(plan) == expected  # warm native lib
    cpu_t = []
    for _ in range(7):
        t0 = time.perf_counter()
        assert execute_plan_host(plan) == expected
        cpu_t.append(time.perf_counter() - t0)
    cold_t = []
    for _ in range(3):
        trie._enc_cache.clear()
        t0 = time.perf_counter()
        assert trie.root_hash() == expected
        cold_t.append(time.perf_counter() - t0)
    return {
        "state_root_cpu_p50_ms": round(float(np.median(cpu_t)) * 1e3, 2),
        "state_root_cpu_coldwalk_p50_ms": round(
            float(np.median(cold_t)) * 1e3, 2
        ),
        "state_root_accounts": int(
            os.environ.get("PHANT_BENCH_SR_ACCOUNTS", "2048")
        ),
    }


def _state_root_trie():
    """Deterministic account trie for the state-root sections. Fixed-width
    leaf values so K block-states share one plan structure (batched roots)."""
    from phant_tpu import rlp
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.mpt.mpt import Trie

    rng = np.random.default_rng(11)
    trie = Trie()
    n_accounts = int(os.environ.get("PHANT_BENCH_SR_ACCOUNTS", "2048"))
    for _ in range(n_accounts):
        leaf = rlp.encode(
            [
                rlp.encode_uint(int(rng.integers(0, 1000))),
                rlp.encode_uint(int(rng.integers(0, 10**18))),
                rng.bytes(32),
                rng.bytes(32),
            ]
        )
        trie.put(keccak256(rng.bytes(20)), leaf)
    return trie, trie.root_hash(), n_accounts


def sec_state_root_device() -> dict:
    """Device state root: single fused dispatch p50, PLUS the K-roots-per-
    dispatch batched variant that amortizes the device round trip across a
    span of blocks (VERDICT r3 #4), PLUS the explicit routing verdict the
    production gate (backend.device_offload_pays) would make for this
    shape on the measured link."""
    from phant_tpu.backend import device_offload_pays, device_link_profile
    from phant_tpu.ops.mpt_jax import (
        build_hash_plan,
        execute_plan_host,
        trie_root_device,
        trie_roots_device_batched,
    )

    trie, expected, n_accounts = _state_root_trie()
    plan = build_hash_plan(trie)
    assert plan is not None
    out: dict = {}

    trie_root_device(trie, plan)  # compile + device-residency
    dev_t = []
    for _ in range(7):
        t0 = time.perf_counter()
        assert trie_root_device(trie, plan) == expected
        dev_t.append(time.perf_counter() - t0)
    out["state_root_tpu_p50_ms"] = round(float(np.median(dev_t)) * 1e3, 2)
    _bank({"state_root_tpu_p50_ms": out["state_root_tpu_p50_ms"]})

    # K block-states in one dispatch: same structure, K value-mutated blobs
    # (the production replay shape — consecutive blocks differ only in the
    # leaves they wrote). Each blob is a full independent root recompute.
    K = int(os.environ.get("PHANT_BENCH_SR_BATCH", "16"))
    import copy

    plans = []
    expecteds = []
    rng = np.random.default_rng(13)
    leaf_off, _ln, _hp, _hc = plan.levels[0]
    for k in range(K):
        p = copy.copy(plan)
        p.blob = plan.blob.copy()
        p.device_args = None
        # mutate 8 leaf values in place (balance-field bytes inside the
        # leaf template) — fixed-width values keep the layout identical
        for i in rng.integers(0, len(leaf_off), size=8):
            off = int(leaf_off[int(i)])
            p.blob[off + 40 : off + 48] = np.frombuffer(rng.bytes(8), np.uint8)
        plans.append(p)
        expecteds.append(execute_plan_host(p))
    got = trie_roots_device_batched(plans)  # compile + correctness
    assert got == expecteds, "batched device roots mismatch host"
    bat_t = []
    for _ in range(5):
        t0 = time.perf_counter()
        got = trie_roots_device_batched(plans)
        bat_t.append(time.perf_counter() - t0)
        assert got == expecteds
    per_root_ms = float(np.median(bat_t)) * 1e3 / K
    out["state_root_tpu_batched_per_root_ms"] = round(per_root_ms, 2)
    out["state_root_tpu_batch"] = K

    # the production routing verdict for this exact shape on this link
    nbytes = int(plan.blob.size)
    up_bps, rtt = device_link_profile()
    out["state_root_routing"] = (
        "device"
        if device_offload_pays(nbytes)
        else f"native (link {up_bps / 1e6:.0f}MB/s, rtt {rtt * 1e3:.0f}ms, "
        f"{nbytes}B/root)"
    )
    return out


def sec_keccak_cpu() -> dict:
    from phant_tpu.utils.native import load_native

    rng = np.random.default_rng(17)
    N = int(os.environ.get("PHANT_BENCH_KECCAK_N", "16384"))
    payloads = [rng.bytes(int(rng.integers(32, 577))) for _ in range(N)]
    native = load_native()
    if native is not None:
        native.keccak256_batch(payloads)  # warm
        cpu_s = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            native.keccak256_batch(payloads)
            cpu_s = min(cpu_s, time.perf_counter() - t0)
    else:
        from phant_tpu.crypto.keccak import keccak256

        t0 = time.perf_counter()
        for p in payloads:
            keccak256(p)
        cpu_s = time.perf_counter() - t0
    out = {
        "keccak_cpu_hashes_per_sec": round(N / cpu_s, 1),
        "keccak_batch": N,
    }
    if native is not None and native.has_fast_keccak:
        # the framework's 8-way AVX-512 multi-buffer batch (bit-identical
        # digests; scalar row above stays the reference-equivalent baseline)
        assert native.keccak256_batch_fast(payloads) == native.keccak256_batch(
            payloads
        )
        simd_s = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            native.keccak256_batch_fast(payloads)
            simd_s = min(simd_s, time.perf_counter() - t0)
        out["keccak_cpu_simd_hashes_per_sec"] = round(N / simd_s, 1)
    return out


def _slope_time_chunked(kernel_fn, wd, nd, max_chunks: int, n: int) -> float:
    """Per-invocation device seconds for a chunked-keccak kernel, isolated
    from the link: chain k data-dependent invocations inside ONE jit call
    and fit the slope between k=1 and k=65, reading back a single element.
    A forced full readback per call measures host<->device round
    trips, not compute — a floor that can sit an order of magnitude above
    the actual kernel time."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("k",))
    def chain(w, nch, k):
        def body(_, carry):
            w_c, acc = carry
            out = kernel_fn(w_c, nch, max_chunks=max_chunks)
            return (w_c ^ out[:, None, :1], acc ^ out)

        _, acc = jax.lax.fori_loop(
            0, k, body, (w, jnp.zeros((n, 8), jnp.uint32))
        )
        return acc[:1, :1]

    # wide k spread: the k-hi run must dwarf the link's
    # round-trip jitter or the fitted slope is noise (observed: a k=17
    # spread once fitted 141M hashes/s — 10x the VPU roofline — and a
    # k=65 spread still swung 2x between runs; k=257 puts ~100ms of real
    # compute on the clock, verified against a numpy u64 ground-truth
    # emulation of the full chain).
    khi = 257
    times = {}
    for k in (1, khi):
        np.asarray(chain(wd, nd, k))  # compile + warm
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(chain(wd, nd, k))
            best = min(best, time.perf_counter() - t0)
        times[k] = best
    return max((times[khi] - times[1]) / (khi - 1), 1e-9)


def sec_keccak_device() -> dict:
    """BASELINE.md config #2 on device: end-to-end (host pack -> transfer
    -> hash -> readback) and device-resident rates for BOTH device kernels
    (Pallas and the jnp/XLA fallback), diffed against the native digests.

    Resident rates are slope-timed (see _slope_time_chunked); the
    end-to-end rate keeps the forced-readback methodology since there the
    link IS the thing being measured."""
    import jax.numpy as jnp

    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.ops.keccak_jax import (
        digests_to_bytes,
        keccak256_chunked,
        keccak256_chunked_auto,
        pack_payloads,
    )
    from phant_tpu.utils.native import load_native

    rng = np.random.default_rng(17)
    N = int(os.environ.get("PHANT_BENCH_KECCAK_N", "16384"))
    payloads = [rng.bytes(int(rng.integers(32, 577))) for _ in range(N)]
    native = load_native()
    want = (
        native.keccak256_batch(payloads)
        if native is not None
        else [keccak256(p) for p in payloads]
    )

    def run():
        words, nchunks, _C = pack_payloads(payloads, 5)
        out = keccak256_chunked_auto(
            jnp.asarray(words), jnp.asarray(nchunks), max_chunks=5
        )
        return digests_to_bytes(np.asarray(out))

    got = run()  # compile + warm
    assert got == want, "device keccak mismatch vs native"
    dev_s = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        run()
        dev_s = min(dev_s, time.perf_counter() - t0)

    words, nchunks, _C = pack_payloads(payloads, 5)
    wd, nd = jnp.asarray(words), jnp.asarray(nchunks)
    on_device = os.environ.get("PHANT_BENCH_DEVICE", "0") == "1"
    out = {
        "keccak_hashes_per_sec": round(N / dev_s, 1),
        "keccak_batch": N,
        "timing_resident": (
            "slope(k=1..257 chained)"
            if on_device
            else "per-call (xla-cpu inline: no link to cancel)"
        ),
    }
    nbytes = sum(len(p) for p in payloads)

    def _percall(kernel_fn) -> float:
        # inline XLA-CPU path: no link, so per-call forced-readback
        # timing is honest — and it reuses the already-compiled program
        # instead of paying two cold chain compiles (gate time)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(kernel_fn(wd, nd, max_chunks=5))
            best = min(best, time.perf_counter() - t0)
        return best

    from phant_tpu.ops.keccak_pallas import (
        keccak256_chunked_pallas,
        pallas_available,
    )

    if pallas_available():
        per = (
            _slope_time_chunked(keccak256_chunked_pallas, wd, nd, 5, N)
            if on_device
            else _percall(keccak256_chunked_pallas)
        )
        out["keccak_pallas_resident_hashes_per_sec"] = round(N / per, 1)
        out["keccak_pallas_resident_mbps"] = round(nbytes / per / 1e6, 1)
        out["keccak_device_resident_hashes_per_sec"] = round(N / per, 1)
    if os.environ.get("PHANT_BENCH_KECCAK_JNP", "1") == "1":
        per = (
            _slope_time_chunked(keccak256_chunked, wd, nd, 5, N)
            if on_device
            else _percall(keccak256_chunked)
        )
        out["keccak_jnp_resident_hashes_per_sec"] = round(N / per, 1)
        out.setdefault("keccak_device_resident_hashes_per_sec", round(N / per, 1))
    return out


def _ecrecover_dataset(B: int):
    from phant_tpu.crypto import secp256k1 as cpu_secp
    from phant_tpu.crypto.keccak import keccak256

    rng = np.random.default_rng(3)
    keys = [int.from_bytes(rng.bytes(32), "big") % cpu_secp.N or 1 for _ in range(B)]
    msgs = [keccak256(rng.bytes(64)) for _ in range(B)]
    sigs = [cpu_secp.sign(m, k) for m, k in zip(msgs, keys)]
    expected = [keccak256(cpu_secp.pubkey_of(k)[1:])[12:] for k in keys]
    return msgs, [s[0] for s in sigs], [s[1] for s in sigs], [s[2] for s in sigs], expected


def _ecrecover_B(platform_is_device: bool) -> int:
    if platform_is_device:
        return int(os.environ.get("PHANT_BENCH_ECRECOVER_B", "1024"))
    return 32  # cache-warm small program on the XLA-CPU fallback


def sec_ecrecover_cpu() -> dict:
    """Config #4 baseline: the fused native batch at the SAME batch size
    as the device (symmetry), reference scope src/crypto/ecdsa.zig:19-26."""
    from phant_tpu.crypto import secp256k1 as cpu_secp
    from phant_tpu.utils.native import load_native

    B = _ecrecover_B(os.environ.get("PHANT_BENCH_DEVICE", "0") == "1")
    msgs, rs, ss, recids, _expected = _ecrecover_dataset(B)
    native = load_native()
    if native is not None:
        native_out = native.ecrecover_batch(msgs, rs, ss, recids)  # warm
        assert all(a is not None for a in native_out)
        cpu_s = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            native.ecrecover_batch(msgs, rs, ss, recids)
            cpu_s = min(cpu_s, time.perf_counter() - t0)
        cpu_rate = B / cpu_s
    else:
        sample = 8
        t0 = time.perf_counter()
        for i in range(sample):
            cpu_secp.recover_pubkey(msgs[i], rs[i], ss[i], recids[i])
        cpu_rate = sample / (time.perf_counter() - t0)
    return {"ecrecover_cpu_baseline_per_sec": round(cpu_rate, 1)}


def sec_ecrecover_device() -> dict:
    """Config #4 on device: the Shamir interleaved ladder (the measured
    winner and production default) at the prefetch-window batch size, with
    the GLV half-width ladder (PHANT_ECRECOVER_KERNEL=glv) as comparison."""
    from phant_tpu.ops.secp256k1_jax import ecrecover_batch

    B = _ecrecover_B(os.environ.get("PHANT_BENCH_DEVICE", "0") == "1")
    msgs, rs, ss, recids, expected = _ecrecover_dataset(B)
    out: dict = {"ecrecover_batch": B}

    # compare both ladders on a real device; on the XLA-CPU fallback each
    # extra kernel is minutes of compile for a non-number, so run only the
    # selected one there
    both = (
        os.environ.get("PHANT_BENCH_ECRECOVER_BOTH", "1") == "1"
        and os.environ.get("PHANT_BENCH_DEVICE", "0") == "1"
    )
    kernels = (
        ("glv", "shamir")
        if both
        else (os.environ.get("PHANT_ECRECOVER_KERNEL", "shamir"),)
    )
    best = None
    for kern in kernels:
        os.environ["PHANT_ECRECOVER_KERNEL"] = kern
        try:
            got = ecrecover_batch(msgs, rs, ss, recids)  # compile + check
            assert got == expected, f"device ecrecover ({kern}) mismatch"
            dev_s = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                ecrecover_batch(msgs, rs, ss, recids)
                dev_s = min(dev_s, time.perf_counter() - t0)
            rate = B / dev_s
            out[f"ecrecover_{kern}_per_sec"] = round(rate, 1)
            _bank({f"ecrecover_{kern}_per_sec": out[f"ecrecover_{kern}_per_sec"],
                   "ecrecover_batch": B})
            if best is None or rate > best:
                best = rate
        except Exception as e:
            out[f"ecrecover_{kern}_error"] = repr(e)[:160]
    if best is not None:
        out["ecrecover_per_sec"] = round(best, 1)

    # slope-timed RESIDENT rate for the production (Shamir) kernel: the
    # per-call rates above include one device round trip per invocation;
    # chaining k data-dependent invocations in one dispatch isolates the
    # ladder itself (same methodology as _slope_time_chunked)
    if os.environ.get("PHANT_BENCH_DEVICE", "0") == "1":
        try:
            out.update(_ecrecover_slope(msgs, rs, ss, recids, B))
        except Exception as e:
            out["ecrecover_slope_error"] = repr(e)[:160]
    return out


def _ecrecover_slope(msgs, rs, ss, recids, B: int) -> dict:
    import functools

    import jax
    import jax.numpy as jnp

    from phant_tpu.ops.secp256k1_jax import ecrecover_kernel, ints_to_limbs

    os.environ["PHANT_ECRECOVER_KERNEL"] = "shamir"
    e0 = jnp.asarray(ints_to_limbs([int.from_bytes(m, "big") for m in msgs]))
    r0 = jnp.asarray(ints_to_limbs(rs))
    s0 = jnp.asarray(ints_to_limbs(ss))
    par = jnp.asarray(np.array([rid & 1 for rid in recids], np.uint32))

    @functools.partial(jax.jit, static_argnames=("k",))
    def chain(e, r, s, p, k):
        def body(_, carry):
            e_c, acc = carry
            digest, _valid = ecrecover_kernel(e_c, r, s, p)
            # fold the digest back into the message limbs (mask to the
            # 16-bit limb domain) — data dependency without changing cost
            e_c = e_c.at[:, :8].set(e_c[:, :8] ^ (digest & 0xFFFF))
            return (e_c, acc ^ digest)

        _, acc = jax.lax.fori_loop(
            0, k, body, (e, jnp.zeros((B, 8), jnp.uint32))
        )
        return acc[:1, :1]

    times = {}
    for k in (1, 9):
        np.asarray(chain(e0, r0, s0, par, k))  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(chain(e0, r0, s0, par, k))
            best = min(best, time.perf_counter() - t0)
        times[k] = best
    per = max((times[9] - times[1]) / 8, 1e-9)
    return {"ecrecover_shamir_resident_per_sec": round(B / per, 1)}


def _replay(backend: str, verify_root: bool) -> dict:
    """One replay variant as its own budgeted measurement (VERDICT r3 #2:
    four variants inside one watchdog could never fit; each now emits its
    own partial result)."""
    from phant_tpu.backend import set_crypto_backend, set_evm_backend
    from phant_tpu.blockchain.chain import Blockchain
    from phant_tpu.evm.native_vm import native_available
    from phant_tpu.state.statedb import StateDB

    genesis, blocks, genesis_accounts, total_txs, n_calls = _replay_chain()
    n_blocks = len(blocks)
    if native_available():
        set_evm_backend("native")
    set_crypto_backend(backend)
    out: dict = {}
    prefix = f"replay_{'stateroot_' if verify_root else ''}{backend}"
    try:
        # device variants run TWICE: the first pass eats the XLA kernel
        # compiles (a cold process may miss the persistent
        # cache, so a single cold pass times a
        # multi-minute compile as if it were replay — r4 interim artifacts
        # recorded 2.9 blocks/s cold vs 142+ warm for the SAME code). The
        # cold pass is banked for transparency; the steady-state pass is
        # the headline number.
        passes = 2 if backend != "cpu" else 1
        dt = float("inf")
        for p in range(passes):
            chain = Blockchain(
                1,
                StateDB(
                    {a: acct.copy() for a, acct in genesis_accounts.items()}
                ),
                genesis,
                verify_state_root=verify_root,
            )
            t0 = time.perf_counter()
            # run_blocks pipelines device sender recovery across blocks on
            # the tpu backend and is a plain loop on cpu
            chain.run_blocks(blocks)
            pass_s = time.perf_counter() - t0
            if passes > 1 and p == 0:
                out[f"{prefix}_cold_blocks_per_sec"] = round(
                    n_blocks / pass_s, 1
                )
                _bank(dict(out))
            dt = min(dt, pass_s)
    finally:
        set_crypto_backend("cpu")
        set_evm_backend("python")
    out.update(
        {
            f"{prefix}_blocks_per_sec": round(n_blocks / dt, 1),
            "replay_blocks": n_blocks,
            "replay_txs_per_block": total_txs,
            "replay_contract_calls_per_block": n_calls,
        }
    )
    return out


def _merge_frag(detail: dict, frag: dict) -> None:
    """detail.update(frag), except the per-section `metrics` snapshots
    deep-merge (each section contributes its own key under
    detail.metrics; a flat update would clobber earlier sections)."""
    m = frag.get("metrics")
    if m:
        frag = {k: v for k, v in frag.items() if k != "metrics"}
        detail.setdefault("metrics", {}).update(m)
    detail.update(frag)


def _metrics_reset() -> None:
    from phant_tpu.utils.trace import metrics

    metrics.reset()


def _metrics_frag(section: str) -> dict:
    """{"metrics": {section: snapshot}} for a just-finished section, or {}
    when the section recorded nothing (keeps artifacts lean)."""
    from phant_tpu.utils.trace import metrics

    snap = metrics.snapshot()
    if not any(snap.values()):
        return {}
    return {"metrics": {section: snap}}


def _bank(frag: dict) -> None:
    """Make a finished measurement durable immediately: into _PARTIAL in
    the parent (the global deadline prints it), onto stdout as a fragment
    line in a device child (the parent merges EVERY fragment line, so a
    later SIGKILL costs only the unfinished work — r3 #2's fix)."""
    _merge_frag(_PARTIAL["detail"], frag)
    if _IS_CHILD:
        print(_FRAGMENT_MARK + json.dumps(frag), flush=True)


def _replay_variants(backend: str) -> dict:
    """Both replay variants, each banked the moment it finishes (r3 #2: one
    shared budget lost BOTH numbers when the second variant timed out)."""
    out: dict = {}
    for verify_root in (False, True):
        frag = _replay(backend, verify_root)
        out.update(frag)
        _bank(frag)
    return out


def sec_replay_cpu() -> dict:
    return _replay_variants("cpu")


def sec_replay_sync() -> dict:
    """Historical replay as a first-class megabatch workload (PR 18,
    phant_tpu/replay/): segment-batched catch-up vs serial import.

    A/B on the SAME disk-cached chain with the backend held fixed (cpu
    crypto, the best available EVM on BOTH legs — the claim isolates the
    SEGMENT PIPELINE, not a backend switch):

      * serial leg: `Blockchain.run_blocks` with the sig lane OFF — the
        pre-r18 import loop (per-block `get_senders_batch`, per-block
        host root walk);
      * segment leg: `ReplayEngine` over K-block segments through an
        installed scheduler — the segment's full tx list as ONE merged
        sig-lane launch, segment N+1's rows built and dispatched under
        segment N's EVM execution (replay depth 2).

    Committed keys: `replay_sync_blocks_per_sec` (the catch-up
    headline), `replay_sync_segment_speedup_pct` vs its A/A twin
    `replay_sync_noise_aa_pct` (paired interleaved runs, medians — the
    `sender_lane_coalesce_*` shape), plus the in-section
    FINAL-STATE-ROOT byte-identity assert on EVERY leg pair (the
    differential contract tests/test_replay_sync.py pins per engine
    core). HONESTY: this box has ONE host core, so the segment
    pipeline's overlap (prefetch under EVM) and its device megabatches
    are structurally unavailable — the committed speedup measures
    per-block dispatch/overhead amortization ONLY, the floor of the
    claim; the default chain shape (many thin blocks) is the catch-up
    regime where that per-block overhead is an honest share of the
    import. The merged sig dispatch is pinned to the fused NATIVE batch
    (the XLA-CPU ladder runs far below it — the sender_lane
    offload-gate finding); on a real accelerator lower
    PHANT_BENCH_REPLAY_SYNC_FLOOR to the production 64 so the merged
    launch takes the device kernel, and raise the scheduler depth (the
    1-core proxy pins it to 1: a 2-deep executor pipeline only adds
    stall noise when there is nothing to overlap against)."""
    from phant_tpu import serving
    from phant_tpu.backend import set_evm_backend
    from phant_tpu.blockchain.chain import Blockchain
    from phant_tpu.evm.native_vm import native_available
    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.replay import ReplayEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )
    from phant_tpu.state.statedb import StateDB

    n_blocks = int(os.environ.get("PHANT_BENCH_REPLAY_SYNC_BLOCKS", "960"))
    txs_per_block = int(os.environ.get("PHANT_BENCH_REPLAY_SYNC_TXS", "1"))
    seg = int(os.environ.get("PHANT_BENCH_REPLAY_SYNC_SEGMENT", "48"))
    pairs = int(os.environ.get("PHANT_BENCH_REPLAY_SYNC_PAIRS", "5"))
    floor = int(os.environ.get("PHANT_BENCH_REPLAY_SYNC_FLOOR", str(1 << 30)))

    def build():
        if native_available():
            set_evm_backend("native")
        try:
            return _build_replay_chain(n_blocks, txs_per_block)
        finally:
            set_evm_backend("python")

    genesis, blocks, genesis_accounts, total_txs, _calls = _cached(
        f"rsync_chain_{n_blocks}_{txs_per_block}", build
    )
    out: dict = {
        "replay_sync_blocks": n_blocks,
        "replay_sync_txs_per_block": total_txs,
        "replay_sync_segment_size": seg,
        "replay_sync_pairs": pairs,
    }

    # the serial leg must be the PRE-r18 import loop: lane off via env
    # (the ReplayEngine talks to the installed scheduler directly and
    # does not consult PHANT_BATCHED_SIG)
    sig_env_prev = os.environ.get("PHANT_BATCHED_SIG")
    os.environ["PHANT_BATCHED_SIG"] = "0"
    if native_available():
        set_evm_backend("native")
    s = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=max(16, seg),
            max_wait_ms=2.0,
            pipeline_depth=int(
                os.environ.get("PHANT_BENCH_REPLAY_SYNC_SCHED_DEPTH", "1")
            ),
            # a fixed wait keeps the A/A legs comparable: the adaptive
            # controller re-tunes between legs and its state would be
            # part of the measurement
            adaptive_wait=False,
            sig_engine_factory=lambda: SigEngine(device_floor=floor),
        ),
    )
    serving.install(s)
    try:

        def fresh():
            return Blockchain(
                1,
                StateDB(
                    {a: acct.copy() for a, acct in genesis_accounts.items()}
                ),
                genesis,
                verify_state_root=True,
            )

        import gc

        def t_serial():
            chain = fresh()
            gc.collect()  # no leftover garbage billed to this leg
            t0 = time.perf_counter()
            chain.run_blocks(blocks)
            return time.perf_counter() - t0, chain.state.state_root()

        def t_segment():
            chain = fresh()
            eng = ReplayEngine(
                segment_blocks=seg, pipeline_depth=2, root_mode="host"
            )
            gc.collect()
            t0 = time.perf_counter()
            rep = eng.run(chain, blocks)
            dt = time.perf_counter() - t0
            assert rep.ok and rep.blocks_ok == n_blocks
            # every segment's merged launch genuinely rode the lane
            assert rep.stats["lane_sig_segments"] == rep.segments
            return dt, rep.final_state_root

        # full warm pair: native caches, scheduler lane ramp, allocator
        # steady state — the first measured pair must not eat the cold
        # costs of either leg
        t_serial()
        t_segment()
        speed, aa = [], []
        best_m = best_s = float("inf")
        for rep_i in range(pairs):
            s1, root_s = t_serial()
            m1, root_m = t_segment()
            m2, root_m2 = t_segment()  # the A/A twin: box, not code
            assert root_m == root_s == root_m2, (
                "segment replay diverged from serial run_blocks"
            )
            speed.append(s1 / m1 - 1)
            aa.append(abs(1 - m2 / m1))
            best_m, best_s = min(best_m, m1, m2), min(best_s, s1)
        speed.sort()
        aa.sort()
        frag = {
            "replay_sync_blocks_per_sec": round(n_blocks / best_m, 1),
            "replay_sync_serial_blocks_per_sec": round(n_blocks / best_s, 1),
            "replay_sync_segment_speedup_pct": round(
                speed[len(speed) // 2] * 100, 1
            ),
            "replay_sync_noise_aa_pct": round(aa[len(aa) // 2] * 100, 1),
            "replay_sync_identity": 1,
        }
        out.update(frag)
        _bank(frag)
    finally:
        serving.uninstall(s)
        s.shutdown()
        set_evm_backend("python")
        if sig_env_prev is None:
            os.environ.pop("PHANT_BATCHED_SIG", None)
        else:
            os.environ["PHANT_BATCHED_SIG"] = sig_env_prev
    return out


def sec_serving_load() -> dict:
    """Open-loop serving saturation sweep (scripts/loadgen.py): Poisson
    arrivals with bursts, a 10:1 backfill:head tenant mix, and slow-loris
    clients against a REAL EngineAPIServer on an ephemeral port — the
    QoS acceptance artifact. Emits the saturation curve (throughput vs
    offered load at 3 points around a measured capacity estimate),
    p50/p99/p999 latency at the nominal point, head-of-chain p99 under
    overload, shed rate, and the no-starvation / zero-serial-shed /
    adaptive-wait verdicts from the server's own flight recorder.
    PHANT_BENCH_LOADGEN_SECONDS sizes each load point (default 30)."""
    scripts_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts")
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    import loadgen

    seconds = float(os.environ.get("PHANT_BENCH_LOADGEN_SECONDS", "30"))
    result = loadgen.run_profile(
        seed=6,
        duration_s=seconds,
        multipliers=(0.5, 1.0, 2.0),
        slow_loris=2,
        log=lambda msg: _log(f"serving_load: {msg}"),
    )
    out = loadgen.bench_keys(result)
    out["serving_load_checks"] = result.get("checks")
    return out


def sec_serving_mesh() -> dict:
    """Mesh-sharded serving dispatch (phant_tpu/serving/mesh_exec.py):
    witness throughput vs DEVICE COUNT through the scheduler's
    `--sched-mesh` pool — per-device executors with pinned engines,
    bucket-affinity routing, least-loaded spillover. Two rates per
    device count on the SAME span:

      * `first` — fresh per-device engines, so the span's novel-node
        hashing dominates (the C keccak releases the GIL, so lanes
        genuinely parallelize on host cores; on a real accelerator each
        lane's compute is off-host entirely);
      * `steady` — the same pool re-verifying the span it just interned
        (linkage-join bound, the serving steady state).

    HONESTY: on this CPU box the scaling axis is host cores (the virtual
    mesh's N "devices" share one socket), so the committed curve is the
    host-parallel floor — the ICI-scaled device model is the MULTICHIP
    dryrun artifact, and a real-v5e re-run is the open claim (README
    "Serving" notes this). The section asserts verdict identity to the
    single-device path and RECORDS per-lane participation (dispatch
    lists + `serving_mesh_d{n}_lanes_active` + the
    `serving_mesh_all_lanes_active` verdict — participation depends on
    timing, so it reports rather than crashes the run).
    PHANT_BENCH_MESH_DEVICES picks the curve points (default "1,2,4,8"
    trimmed to host cores)."""
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    warm, span = _witness_chain()
    n_blocks = len(span)
    b = int(os.environ.get("PHANT_BENCH_MESH_BATCH", "32"))
    # default curve: 1,2,4,8 lanes trimmed to the host's core count — on
    # the CPU mesh each lane's compute runs on a host core, so points past
    # the cores only measure oversubscription, not the dispatch layer
    # (PHANT_BENCH_MESH_DEVICES overrides, e.g. "1,2,4,8" on a v5e host)
    cores = max(2, os.cpu_count() or 2)
    default_counts = ",".join(str(n) for n in (1, 2, 4, 8) if n <= cores)
    counts = tuple(
        int(x)
        for x in os.environ.get(
            "PHANT_BENCH_MESH_DEVICES", default_counts
        ).split(",")
    )
    reps = int(os.environ.get("PHANT_BENCH_MESH_REPS", "2"))

    # correctness first: mesh verdicts must be identical to the direct
    # single-engine path, bad witnesses included
    oracle_wits = list(span[:24])
    oracle_wits[3] = (b"\x11" * 32, oracle_wits[3][1])  # corrupt: False
    want = np.asarray(WitnessEngine().verify_batch(oracle_wits))
    with VerificationScheduler(
        config=SchedulerConfig(
            max_batch=b, max_wait_ms=2.0, queue_depth=len(span) + 64,
            mesh_devices=max(counts),
        )
    ) as s_chk:
        got = s_chk.verify_many(oracle_wits)
    assert (got == want).all(), "mesh verdicts diverge from single-device"

    out: dict = {"serving_mesh_batch": b}
    rate_by_n: dict = {}
    for n in counts:
        first_s = steady_s = float("inf")
        participation = None
        for _ in range(max(reps, 1)):
            with VerificationScheduler(
                config=SchedulerConfig(
                    max_batch=b,
                    max_wait_ms=2.0,
                    queue_depth=len(span) + 64,
                    mesh_devices=n,
                )
            ) as s:
                t0 = time.perf_counter()
                assert s.verify_many(span).all()
                first_s = min(first_s, time.perf_counter() - t0)
                t0 = time.perf_counter()
                assert s.verify_many(span).all()
                steady_s = min(steady_s, time.perf_counter() - t0)
                mesh_stats = s.stats_snapshot()["mesh"]
            dispatches = mesh_stats["dispatches"]
            participation = sum(1 for d in dispatches if d > 0)
        # participation is a RECORDED verdict, not an assert: whether every
        # lane dispatched depends on timing (lanes that drain faster than
        # assembly never back the home lane up past spill_depth), and a
        # load-balancing outcome the code does not guarantee must not
        # crash the bench run — the committed counters tell the story
        out[f"serving_mesh_d{n}_lanes_active"] = participation
        if participation < n:
            _log(
                f"serving_mesh: only {participation}/{n} lanes dispatched "
                f"({dispatches}) — lanes outpaced assembly, no spill needed"
            )
        rate_by_n[n] = n_blocks / first_s
        out[f"serving_mesh_d{n}_blocks_per_sec"] = round(n_blocks / first_s, 2)
        out[f"serving_mesh_d{n}_steady_blocks_per_sec"] = round(
            n_blocks / steady_s, 2
        )
        out[f"serving_mesh_d{n}_dispatches"] = dispatches
        _bank({f"serving_mesh_d{n}_blocks_per_sec": out[f"serving_mesh_d{n}_blocks_per_sec"]})
        _log(
            f"serving_mesh: {n} lane(s) -> {out[f'serving_mesh_d{n}_blocks_per_sec']}"
            f" first / {out[f'serving_mesh_d{n}_steady_blocks_per_sec']} steady blocks/s"
        )
    if 1 in rate_by_n and len(rate_by_n) > 1:
        best_n = max(rate_by_n, key=rate_by_n.get)
        out["serving_mesh_devices"] = max(counts)
        out["serving_mesh_best_devices"] = best_n
        out["serving_mesh_speedup"] = round(
            rate_by_n[best_n] / rate_by_n[1], 3
        )
        # the acceptance surface: did every lane of the LARGEST curve
        # point dispatch work? (1 = yes; an informational verdict, the
        # per-point dispatch lists carry the detail)
        out["serving_mesh_all_lanes_active"] = int(
            out.get(f"serving_mesh_d{max(counts)}_lanes_active", 0)
            == max(counts)
        )
    return out


def sec_engine_pipeline() -> dict:
    """Pipelined witness execution A/B (the PR 5 tentpole): the same span
    through the serving scheduler at pipeline depth 1 (serialized pack ->
    dispatch -> resolve, the pre-pipeline behavior) vs depth 2 (pack of
    batch N+1 overlaps device compute + digest resolve of batch N), on
    the DEVICE-routed engine (device_batch_floor=0, so every novel batch
    ships to the accelerator).

    On a CPU-only run the XLA-CPU backend is the device proxy
    (PHANT_ALLOW_JAX_CPU=1). Honesty note, measured on the 2-core dev
    box: the proxy's "device" compute runs on the same host cores the
    pack stage needs, so the demonstrable overlap is bounded by the
    host-side fraction of a batch (~+10% median there); on a real
    accelerator the compute is off-host and the full pack/compute overlap
    applies. The box also swings single runs ±30%, so the headline
    overlap number is the MEDIAN of PAIRED interleaved runs (robust to
    load drift), published next to the measured A/A noise bar
    (`pipeline_noise_aa_pct`, the same median statistic over depth-1 vs
    depth-1 pairs) — the win claim is `pipeline_overlap_pct >
    pipeline_noise_aa_pct`, never a raw delta against box noise.
    Verdicts are asserted byte-identical to direct verify_batch once per
    section (the compile-warm run)."""
    import jax

    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    warm, span = _witness_chain()
    n_blocks = len(span)
    out: dict = {"backend": jax.devices()[0].platform}
    if jax.default_backend() == "cpu":
        os.environ["PHANT_ALLOW_JAX_CPU"] = "1"
        out["pipeline_proxy"] = "xla-cpu"
    mb = int(os.environ.get("PHANT_BENCH_PIPELINE_BATCH", "16"))
    pairs = int(os.environ.get("PHANT_BENCH_PIPELINE_PAIRS", "5"))
    wb = int(os.environ.get("PHANT_BENCH_ENGINE_BATCH", "256"))

    set_crypto_backend("cpu")
    oracle = WitnessEngine()
    for i in range(0, len(warm), wb):
        assert oracle.verify_batch(warm[i : i + wb]).all()
    want = oracle.verify_batch(span)

    def one(depth: int, check: bool = False) -> float:
        set_crypto_backend("cpu")  # warm the cache on the fast native route
        eng = WitnessEngine(device_batch_floor=0)
        for i in range(0, len(warm), wb):
            assert eng.verify_batch(warm[i : i + wb]).all()
        set_crypto_backend("tpu")  # timed span: device-routed
        try:
            with VerificationScheduler(
                engine=eng,
                config=SchedulerConfig(
                    max_batch=mb, max_wait_ms=100.0,
                    queue_depth=n_blocks + 1, pipeline_depth=depth,
                ),
            ) as s:
                t0 = time.perf_counter()
                got = s.verify_many(span)
                dt = time.perf_counter() - t0
            if check:
                assert (got == np.asarray(want)).all(), (
                    "pipelined verdicts diverge from direct verify_batch"
                )
            else:
                assert got.all()
            return dt
        finally:
            set_crypto_backend("cpu")

    one(2, check=True)  # compile warm + byte-identity check, discarded
    d1: list = []
    d2: list = []
    overlaps: list = []
    aa: list = []
    for _ in range(pairs):
        a = one(1)
        b2 = one(2)
        a2 = one(1)  # the A/A twin measures the box, not the code
        d1 += [a, a2]
        d2.append(b2)
        overlaps.append(1.0 - b2 / a)
        aa.append(abs(1.0 - a2 / a))
    overlaps.sort()
    aa.sort()
    out.update(
        {
            "engine_pipeline_d1_blocks_per_sec": round(n_blocks / min(d1), 2),
            "engine_pipeline_d2_blocks_per_sec": round(n_blocks / min(d2), 2),
            "pipeline_overlap_pct": round(
                overlaps[len(overlaps) // 2] * 100, 1
            ),
            "pipeline_noise_aa_pct": round(aa[len(aa) // 2] * 100, 1),
            "pipeline_batch": mb,
            "pipeline_pairs": pairs,
        }
    )
    _bank(out)
    return out


def sec_witness_resident() -> dict:
    """Device-resident intern table (ops/witness_resident.py): the
    link-independent steady-state witness verification rate — the
    architectural fix behind the paper's >=10x headline.

    Three measurements on the standard witness chain:

      * engine route, first pass — residency building: truly-novel bytes
        upload once, verdicts computed on device, host tables commit
        from the device digests (verdict identity to the host route is
        asserted, corrupt witness included);
      * engine route, steady state — everything resident: per-batch
        uplink is row ids + roots only, and the committed
        `resident_novel_bytes_per_block_steady` must sit WELL below
        `witness_bytes_per_block` (the acceptance claim; PAPERS.md
        2408.14217 quantifies why reuse makes this the common case);
      * `witness_fused_resident_slope_blocks_per_sec` — the headline:
        k chained device iterations (row LOOKUP from fingerprints via
        the resident open-addressed index + the resident verdict join)
        inside ONE jit, slope-fitted between k=1 and k=65 exactly like
        the keccak kernel's resident rate (_slope_time_chunked), so the
        number is RTT-INSENSITIVE — it measures the chip, not the
        host<->device round trip.

    On a CPU-only run the XLA-CPU backend is the device proxy: the
    committed slope rate then measures the HOST executing the device
    program (compute attribution, no link in the loop), the artifact
    keeps the memoized-engine headline, and
    `witness_resident_gap_attribution` states the gap. On a real v5e the
    slope rate becomes the artifact's `value`/`vs_baseline`
    (_refresh_headline) — the driver-captured >=10x claim."""
    import jax

    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.ops import witness_resident as wr
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.ops.witness_jax import _pow2ceil, roots_to_words
    from phant_tpu.utils.native import load_native

    warm, span = _witness_chain()
    n_blocks = len(span)
    node_lists = [nodes for _root, nodes in span]
    witness_bytes = sum(len(n) for nl in node_lists for n in nl)
    out: dict = {
        "witness_resident_backend": jax.devices()[0].platform,
        "witness_resident_blocks": n_blocks,
    }
    if jax.default_backend() == "cpu":
        os.environ["PHANT_ALLOW_JAX_CPU"] = "1"
        out["witness_resident_proxy"] = "xla-cpu"
    prev_resident = os.environ.get("PHANT_RESIDENT")
    prev_start = os.environ.get("PHANT_RESIDENT_START_CAP")
    os.environ["PHANT_RESIDENT"] = "1"
    # pre-size the resident row space to the chain's working set: pow2
    # GROWTH recompiles the update program per step, and those compiles
    # must not land inside the timed passes
    unique_nodes = len({n for _r, nl in (warm + span) for n in nl})
    os.environ["PHANT_RESIDENT_START_CAP"] = str(unique_nodes + 1)
    b = int(os.environ.get("PHANT_BENCH_ENGINE_BATCH", "256"))

    # host oracle (the byte-identity claim), corruption included
    set_crypto_backend("cpu")
    oracle = WitnessEngine(resident=False)
    chk = list(span[:16])
    chk[3] = (b"\x11" * 32, chk[3][1])  # corrupt root: must stay False
    want_chk = np.asarray(oracle.verify_batch(chk))
    want_span = np.asarray(oracle.verify_batch(span))
    assert want_span.all() and not want_chk[3]

    set_crypto_backend("tpu")
    eng = WitnessEngine(resident=True)
    try:
        for i in range(0, len(warm), b):  # warm: compiles + first uploads
            assert eng.verify_batch(warm[i : i + b]).all()
        # compile warm-up: one throwaway span pass (the update/verdict
        # programs compile per shape bucket, and those compiles must not
        # pollute the timed FIRST pass), then reset to a cold resident
        # table — the jit cache survives the reset, the rows don't
        for i in range(0, len(span), b):
            assert eng.verify_batch(span[i : i + b]).all()
        eng.reset()
        for i in range(0, len(warm), b):
            assert eng.verify_batch(warm[i : i + b]).all()
        st0 = eng.stats_snapshot()["resident"]
        t0 = time.perf_counter()
        for i in range(0, len(span), b):
            got = eng.verify_batch(span[i : i + b])
            assert (got == want_span[i : i + b]).all(), (
                "resident verdicts diverge from the host route"
            )
        first_s = time.perf_counter() - t0
        st1 = eng.stats_snapshot()["resident"]
        t0 = time.perf_counter()
        for i in range(0, len(span), b):  # steady: zero novel uploads
            assert eng.verify_batch(span[i : i + b]).all()
        steady_s = time.perf_counter() - t0
        st2 = eng.stats_snapshot()["resident"]
        got_chk = np.asarray(eng.verify_batch(chk))
        assert (got_chk == want_chk).all(), (
            "resident verdicts diverge from the host route (corruption)"
        )
        out.update(
            {
                "witness_resident_first_blocks_per_sec": round(
                    n_blocks / first_s, 2
                ),
                "witness_resident_steady_blocks_per_sec": round(
                    n_blocks / steady_s, 2
                ),
                "resident_novel_bytes_per_block_first": round(
                    (st1["uploaded_bytes"] - st0["uploaded_bytes"]) / n_blocks,
                    1,
                ),
                "resident_novel_bytes_per_block_steady": round(
                    (st2["uploaded_bytes"] - st1["uploaded_bytes"]) / n_blocks,
                    1,
                ),
                "witness_bytes_per_block": round(witness_bytes / n_blocks),
                "resident_rows": st2["rows"],
                "resident_index_dropped": st2["index_dropped"],
            }
        )
        _bank(out)

        # --- the slope-timed chained fused step (the headline) -------------
        all_nodes = [n for nl in node_lists for n in nl]
        native = load_native()
        if native is not None:
            digs = list(native.keccak256_batch_fast(all_nodes))
        else:
            from phant_tpu.crypto.keccak import keccak256

            digs = [keccak256(n) for n in all_nodes]
        n_nodes = len(all_nodes)
        np_pad = _pow2ceil(n_nodes)
        fps = np.zeros((np_pad, 2), np.uint32)
        fps[:n_nodes] = np.stack([np.frombuffer(d[:8], "<u4") for d in digs])
        live = np.zeros(np_pad, bool)
        live[:n_nodes] = True
        block_id = np.zeros(np_pad, np.int32)
        counts = [len(nl) for nl in node_lists]
        block_id[:n_nodes] = np.repeat(
            np.arange(n_blocks, dtype=np.int32), counts
        )
        nb_pad = _pow2ceil(n_blocks)
        roots_w = np.zeros((nb_pad, 8), np.uint32)
        roots_w[:n_blocks] = roots_to_words([r for r, _ in span])
        table = eng.resident_table()
        # the device scan must resolve every resident span node before
        # the chain is worth timing (a miss fails its block)
        rows_dev = table.device_lookup(fps)
        assert (rows_dev[:n_nodes] >= 0).all(), "device index missed rows"
        # the wide k spread exists to dwarf a LINK's round-trip jitter;
        # the inline XLA-CPU proxy has no link to cancel, and its
        # per-iteration cost is host-compute-bound seconds — a short
        # chain keeps the section inside its budget without changing
        # what the slope isolates there
        on_device = out["witness_resident_backend"] != "cpu"
        k_hi = 65 if on_device else 5
        per_iter = wr.slope_time_resident(
            table, fps, live, block_id, roots_w,
            k_hi=k_hi, reps=3 if on_device else 2,
        )
        slope_rate = n_blocks / per_iter
        out["witness_fused_resident_slope_blocks_per_sec"] = round(
            slope_rate, 2
        )
        out["witness_resident_slope_timing"] = (
            f"slope(k=1..{k_hi} chained device lookup+verdict)"
        )

        # self-contained baseline ratio (the artifact headline uses the
        # engine section's cpu_baseline when both ran in this artifact)
        verify_cpu(span[:4])
        t0 = time.perf_counter()
        assert verify_cpu(span) == n_blocks
        cpu_s = time.perf_counter() - t0
        out["witness_resident_cpu_baseline_blocks_per_sec"] = round(
            n_blocks / cpu_s, 2
        )
        out["witness_resident_slope_vs_baseline"] = round(
            slope_rate * cpu_s / n_blocks, 2
        )

        # locally-attached projection: the slope rate is RTT-free; a
        # locally attached chip adds only the steady-state uplink (4 B of
        # row id per node + 32 B of root per block) at PCIe-class
        # bandwidth (stated assumption: 8 GB/s)
        rowid_bytes_per_block = 4 * (n_nodes / n_blocks) + 32
        proj = 1.0 / (1.0 / slope_rate + rowid_bytes_per_block / 8e9)
        out["witness_resident_local_projection_blocks_per_sec"] = round(
            proj, 2
        )
        if out["witness_resident_backend"] == "cpu":
            out["witness_resident_gap_attribution"] = (
                "XLA-CPU proxy run: the 'device' program executes on the "
                "host cores, so the slope rate measures host COMPUTE of "
                "the resident lookup+verdict step — no link is in the "
                "loop by construction (the chain uploads nothing per "
                "iteration). The gap to the >=10x claim is therefore "
                "entirely compute attribution (XLA-CPU keccak/sort-join "
                "vs the v5e kernels: the Pallas sponge alone measured "
                "91.9M hashes/s, ~74x host SIMD), not the link; on a "
                "real v5e 'value'/'vs_baseline' switch to this slope "
                "metric (_refresh_headline)."
            )
        else:
            out.update(_link_profile())
            out["witness_resident_gap_attribution"] = (
                "real-accelerator run: the slope rate is the chip's "
                "steady-state resident step with zero per-iteration "
                "traffic; the locally-attached projection adds the row-id "
                "uplink at the stated 8 GB/s assumption."
            )
    finally:
        try:
            eng.reset()  # release the device arrays deterministically
        except Exception:
            pass
        if prev_resident is None:
            os.environ.pop("PHANT_RESIDENT", None)
        else:
            os.environ["PHANT_RESIDENT"] = prev_resident
        if prev_start is None:
            os.environ.pop("PHANT_RESIDENT_START_CAP", None)
        else:
            os.environ["PHANT_RESIDENT_START_CAP"] = prev_start
        set_crypto_backend("cpu")
    return out


def sec_replay_device() -> dict:
    return _replay_variants("tpu")


def sec_witness_stream() -> dict:
    """Streaming witness ingestion (PR 9), the two coupled claims.

    (a) PREFETCH OVERLAP: the same span through the serving scheduler at
    pipeline depth 2 with the 4th (prefetch) stage ON vs OFF, on the
    device-routed engine (XLA-CPU proxy on CPU-only runs). The box
    swings single runs ±30%, so the headline is the MEDIAN of PAIRED
    interleaved runs published next to the same-statistic A/A (on vs on)
    noise bar — the win claim is `witness_stream_prefetch_overlap_pct >
    witness_stream_noise_aa_pct`, never a raw delta. The overlap AUDIT
    comes from the phase metrics: `witness_engine.prefetch` is what the
    worker spent decoding + pre-scanning, `sched.prefetch_wait` is the
    part the executor actually had to wait for —
    `witness_stream_prefetch_hidden_pct` = the fraction that hid under
    dispatch/resolve (the >=80% acceptance surface; on this 2-core box
    the proxy's "device" compute shares the host cores, so the hidden
    fraction is the honest claim and the wall-clock overlap is bounded
    by the host-side fraction of a batch).

    (b) TIERED EVICTION: an over-cap forward replay of the PR 8
    depth-skew span (static trie, rotating account picks — the
    reuse-dominated regime 2408.14217 predicts, novel bytes/block -> 0)
    under flat-flush vs depth-tiered eviction (PHANT_PIN_DEPTH tiers
    pinned across generation flushes; the pinned set liveness-prunes at
    each flush, and the steady state is measured over the span's second
    half). Verdict identity — corrupt witnesses included — is asserted
    IN-SECTION against an uncapped oracle; the committed claim is the
    steady-state hit-rate margin (`witness_stream_tiered_hit_rate` vs
    `witness_stream_flat_hit_rate` — benchtrend trend-gates both, plus
    the hidden/overlap keys)."""
    import jax

    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )
    from phant_tpu.utils.trace import metrics as _m

    warm, span = _witness_chain()
    n_blocks = len(span)
    out: dict = {
        "witness_stream_backend": jax.devices()[0].platform,
        "witness_stream_blocks": n_blocks,
    }
    if jax.default_backend() == "cpu":
        os.environ["PHANT_ALLOW_JAX_CPU"] = "1"
        out["witness_stream_proxy"] = "xla-cpu"
    mb = int(os.environ.get("PHANT_BENCH_STREAM_BATCH", "16"))
    pairs = int(os.environ.get("PHANT_BENCH_STREAM_PAIRS", "5"))
    wb = int(os.environ.get("PHANT_BENCH_ENGINE_BATCH", "256"))

    set_crypto_backend("cpu")
    oracle = WitnessEngine()
    for i in range(0, len(warm), wb):
        assert oracle.verify_batch(warm[i : i + wb]).all()
    want = np.asarray(oracle.verify_batch(span))

    hidden: list = []

    def one(prefetch: bool, check: bool = False) -> float:
        set_crypto_backend("cpu")  # warm the cache on the fast native route
        eng = WitnessEngine(device_batch_floor=0)
        for i in range(0, len(warm), wb):
            assert eng.verify_batch(warm[i : i + wb]).all()
        set_crypto_backend("tpu")  # timed span: device-routed
        t_before = _m.snapshot()["timers"]
        try:
            with VerificationScheduler(
                engine=eng,
                config=SchedulerConfig(
                    max_batch=mb, max_wait_ms=100.0,
                    queue_depth=n_blocks + 1, pipeline_depth=2,
                    prefetch=prefetch,
                ),
            ) as s:
                t0 = time.perf_counter()
                got = s.verify_many(span)
                dt = time.perf_counter() - t0
                st = s.stats_snapshot()
            if prefetch:
                assert st["prefetched_batches"] >= 1, st
                t_after = _m.snapshot()["timers"]

                def delta(name):
                    return t_after.get(name, {}).get("total_s", 0.0) - (
                        t_before.get(name, {}).get("total_s", 0.0)
                    )

                pf, wait = delta("witness_engine.prefetch"), delta(
                    "sched.prefetch_wait"
                )
                if pf > 0 and not check:
                    # the compile-warm run is excluded: its 10s-scale XLA
                    # compile under dispatch gives the worker unlimited
                    # lead and would bias the hidden fraction UP
                    hidden.append(max(0.0, 1.0 - wait / pf))
            if check:
                assert (got == want).all(), (
                    "prefetched verdicts diverge from direct verify_batch"
                )
            else:
                assert got.all()
            return dt
        finally:
            set_crypto_backend("cpu")

    one(True, check=True)  # compile warm + byte-identity check, discarded
    d_off: list = []
    d_on: list = []
    overlaps: list = []
    aa: list = []
    for _ in range(pairs):
        a = one(False)
        b_on = one(True)
        a_on2 = one(True)  # the A/A twin measures the box, not the code
        d_off.append(a)
        # the twin feeds ONLY the noise bar: committed on/off rates take
        # min() over EQUAL sample counts (2x on-draws would bias the
        # on-key's minimum down on a noisy box with zero real speedup)
        d_on.append(b_on)
        overlaps.append(1.0 - b_on / a)
        aa.append(abs(1.0 - a_on2 / b_on))
    overlaps.sort()
    aa.sort()
    hidden.sort()
    out.update(
        {
            "witness_stream_prefetch_off_blocks_per_sec": round(
                n_blocks / min(d_off), 2
            ),
            "witness_stream_prefetch_on_blocks_per_sec": round(
                n_blocks / min(d_on), 2
            ),
            "witness_stream_prefetch_overlap_pct": round(
                overlaps[len(overlaps) // 2] * 100, 1
            ),
            "witness_stream_noise_aa_pct": round(aa[len(aa) // 2] * 100, 1),
            "witness_stream_prefetch_hidden_pct": round(
                hidden[len(hidden) // 2] * 100, 1
            )
            if hidden
            else None,
            "witness_stream_batch": mb,
            "witness_stream_pairs": pairs,
        }
    )
    _bank(out)

    # -- (b) flat vs depth-tiered eviction on the over-cap replay ----------
    # The eviction claim lives in the REUSE-DOMINATED regime the paper's
    # trie analysis (2408.14217) predicts and PR 8 measured (novel bytes
    # per block -> ~0): a depth-skewed span over a STATIC trie with
    # rotating account picks — the PR 8 depth-histogram workload. Part
    # (a)'s churning chain stays the prefetch-overlap workload; under
    # heavy per-block writes the working set churns and no eviction
    # policy can manufacture reuse that isn't there.
    skew = _cached(
        "wskew_256_16384_32",
        lambda: build_witness_chain(
            256,
            trie_size=16384,
            reads=32,
            writes=0,
            storage_slots=2048,
            storage_reads_per_block=8,
        ),
    )
    # corruption classes ride mid-span so the identity assert has teeth
    # (bad witnesses must FAIL identically under both policies)
    sroot, snodes = skew[40]
    skew = (
        skew[:80]
        + [(b"\x00" * 32, list(snodes)), (sroot, [])]
        + skew[80:]
    )
    uniq = len({n for _r, ns in skew for n in ns})
    cap = max(48, uniq // 3)
    # pin budget: half the cap (the conservative engine default,
    # max_nodes // 8, under-pins the depth<=2 tier at bench shapes —
    # the committed knob is part of the claim)
    pin_budget = cap // 2
    chunk = max(2, mb // 4)
    want_b = [bool(v) for v in WitnessEngine().verify_batch(skew)]
    assert not all(want_b) and any(want_b), "corruptions must fail"

    # steady state is measured FORWARD: the span's second half, once the
    # tables warmed and over-cap flushes cycle. The skew span serves one
    # state root throughout (mainnet steady state at the head: verify
    # traffic clusters on recent roots), so the pin tracker's flush-time
    # liveness prune keeps the live shallow tier while a flat flush
    # throws it away with everything else.
    half = (len(skew) // (2 * chunk)) * chunk

    def measured_replay(eng) -> tuple:
        verdicts: list = []
        for i in range(0, half, chunk):
            verdicts.extend(
                np.asarray(eng.verify_batch(skew[i : i + chunk])).tolist()
            )
        h0, m0 = eng.stats["hits"], eng.stats["hashed"]
        for i in range(half, len(skew), chunk):
            verdicts.extend(
                np.asarray(eng.verify_batch(skew[i : i + chunk])).tolist()
            )
        dh = eng.stats["hits"] - h0
        dm = eng.stats["hashed"] - m0
        return verdicts, dh / max(1, dh + dm)

    flat = WitnessEngine(max_nodes=cap, tiered_evict=False)
    tier = WitnessEngine(
        max_nodes=cap, tiered_evict=True, pin_budget=pin_budget
    )
    vf, rate_flat = measured_replay(flat)
    vt, rate_tier = measured_replay(tier)
    assert vf == vt == want_b, "tiered eviction changed a verdict"
    frag_b = {
        "witness_stream_cap": cap,
        "witness_stream_pin_budget": pin_budget,
        "witness_stream_unique_nodes": uniq,
        "witness_stream_flat_hit_rate": round(rate_flat, 4),
        "witness_stream_tiered_hit_rate": round(rate_tier, 4),
        "witness_stream_tiered_hit_gain_pct": round(
            (rate_tier - rate_flat) * 100, 2
        ),
        "witness_stream_flat_evictions": flat.stats["evictions"],
        "witness_stream_tiered_evictions": tier.stats["evictions"],
        "witness_stream_pinned_retained": tier.stats.get(
            "pinned_retained", 0
        ),
    }
    out.update(frag_b)
    _bank(frag_b)
    return out


def sec_post_root() -> dict:
    """Batched post-state-root recomputation (PR 11).

    Three coupled measurements over K identically-shaped stateless
    requests (distinct mutation values, so every digest differs):

    (a) ROOTS-BYTE-IDENTITY, asserted in-section: every mutation class —
    slot update, storage-zeroing delete, account delete,
    selfdestruct-recreate — through the FORCED-DEVICE merged dispatch
    must equal the host `state_root()` oracle, and an
    insufficient-witness deletion must raise StatelessError on BOTH
    paths (the corrupt case).

    (b) COALESCING SPEEDUP (the committed >noise-bar claim,
    `post_root_coalesce_speedup_pct` vs `post_root_coalesce_noise_aa_pct`):
    ONE merged dispatch for all K requests vs K per-request dispatches,
    median of paired interleaved runs — the dispatch amortization
    cross-request coalescing exists for, measurable even on the XLA-CPU
    proxy because both legs share the backend.

    (c) BATCHED-VS-HOST, committed honestly
    (`post_root_batched_vs_host_pct` vs `post_root_noise_aa_pct`): on
    this 2-core box the proxy's "device" keccak shares the host cores
    and XLA-CPU hashes well below the native rate, so the number is
    NEGATIVE — which is precisely why THE offload gate
    (ops/root_engine.py) keeps production requests on the host walk on
    such hosts, and why the single-request path
    (`post_root_single_parity_pct`, the gated host route vs the direct
    walk) sits at parity by construction. On a real TPU the device
    child recomputes (b) and (c) with the device off-host — the
    real-v5e re-run is the ROADMAP claim."""
    import jax

    from phant_tpu import rlp as _rlp
    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.crypto.keccak import keccak256 as _k
    from phant_tpu.mpt.mpt import Trie as _Trie
    from phant_tpu.mpt.proof import generate_proof as _proof
    from phant_tpu.ops.root_engine import RootEngine
    from phant_tpu.state.root import account_leaf as _aleaf
    from phant_tpu.stateless import StatelessError, WitnessStateDB
    from phant_tpu.types.account import Account as _Acct

    out: dict = {"post_root_backend": jax.devices()[0].platform}
    if jax.default_backend() == "cpu":
        os.environ["PHANT_ALLOW_JAX_CPU"] = "1"
        out["post_root_proxy"] = "xla-cpu"
    K = int(os.environ.get("PHANT_BENCH_ROOT_BATCH", "16"))
    pairs = int(os.environ.get("PHANT_BENCH_ROOT_PAIRS", "5"))
    n_acc, touch, slots = 96, 12, 16

    def _spec(seed: int):
        """FULL-coverage witness (every account path, every slot of the
        touched accounts): deletes and collapses stay inside the
        witnessed region — the corrupt case below builds its own
        partial witness."""
        accounts = {
            bytes([1 + (i % 23), i % 251, (i * 7) % 251]) * 6
            + bytes([seed % 250, i % 250]): _Acct(
                nonce=i % 5,
                balance=i * 10**12 + seed + 1,
                storage=(
                    {j: j + seed + 1 for j in range(1, slots + 1)}
                    if i % 4 == 0
                    else {}
                ),
            )
            for i in range(n_acc)
        }
        touched = [a for a in accounts if accounts[a].storage][:touch]
        trie = _Trie()
        for a, acct in accounts.items():
            trie.put(_k(a), _aleaf(acct))
        nodes: dict = {}
        for a in accounts:
            for enc in _proof(trie, _k(a)):
                nodes[enc] = None
        for a in touched:
            st = _Trie()
            for s, v in accounts[a].storage.items():
                st.put(
                    _k(s.to_bytes(32, "big")), _rlp.encode(_rlp.encode_uint(v))
                )
            for s in accounts[a].storage:
                for enc in _proof(st, _k(s.to_bytes(32, "big"))):
                    nodes[enc] = None
        return trie.root_hash(), list(nodes), touched

    spec = _spec(0)

    def _mk(seed: int, mutate=None):
        root, nodes, touched = spec
        db = WitnessStateDB(root, nodes, [])
        if mutate is not None:
            mutate(db, touched)
            return db
        for kk, a in enumerate(touched):
            db.set_storage(a, 1 + (kk % 4), 10_000 + seed + kk)
            if kk % 3 == 0:
                db.get_balance(a)
                db.accounts[a].balance += seed + 1
        return db

    set_crypto_backend("tpu")
    eng = RootEngine(device_floor=0)
    try:
        # -- (a) identity: mutation classes + corrupt/dirty-delete -------
        def m_update(db, touched):
            db.set_storage(touched[0], 1, 31337)

        def m_zero(db, touched):
            for s in range(2, slots + 1):
                db.set_storage(touched[1], s, 0)  # storage collapse

        def m_delete(db, touched):
            db.get_balance(touched[2])
            del db.accounts[touched[2]]

        def m_recreate(db, touched):
            db.get_storage(touched[3], 1)
            db.accounts[touched[3]] = _Acct(balance=1)
            db.set_storage(touched[3], 2, 9)

        classes = (m_update, m_zero, m_delete, m_recreate)
        wants = [_mk(0, m).state_root() for m in classes]
        dbs = [_mk(0, m) for m in classes]
        prps = [db.post_root_plan() for db in dbs]
        assert all(p is not None for p in prps), "mutation class unplannable"
        for db, prp, got, want in zip(
            dbs, prps, eng.root_many([p.plan for p in prps]), wants
        ):
            assert db.apply_post_root(prp, got) == want, (
                "batched post root diverged from the host oracle"
            )
            assert db.state_root() == want  # memo agrees after apply
        # corrupt: an account deletion whose branch collapse crosses an
        # UNWITNESSED sibling must raise StatelessError on BOTH paths.
        # Deterministic construction: two accounts whose keccak keys
        # diverge at the first nibble (root branch, two children), the
        # witness covering only the deleted one — the collapse needs the
        # sibling's encoding, which only its HashNode digest represents.
        a_del, a_sib = None, None
        for i in range(256):
            cand = bytes([i]) * 20
            if a_del is None:
                a_del = cand
            elif _k(cand)[0] >> 4 != _k(a_del)[0] >> 4:
                a_sib = cand
                break
        ctrie = _Trie()
        ctrie.put(_k(a_del), _aleaf(_Acct(balance=1)))
        ctrie.put(_k(a_sib), _aleaf(_Acct(balance=2)))
        cnodes = list(dict.fromkeys(_proof(ctrie, _k(a_del))))
        for path in ("host", "plan"):
            db = WitnessStateDB(ctrie.root_hash(), cnodes, [])
            db.get_balance(a_del)
            del db.accounts[a_del]
            try:
                if path == "host":
                    db.state_root()
                else:
                    db.post_root_plan()
                raise AssertionError(f"{path}: insufficient witness passed")
            except StatelessError:
                pass  # identical verdict on both paths
        frag = {"post_root_identity_classes": len(classes) + 1}
        out.update(frag)
        _bank(out)

        # -- (b)+(c): paired timing legs ---------------------------------
        def plans_for(seed: int):
            states = [_mk(seed * K + i) for i in range(K)]
            return [s.post_root_plan() for s in states]

        warm = plans_for(997)
        eng.root_many([p.plan for p in warm])  # merged-K compile
        eng.root_many([plans_for(996)[0].plan])  # single-plan compile
        out["post_root_requests"] = K
        out["post_root_plan_nodes"] = warm[0].plan.n_nodes
        out["post_root_levels"] = len(warm[0].plan.levels)

        def t_host(seed: int) -> float:
            states = [_mk(seed * K + i) for i in range(K)]
            t0 = time.perf_counter()
            for s in states:
                s.state_root()
            return time.perf_counter() - t0

        def t_merged(seed: int) -> float:
            prps = plans_for(seed)
            t0 = time.perf_counter()
            eng.root_many([p.plan for p in prps])
            return time.perf_counter() - t0

        def t_singles(seed: int) -> float:
            prps = plans_for(seed)
            t0 = time.perf_counter()
            for p in prps:
                eng.root_many([p.plan])
            return time.perf_counter() - t0

        coal, aa, vs_host = [], [], []
        best_m, best_h = float("inf"), float("inf")
        for rep in range(pairs):
            h = t_host(rep * 4)
            s1 = t_singles(rep * 4 + 1)
            m1 = t_merged(rep * 4 + 2)
            m2 = t_merged(rep * 4 + 3)  # the A/A twin: box, not code
            coal.append(s1 / m1 - 1)
            aa.append(abs(1 - m2 / m1))
            vs_host.append(h / m1 - 1)
            best_m, best_h = min(best_m, m1), min(best_h, h)
        coal.sort()
        aa.sort()
        vs_host.sort()
        frag = {
            "post_root_coalesce_speedup_pct": round(
                coal[len(coal) // 2] * 100, 1
            ),
            "post_root_coalesce_noise_aa_pct": round(
                aa[len(aa) // 2] * 100, 1
            ),
            "post_root_batched_vs_host_pct": round(
                vs_host[len(vs_host) // 2] * 100, 1
            ),
            "post_root_noise_aa_pct": round(aa[len(aa) // 2] * 100, 1),
            "post_root_batched_roots_per_sec": round(K / best_m, 1),
            "post_root_host_roots_per_sec": round(K / best_h, 1),
            "post_root_pairs": pairs,
        }
        out.update(frag)
        _bank(frag)
    finally:
        set_crypto_backend("cpu")

    # -- single-request parity: the gated host route vs the direct walk --
    # (on a CPU backend the lane pre-filter keeps the walk; the measured
    # ratio documents the zero-overhead contract for the default
    # deployment — the lone-request guard on a REAL tpu link is pinned
    # structurally in tests/test_post_root.py)
    par = []
    for rep in range(pairs):
        s1 = _mk(rep)
        t0 = time.perf_counter()
        from phant_tpu.stateless import compute_post_root

        r1 = compute_post_root(s1)  # no scheduler/backend: the host walk
        t_gated = time.perf_counter() - t0
        s2 = _mk(rep)
        t0 = time.perf_counter()
        r2 = s2.state_root()
        t_direct = time.perf_counter() - t0
        assert r1 == r2
        par.append(t_direct / t_gated - 1)
    par.sort()
    frag = {
        "post_root_single_parity_pct": round(par[len(par) // 2] * 100, 1)
    }
    out.update(frag)
    _bank(frag)
    return out


def sec_sender_lane() -> dict:
    """Coalesced sender recovery (PR 14, ops/sig_engine.py).

    Four coupled measurements over K block-shaped tx lists (each BELOW
    the per-request PHANT_TPU_MIN_ECRECOVER floor — the serving regime
    the lane exists for):

    (a) SENDER BYTE-IDENTITY, asserted in-section: every request's
    sender slice through the FORCED-DEVICE merged dispatch must equal
    the direct `get_senders_batch` / `recover_senders_async(force_cpu)`
    oracle — including a block with an INVALID signature (same None
    position, same `unrecoverable signature at tx index i` attribution)
    and a pre-EIP-155 block (v=27/28 legacy signing).

    (b) COALESCING SPEEDUP (the committed >noise-bar claim,
    `sender_lane_coalesce_speedup_pct` vs
    `sender_lane_coalesce_noise_aa_pct`): ONE merged dispatch for all K
    requests vs K per-request dispatches, median of paired interleaved
    runs — dispatch amortization with the backend held fixed, the same
    claim shape as `post_root_coalesce_speedup_pct`. The in-section
    merged-rows assert pins K>1 requests per device call.

    (c) BATCHED-VS-NATIVE, committed honestly
    (`sender_lane_batched_vs_native_pct`): the merged device dispatch vs
    the fused native batch over the SAME rows. On this box the XLA-CPU
    proxy's 256-step ladder shares the host cores with (and runs far
    below) the native C path, so the number is NEGATIVE — which is
    precisely why THE offload gate (ops/sig_engine.py) keeps lone /
    sub-floor traffic on the fused native batch, and the lone-request
    gate is asserted structurally in-section (zero merged-dispatch
    work). On a real TPU the device child recomputes it off-host.

    (d) HIDDEN-FRACTION AUDIT (`sender_lane_hidden_pct`): the serving
    shape — dispatch at decode time, join before execution — through a
    real depth-2 scheduler, with each request running its witness
    verification between dispatch and join. `sched.sig_wait` is the
    recovery cost the request thread actually blocked on;
    the `witness_engine.sig_*` phases are what recovery cost in total —
    the hidden fraction is what the overlap removed from the critical
    path (the proxy's "device" shares the host cores, so this audit —
    not wall clock — is the honest committed claim)."""
    import jax

    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.signer.signer import TxSigner
    from phant_tpu.types.transaction import LegacyTx
    from phant_tpu.utils.trace import metrics as _m

    out: dict = {"sender_lane_backend": jax.devices()[0].platform}
    if jax.default_backend() == "cpu":
        os.environ["PHANT_ALLOW_JAX_CPU"] = "1"
        out["sender_lane_proxy"] = "xla-cpu"
    # proxy-sized defaults: the XLA-CPU ladder compiles ~1s and runs
    # ~25ms per row-of-32 bucket on the 2-core box, so the merged shape
    # stays in the 64-row bucket (raise K/T on a real accelerator —
    # every request still sits BELOW the 64-row per-request floor, the
    # serving regime the lane exists for)
    K = int(os.environ.get("PHANT_BENCH_SIG_BATCH", "8"))
    T = int(os.environ.get("PHANT_BENCH_SIG_TXS", "6"))
    pairs = int(os.environ.get("PHANT_BENCH_SIG_PAIRS", "3"))

    signer = TxSigner(1)

    def _mk_txs(seed: int, n: int = T, pre155: bool = False, bad_at: int = -1):
        txs = []
        for i in range(n):
            tx = LegacyTx(
                nonce=i,
                gas_price=10 + seed,
                gas_limit=21_000,
                to=bytes([0x7E]) * 20,
                value=1 + seed + i,
                data=b"",
                v=27 if pre155 else 37,
                r=0,
                s=0,
            )
            tx = signer.sign(tx, 0xB00B + seed * 1009 + i)
            if i == bad_at:
                from dataclasses import replace

                tx = replace(tx, v=99)  # unrecoverable: v inconsistent
            txs.append(tx)
        return txs

    def requests_for(seed: int):
        reqs = [_mk_txs(seed * K + i) for i in range(K)]
        return reqs, [signer.signature_rows(t) for t in reqs]

    # -- (a) identity incl. invalid-signature + pre-EIP-155 blocks -------
    id_reqs = [_mk_txs(0), _mk_txs(1, pre155=True), _mk_txs(2, bad_at=3)]
    id_rows = [signer.signature_rows(t) for t in id_reqs]
    oracles = [
        signer.recover_senders_async(t, force_cpu=True)() for t in id_reqs
    ]
    set_crypto_backend("tpu")
    try:
        eng = SigEngine(device_floor=0)
        got = eng.sig_many(id_rows)
        for g, want, txs in zip(got, oracles, id_reqs):
            assert g == want, "merged senders diverged from the oracle"
        assert got[2][3] is None, "invalid signature not attributed"
        assert eng.stats["device_batches"] == 1
        frag = {"sender_lane_identity_requests": len(id_reqs)}
        out.update(frag)
        _bank(out)

        # -- (b)+(c): paired timing legs ---------------------------------
        warm_reqs, warm_rows = requests_for(997)
        eng.sig_many(warm_rows)  # merged-K compile
        eng.sig_many([warm_rows[0]])  # single-request compile
        out["sender_lane_requests"] = K
        out["sender_lane_txs_per_request"] = T
        assert eng.stats["sig_rows"] >= K * T + T
        # the merged-dispatch counter claim: K>1 requests per device call
        rows_per_dispatch = K * T
        assert rows_per_dispatch > T
        out["sender_lane_merged_rows_per_dispatch"] = rows_per_dispatch

        def t_merged(seed: int) -> float:
            _reqs, rows = requests_for(seed)
            t0 = time.perf_counter()
            eng.sig_many(rows)
            return time.perf_counter() - t0

        def t_singles(seed: int) -> float:
            _reqs, rows = requests_for(seed)
            t0 = time.perf_counter()
            for r in rows:
                eng.sig_many([r])
            return time.perf_counter() - t0

        def t_native(seed: int) -> float:
            # rows PREBUILT outside the timer, exactly like the merged
            # leg: both legs time recovery only, so vs_native isolates
            # the backend and carries no row-build (signing-hash keccak)
            # bias in the merged dispatch's favor
            _reqs, rows = requests_for(seed)
            t0 = time.perf_counter()
            for r in rows:
                signer.recover_rows_async(r, force_cpu=True)()
            return time.perf_counter() - t0

        # per-request dispatches must clear the floor too (backend held
        # fixed — the coalescing claim isolates dispatch amortization)
        coal, aa, vs_native = [], [], []
        best_m, best_n = float("inf"), float("inf")
        for rep in range(pairs):
            nat = t_native(rep * 4)
            s1 = t_singles(rep * 4 + 1)
            m1 = t_merged(rep * 4 + 2)
            m2 = t_merged(rep * 4 + 3)  # the A/A twin: box, not code
            coal.append(s1 / m1 - 1)
            aa.append(abs(1 - m2 / m1))
            vs_native.append(nat / m1 - 1)
            best_m, best_n = min(best_m, m1), min(best_n, nat)
        coal.sort()
        aa.sort()
        vs_native.sort()
        frag = {
            "sender_lane_coalesce_speedup_pct": round(
                coal[len(coal) // 2] * 100, 1
            ),
            "sender_lane_coalesce_noise_aa_pct": round(
                aa[len(aa) // 2] * 100, 1
            ),
            "sender_lane_batched_vs_native_pct": round(
                vs_native[len(vs_native) // 2] * 100, 1
            ),
            "sender_lane_merged_senders_per_sec": round(K * T / best_m, 1),
            "sender_lane_native_senders_per_sec": round(K * T / best_n, 1),
            "sender_lane_pairs": pairs,
        }
        out.update(frag)
        _bank(frag)

        # -- lone-request gate: native path, zero merged dispatches ------
        # the production floor, pinned explicitly (test runs lower the
        # PHANT_TPU_MIN_ECRECOVER env to 1): a lone sub-floor request
        # lands on the fused native batch with zero merged-dispatch work
        lone = SigEngine(device_floor=64)
        lone_rows = signer.signature_rows(_mk_txs(553))
        assert lone.sig_many([lone_rows])[0] == (
            signer.recover_senders_async(_mk_txs(553), force_cpu=True)()
        )
        assert lone.stats["device_batches"] == 0, lone.stats
        assert (
            lone.stats["native_batches"] + lone.stats["scalar_batches"] == 1
        )
        frag = {"sender_lane_lone_gate_native": 1}
        out.update(frag)
        _bank(frag)

        # -- (d) hidden-fraction audit through the REAL request path -----
        # stateless.dispatch_sender_recovery against an installed depth-2
        # scheduler: dispatch at decode time, the request's witness
        # verification in between, the `sched.sig_wait`-timed join before
        # execution — the serving code path itself, not a simulation
        import threading

        from phant_tpu import serving
        from phant_tpu.ops.witness_engine import WitnessEngine
        from phant_tpu.serving.scheduler import (
            SchedulerConfig,
            VerificationScheduler,
        )
        from phant_tpu.stateless import dispatch_sender_recovery

        wit_root, wit_nodes = _sender_lane_witness()
        woracle = WitnessEngine()
        assert woracle.verify(wit_root, wit_nodes)
        t_before = _m.snapshot()["timers"]

        def _delta(t_after, name):
            return t_after.get(name, {}).get("total_s", 0.0) - (
                t_before.get(name, {}).get("total_s", 0.0)
            )

        sig_env_prev = os.environ.get("PHANT_BATCHED_SIG")
        os.environ["PHANT_BATCHED_SIG"] = "1"
        s = VerificationScheduler(
            engine=WitnessEngine(),
            config=SchedulerConfig(
                max_batch=K,
                max_wait_ms=50.0,
                pipeline_depth=2,
                sig_engine_factory=lambda: SigEngine(device_floor=0),
            ),
        )
        serving.install(s)
        try:
            reqs, _rows = requests_for(771)
            results = [None] * K

            def one(i):
                resolve = dispatch_sender_recovery(1, reqs[i])
                assert resolve is not None, "sig lane not engaged"
                assert s.verify_traced(wit_root, wit_nodes)[0]
                results[i] = resolve()

            threads = [
                threading.Thread(target=one, args=(i,)) for i in range(K)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            st = s.stats_snapshot()
        finally:
            serving.uninstall(s)
            s.shutdown()
            if sig_env_prev is None:
                os.environ.pop("PHANT_BATCHED_SIG", None)
            else:
                os.environ["PHANT_BATCHED_SIG"] = sig_env_prev
        for i, got_s in enumerate(results):
            want = signer.recover_senders_async(reqs[i], force_cpu=True)()
            assert got_s == want, "sig-lane senders diverged under overlap"
        assert st["sig_coalesced"] >= 2, st
        t_after = _m.snapshot()["timers"]
        # the hidden-fraction denominator is the ENGINE's recovery cost
        # only — stateless.sig_rows runs on the request's own handler
        # thread and is never hidden, so counting it would inflate the
        # claim by exactly the on-critical-path row-build time
        cost = sum(
            _delta(t_after, f"witness_engine.sig_{ph}")
            for ph in ("prefetch", "pack", "dispatch", "resolve")
        )
        wait = _delta(t_after, "sched.sig_wait")
        frag = {
            "sender_lane_hidden_pct": round(
                max(0.0, 1.0 - wait / cost) * 100, 1
            )
            if cost > 0
            else None,
            "sender_lane_sched_coalesced": st["sig_coalesced"],
        }
        out.update(frag)
        _bank(frag)
    finally:
        set_crypto_backend("cpu")
    return out


def _sender_lane_witness():
    """A small witnessed account trie for the hidden-fraction audit's
    per-request witness-verification leg."""
    from phant_tpu.crypto.keccak import keccak256 as _k
    from phant_tpu.mpt.mpt import Trie as _Trie
    from phant_tpu.mpt.proof import generate_proof as _proof
    from phant_tpu.state.root import account_leaf as _aleaf
    from phant_tpu.types.account import Account as _Acct

    trie = _Trie()
    addrs = [bytes([1 + i]) * 20 for i in range(48)]
    for i, a in enumerate(addrs):
        trie.put(_k(a), _aleaf(_Acct(balance=i * 10**12 + 1)))
    nodes: dict = {}
    for a in addrs:
        for enc in _proof(trie, _k(a)):
            nodes[enc] = None
    return trie.root_hash(), list(nodes)


def sec_commitment_compare() -> dict:
    """Pluggable commitment schemes (phant_tpu/commitment/): the hexary
    MPT vs the binary Merkle backend on the SAME span.

    One deterministic mutating workload (hot/cold account touches +
    storage writes over a rolling state) is committed under BOTH schemes;
    per scheme the section measures witness bytes/block + nodes/block
    (the 2504.14069 axis: what a stateless client downloads) and
    blocks/s through the serving scheduler's verify_many (first pass =
    hash-bound, steady pass = memoized linkage-bound — the engine is
    scheme-blind by the ref-transparency contract, so this is the same
    code path either way). VERDICT IDENTITY is asserted in-section: the
    span carries corrupt witnesses (byte flips, a wrong root) and both
    schemes must accept/reject the identical pattern.

    Reading it: `commitment_binary_witness_savings_vs_mpt_pct` > 0 is
    the binary scheme's witness-size win (gated up by benchtrend;
    DETERMINISTIC — it is a byte count over a fixed span, identical on
    every rerun). `commitment_binary_throughput_vs_mpt_pct` is the
    verify-throughput margin — binary witnesses carry MORE, SMALLER
    nodes (deeper 2-ary paths), so per-node table costs push it down
    while per-byte hashing pushes it up; on the 2-core proxy box the two
    wash to parity within the box's noise (observed −16..+9% across
    identical reruns), so the committed number is an honest echo, not a
    claim. Both `commitment_*_witness_bytes_per_block` keys trend-gate
    down (growth = that scheme's encoding fattened)."""
    import random

    from phant_tpu.commitment import get_scheme
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )
    from phant_tpu.types.account import Account

    # 4096 accounts puts the hexary trie in its DENSE regime (path
    # branches near-full at ~530 B/level) — the regime the 2504.14069
    # witness-size comparison is about, and the one mainnet state lives
    # in; at a few hundred accounts the hexary path levels are sparse
    # (tiny branch encodings) and the comparison flatters neither scheme
    n_accounts = int(os.environ.get("PHANT_BENCH_COMMITMENT_ACCOUNTS", "4096"))
    n_blocks = int(os.environ.get("PHANT_BENCH_COMMITMENT_BLOCKS", "96"))
    touches = 6
    out: dict = {
        "commitment_compare_accounts": n_accounts,
        "commitment_compare_blocks": n_blocks,
    }

    def addr(i: int) -> bytes:
        return (
            b"\x00" * 17 + i.to_bytes(3, "big") if i >= 256 else bytes([i]) * 20
        )

    stored = tuple(range(1, 9))  # accounts with storage

    def build_span(scheme_name: str):
        """(witnesses, expected verdicts): the deterministic span under
        one scheme — same mutation sequence, same corruption pattern."""
        scheme = get_scheme(scheme_name)
        accounts = {}
        for i in range(1, n_accounts + 1):
            storage = (
                {j: j * 31 + 1 for j in range(1, 7)} if i in stored else {}
            )
            accounts[addr(i)] = Account(
                nonce=i % 5, balance=i * 10**12 + 7, storage=storage
            )
        trie = scheme.build_state_trie(accounts)
        rng = random.Random(0xC0117)
        witnesses, expect = [], []
        for b in range(n_blocks):
            # mainnet-shaped touch mix: a hot head + a cold tail
            touched = [addr(1 + rng.randrange(8))] + [
                addr(1 + rng.randrange(n_accounts))
                for _ in range(touches - 1)
            ]
            nodes: dict = {}
            for a in touched:
                for enc in scheme.proof_nodes(trie, keccak256(a)):
                    nodes[enc] = None
                st = accounts[a].storage
                if st:
                    strie = scheme.build_storage_trie(st)
                    slot = rng.choice(sorted(st))
                    for enc in scheme.proof_nodes(
                        strie, keccak256(slot.to_bytes(32, "big"))
                    ):
                        nodes[enc] = None
            root = trie.root_hash()
            nl = list(nodes)
            if b % 8 == 5:  # corrupt witness: byte flip in one node
                nl[0] = nl[0][:-1] + bytes([nl[0][-1] ^ 1])
                witnesses.append((root, nl))
                expect.append(False)
            elif b % 8 == 7:  # wrong root
                witnesses.append((bytes([b % 250 + 1]) * 32, nl))
                expect.append(False)
            else:
                witnesses.append((root, nl))
                expect.append(True)
            # roll the state forward (identical sequence per scheme)
            for a in touched:
                acct = accounts[a]
                acct.balance += b + 1
                if acct.storage:
                    slot = rng.choice(sorted(acct.storage))
                    acct.storage[slot] = acct.storage[slot] * 3 + b
                trie.put(keccak256(a), scheme.account_leaf(acct))
        return witnesses, expect

    def measure(witnesses):
        eng = WitnessEngine(max_nodes=1 << 20)
        with VerificationScheduler(
            engine=eng,
            config=SchedulerConfig(
                max_batch=64, max_wait_ms=2.0, queue_depth=4096
            ),
        ) as sched:
            t0 = time.perf_counter()
            first = list(sched.verify_many(witnesses))
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            steady = list(sched.verify_many(witnesses))
            steady_s = time.perf_counter() - t0
        assert steady == first  # memoization must not change verdicts
        return first, first_s, steady_s

    spans = {name: build_span(name) for name in ("mpt", "binary")}
    rates: dict = {}
    for rep in range(2):  # interleaved best-of: box noise, not code
        for name, (witnesses, expect) in spans.items():
            verdicts, first_s, steady_s = measure(witnesses)
            # in-section verdict-identity assert: both schemes must
            # accept/reject the identical corruption pattern
            if verdicts != expect:
                raise AssertionError(
                    f"commitment_compare: {name} verdicts diverge from "
                    f"the span's expected accept/reject pattern"
                )
            cur = rates.setdefault(name, [float("inf"), float("inf")])
            cur[0] = min(cur[0], first_s)
            cur[1] = min(cur[1], steady_s)

    for name, (witnesses, _e) in spans.items():
        total_bytes = sum(len(n) for _r, nl in witnesses for n in nl)
        total_nodes = sum(len(nl) for _r, nl in witnesses)
        first_s, steady_s = rates[name]
        frag = {
            f"commitment_{name}_witness_bytes_per_block": round(
                total_bytes / n_blocks, 1
            ),
            f"commitment_{name}_nodes_per_block": round(
                total_nodes / n_blocks, 1
            ),
            f"commitment_{name}_blocks_per_sec": round(n_blocks / first_s, 2),
            f"commitment_{name}_steady_blocks_per_sec": round(
                n_blocks / steady_s, 2
            ),
        }
        out.update(frag)
        _bank(frag)
        print(
            f"commitment_compare: {name} -> "
            f"{out[f'commitment_{name}_witness_bytes_per_block']} B/block, "
            f"{out[f'commitment_{name}_blocks_per_sec']} blocks/s first / "
            f"{out[f'commitment_{name}_steady_blocks_per_sec']} steady",
            file=sys.stderr,
        )
    frag = {
        "commitment_binary_witness_savings_vs_mpt_pct": round(
            (
                1
                - out["commitment_binary_witness_bytes_per_block"]
                / out["commitment_mpt_witness_bytes_per_block"]
            )
            * 100,
            1,
        ),
        "commitment_binary_throughput_vs_mpt_pct": round(
            (
                out["commitment_binary_blocks_per_sec"]
                / out["commitment_mpt_blocks_per_sec"]
                - 1
            )
            * 100,
            1,
        ),
        "commitment_verdict_identity": 1,  # the asserts above would have raised
    }
    out.update(frag)
    _bank(frag)
    return out


def sec_obs_overhead() -> dict:
    """Critical-path attribution overhead (PR 15): the proof that the
    observability layer is free enough to leave ON in production.

    The depth-2 serving path (the witness_stream shape: handler threads
    opening `verify_block` spans and coalescing through one pipelined
    VerificationScheduler) runs with the attribution layer ON
    (critpath rollup at every span close + per-lane device-busy
    integration, obs/critpath.py + obs/busy.py) vs OFF
    (PHANT_OBS_ATTRIBUTION=0 — the same switch an operator has). The box
    swings single runs, so the committed claim is the MEDIAN of PAIRED
    interleaved runs next to a same-statistic A/A (on vs on) noise bar:
    acceptance is `obs_overhead_pct` WITHIN `obs_overhead_noise_aa_pct`,
    never a raw delta. In-section, the attribution-on legs must also
    prove the layer WORKS: verdict identity against the direct
    verify_batch oracle (attribution may never change an answer), and
    the critical-path coverage assert — attributed phases >= 95% of
    wall clock (`critpath.coverage_pct`'s acceptance surface; the
    residual gauge is the honesty check)."""
    import threading

    from phant_tpu import serving
    from phant_tpu.obs import critpath
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )
    from phant_tpu.stateless import verify_witness_nodes
    from phant_tpu.utils.trace import metrics as _m
    from phant_tpu.utils.trace import span, trace_context

    warm, chain = _witness_chain()
    n = len(chain)
    pairs = int(os.environ.get("PHANT_BENCH_OBS_PAIRS", "5"))
    workers = int(os.environ.get("PHANT_BENCH_OBS_THREADS", "8"))
    mb = int(os.environ.get("PHANT_BENCH_STREAM_BATCH", "16"))

    # ONE warmed memoized engine shared by every leg: steady-state serving
    # (the reuse-dominated regime) is where a fixed per-request
    # attribution cost is the LARGEST fraction of wall clock — measuring
    # there is the conservative choice
    eng = WitnessEngine()
    wb = int(os.environ.get("PHANT_BENCH_ENGINE_BATCH", "256"))
    for i in range(0, len(warm), wb):
        assert eng.verify_batch(warm[i : i + wb]).all()
    want = [bool(v) for v in eng.verify_batch(chain)]

    def leg(enabled: bool) -> float:
        critpath.configure(enabled=enabled)
        got: list = [None] * n
        with VerificationScheduler(
            engine=eng,
            config=SchedulerConfig(
                max_batch=mb,
                max_wait_ms=4.0,
                queue_depth=n + 1,
                pipeline_depth=2,
            ),
        ) as s:
            serving.install(s)
            try:
                pending = list(range(n))
                plock = threading.Lock()

                def drive() -> None:
                    while True:
                        with plock:
                            if not pending:
                                return
                            i = pending.pop()
                        root, nodes = chain[i]
                        # the serving request shape: one verify_block
                        # span per request, the witness phase inside it,
                        # the scheduler's batch record folded in by
                        # verify_witness_nodes — exactly what the
                        # critpath sink rolls up on a live server
                        with trace_context(), span(
                            "verify_block", block=i, nodes=len(nodes), codes=0
                        ):
                            with _m.phase("stateless.witness_verify"):
                                got[i] = verify_witness_nodes(root, nodes)

                t0 = time.perf_counter()
                threads = [
                    threading.Thread(target=drive) for _ in range(workers)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                dt = time.perf_counter() - t0
            finally:
                serving.uninstall(s)
        assert got == want, "attribution changed a verdict"
        return dt

    try:
        leg(True)  # warm the serving path; discarded
        w0, a0 = critpath.totals()
        d_on: list = []
        d_off: list = []
        deltas: list = []
        aa: list = []
        for _ in range(pairs):
            off = leg(False)
            on = leg(True)
            on2 = leg(True)  # the A/A twin measures the box, not the code
            d_off.append(off)
            # the twin feeds ONLY the noise bar (equal sample counts for
            # the committed rates — the witness_stream discipline)
            d_on.append(on)
            deltas.append(on / off - 1.0)
            aa.append(abs(1.0 - on2 / on))
        w1, a1 = critpath.totals()
    finally:
        critpath.configure(enabled=True)
    coverage = 100.0 * (a1 - a0) / max(w1 - w0, 1e-9)
    # THE in-section acceptance: attributed phases must cover >= 95% of
    # the serving path's wall clock — anything lower means the tiling is
    # missing a real cost and the whole family overstates itself
    assert coverage >= 95.0, f"critpath coverage {coverage:.2f}% < 95%"
    deltas.sort()
    aa.sort()
    frag = {
        "obs_overhead_blocks": n,
        "obs_overhead_pairs": pairs,
        "obs_overhead_workers": workers,
        "obs_overhead_off_blocks_per_sec": round(n / min(d_off), 2),
        "obs_overhead_on_blocks_per_sec": round(n / min(d_on), 2),
        "obs_overhead_pct": round(deltas[len(deltas) // 2] * 100, 2),
        "obs_overhead_noise_aa_pct": round(aa[len(aa) // 2] * 100, 2),
        "obs_overhead_coverage_pct": round(coverage, 2),
        "obs_overhead_verdict_identity": 1,  # the leg asserts would raise
    }
    _bank(frag)
    return frag


def sec_timeline_overhead() -> dict:
    """Timeline recorder overhead (PR 16): the proof the tail-sampled
    timeline layer (obs/timeline.py — the third span sink plus the
    scheduler batch and device-busy taps) is free enough to leave ON, on
    the same depth-2 serving path and with the same statistics discipline
    as `obs_overhead`: MEDIAN of PAIRED interleaved on/off runs against a
    same-statistic A/A (on vs on) noise bar — acceptance is
    `timeline_overhead_pct` WITHIN `timeline_overhead_noise_aa_pct`,
    never a raw delta. The attribution layer stays ON in BOTH legs (the
    A/B isolates the timeline increment). In-section the on legs must
    also prove the layer WORKS: verdict identity (the recorder may never
    change an answer), tail-sampling reconciliation — kept + sampled_out
    EXACTLY equals offered load (sampling is never silent), and a final
    export must parse as Chrome-trace JSON with events in it."""
    import json as _json
    import random as _random
    import threading

    from phant_tpu import serving
    from phant_tpu.obs import critpath, timeline
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )
    from phant_tpu.stateless import verify_witness_nodes
    from phant_tpu.utils.trace import metrics as _m
    from phant_tpu.utils.trace import span, trace_context

    warm, chain = _witness_chain()
    n = len(chain)
    pairs = int(os.environ.get("PHANT_BENCH_OBS_PAIRS", "5"))
    workers = int(os.environ.get("PHANT_BENCH_OBS_THREADS", "8"))
    mb = int(os.environ.get("PHANT_BENCH_STREAM_BATCH", "16"))
    sample_n = int(os.environ.get("PHANT_TIMELINE_SAMPLE_N", "16"))

    eng = WitnessEngine()
    wb = int(os.environ.get("PHANT_BENCH_ENGINE_BATCH", "256"))
    for i in range(0, len(warm), wb):
        assert eng.verify_batch(warm[i : i + wb]).all()
    want = [bool(v) for v in eng.verify_batch(chain)]

    def leg(enabled: bool) -> float:
        timeline.configure(enabled=enabled)
        got: list = [None] * n
        with VerificationScheduler(
            engine=eng,
            config=SchedulerConfig(
                max_batch=mb,
                max_wait_ms=4.0,
                queue_depth=n + 1,
                pipeline_depth=2,
            ),
        ) as s:
            serving.install(s)
            try:
                pending = list(range(n))
                plock = threading.Lock()

                def drive() -> None:
                    while True:
                        with plock:
                            if not pending:
                                return
                            i = pending.pop()
                        root, nodes = chain[i]
                        with trace_context(), span(
                            "verify_block", block=i, nodes=len(nodes), codes=0
                        ):
                            with _m.phase("stateless.witness_verify"):
                                got[i] = verify_witness_nodes(root, nodes)

                t0 = time.perf_counter()
                threads = [
                    threading.Thread(target=drive) for _ in range(workers)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                dt = time.perf_counter() - t0
            finally:
                serving.uninstall(s)
        assert got == want, "timeline recorder changed a verdict"
        return dt

    try:
        critpath.configure(enabled=True)
        leg(True)  # warm the serving path; discarded
        # reconciliation window starts HERE: every request driven with
        # the recorder on from now must land in kept or sampled_out
        timeline.reset()
        timeline.configure(
            sample_n=sample_n, rng=_random.Random(0xF00D)
        )
        d_on: list = []
        d_off: list = []
        deltas: list = []
        aa: list = []
        for _ in range(pairs):
            off = leg(False)
            on = leg(True)
            on2 = leg(True)  # the A/A twin measures the box, not the code
            d_off.append(off)
            d_on.append(on)
            deltas.append(on / off - 1.0)
            aa.append(abs(1.0 - on2 / on))
        st = timeline.stats()
        export = timeline.export(window_s=3600.0)
    finally:
        timeline.configure(enabled=True)
    offered = 2 * pairs * n  # the on + on2 legs; off legs record nothing
    kept_total = sum(st["kept"].values())
    sampled_out = st["dropped"].get("sampled_out", 0)
    # THE in-section acceptance: tail-sampling is never silent — the
    # counters reconcile EXACTLY with offered load (ring_full evictions
    # count previously-kept entries and stay out of this identity)
    assert kept_total + sampled_out == offered, (
        f"timeline counters leak: kept {kept_total} + sampled_out "
        f"{sampled_out} != offered {offered}"
    )
    # and the export is real Chrome-trace JSON with the load in it
    events = _json.loads(_json.dumps(export, default=str))["traceEvents"]
    assert events, "timeline export came back empty"
    deltas.sort()
    aa.sort()
    frag = {
        "timeline_overhead_blocks": n,
        "timeline_overhead_pairs": pairs,
        "timeline_overhead_workers": workers,
        "timeline_overhead_sample_n": sample_n,
        "timeline_overhead_off_blocks_per_sec": round(n / min(d_off), 2),
        "timeline_overhead_on_blocks_per_sec": round(n / min(d_on), 2),
        "timeline_overhead_pct": round(deltas[len(deltas) // 2] * 100, 2),
        "timeline_overhead_noise_aa_pct": round(aa[len(aa) // 2] * 100, 2),
        "timeline_overhead_kept": kept_total,
        "timeline_overhead_sampled_out": sampled_out,
        "timeline_overhead_offered": offered,
        "timeline_overhead_export_events": len(events),
        "timeline_overhead_reconciled": 1,  # the assert above would raise
        "timeline_overhead_verdict_identity": 1,  # leg asserts would raise
    }
    _bank(frag)
    return frag


def sec_sanitizer_overhead() -> dict:
    """phantsan lockset-sanitizer overhead (PR 17): what the sanitized
    gate costs, so `make sanitize-py` and check.sh's serving_sanitized
    group carry a committed price tag instead of folklore.

    The depth-2 serving path (handler threads submitting witness jobs
    through one pipelined VerificationScheduler) runs with phantsan ON
    (instrumented Lock/RLock proxies + per-field lockset tracking on the
    scheduler class, analysis/sanitizer.py) vs OFF. Statistics discipline
    as in `obs_overhead`: MEDIAN of PAIRED interleaved on/off runs next
    to a same-statistic A/A (on vs on) noise bar. Unlike the obs legs the
    overhead is NOT expected to sit within the bar — it is the price of
    opting in — so the committed claim is the honest number itself.
    In-section the legs must prove the sanitizer WORKS and the path is
    CLEAN: verdict identity (instrumentation may never change an answer),
    zero race reports from the scheduler legs (the race-free gate this
    bench rides on), and a positive control — a deliberately racy
    unlocked counter class must produce a two-stack report, or the zero
    above is the silence of a dead detector."""
    import threading

    from phant_tpu.analysis import sanitizer
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import (
        SchedulerConfig,
        VerificationScheduler,
    )

    warm, chain = _witness_chain()
    n = len(chain)
    pairs = int(os.environ.get("PHANT_BENCH_OBS_PAIRS", "5"))
    workers = int(os.environ.get("PHANT_BENCH_OBS_THREADS", "8"))
    mb = int(os.environ.get("PHANT_BENCH_STREAM_BATCH", "16"))

    eng = WitnessEngine()
    wb = int(os.environ.get("PHANT_BENCH_ENGINE_BATCH", "256"))
    for i in range(0, len(warm), wb):
        assert eng.verify_batch(warm[i : i + wb]).all()
    want = [bool(v) for v in eng.verify_batch(chain)]

    # positive control FIRST: a two-thread unlocked counter on a
    # registered class must produce a report, or every "zero races"
    # number below is the silence of a dead detector
    class _RacyControl:
        def __init__(self):
            self.hits = 0

    sanitizer.enable()
    sanitizer.register_shared_class(_RacyControl)
    try:
        ctl = _RacyControl()
        gate = threading.Barrier(2)

        def bump() -> None:
            gate.wait()
            for _ in range(64):
                ctl.hits += 1

        ts = [threading.Thread(target=bump) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        control = sanitizer.drain_reports()
    finally:
        sanitizer.unregister(_RacyControl)
        sanitizer.disable()
    assert control, "positive control: deliberate race produced no report"

    def leg(sanitized: bool) -> float:
        reports: list = []
        if sanitized:
            sanitizer.enable()
            sanitizer.register_shared_class(VerificationScheduler)
        try:
            got: list = [None] * n
            # constructed AFTER enable(): the scheduler's own locks must
            # be proxies for the lockset tracking to see them held
            with VerificationScheduler(
                engine=eng,
                config=SchedulerConfig(
                    max_batch=mb,
                    max_wait_ms=4.0,
                    queue_depth=n + 1,
                    pipeline_depth=2,
                ),
            ) as s:
                pending = list(range(n))
                plock = threading.Lock()

                def drive() -> None:
                    while True:
                        with plock:
                            if not pending:
                                return
                            i = pending.pop()
                        root, nodes = chain[i]
                        got[i] = s.submit_witness(root, nodes).result(
                            timeout=300
                        )

                t0 = time.perf_counter()
                threads = [
                    threading.Thread(target=drive) for _ in range(workers)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                dt = time.perf_counter() - t0
        finally:
            if sanitized:
                reports.extend(sanitizer.drain_reports())
                sanitizer.unregister(VerificationScheduler)
                sanitizer.disable()
        assert got == want, "sanitizer instrumentation changed a verdict"
        assert not reports, (
            "sanitized serving leg raced:\n" + reports[0].format()
        )
        return dt

    leg(True)  # warm the serving path (and the proxy classes); discarded
    d_on: list = []
    d_off: list = []
    deltas: list = []
    aa: list = []
    for _ in range(pairs):
        off = leg(False)
        on = leg(True)
        on2 = leg(True)  # the A/A twin measures the box, not the code
        d_off.append(off)
        d_on.append(on)
        deltas.append(on / off - 1.0)
        aa.append(abs(1.0 - on2 / on))
    deltas.sort()
    aa.sort()
    frag = {
        "sanitizer_overhead_blocks": n,
        "sanitizer_overhead_pairs": pairs,
        "sanitizer_overhead_workers": workers,
        "sanitizer_overhead_off_blocks_per_sec": round(n / min(d_off), 2),
        "sanitizer_overhead_on_blocks_per_sec": round(n / min(d_on), 2),
        "sanitizer_overhead_pct": round(deltas[len(deltas) // 2] * 100, 2),
        "sanitizer_overhead_noise_aa_pct": round(aa[len(aa) // 2] * 100, 2),
        "sanitizer_overhead_reports": 0,  # the leg asserts would raise
        "sanitizer_overhead_positive_control": len(control),
        "sanitizer_overhead_verdict_identity": 1,  # leg asserts would raise
    }
    _bank(frag)
    return frag


# priority order matters: when the window is short, the headline
# engine number and the GLV proof come first
_CPU_SECTIONS = {
    "engine": sec_engine_cpu,
    "serving_load": sec_serving_load,
    "serving_mesh": sec_serving_mesh,
    "commitment_compare": sec_commitment_compare,
    "obs_overhead": sec_obs_overhead,
    "timeline_overhead": sec_timeline_overhead,
    "sanitizer_overhead": sec_sanitizer_overhead,
    "replay": sec_replay_cpu,
    "replay_sync": sec_replay_sync,
    "state_root": sec_state_root_cpu,
    "ecrecover": sec_ecrecover_cpu,
    "keccak": sec_keccak_cpu,
}
_DEVICE_SECTIONS = {
    # priority order under the global budget: the headline (engine) first,
    # then the resident-table slope claim (the >=10x driver capture this
    # architecture exists for), the pipelined A/B (the PR 5 overlap
    # claim), keccak (cheap, and r5's device-kernel story rides on its
    # slope-timed resident rates), then the long ecrecover/replay runs
    "engine": sec_engine_device,
    "witness_resident": sec_witness_resident,
    "engine_pipeline": sec_engine_pipeline,
    "witness_stream": sec_witness_stream,
    "post_root": sec_post_root,
    "sender_lane": sec_sender_lane,
    "keccak": sec_keccak_device,
    "ecrecover": sec_ecrecover_device,
    "replay": sec_replay_device,
    "state_root": sec_state_root_device,
}
# per-section child budgets (seconds); cold device compiles dominate
_DEVICE_BUDGET = {
    "engine": 700,
    "witness_resident": 420,
    "engine_pipeline": 420,
    "witness_stream": 420,
    "post_root": 420,
    "sender_lane": 420,
    "ecrecover": 900,
    "replay": 700,
    "state_root": 480,
    "keccak": 360,
}
_FRAGMENT_MARK = "@@BENCH_FRAGMENT@@ "
_IS_CHILD = False


def _child_main(name: str) -> None:
    """Child-process entry: run ONE device section against whatever jax
    platform the environment provides, print the fragment, exit. A hang
    here is killed by the parent without poisoning anything else."""
    global _IS_CHILD

    _IS_CHILD = True
    from phant_tpu.ops._cache import enable_compilation_cache

    enable_compilation_cache()
    _metrics_reset()
    try:
        frag = _DEVICE_SECTIONS[name]()
    except Exception as e:
        frag = {f"{name}_device_error": repr(e)[:240]}
    # per-section phase attribution rides in the same fragment line (a
    # kill after the section loses only this snapshot, not measurements);
    # keyed `<name>_device` so the CPU section of the same name can never
    # clobber the device attribution in detail.metrics (or vice versa)
    frag.update(_metrics_frag(f"{name}_device"))
    print(_FRAGMENT_MARK + json.dumps(frag), flush=True)


def _spawn_section(name: str, timeout_s: float, device_env: dict) -> dict:
    """Run one device section in a killable child; returns its fragment."""
    t0 = time.perf_counter()
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--section", name],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=device_env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        _CHILDREN.append(proc)
        killed = False
        try:
            out, err = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            killed = True
            proc.kill()
            out, err = proc.communicate()
        finally:
            _CHILDREN.remove(proc)
        # merge EVERY fragment line in order: sections bank intermediate
        # measurements (e.g. each replay variant) as they finish, so a kill
        # or crash costs only the unfinished work
        frag: dict = {}
        for line in (out or "").splitlines():
            if line.startswith(_FRAGMENT_MARK):
                try:
                    frag.update(json.loads(line[len(_FRAGMENT_MARK) :]))
                except json.JSONDecodeError:
                    pass  # a torn final line from the kill
        if killed:
            frag[f"{name}_device_error"] = f"child killed after {timeout_s:.0f}s"
        elif not frag:
            frag[f"{name}_device_error"] = (
                f"no fragment (rc={proc.returncode}): " + ((err or out) or "")[-240:]
            )
        frag[f"{name}_device_seconds"] = round(time.perf_counter() - t0, 1)
        return frag
    except Exception as e:
        return {f"{name}_device_error": repr(e)[:240]}


def _probe_device(device_env: dict, timeout_s: float) -> tuple:
    """(ok, err) — one throwaway-subprocess liveness check with a real
    compute + forced readback."""
    try:
        probe = subprocess.run(
            [
                sys.executable,
                "-c",
                "import jax, numpy as np, jax.numpy as jnp; d = jax.devices(); "
                "x = jnp.ones((64, 64)); r = np.asarray(x @ x); "
                "print(d[0].platform, r[0, 0])",
            ],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=device_env,
        )
        if probe.returncode == 0 and probe.stdout.strip():
            plat = probe.stdout.strip().splitlines()[-1].split()[0]
            if plat != "cpu":
                return True, None
            return False, "probe returned cpu despite TPU env"
        return False, (probe.stderr or "empty probe output")[-240:]
    except subprocess.TimeoutExpired:
        return False, f"probe timed out after {timeout_s:.0f}s"


def main() -> None:
    import faulthandler
    import signal as _signal

    # kill -USR1 <pid> dumps all python stacks to stderr — the one-line
    # debugger for "which device call never returned"
    faulthandler.register(_signal.SIGUSR1)

    # the driver's own `timeout` sends SIGTERM before SIGKILL; a run killed
    # that way must STILL publish its partial JSON (BENCH_r05 died rc=124
    # with parsed=null — every finished CPU section lost). Same final-print
    # path as the internal global deadline.
    def _on_term(signum, _frame):
        _PARTIAL["detail"]["terminated_by_signal"] = signum
        # emit FIRST: `timeout -k` escalates TERM->KILL after a short
        # grace, and the artifact matters more than reaping children
        _emit_final()
        for p in _CHILDREN:
            try:
                p.kill()
            except Exception:
                pass
        os._exit(0)

    _signal.signal(_signal.SIGTERM, _on_term)
    _signal.signal(_signal.SIGINT, _on_term)
    t_start = time.perf_counter()
    global_budget = _GLOBAL_BUDGET
    _arm_global_deadline()
    detail = _PARTIAL["detail"]

    only = os.environ.get("PHANT_BENCH_ONLY", "")
    selected = [s.strip() for s in only.split(",") if s.strip()] or (
        list(_CPU_SECTIONS)
        + [
            "witness_resident",
            "engine_pipeline",
            "witness_stream",
            "post_root",
            "sender_lane",
        ]
    )
    # legacy per-section kill switches stay honored
    for flag, sec in (
        ("PHANT_BENCH_STATE_ROOT", "state_root"),
        ("PHANT_BENCH_REPLAY", "replay"),
        ("PHANT_BENCH_KECCAK", "keccak"),
        ("PHANT_BENCH_ECRECOVER", "ecrecover"),
    ):
        if os.environ.get(flag, "1") in ("0", "") and sec in selected:
            selected.remove(sec)

    # the parent stays off jax (a chip belongs to one process, and the
    # device sections run in children); the child env keeps whatever
    # platform selection the caller made. Whether a chip is there is the
    # child probe's answer below, never a guess from environment names —
    # except that JAX_PLATFORMS=cpu is the caller asking for a CPU bench.
    device_env = dict(os.environ)
    env_platforms = os.environ.get("JAX_PLATFORMS", "")
    os.environ["JAX_PLATFORMS"] = "cpu"

    probe_attempts: list = []
    detail["tpu_probe_attempts"] = probe_attempts

    def probe(timeout_s: float) -> bool:
        ok, err = _probe_device(device_env, timeout_s)
        probe_attempts.append(
            {
                "t_s": round(time.perf_counter() - t_start, 1),
                "ok": ok,
                **({"err": err[-120:]} if err else {}),
            }
        )
        return ok

    def remaining() -> float:
        return global_budget - (time.perf_counter() - t_start)

    def afford(env_key: str, default: int) -> int:
        """A section watchdog capped at what the wall budget can still
        afford (reserve intact): one definition so the three in-process
        section kinds cannot drift."""
        return min(
            int(os.environ.get(env_key, default)),
            max(int(remaining() - _BUDGET_RESERVE), 1),
        )

    alive = False
    n_initial = int(os.environ.get("PHANT_BENCH_PROBE_RETRIES", "2"))
    probe_timeout = float(os.environ.get("PHANT_BENCH_PROBE_TIMEOUT", "90"))
    if env_platforms != "cpu":
        for _ in range(n_initial):
            _log(f"probing device (timeout {probe_timeout:.0f}s) ...")
            if probe(probe_timeout):
                alive = True
                break
        _log(f"device {'ALIVE' if alive else 'absent'} after initial probes")
    if not alive and os.environ.get("PHANT_BENCH_REQUIRE_TPU"):
        last_err = probe_attempts[-1].get("err") if probe_attempts else "unprobed"
        print(f"[bench] FATAL: no device ({env_platforms!r}): {last_err}", file=sys.stderr)
        sys.exit(2)
    tpu_expected = alive
    # CPU baselines must run at the same batch sizes the device run uses
    # (r2 asymmetry lesson): a device-bound run sizes both sides big
    os.environ["PHANT_BENCH_DEVICE"] = "1" if tpu_expected else "0"

    # datasets first (outside any watchdog; disk-cached for repeat runs)
    _log("building datasets ...")
    t0 = time.perf_counter()
    if "engine" in selected:
        _witness_chain()
    if "replay" in selected:
        try:
            _replay_chain()
        except Exception as e:
            detail["replay_error"] = f"chain build: {repr(e)[:200]}"
            selected.remove("replay")
    detail["dataset_build_seconds"] = round(time.perf_counter() - t0, 1)

    run_device_inline = not tpu_expected  # CPU-only run: XLA-CPU inline
    device_done: set = set()

    def run_device_sections() -> None:
        """Device sections in priority order, each in a killable child."""
        for name in _DEVICE_SECTIONS:
            if name not in selected or name in device_done:
                continue
            if name == "ecrecover" and os.environ.get(
                "PHANT_BENCH_ECRECOVER", "1"
            ) in ("0", ""):
                continue
            budget = min(
                float(
                    os.environ.get(
                        f"PHANT_BENCH_SEC_{name.upper()}_TIMEOUT",
                        _DEVICE_BUDGET[name],
                    )
                ),
                remaining() - 90,  # leave room for the final print
            )
            if budget < max(60.0, _BUDGET_RESERVE):
                _skip_budget(detail, f"{name}_device")
                continue
            device_env["PHANT_BENCH_DEVICE"] = "1"
            frag = _spawn_section(name, budget, device_env)
            _merge_frag(detail, frag)
            device_done.add(name)

    def run_cpu_sections() -> None:
        for name, fn in _CPU_SECTIONS.items():
            if name not in selected:
                continue
            # budget check BEFORE starting: work the deadline would kill
            # mid-flight is better spent emitting what already finished
            if remaining() < _BUDGET_RESERVE:
                _skip_budget(detail, name)
                continue
            _log(f"cpu section {name} ...")
            t0 = time.perf_counter()
            _metrics_reset()
            try:
                # the watchdog is capped at what the wall budget can still
                # afford, so a slow section times out into ITS error key
                # (with the reserve intact) instead of eating the run
                with _watchdog(afford("PHANT_BENCH_SECTION_TIMEOUT", 480)):
                    _merge_frag(detail, fn())
            except Exception as e:
                detail[f"{name}_cpu_error"] = repr(e)[:200]
            # snapshot whatever the section recorded (even on a timeout —
            # partial phase attribution still explains the artifact)
            _merge_frag(detail, _metrics_frag(name))
            _log(f"cpu section {name} done in {time.perf_counter() - t0:.1f}s")
            _refresh_headline()

    def run_device_inline_sections() -> None:
        """CPU-only runs execute the device sections inline as the XLA-CPU
        path (the r1-r3 contract: keccak/replay-tpu keys exist on every
        artifact). engine/state_root device variants are skipped — minutes
        of XLA-CPU compile for a non-number (r3 lesson)."""
        os.environ["PHANT_BENCH_DEVICE"] = "0"
        _pin_jax_cpu()
        # engine_pipeline + witness_resident run inline on CPU-only boxes
        # (XLA-CPU device proxy): the depth A/B is the PR 5 acceptance
        # number, the resident slope/byte-accounting keys are the PR 8
        # acceptance surface, and their witness-shape compiles are
        # seconds, not the minutes that keep engine/state_root device
        # variants out of the inline list
        for name in (
            "witness_resident",
            "engine_pipeline",
            "witness_stream",
            "post_root",
            "sender_lane",
            "replay",
            "keccak",
        ):
            if name not in selected:
                continue
            if name == "replay":
                # its device variant selects --crypto_backend=tpu, which
                # refuses the CPU platform (backend.set_crypto_backend); a
                # host-routed replay is not written under a device name,
                # and the artifact says so instead of silently lacking it
                detail["replay_device_refused"] = (
                    "cpu platform: --crypto_backend=tpu needs a TPU; no "
                    "replay_tpu_* keys on a CPU bench"
                )
                continue
            if name == "keccak" and os.environ.get("PHANT_BENCH_KECCAK", "1") in ("0", ""):
                continue
            if remaining() < _BUDGET_RESERVE:
                _skip_budget(detail, f"{name}_device_inline")
                continue
            _log(f"inline device section {name} ...")
            t0 = time.perf_counter()
            _metrics_reset()
            try:
                with _watchdog(afford("PHANT_BENCH_SECTION_TIMEOUT", 480)):
                    _merge_frag(detail, _DEVICE_SECTIONS[name]())
            except Exception as e:
                detail[f"{name}_device_error"] = repr(e)[:200]
            _merge_frag(detail, _metrics_frag(f"{name}_device_inline"))
            _log(f"inline device section {name} done in {time.perf_counter() - t0:.1f}s")
        if "ecrecover" in selected and os.environ.get(
            "PHANT_BENCH_ECRECOVER", "1"
        ) not in ("0", ""):
            if remaining() < _BUDGET_RESERVE:
                _skip_budget(detail, "ecrecover_device_inline")
                return
            _metrics_reset()
            try:
                with _watchdog(afford("PHANT_BENCH_ECRECOVER_TIMEOUT", 900)):
                    _merge_frag(detail, sec_ecrecover_device())
            except Exception as e:
                detail["ecrecover_device_error"] = repr(e)[:200]
            _merge_frag(detail, _metrics_frag("ecrecover_device_inline"))

    def _refresh_headline() -> None:
        cpu_rate = detail.get("cpu_baseline_blocks_per_sec")
        dev = detail.get("engine_tpu_blocks_per_sec") or detail.get(
            "engine_cpu_blocks_per_sec"
        )
        # the north-star headline: once the resident slope rate was
        # measured on a REAL accelerator, the artifact's value /
        # vs_baseline come from it (RTT-insensitive, the >=10x driver
        # capture). The XLA-CPU proxy run keeps the memoized-engine
        # headline — its slope number measures host compute, and the
        # section's gap_attribution key says so.
        slope = detail.get("witness_fused_resident_slope_blocks_per_sec")
        if slope and detail.get("witness_resident_backend") not in (None, "cpu"):
            dev = slope
        if dev:
            _PARTIAL["value"] = dev
            if cpu_rate:
                _PARTIAL["vs_baseline"] = round(dev / cpu_rate, 2)

    # --- orchestration: device first when alive; otherwise CPU first then
    # retry the probe for the remainder of the window -----------------------
    if alive:
        run_device_sections()
        run_cpu_sections()
    else:
        run_cpu_sections()
        if run_device_inline:
            run_device_inline_sections()
    detail.setdefault("backend", "cpu")  # children set the real platform
    detail["timing"] = "forced-readback"
    _refresh_headline()
    _emit_final()


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--section":
        _child_main(sys.argv[2])
    else:
        main()
