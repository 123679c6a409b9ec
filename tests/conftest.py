"""Test configuration: force an 8-device virtual CPU mesh before JAX import.

Multi-chip hardware is unavailable in CI; sharding tests run on a virtual
CPU mesh (the driver separately validates the multi-chip path via
__graft_entry__.dryrun_multichip). The chip itself is exercised by
`chip_smoke.py`, never by this suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# `set_crypto_backend("tpu")` (phant_tpu/backend.py) refuses the CPU
# platform unless told the CPU-mesh jax run IS the point — here it is
os.environ["PHANT_ALLOW_JAX_CPU"] = "1"
# test-suite compile cache: jax segfaults (not raises) on a cache
# entry corrupted by concurrent writers, so each process CLASS places
# its own dir — the serving CLI uses build/jax_cache, check.sh
# groups use build/jax_cache_tests (sequential), and direct pytest
# invocations default to build/jax_cache_pytest here. The dir is
# persistent on purpose: a throwaway per-session tmpdir made EVERY
# pytest invocation recompile every kernel cold — the tier-1 driver
# command timed out at ~26% of the suite purely on recompiles
# (test_cancun_block_end_to_end alone: 163s cold vs 79s warm). Entries
# already present are read-only, so repeat runs shrink the sporadic
# write-a-cache-entry SIGSEGV window rather than widening it. Residual
# risk: two SIMULTANEOUS direct pytest runs share this dir — don't do
# that (or point JAX_COMPILATION_CACHE_DIR somewhere private, which
# always wins).
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "build",
        "jax_cache_pytest",
    )
    os.makedirs(_cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
os.environ.setdefault("PHANT_TPU_FORCE_TRIE", "1")  # bypass the link
# cost model: differential tests must exercise the device dispatch even
# though a CPU-mesh "link" never pays off for tiny tries
os.environ.setdefault("PHANT_TPU_MIN_TRIE", "1")  # small test tries must
# still exercise the device dispatch path
os.environ.setdefault("PHANT_TPU_MIN_ECRECOVER", "1")  # likewise for the
# batched device ecrecover (production floor is 64)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# the config outranks the env var, so pin it too (backends initialize
# lazily, so this is still early enough)
import jax

jax.config.update("jax_platforms", "cpu")


import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from phant_tpu.ops._cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


import pytest  # noqa: E402


def _backend_combo(param: str):
    """Shared backend-switching protocol for the evm_backend* fixtures:
    one place owns the skip condition, the set, and the teardown."""
    from phant_tpu.backend import set_crypto_backend, set_evm_backend
    from phant_tpu.evm.native_vm import native_available

    if param in ("native", "tpu") and not native_available():
        pytest.skip("native toolchain unavailable")
    set_evm_backend("python" if param == "python" else "native")
    set_crypto_backend("tpu" if param == "tpu" else "cpu")
    yield param
    set_evm_backend("python")
    set_crypto_backend("cpu")


@pytest.fixture(params=["python", "native", "tpu"])
def evm_backend(request):
    """Run a test across backend combinations: "python"/"native" diff the two
    EVM backends (the C++ core is the reference's evmone analog) on the cpu
    crypto backend; "tpu" runs the native EVM with `--crypto_backend=tpu`
    (batched jax ecrecover + device trie roots on the CPU mesh), so the whole
    pipeline is differentially verified end-to-end (SURVEY §4)."""
    yield from _backend_combo(request.param)


@pytest.fixture(params=["python", "native"])
def evm_backend_cpu(request):
    """The two EVM backends on the cpu crypto backend only.  For test
    families whose per-test "tpu" value is redundant: the tpu param
    exercises the SAME batched-jax sender-recovery/trie code for every
    test in a family (it has no per-test surface), and each run costs
    seconds of XLA-CPU kernel execution on the gate's one core — so a
    family keeps a couple of representative 3-backend tests on
    `evm_backend` and runs the rest here (VERDICT r4 #10: gate time)."""
    yield from _backend_combo(request.param)


# ---------------------------------------------------------------------------
# phantsan: PHANT_SANITIZE=1 runs the whole session under the lockset race
# sanitizer (phant_tpu/analysis/sanitizer.py). Enabled at conftest import —
# before any test module imports the serving stack — so every
# threading.Lock/RLock the scheduler, engines, and obs rings construct is a
# tracking proxy. sessionfinish fails the run on undrained reports; the
# deliberately-racy fixtures in test_sanitizer.py drain their own.
# ---------------------------------------------------------------------------

_PHANT_SANITIZE = os.environ.get("PHANT_SANITIZE") == "1"

if _PHANT_SANITIZE:
    from phant_tpu.analysis import sanitizer as _sanitizer

    _sanitizer.enable()
    _sanitizer.register_default_shared_classes()


def pytest_sessionfinish(session, exitstatus):
    if not _PHANT_SANITIZE:
        return
    from phant_tpu.analysis import sanitizer as _sanitizer

    reports = _sanitizer.drain_reports()
    if reports:
        sys.stderr.write("\n\n".join(r.format() for r in reports) + "\n")
        sys.stderr.write(
            f"\nphantsan: {len(reports)} data race(s) detected — failing "
            "the sanitized session\n"
        )
        session.exitstatus = 1


@pytest.fixture(params=["native", "python"])
def node_encoder(request, monkeypatch):
    """The program's trie-node encoder: the extension's (native/pyext.cc),
    or the Python one with the extension masked out the way a machine
    without a compiler has it (`rlp.encode` alone keeps the encoder it
    found at its first call in the process)."""
    from phant_tpu.utils.native import load_engine_ext

    if request.param == "python":
        monkeypatch.setenv("PHANT_ENGINE_EXT", "0")
        assert load_engine_ext() is None
    elif load_engine_ext() is None:
        pytest.skip("no extension on this machine")
    return request.param


@pytest.fixture
def walk_counts(node_encoder):
    """`walk_counts()`: (`mpt.node_encodings`, `mpt.ref_hashes`) of the
    encoder under test so far: what its host walks encoded, and hashed."""
    from phant_tpu.utils.trace import metrics

    def counts() -> tuple:
        counters = metrics.snapshot()["counters"]
        return tuple(
            counters.get(f'mpt.{name}{{impl="{node_encoder}"}}', 0)
            for name in ("node_encodings", "ref_hashes")
        )

    return counts


@pytest.fixture(scope="module")
def reference_block(tmp_path_factory):
    """Block 2 of a chain of the benchmark's own reference
    (benchmarks/reference/chain.py, which imports nothing of the program):
    a small genesis, the cell's mix of transfers and contract calls."""
    import json
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parent.parent / "benchmarks"
    sys.path.insert(0, str(bench))
    try:
        from reference import keccak as ref_keccak
        from reference.chain import Chain

        try:
            ref_keccak.load(tmp_path_factory.mktemp("refkeccak"))
        except Exception as e:  # no C compiler on this machine
            pytest.skip(f"the reference's keccak does not build here: {e}")
        mix = json.loads((bench / "traffic" / "lone.json").read_text())["chain"]
        chain = Chain(
            35,
            {
                **mix,
                "genesis_log2": 10,
                "sender_pool": 128,
                "contracts": 4,
                "transfers_per_block": 40,
                "calls_per_block": 20,
                "slots_per_contract": 8,
            },
        )
        chain.extend(2)
        block = chain.blocks[1]
        return block, json.loads(block.body(2))
    finally:
        sys.path.remove(str(bench))
