"""Device selection is loud (PR 24): `--crypto_backend=tpu` resolves the jax
device once, at start-up, and fails there; the Pallas kernel is the keccak
on a TPU and its failures propagate; the compile cache has one directory,
placeable from outside; `chip_smoke.py` refuses to run without a chip."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from phant_tpu import backend

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_backend():
    yield
    backend.set_crypto_backend("cpu")


def test_tpu_backend_refuses_the_cpu_platform(monkeypatch, restore_backend):
    monkeypatch.delenv("PHANT_ALLOW_JAX_CPU")
    monkeypatch.setattr(backend, "_DEVICE", None)
    with pytest.raises(RuntimeError, match="needs a TPU.*platform 'cpu'"):
        backend.set_crypto_backend("tpu")
    assert backend.crypto_backend() == "cpu"
    assert not backend.jax_device_ok()


def test_tpu_backend_lets_a_jax_failure_out(monkeypatch, restore_backend):
    import jax

    def no_devices():
        raise RuntimeError("TPU initialization failed")

    monkeypatch.setattr(jax, "devices", no_devices)
    with pytest.raises(RuntimeError, match="TPU initialization failed"):
        backend.set_crypto_backend("tpu")
    assert backend.crypto_backend() == "cpu"


def test_virtual_cpu_mesh_is_admitted_and_recorded(monkeypatch, restore_backend):
    monkeypatch.setattr(backend, "_DEVICE", None)
    assert not backend.jax_device_ok()  # a read of the decision, not a probe
    backend.set_crypto_backend("tpu")  # conftest sets PHANT_ALLOW_JAX_CPU=1
    assert backend.jax_device_ok()
    assert backend._DEVICE[0] == "cpu"


def test_pallas_is_the_keccak_on_tpu_and_its_failure_propagates(monkeypatch):
    import jax
    import jax.numpy as jnp

    import phant_tpu.ops.keccak_pallas as kp
    from phant_tpu.ops.keccak_jax import keccak256_chunked_auto

    def mosaic_refuses(*_a, **_k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(kp, "_INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kp, "keccak256_chunked_pallas", mosaic_refuses)
    assert kp.pallas_available() is True  # no probe that could say otherwise
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        keccak256_chunked_auto(
            jnp.zeros((1, 1, 34), jnp.uint32), jnp.ones((1,), jnp.int32), max_chunks=1
        )


def test_pallas_availability_is_decidable_inside_a_trace(monkeypatch):
    """The fused programs ask mid-graph; the answer must not run a kernel
    (a trial run under a trace returns a tracer — the bug that had every
    lane on the jnp program on the chip)."""
    import jax
    import jax.numpy as jnp

    import phant_tpu.ops.keccak_pallas as kp

    seen = []

    @jax.jit
    def program(x):
        seen.append(kp.pallas_available())
        return x + 1

    program(jnp.zeros((4,), jnp.uint32))
    assert seen == [kp._INTERPRET]  # CPU platform: only interpret mode says yes


def test_pallas_unavailable_only_on_plain_cpu(monkeypatch):
    import phant_tpu.ops.keccak_pallas as kp

    monkeypatch.setattr(kp, "_INTERPRET", False)
    assert kp.pallas_available() is False
    monkeypatch.setattr(kp, "_INTERPRET", True)
    assert kp.pallas_available() is True


def test_link_probe_failure_propagates(monkeypatch):
    monkeypatch.setattr(backend, "_LINK_PROFILE", None)
    monkeypatch.delenv("PHANT_LINK_MBPS", raising=False)

    def dead():
        raise RuntimeError("device call never returned")

    monkeypatch.setattr(backend, "_measure_link", dead)
    with pytest.raises(RuntimeError, match="never returned"):
        backend.device_link_profile()
    assert backend._LINK_PROFILE is None


def test_link_probe_remeasures_once_then_raises(monkeypatch):
    monkeypatch.setattr(backend, "_LINK_PROFILE", None)
    monkeypatch.delenv("PHANT_LINK_MBPS", raising=False)
    calls = []

    def hiccup():
        calls.append(1)
        return 1e-4, -1e-3 if len(calls) == 1 else 2e-3

    monkeypatch.setattr(backend, "_measure_link", hiccup)
    up, rtt = backend.device_link_profile()
    assert len(calls) == 2 and rtt == 1e-4 and up == (11 << 20) / 2e-3

    monkeypatch.setattr(backend, "_LINK_PROFILE", None)
    monkeypatch.setattr(backend, "_measure_link", lambda: (1e-4, 0.0))
    with pytest.raises(RuntimeError, match="link probe is unusable"):
        backend.device_link_profile()


def test_cost_model_rates_are_for_one_device_kind(monkeypatch):
    import jax

    class Other:
        platform = "tpu"
        device_kind = "TPU v4"

    monkeypatch.setattr(jax, "devices", lambda: [Other()])
    with pytest.raises(RuntimeError, match="'TPU v5 lite' only, not for 'TPU v4'"):
        backend.device_hash_bps()


def test_device_fallback_counts_by_site():
    from phant_tpu.utils.trace import METRIC_HELP, _labels_key, metrics

    assert "backend.device_fallbacks" in METRIC_HELP
    key = _labels_key("backend.device_fallbacks", {"site": "unit_test"})
    before = metrics.snapshot()["counters"].get(key, 0)
    backend.device_fallback("unit_test")
    assert metrics.snapshot()["counters"][key] == before + 1


# compile one program that is big enough to persist, then say where the
# program thinks the cache is
_CACHE_PROBE = (
    "import jax, jax.numpy as jnp, phant_tpu.ops;"
    "from phant_tpu.ops._cache import compilation_cache_dir;"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0);"
    "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((8, 8))).block_until_ready();"
    "print(compilation_cache_dir())"
)


def _cache_dir_of_a_fresh_process(env) -> str:
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_cache_dir_placed_from_outside_is_the_one_jax_writes(tmp_path):
    placed = tmp_path / "placed"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(placed))
    assert _cache_dir_of_a_fresh_process(env) == str(placed)
    assert any(placed.iterdir())  # jax itself read the variable


def test_cache_dir_defaults_to_build_jax_cache_under_the_checkout():
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    default = REPO / "build" / "jax_cache"
    assert _cache_dir_of_a_fresh_process(env) == str(default)
    assert any(default.iterdir())


def test_cache_opt_out_leaves_a_placed_directory_empty(tmp_path):
    placed = tmp_path / "placed"
    placed.mkdir()
    env = dict(
        os.environ, JAX_COMPILATION_CACHE_DIR=str(placed), PHANT_NO_COMPILE_CACHE="1"
    )
    _cache_dir_of_a_fresh_process(env)
    assert not any(placed.iterdir())


def test_chip_smoke_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs the tpu platform" in out.stdout
