"""Crypto-backend selection (`--crypto_backend=cpu|tpu`).

The reference has no such switch (its crypto is always native CPU,
reference: src/crypto/hasher.zig, src/crypto/ecdsa.zig); this framework's
north star adds a TPU device path for the stateless hot loop (batched
keccak / MPT witness verify / ecrecover, see phant_tpu/ops/). The selected
backend is process-global, mirroring how the reference picks its chain
config once at startup (reference: src/main.zig:109-118).
"""

from __future__ import annotations

import logging
import os
import threading
import time

log = logging.getLogger("phant.backend")

_CRYPTO_BACKEND = "cpu"
_VALID = ("cpu", "tpu")

# Engine API handler threads race into the lazy link probe below
# (phantlint LOCK): it takes ~0.3s, so an unserialized race is double
# probing. One lock, one measurement.
_probe_lock = threading.Lock()

# EVM bytecode execution backend: "python" (phant_tpu/evm/interpreter.py) or
# "native" (the C++ core in native/evm.cc, the reference's evmone analog,
# entered through the extension's host binding: phant_tpu/evm/native_vm.py;
# where the extension does not load, the Python interpreter runs).
_EVM_BACKEND = "python"
_VALID_EVM = ("python", "native")

#: (platform, device_kind, count) of the jax device the tpu backend serves
#: from — resolved ONCE by `set_crypto_backend("tpu")`, None until then.
_DEVICE: tuple | None = None


def set_crypto_backend(name: str) -> None:
    """Select the crypto backend. "tpu" resolves the jax device HERE, at
    start-up, and lets every failure out: `jax.devices()` raising, or a
    platform other than `tpu`, is an error — a server started for the
    chip on a machine where the chip did not come up must not answer from
    the host as if nothing happened. PHANT_ALLOW_JAX_CPU=1 admits the CPU
    platform (the tests' and the multi-chip dry run's virtual CPU mesh,
    where running the device programs on XLA-CPU is the point)."""
    global _CRYPTO_BACKEND, _DEVICE
    if name not in _VALID:
        raise ValueError(f"crypto backend must be one of {_VALID}, got {name!r}")
    if name == "tpu":
        import jax

        devices = jax.devices()
        found = (devices[0].platform, devices[0].device_kind, len(devices))
        log.info("jax device: platform=%s device_kind=%s count=%d", *found)
        if found[0] != "tpu" and os.environ.get("PHANT_ALLOW_JAX_CPU", "0") in (
            "",
            "0",
        ):
            raise RuntimeError(
                "--crypto_backend=tpu needs a TPU, but jax found platform "
                f"{found[0]!r} ({found[1]} x{found[2]}); set "
                "PHANT_ALLOW_JAX_CPU=1 to run the device programs on the "
                "CPU platform (tests, dry runs)"
            )
        _DEVICE = found
    from phant_tpu.utils.trace import metrics

    metrics.count("backend.selected", backend=name)
    _CRYPTO_BACKEND = name


def crypto_backend() -> str:
    return _CRYPTO_BACKEND


def jax_device_ok() -> bool:
    """Whether the tpu backend resolved a jax device at selection time —
    a read of `set_crypto_backend`'s decision, never a probe."""
    return _DEVICE is not None


def device_fallback(site: str) -> None:
    """Count one run-time degradation: a device (or device-lane) failure
    AFTER a healthy start that made `site` serve its batch from the host.
    The serving layer keeps answering (tested behaviour); this family is
    how an operator — and chip_smoke.py, which requires zero — sees it."""
    from phant_tpu.utils.trace import metrics

    metrics.count("backend.device_fallbacks", site=site)


_LINK_PROFILE: tuple | None = None


def device_link_profile() -> tuple:
    """(upload_bytes_per_sec, roundtrip_sec) of the host<->device link,
    measured once per process.

    The offload cost model needs real link numbers: upload bandwidth and
    dispatch round trip decide which batch sizes are worth shipping.
    Probing costs ~0.3s once. Overridable for tests/ops via
    PHANT_LINK_MBPS / PHANT_LINK_RTT_MS. A probe that raises propagates:
    the device was selected at start-up, so a link that cannot be
    measured is a fault to surface, not a reason to route to the host."""
    if _LINK_PROFILE is not None:  # lock-free fast path: write-once tuple
        return _LINK_PROFILE
    # serialize the probe (phantlint LOCK): concurrent handler threads
    # wait for one measurement instead of running N probes
    with _probe_lock:
        return _device_link_profile_locked()


def _device_link_profile_locked() -> tuple:
    global _LINK_PROFILE

    if _LINK_PROFILE is not None:
        return _LINK_PROFILE
    mbps = os.environ.get("PHANT_LINK_MBPS")
    rtt = os.environ.get("PHANT_LINK_RTT_MS")
    if mbps and rtt:
        _LINK_PROFILE = (float(mbps) * 1e6, float(rtt) / 1e3)
        return _LINK_PROFILE
    lat, delta = _measure_link()
    if delta <= 0:
        # a scheduler hiccup ate t_small: one re-measure, then it is a fault
        lat, delta = _measure_link()
        if delta <= 0:
            raise RuntimeError(
                "host<->device link probe is unusable: the 12 MiB upload "
                f"did not take longer than the 1 MiB one twice (delta {delta}s)"
            )
    # floor at a 50 GB/s physical ceiling (no real link is faster)
    up = max(delta, (_PROBE_BIG - _PROBE_SMALL) / 50e9)
    _LINK_PROFILE = ((_PROBE_BIG - _PROBE_SMALL) / up, lat)
    return _LINK_PROFILE


_PROBE_SMALL = 1 << 20
_PROBE_BIG = 12 << 20


def _measure_link() -> tuple:
    """(roundtrip_sec, t_big - t_small): one pass of the link probe."""
    import jax.numpy as jnp
    import numpy as np

    tiny = jnp.zeros((8,), jnp.uint32)
    # the probe MEASURES the round trip — the sync is the point here
    int(jnp.sum(tiny))  # warm dispatch path # phantlint: disable=HOSTSYNC
    # best-of-3 samples: a single scheduler hiccup must not skew
    # routing for the whole process lifetime
    lat = min(
        _timed(lambda: int(jnp.sum(tiny))) for _ in range(3)  # phantlint: disable=HOSTSYNC — timed probe
    )
    # random payloads, DISTINCT pre-generated buffer per sample: a
    # compressing transport must not flatter the probe, jax dedupes a
    # repeated transfer of the same host buffer (observed: the second
    # sample of one array measured ~0s -> a petabytes/s "link"), and
    # RNG generation must stay OUTSIDE the timed window.
    # TWO sizes, bandwidth from the SLOPE: a single small transfer
    # minus the round trip is meaningless where the transport buffers
    # writes (a 1 MiB upload can be acknowledged before it has moved).
    # The big buffer must be large enough that transfer time >> RTT.
    rng = np.random.default_rng(0)
    warm_buf = rng.integers(0, 256, _PROBE_SMALL, dtype=np.uint8)
    bufs_small = [rng.integers(0, 256, _PROBE_SMALL, dtype=np.uint8) for _ in range(3)]
    bufs_big = [rng.integers(0, 256, _PROBE_BIG, dtype=np.uint8) for _ in range(3)]
    # sum the WHOLE buffer: consuming only a slice lets the transport
    # defer most of the transfer (observed: a sliced readback clocked
    # the 1MB upload at the 50 GB/s sanity clamp). The on-device sum
    # is noise next to any real link time.
    int(jnp.sum(jnp.asarray(warm_buf)))  # warm transfer path # phantlint: disable=HOSTSYNC
    # min-of-3 per size (same rationale as the latency probe)
    t_small = min(
        _timed(lambda b=b: int(jnp.sum(jnp.asarray(b))))  # phantlint: disable=HOSTSYNC — timed probe
        for b in bufs_small
    )
    t_big = min(
        _timed(lambda b=b: int(jnp.sum(jnp.asarray(b))))  # phantlint: disable=HOSTSYNC — timed probe
        for b in bufs_big
    )
    # slope over the size delta cancels RTT and fixed dispatch costs
    return lat, t_big - t_small


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# Throughput constants of the adaptive offload cost model (bytes/s of
# keccak input). ASSUMPTIONS, not measurements of today's code: the
# native figure is the 8-way AVX-512 batch on one core at MPT node sizes;
# the device figure is a slope-timed rate of the Pallas kernel
# (ops/keccak_pallas.py, 44.4M hashes/s at MPT node shapes — the kernel
# that IS the keccak on a TPU) taken on a `TPU v5 lite` chip before
# PR 5 — `device_hash_bps` raises for a TPU of any other device_kind
# rather than reuse it. Whether the gate they feed opens on a locally
# attached chip is ROADMAP S1/D7.
NATIVE_HASH_BPS = 300e6
DEVICE_HASH_BPS_PALLAS = 13.5e9
DEVICE_HASH_BPS_XLA_CPU = 110e6  # jnp kernel on the host CPU: loses to native
_RATES_DEVICE_KIND = "TPU v5 lite"


def device_hash_bps() -> float:
    """Device keccak throughput for the cost model: which kernel would
    actually serve the batch on this host (Pallas on a TPU, the jnp
    program on the CPU platform — the same choice
    keccak256_chunked_auto makes).

    On the CPU platform (tests' virtual mesh, PHANT_ALLOW_JAX_CPU) the
    "device" is the host itself running the XLA-CPU keccak, which loses
    to the native AVX-512 batch outright — report it as such so the
    offload gate stays closed there (tests that need the device dispatch
    anyway bypass the gate via PHANT_TPU_FORCE_TRIE)."""
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        return DEVICE_HASH_BPS_XLA_CPU
    if device.device_kind != _RATES_DEVICE_KIND:
        raise RuntimeError(
            "the offload cost model has keccak rates for "
            f"{_RATES_DEVICE_KIND!r} only, not for {device.device_kind!r}"
        )
    return DEVICE_HASH_BPS_PALLAS


def device_offload_possible() -> bool:
    """Could device_offload_pays() EVER return True under the current
    cost model? False while the device hash term alone exceeds the native
    cost — the single predicate both the gate's short-circuit and the
    engine's finish_native fast path key on (one definition, so they
    cannot diverge if the model is reworked)."""
    return device_hash_bps() > NATIVE_HASH_BPS


def device_offload_pays(nbytes: int) -> bool:
    """Shared offload gate for byte-dense hashing work (witness novel-node
    batches, trie-root plans): ship only if upload + round trip + device
    hash beats hashing the same bytes natively on the host. Callers must
    check the crypto backend BEFORE calling — this probes the device link.
    Every verdict counts into `backend.offload_decisions{route=...}` so the
    gate's behavior is auditable from /metrics."""
    from phant_tpu.utils.trace import metrics

    if not device_offload_possible():
        # no link speed can make the inequality hold; skip the probe
        metrics.count("backend.offload_decisions", route="native")
        return False
    up_bps, rtt = device_link_profile()
    pays = (
        nbytes / up_bps + rtt + nbytes / device_hash_bps() < nbytes / NATIVE_HASH_BPS
    )
    metrics.count("backend.offload_decisions", route="device" if pays else "native")
    return pays


def set_evm_backend(name: str) -> None:
    global _EVM_BACKEND
    if name not in _VALID_EVM:
        raise ValueError(f"evm backend must be one of {_VALID_EVM}, got {name!r}")
    _EVM_BACKEND = name


def evm_backend() -> str:
    return _EVM_BACKEND
