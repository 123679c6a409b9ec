"""Differential tests for the Pallas keccak kernel (ops/keccak_pallas.py).

On the CPU test mesh Mosaic is unavailable, so the kernel body runs under
the Pallas interpreter (PHANT_PALLAS_INTERPRET) — same jaxpr, same
arithmetic, no TPU required.  Set PHANT_TEST_TPU=1 to run the compiled
kernel on real hardware instead (conftest routes jax at the chip).

Oracle: phant_tpu/crypto/keccak.py (itself pinned by NIST/mainnet vectors
in tests/test_keccak.py).
"""

import importlib
import os

import numpy as np
import pytest

import phant_tpu.ops.keccak_pallas as kp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.ops.keccak_jax import digests_to_bytes, pack_payloads


@pytest.fixture(scope="module", autouse=True)
def interpret_mode():
    """Force interpreter mode for the module when no TPU is attached."""
    import jax

    if jax.default_backend() != "cpu":
        yield  # real hardware: compiled path
        return
    old = kp._INTERPRET
    kp._INTERPRET = True
    yield
    kp._INTERPRET = old


def _run(payloads, max_chunks=None):
    import jax.numpy as jnp

    words, nchunks, C = pack_payloads(payloads, max_chunks)
    out = kp.keccak256_chunked_pallas(
        jnp.asarray(words), jnp.asarray(nchunks), max_chunks=C
    )
    return digests_to_bytes(np.asarray(out))


def test_boundary_lengths():
    # rate boundaries: 0, 1, 135, 136, 137, 271, 272, 544 bytes
    rng = np.random.default_rng(7)
    payloads = [
        rng.bytes(n) for n in (0, 1, 31, 32, 135, 136, 137, 271, 272, 543, 544)
    ]
    assert _run(payloads, 5) == [keccak256(p) for p in payloads]


def test_mixed_batch_padding_tail():
    # batch not a multiple of the SUB*128 tile: exercises the pad/slice path
    rng = np.random.default_rng(8)
    payloads = [rng.bytes(int(rng.integers(32, 577))) for _ in range(37)]
    assert _run(payloads) == [keccak256(p) for p in payloads]


def test_matches_jnp_kernel_bitexact():
    import jax.numpy as jnp

    from phant_tpu.ops.keccak_jax import keccak256_chunked

    rng = np.random.default_rng(9)
    payloads = [rng.bytes(int(rng.integers(1, 300))) for _ in range(19)]
    words, nchunks, C = pack_payloads(payloads, 4)
    a = np.asarray(
        kp.keccak256_chunked_pallas(
            jnp.asarray(words), jnp.asarray(nchunks), max_chunks=C
        )
    )
    b = np.asarray(
        keccak256_chunked(jnp.asarray(words), jnp.asarray(nchunks), max_chunks=C)
    )
    assert np.array_equal(a, b)
