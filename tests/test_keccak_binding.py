"""The scalar keccak256's binding (PR 39): `crypto/keccak.keccak256` asks the
CPython extension (native/pyext.cc `keccak256`) first. It hashes a short
input with the interpreter lock held and one of `KECCAK_UNLOCK_BYTES` or
more with it released, and counts both (`native.keccak_calls{lock=}`)."""

from __future__ import annotations

import sys
import threading
import time

import pytest

from phant_tpu.crypto import keccak
from phant_tpu.utils import native
from phant_tpu.utils.trace import metrics

ext = native.load_ext()
pytestmark = pytest.mark.skipif(ext is None, reason="no toolchain: the extension cannot be built here")
THRESHOLD = ext.KECCAK_UNLOCK_BYTES if ext is not None else 4096

LENGTHS = [0, 1, 31, 32, 55, 64, 135, 136, 137, 271, 272, 273, THRESHOLD - 1, THRESHOLD, THRESHOLD + 1, 100_000]


def _data(n: int) -> bytes:
    return bytes((i * 131 + n) % 251 for i in range(n))


def _grown(before: dict) -> dict:
    after = native.keccak_calls()
    return {lock: after[lock] - before[lock] for lock in after}


def test_the_scalar_hash_is_the_extensions():
    assert keccak._ext_keccak256 is ext.keccak256
    assert THRESHOLD == 4096 and "keccak" in native.LOCK_SITES


@pytest.mark.parametrize("n", LENGTHS)
def test_the_extension_against_the_python_spec(n):
    data = _data(n)
    digest = keccak.keccak256_python(data)
    assert ext.keccak256(data) == digest == keccak.keccak256(data)
    assert type(ext.keccak256(data)) is bytes and len(digest) == 32
    if native.load_native() is not None:
        assert native.load_native().keccak256(data) == digest


@pytest.mark.parametrize("n", [0, 20, 137, THRESHOLD - 1, THRESHOLD, 100_000])
def test_any_contiguous_buffer_hashes_as_its_bytes(n):
    data = _data(n)
    digest = ext.keccak256(data)
    assert ext.keccak256(bytearray(data)) == digest
    assert ext.keccak256(memoryview(data)) == digest
    padded = bytearray(b"\xff" + data + b"\xff")
    assert ext.keccak256(memoryview(padded)[1 : n + 1]) == digest


@pytest.mark.parametrize("bad", ["text", 7, None, [1, 2]])
def test_what_is_no_buffer_raises_type_error(bad):
    before = native.keccak_calls()
    with pytest.raises(TypeError):
        ext.keccak256(bad)
    assert _grown(before) == {"held": 0, "released": 0}


def test_a_strided_view_is_refused_not_hashed_wrong():
    with pytest.raises(BufferError):
        ext.keccak256(memoryview(_data(64))[::2])


@pytest.mark.parametrize(
    "n,lock",
    [(0, "held"), (32, "held"), (THRESHOLD - 1, "held"), (THRESHOLD, "released"), (THRESHOLD + 1, "released"), (100_000, "released")],
)  # fmt: skip
def test_the_length_decides_what_happens_to_the_lock(n, lock):
    """One call, one count, under the label its length gives; a released
    call is clocked at the `Unlocked` scope's seventh site."""
    data = _data(n)
    before, clocks = native.keccak_calls(), native.lock_clocks()["keccak"]
    keccak.keccak256(data)
    assert _grown(before) == {"held": int(lock == "held"), "released": int(lock == "released")}
    after = native.lock_clocks()["keccak"]
    if lock == "released":
        assert after[0] > clocks[0] and after[1] > clocks[1]
    else:
        assert after == clocks


def test_the_counts_are_on_the_exposition():
    def read(text: str, lock: str) -> int:
        return int(float(text.split('\nphant_native_keccak_calls{lock="%s"} ' % lock)[1].split()[0]))

    text = metrics.prometheus_text()
    assert "# TYPE phant_native_keccak_calls gauge" in text and "# HELP phant_native_keccak_calls " in text
    held, released = read(text, "held"), read(text, "released")
    keccak.keccak256(b"short")
    keccak.keccak256(bytes(THRESHOLD))
    keccak.keccak256_with_prefix(2, b"typed transaction")
    text = metrics.prometheus_text()
    assert (read(text, "held") - held, read(text, "released") - released) == (2, 1)
    assert 'phant_native_unlocked_seconds{site="keccak"}' in text
    assert 'phant_native_lock_retake_seconds{site="keccak"}' in text


def test_sixteen_threads_of_both_sizes_read_back_their_own_digests():
    """Short hashes (lock held) and long ones (lock released, so they run
    beside each other and beside the short ones) from sixteen threads at
    once: every digest is its own input's."""
    inputs = [_data(40 + i) if i % 2 else _data(THRESHOLD + 1000 * i) for i in range(16)]
    want = [keccak.keccak256_python(d) for d in inputs]
    wrong, calls = [], [0] * 16
    gate = threading.Barrier(16)

    def body(i: int) -> None:
        gate.wait()
        end = time.monotonic() + 0.2
        while time.monotonic() < end:
            if keccak.keccak256(inputs[i]) != want[i]:
                wrong.append(i)
            calls[i] += 1

    before = native.keccak_calls()
    threads = [threading.Thread(target=body, args=(i,)) for i in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # a forced hand-over every few short hashes
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == [] and all(calls)
    assert _grown(before) == {"held": sum(calls[1::2]), "released": sum(calls[0::2])}
