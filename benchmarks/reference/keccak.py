"""Keccak-256 for the reference: keccak.c, compiled once into the build
directory the harness names, loaded with ctypes. Nothing of the program."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from pathlib import Path

_SRC = Path(__file__).with_name("keccak.c")
_lib = None


def load(build_dir: str | os.PathLike) -> None:
    """Compile (if this source was not compiled there yet) and load."""
    global _lib
    if _lib is not None:
        return
    src = _SRC.read_bytes()
    out = Path(build_dir) / f"refkeccak-{hashlib.sha256(src).hexdigest()[:12]}.so"
    if not out.exists():
        out.parent.mkdir(parents=True, exist_ok=True)
        tmp = out.with_suffix(f".{os.getpid()}.tmp")
        subprocess.run(
            ["cc", "-O2", "-shared", "-fPIC", "-o", str(tmp), str(_SRC)], check=True
        )
        os.replace(tmp, out)
    lib = ctypes.CDLL(str(out))
    lib.keccak256.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p]
    lib.keccak256.restype = None
    lib.keccak256_many.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.keccak256_many.restype = None
    _lib = lib


def keccak256(data: bytes) -> bytes:
    out = ctypes.create_string_buffer(32)
    _lib.keccak256(data, len(data), out)
    return out.raw


def keccak256_many(messages: list) -> list:
    """Digests of many messages in one call (the genesis trie's leaves)."""
    import numpy as np

    n = len(messages)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(m) for m in messages], out=offsets[1:])
    out = ctypes.create_string_buffer(32 * n)
    _lib.keccak256_many(b"".join(messages), offsets.ctypes.data, n, out)
    raw = out.raw
    return [raw[32 * i : 32 * i + 32] for i in range(n)]
