"""Loader/builder for the native C++ runtime (native/*.cc -> libphant_native-<key>.so).

The reference builds its native components (ethash keccak, evmone, secp256k1)
as static libs inside build.zig (reference: build.zig:79-135). Here the native
runtime is a single shared library compiled on demand with g++ and loaded via
ctypes; if the toolchain is unavailable the pure-Python fallbacks take over.

Built libraries are keyed (in their file name under build/) on a hash of
their sources, the compiler flags and the host's CPU flags: the flags
include -march=native, so a build/ carried over from another machine —
the chip tool copies the checkout as it stands on disk — is never loaded,
and everything the program loads comes from files git would commit.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import List, Optional, Sequence

_REPO_ROOT = Path(__file__).resolve().parent.parent.parent
_NATIVE_DIR = _REPO_ROOT / "native"
_BUILD_DIR = _REPO_ROOT / "build"

_lock = threading.Lock()
_loaded: Optional["NativeLib"] = None
_load_failed = False


def _sources() -> List[Path]:
    # selftest.cc is the standalone sanitizer harness (`make sanitize`);
    # pyext.cc is the CPython extension (its own .so, load_ext) and evm.cc
    # the VM that only the extension's host binding enters — none of them
    # belongs in the ctypes shared library
    return sorted(
        p
        for p in _NATIVE_DIR.glob("*.cc")
        if p.name not in ("selftest.cc", "pyext.cc", "evm.cc")
    )


def _host_cpu_flags() -> str:
    """What -march=native resolves against on this host."""
    import platform

    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line
    except OSError:
        pass
    return platform.machine() + platform.processor()


def _keyed_build(stem: str, srcs: Sequence[Path], flags: Sequence[str], verbose: bool = False) -> Path:
    """Compile `srcs` with `g++ flags` into build/<stem>-<key>.so unless
    that exact file exists; the key covers source bytes (native/*.h too),
    flags and the host's CPU flags. The compiler writes a private temp file that is
    renamed into place, so concurrent first builds (pytest workers) never
    load a half-written library."""
    h = hashlib.sha256()
    for src in (*srcs, *sorted(_NATIVE_DIR.glob("*.h"))):
        h.update(src.name.encode())
        h.update(src.read_bytes())
    h.update(" ".join(flags).encode())
    h.update(_host_cpu_flags().encode())
    path = _BUILD_DIR / f"{stem}-{h.hexdigest()[:16]}.so"
    if not path.exists():
        _BUILD_DIR.mkdir(exist_ok=True)
        tmp = path.with_suffix(f".{os.getpid()}.tmp")
        cmd = ["g++", *flags, *(str(s) for s in srcs), "-o", str(tmp)]
        if verbose:
            print("[phant_tpu.native]", " ".join(cmd))
        subprocess.run(cmd, check=True, capture_output=not verbose)
        os.replace(tmp, path)
    return path


def _arch_flags() -> list:
    """-march=native only where it exists: aarch64 gcc spells it -mcpu and
    cross-builds (reference CI cross-compiles aarch64, its ci.yml) must not
    die on an x86-only flag. PHANT_NATIVE_ARCH_FLAGS overrides outright."""
    import os
    import platform

    override = os.environ.get("PHANT_NATIVE_ARCH_FLAGS")
    if override is not None:
        return override.split()
    machine = platform.machine().lower()
    if machine in ("x86_64", "amd64", "i686"):
        return ["-march=native"]
    if machine in ("aarch64", "arm64"):
        return ["-mcpu=native"]
    return []


def build_native(verbose: bool = False) -> Path:
    """Compile native/*.cc into build/libphant_native-<key>.so (idempotent)."""
    flags = [
        "-O3", *_arch_flags(), "-std=c++20", "-shared", "-fPIC",
        "-fno-exceptions", "-fno-rtti", "-Wall",
    ]
    return _keyed_build("libphant_native", _sources(), flags, verbose)


class NativeLib:
    """ctypes facade over the native runtime."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        lib.phant_keccak256.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.phant_keccak256.restype = None
        lib.phant_keccak256_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_size_t,
            ctypes.c_char_p,
        ]
        lib.phant_keccak256_batch.restype = None
        self.has_fast_keccak = hasattr(lib, "phant_keccak256_batch_fast")
        if self.has_fast_keccak:
            lib.phant_keccak256_batch_fast.argtypes = (
                lib.phant_keccak256_batch.argtypes
            )
            lib.phant_keccak256_batch_fast.restype = None
        lib.phant_pack_keccak.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.phant_pack_keccak.restype = ctypes.c_int
        lib.phant_pack_rows.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_void_p,
        ]
        lib.phant_pack_rows.restype = ctypes.c_int
        lib.phant_scan_refs.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_size_t,
        ]
        lib.phant_scan_refs.restype = ctypes.c_long
        lib.phant_ecrecover.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int32, ctypes.c_char_p,
        ]
        lib.phant_ecrecover.restype = ctypes.c_int32
        lib.phant_ecrecover_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.phant_ecrecover_batch.restype = None
        self.has_engine = hasattr(lib, "phant_engine_new")
        if self.has_engine:
            lib.phant_engine_new.argtypes = []
            lib.phant_engine_new.restype = ctypes.c_void_p
            lib.phant_engine_free.argtypes = [ctypes.c_void_p]
            lib.phant_engine_free.restype = None
            lib.phant_engine_flush.argtypes = [ctypes.c_void_p]
            lib.phant_engine_flush.restype = None
            lib.phant_engine_nodes.argtypes = [ctypes.c_void_p]
            lib.phant_engine_nodes.restype = ctypes.c_uint64
            lib.phant_engine_digests.argtypes = [ctypes.c_void_p]
            lib.phant_engine_digests.restype = ctypes.c_uint64
            lib.phant_engine_scan.argtypes = [ctypes.c_void_p] + [
                ctypes.c_void_p
            ] * 3 + [ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p,
                     ctypes.c_void_p]
            lib.phant_engine_scan.restype = ctypes.c_int
            lib.phant_engine_commit.argtypes = [ctypes.c_void_p] + [
                ctypes.c_void_p
            ] * 3 + [ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p,
                     ctypes.c_uint64, ctypes.c_char_p]
            lib.phant_engine_commit.restype = ctypes.c_int64
            lib.phant_engine_verdict.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_uint64, ctypes.c_char_p, ctypes.c_void_p,
            ]
            lib.phant_engine_verdict.restype = ctypes.c_int

    def keccak256(self, data: bytes) -> bytes:
        out = ctypes.create_string_buffer(32)
        self._lib.phant_keccak256(data, len(data), out)
        return out.raw

    @staticmethod
    def _layout(payloads: Sequence[bytes]):
        """Concatenate payloads and build the C-ABI (blob, offsets, lens).
        The length/offset tables come from numpy (fromiter + cumsum) — the
        old per-item Python loop cost more than the C keccak it fed at
        witness novel-batch sizes (~10k items)."""
        import numpy as np

        n = len(payloads)
        blob = b"".join(payloads)
        lens_np = np.fromiter(map(len, payloads), np.uint32, n)
        offsets_np = np.zeros(n, np.uint64)
        if n > 1:
            np.cumsum(lens_np[:-1], dtype=np.uint64, out=offsets_np[1:])
        offsets = (ctypes.c_uint64 * n).from_buffer(offsets_np)
        lens = (ctypes.c_uint32 * n).from_buffer(lens_np)
        return blob, offsets, lens

    def pack_keccak(self, payloads: Sequence[bytes], max_chunks: int):
        """Pad+chunk payloads into the device keccak layout.

        Returns (buf (B, max_chunks*136) u8 ndarray, nchunks (B,) i32 ndarray);
        the caller reshapes/views into (B, C, 34) u32 words."""
        import numpy as np

        n = len(payloads)
        blob, offsets, lens = self._layout(payloads)
        buf = np.zeros((n, max_chunks * 136), dtype=np.uint8)
        nchunks = np.zeros((n,), dtype=np.int32)
        rc = self._lib.phant_pack_keccak(
            blob,
            offsets,
            lens,
            n,
            max_chunks,
            buf.ctypes.data_as(ctypes.c_void_p),
            nchunks.ctypes.data_as(ctypes.c_void_p),
        )
        if rc != 0:
            raise ValueError(f"payload exceeds bucket bound {max_chunks}")
        return buf, nchunks

    def pack_rows(self, payloads: Sequence[bytes], rows: int, row_bytes: int):
        """(rows, row_bytes) u8: payload i in row i, zero past its length
        and in the rows past the last payload (no keccak padding: the
        device pads). Raises if a payload fills its row."""
        import numpy as np

        n = len(payloads)
        blob, offsets, lens = self._layout(payloads)
        buf = np.zeros((rows, row_bytes), dtype=np.uint8)
        rc = self._lib.phant_pack_rows(
            blob, offsets, lens, n, row_bytes, buf.ctypes.data_as(ctypes.c_void_p)
        )
        if rc != 0:
            raise ValueError(f"payload fills its row of {row_bytes} bytes")
        return buf

    def ecrecover(self, msg_hash: bytes, r: int, s: int, recid: int) -> Optional[bytes]:
        """64-byte uncompressed pubkey (X||Y) or None if unrecoverable
        (reference scope: src/crypto/ecdsa.zig:19-26 via libsecp256k1)."""
        out = ctypes.create_string_buffer(64)
        rc = self._lib.phant_ecrecover(
            msg_hash, r.to_bytes(32, "big"), s.to_bytes(32, "big"), recid, out
        )
        return out.raw if rc == 0 else None

    def ecrecover_batch(self, msg_hashes, rs, ss, recids):
        """[(address|None)] for each signature: recover + keccak + slice."""
        n = len(msg_hashes)
        if n == 0:
            return []
        msgs = b"".join(msg_hashes)
        r_blob = b"".join(v.to_bytes(32, "big") for v in rs)
        s_blob = b"".join(v.to_bytes(32, "big") for v in ss)
        recid_arr = (ctypes.c_int32 * n)(*recids)
        addrs = ctypes.create_string_buffer(20 * n)
        ok = ctypes.create_string_buffer(n)
        self._lib.phant_ecrecover_batch(
            msgs, r_blob, s_blob, recid_arr, n, addrs, ok
        )
        raw, okb = addrs.raw, ok.raw
        return [raw[20 * i : 20 * i + 20] if okb[i] else None for i in range(n)]

    def scan_refs(self, blob, offsets, lens):
        """Child-ref scan over RLP trie nodes laid out in `blob` (numpy
        arrays: offsets u64, lens u32). Returns (ref_off i64, ref_node i32)
        numpy arrays, or raises ValueError on malformed RLP."""
        import numpy as np

        offsets = np.ascontiguousarray(offsets, np.uint64)
        lens = np.ascontiguousarray(lens, np.uint32)
        n = len(offsets)
        cap = max(int(lens.sum()) // 33 + 17, 17)  # >= max possible refs
        ref_off = np.empty(cap, np.int64)
        ref_node = np.empty(cap, np.int32)
        blob = np.ascontiguousarray(blob, dtype=np.uint8)
        cnt = self._lib.phant_scan_refs(
            blob.ctypes.data_as(ctypes.c_void_p),
            offsets.ctypes.data_as(ctypes.c_void_p),
            lens.ctypes.data_as(ctypes.c_void_p),
            n,
            ref_off.ctypes.data_as(ctypes.c_void_p),
            ref_node.ctypes.data_as(ctypes.c_void_p),
            cap,
        )
        if cnt < 0:
            raise ValueError("malformed RLP in witness node")
        return ref_off[:cnt], ref_node[:cnt]

    def new_engine(self) -> Optional["EngineCore"]:
        """Fresh native witness-engine core (None on an old library)."""
        return EngineCore(self._lib) if self.has_engine else None

    def keccak256_batch(self, payloads: Sequence[bytes]) -> List[bytes]:
        """Strictly scalar batch — the reference-equivalent baseline
        (the reference hashes one node at a time, crypto/hasher.zig:4-17)."""
        return self._batch_hash(payloads, self._lib.phant_keccak256_batch)

    def keccak256_batch_fast(self, payloads: Sequence[bytes]) -> List[bytes]:
        """The framework's own hashing path: 8-way AVX-512 multi-buffer on
        capable x86 hosts, bit-identical scalar dispatch elsewhere."""
        fn = (
            self._lib.phant_keccak256_batch_fast
            if self.has_fast_keccak
            else self._lib.phant_keccak256_batch
        )
        return self._batch_hash(payloads, fn)

    def _batch_hash(self, payloads: Sequence[bytes], fn) -> List[bytes]:
        n = len(payloads)
        if n == 0:
            return []
        blob, offsets, lens = self._layout(payloads)
        out = ctypes.create_string_buffer(32 * n)
        fn(blob, offsets, lens, n, out)
        raw = out.raw
        return [raw[32 * i : 32 * i + 32] for i in range(n)]


class EngineCore:
    """Handle to one native witness-engine core (native/engine.cc): the
    interning tables + verdict join of ops/witness_engine.WitnessEngine,
    kept in C++. The Python engine drives the scan/hash/commit/verdict
    protocol and keeps policy (hashing backend route, eviction, stats)."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        self._h = lib.phant_engine_new()
        import weakref

        # bind finalizer args by value — no ref back to self
        self._finalizer = weakref.finalize(
            self, lib.phant_engine_free, self._h
        )

    @property
    def nodes(self) -> int:
        return int(self._lib.phant_engine_nodes(self._h))

    @property
    def digests(self) -> int:
        return int(self._lib.phant_engine_digests(self._h))

    def flush(self) -> None:
        self._lib.phant_engine_flush(self._h)

    def scan(self, blob, offsets, lens):
        """(rows i64[n], novel_idx u32[n_novel], miss_count). rows[i] is a
        row id or -2-k for the k-th novel first occurrence of the batch."""
        import numpy as np

        n = len(lens)
        rows = np.empty(n, np.int64)
        novel_idx = np.empty(n, np.uint32)
        counts = np.zeros(2, np.uint64)
        rc = self._lib.phant_engine_scan(
            self._h,
            blob.ctypes.data_as(ctypes.c_void_p),
            offsets.ctypes.data_as(ctypes.c_void_p),
            lens.ctypes.data_as(ctypes.c_void_p),
            n,
            rows.ctypes.data_as(ctypes.c_void_p),
            novel_idx.ctypes.data_as(ctypes.c_void_p),
            counts.ctypes.data_as(ctypes.c_void_p),
        )
        if rc != 0:
            raise RuntimeError(f"engine scan failed ({rc})")
        return rows, novel_idx[: int(counts[1])], int(counts[0])

    def commit(self, blob, offsets, lens, rows, novel_idx, digests: bytes):
        """Insert the scanned novel nodes with their (caller-computed)
        digests; patches the negative entries of `rows` in place."""
        self._lib.phant_engine_commit(
            self._h,
            blob.ctypes.data_as(ctypes.c_void_p),
            offsets.ctypes.data_as(ctypes.c_void_p),
            lens.ctypes.data_as(ctypes.c_void_p),
            len(lens),
            rows.ctypes.data_as(ctypes.c_void_p),
            novel_idx.ctypes.data_as(ctypes.c_void_p),
            len(novel_idx),
            digests,
        )

    def verdict(self, rows, block_offs, roots: bytes):
        """(n_blocks,) bool verdicts; block b = rows[block_offs[b]:
        block_offs[b+1]], roots = concatenated 32B root digests."""
        import numpy as np

        n_blocks = len(block_offs) - 1
        ok = np.zeros(n_blocks, np.uint8)
        self._lib.phant_engine_verdict(
            self._h,
            rows.ctypes.data_as(ctypes.c_void_p),
            block_offs.ctypes.data_as(ctypes.c_void_p),
            n_blocks,
            roots,
            ok.ctypes.data_as(ctypes.c_void_p),
        )
        return ok.astype(bool)


_ext_lock = threading.Lock()
_ext_mod = None
_ext_failed = False


def load_engine_ext():
    """The extension (`load_ext`) for the witness engine's driver and the
    trie-node encoder, or None; PHANT_ENGINE_EXT=0 masks it from THEM (the
    ctypes core then serves, PHANT_ENGINE_NATIVE=0 the Python twin; the
    Python encoders). The EVM's host binding asks `load_ext` itself: what
    runs the VM is chosen by what loaded, never by a name."""
    # env check FIRST: the kill switch must keep working after the module
    # has been cached in-process (the test matrix's "ctypes" run relies on
    # PHANT_ENGINE_EXT=0 actually forcing the fallback)
    if os.environ.get("PHANT_ENGINE_EXT", "1") != "1":
        return None
    return load_ext()


def load_ext():
    """Build (if stale) and import the CPython extension (native/pyext.cc +
    engine.cc + keccak.cc + evm.cc): the witness-engine driver (`Engine`),
    the trie-node encoder, the EVM's host binding (`EvmHost`) and the
    scalar `keccak256`. Returns the module or None (no toolchain,
    PHANT_NO_NATIVE)."""
    global _ext_mod, _ext_failed
    if _ext_failed or os.environ.get("PHANT_NO_NATIVE"):
        return None
    if _ext_mod is not None:
        return _ext_mod
    with _ext_lock:
        if _ext_mod is not None:
            return _ext_mod
        try:
            import sysconfig

            # keccak.cc backs the engine's finish_native in-C hashing, the
            # VM's KECCAK256 and the scalar keccak256
            srcs = [
                _NATIVE_DIR / "pyext.cc",
                _NATIVE_DIR / "engine.cc",
                _NATIVE_DIR / "keccak.cc",
                _NATIVE_DIR / "evm.cc",
            ]
            flags = [
                "-O3", *_arch_flags(), "-std=c++20", "-shared",
                "-fPIC", "-fno-rtti",
                f"-I{sysconfig.get_paths()['include']}",
            ]
            # the one-time g++ compile runs UNDER _ext_lock on purpose:
            # concurrent first callers must wait for one build, not
            # run two compilers
            ext_path = _keyed_build("phant_engine_ext", srcs, flags)  # phantlint: disable=LOCKBLOCK — serialized one-time build
            import importlib.util
            from importlib.machinery import ExtensionFileLoader

            loader = ExtensionFileLoader("phant_engine_ext", str(ext_path))
            spec = importlib.util.spec_from_loader("phant_engine_ext", loader)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _ext_mod = mod
        except Exception:
            _ext_failed = True
            return None
    return _ext_mod


#: where the extension gives the interpreter lock away around native work
#: (native/pyext.cc `LockSite`): the witness engine's scan, hash and commit,
#: and the scalar `keccak256` (crypto/keccak.py) for an input of
#: `KECCAK_UNLOCK_BYTES` or more; under that it hashes with the lock held
LOCK_SITES = ("scan", "verdict", "commit", "commit_hash", "hash", "finish_commit", "keccak")


def lock_clocks() -> dict:
    """{site: (seconds the extension ran native work with the interpreter
    lock released, seconds it then waited to take the lock back)} since
    process start; zeros where the extension is not loaded (it is never
    built for this)."""
    mod = _ext_mod
    if mod is None:
        return dict.fromkeys(LOCK_SITES, (0.0, 0.0))
    return mod.lock_clocks()


def keccak_calls() -> dict:
    """{"held": n, "released": n}: calls of the extension's scalar
    `keccak256` since process start, by what they did with the interpreter
    lock; zeros where the extension is not loaded."""
    mod = _ext_mod
    if mod is None:
        return {"held": 0, "released": 0}
    return mod.keccak_calls()


def load_native() -> Optional[NativeLib]:
    """Build (if stale) and load the native runtime; None if unavailable."""
    global _loaded, _load_failed
    if _loaded is not None:
        return _loaded
    if _load_failed or os.environ.get("PHANT_NO_NATIVE"):
        return None
    with _lock:
        if _loaded is not None:
            return _loaded
        try:
            # same contract as load_engine_ext: the (possibly seconds-long)
            # build is serialized under _lock so exactly one compile runs
            path = build_native()  # phantlint: disable=LOCKBLOCK — serialized one-time build
            _loaded = NativeLib(ctypes.CDLL(str(path)))
        except Exception:
            _load_failed = True
            return None
    return _loaded
