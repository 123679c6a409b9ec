"""Persistent XLA compilation cache for the device kernels.

The heavy kernels (the ecrecover ladders, the witness verdict's sort)
take from tens of seconds to minutes to compile but milliseconds to run;
caching compiled programs makes every process after the first start
instantly.

ONE directory, placeable from outside: where `JAX_COMPILATION_CACHE_DIR`
is set, jax reads it itself and this module sets no directory at all;
where it is not, the directory is `<checkout>/build/jax_cache`, always
(the path is part of the cache's key, so a directory that moves never
hits). jax SEGFAULTS — not raises — reading or writing a cache entry
corrupted by concurrent writers, so process classes that may run side by
side (the test suite, the benchmark's own tests, the driver dry run)
each place their own directory through that variable.
PHANT_NO_COMPILE_CACHE=1 (PHANT_NO_JAX_CACHE is a legacy alias) switches
jax's persistent cache off for the process, wherever its directory is.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

_configured = False
_configure_lock = threading.Lock()
_DEFAULT_DIR = Path(__file__).resolve().parents[2] / "build" / "jax_cache"


def compilation_cache_dir() -> str:
    """The one directory compiled programs persist in (see the module
    docstring): the caller's placement, else the checkout's default."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_DEFAULT_DIR)


def enable_compilation_cache() -> None:
    global _configured
    if _configured:
        return
    # lock-serialized (phantlint LOCK): concurrent first-use from two
    # request threads must not interleave the jax.config.update calls
    # (the config object is process-global)
    with _configure_lock:
        if _configured:
            return
        _configured = True
        import jax

        if (
            os.environ.get("PHANT_NO_JAX_CACHE", "0") not in ("", "0")
            or os.environ.get("PHANT_NO_COMPILE_CACHE", "0") not in ("", "0")
        ):
            # jax reads JAX_COMPILATION_CACHE_DIR itself: leaving the
            # directory unset here would not keep it from caching there
            jax.config.update("jax_enable_compilation_cache", False)
            return
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            _DEFAULT_DIR.mkdir(parents=True, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
