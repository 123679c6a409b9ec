"""These tests are run by hand (`python3 -m pytest benchmarks/tests -q`);
the tier-1 command collects `tests/` only. They run on the CPU."""

import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parent.parent))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PHANT_ALLOW_JAX_CPU", "1")
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", str(HERE.parent.parent / "build" / "jax_cache_benchtests")
)
