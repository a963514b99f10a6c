"""Where the persistent XLA compile cache lives: one rule for every process
that owns the device (the worker, chip_smoke.py, bench.py, benchmarks/).

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here, so the machine's own placement wins.
- CPU backend: the cache stays off. XLA:CPU entries are AOT code for the
  machine that wrote them (entries from another host load with a
  CPU-feature-mismatch warning), and tests/conftest.py records the donation
  miscompile the CPU cache path once caused.
- otherwise: ``<checkout>/.jax_cache`` (gitignored). The path is fixed
  because it is part of what a later run has to find again.

Call it before the first compile: JAX fixes the cache at its first use.
"""

from __future__ import annotations

import os
from typing import Optional

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_compile_cache() -> Optional[str]:
    """Place the persistent compile cache; returns its directory or None
    when it is off. Initializes the JAX backend: device owners only."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
