"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU platform BEFORE jax is imported anywhere,
so multi-chip sharding (mesh over the service axis) is exercised without TPU
hardware. The driver's dryrun_multichip uses the same mechanism.
"""

import os

# Force CPU regardless of the environment's JAX_PLATFORMS: unit tests never
# take a chip (tests/test_chip_compile.py compiles for a DESCRIBED v5e).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_X64"] = "True"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

# The persistent XLA compilation cache is DISABLED for the suite: setting
# jax_compilation_cache_dir routes XLA:CPU through the cpu_aot_loader
# compile path, which MISCOMPILED buffer donation for fused (single-program
# read+write) steps — reproduced deterministically (round 6): two
# PipelineDrivers stepping the same donated program in one process corrupt
# each other's state leaves (zeros/garbage rings, window stats from freed
# buffers), and np.savez over zero-copy views of the corrupted buffers was
# the long-flaky suite segfault. The corruption appeared on COLD runs too —
# it is the AOT codegen path, not stale cache entries.
#
# RETESTED (round 12, jax 0.4.37): NOT reproducible — the two-driver donated
# fused repro and the fused-tick parity suite are bit-identical oracle vs
# cold-cache vs warm-cache. tests/test_xla_cache_retest.py keeps that repro
# as a standing regression gate for future jax bumps. The suite leaves the
# cache off regardless, like apmbackend_tpu/utils/compile_cache.py does on
# every CPU backend: its only upside here is compile time.

import pytest  # noqa: E402


@pytest.fixture
def tmp_logger():
    import logging

    return logging.getLogger("apm.test")
