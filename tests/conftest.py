"""Test config: force JAX onto a virtual 8-device CPU mesh so multi-chip
sharding paths compile/execute without TPU hardware (SURVEY.md section 4
blueprint: 'jax CPU devices / multiprocess ICI emulation covers what
Mockito does' for the reference's transport suites).

XLA_FLAGS must be set before the backend initializes; the platform is
pinned with jax.config so the suite runs on the CPU whatever
JAX_PLATFORMS says (tier-1 sets it to cpu anyway).
"""

import os
import sys

# No persistent XLA cache under pytest, by JAX's own switch (read at
# import, inherited by the CLI subprocesses tests start, and honoured
# by device_manager.initialize): XLA:CPU AOT entries have repeatedly
# deserialized into SIGSEGV (machine-feature pinning + concurrent-writer
# corruption); CPU compiles are fast enough to redo
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in _flags:
    # The suite is compile-bound: hundreds of distinct XLA programs,
    # recompiled per module (see _clear_jax_caches_per_module). Tests
    # assert CORRECTNESS against the CPU oracle, not codegen quality,
    # and O0 halves the wall of the compile-heavy modules while staying
    # bit-identical (XLA optimization passes are semantics-preserving;
    # no fast-math is enabled at any level). bench.py is unaffected.
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) == 8, jax.devices()


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Bound in-process XLA:CPU executable accumulation: hundreds of
    tests x fresh program shapes have repeatedly ended in a SIGSEGV
    inside backend_compile late in the run (LLVM JIT state corruption
    after thousands of live executables). Dropping JAX's traces and
    executables between modules keeps the process small; modules
    recompile what they reuse."""
    yield
    import jax
    jax.clear_caches()


def pytest_configure(config):
    # tier-1 selects with `-m 'not slow'`, so `fault` tests (the
    # robustness/fault-injection corpus) run IN tier-1 by default
    config.addinivalue_line(
        "markers", "slow: long-running test excluded from tier-1")
    config.addinivalue_line(
        "markers", "fault: fault-injection robustness test "
        "(docs/robustness.md); included in tier-1")
