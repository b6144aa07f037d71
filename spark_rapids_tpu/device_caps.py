"""Device numeric-capability probes.

The bit-identical contract (reference README.md:15-16) meets TPU reality
here: GPUs execute IEEE binary64 natively, TPUs do not. On TPU v5, XLA
*emulates* f64 — measured on hardware: f64 add/mul/div/sqrt (and f32
div/sqrt, which lower to reciprocal+Newton) are NOT correctly rounded,
while int64 arithmetic, f64 comparisons, floor/trunc, and int<->float
casts are exact.

Rather than hard-coding per-platform tables, we probe the live backend
once with tiny jitted kernels and compare against numpy (the CPU-Spark
oracle). The rewrite engine consults these flags when tagging
float-arithmetic expressions: on an exact backend (CPU mesh in CI, or a
future platform with native f64) they run on device unconditionally; on
an inexact backend they fall back to CPU unless the user opts in via
``spark.rapids.sql.incompatibleOps.enabled`` — the same shipping strategy
the reference uses for its not-bit-exact ops (GpuOverrides .incompat()).
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def f64_arith_exact() -> bool:
    """True when device f64 +,*,/ are bit-identical to IEEE (numpy)."""
    import jax
    import jax.numpy as jnp

    a = np.array([110.0, 0.1, 1e300, 7.0, 1.0, -0.3], dtype=np.float64)
    b = np.array([3.0, 0.3, 7.0, 11.0, 3.0, 0.7], dtype=np.float64)

    def probe(x, y):
        return x + y, x * y, x / y, jnp.sum(x)

    try:
        # tpu-lint: disable=jit-direct(one-shot lru_cached capability probe, never re-compiled)
        add, mul, div, s = jax.jit(probe)(a, b)
    except Exception:
        return False
    with np.errstate(all="ignore"):
        return (np.array_equal(np.asarray(add), a + b)
                and np.array_equal(np.asarray(mul), a * b)
                and np.array_equal(np.asarray(div), a / b)
                and float(s) == float(np.sum(a)))


@functools.lru_cache(maxsize=None)
def float_div_exact() -> bool:
    """True when device f32/f64 division and sqrt are correctly rounded."""
    import jax
    import jax.numpy as jnp

    a32 = np.array([1.5, 0.1, 7.0, 110.0], dtype=np.float32)
    b32 = np.array([3.0, 0.3, 11.0, 3.0], dtype=np.float32)

    def probe(x, y):
        return x / y, jnp.sqrt(x)

    try:
        # tpu-lint: disable=jit-direct(one-shot lru_cached capability probe, never re-compiled)
        div, sq = jax.jit(probe)(a32, b32)
    except Exception:
        return False
    return (np.array_equal(np.asarray(div), a32 / b32)
            and np.array_equal(np.asarray(sq), np.sqrt(a32))
            and f64_arith_exact())


@functools.lru_cache(maxsize=None)
def f64_bitcast_exact() -> bool:
    """True when the backend can bitcast int64 <-> float64 exactly (the
    device parquet decode rebuilds DOUBLE columns from raw page bytes
    this way; the TPU lowering stack rejects 64-bit float bitcasts, so
    DOUBLE columns fall back to the host decode there)."""
    import jax
    import jax.numpy as jnp

    bits = np.array([0x3FF0000000000000, -0x10000000000000000 +
                     0xC000000000000000, 0x7FF0000000000000, 0],
                    dtype=np.int64)
    try:
        # tpu-lint: disable=jit-direct(one-shot lru_cached capability probe, never re-compiled)
        out = jax.jit(lambda x: jax.lax.bitcast_convert_type(
            x, jnp.float64))(bits)
        return np.array_equal(np.asarray(out),
                              bits.view(np.float64), equal_nan=True)
    except Exception:
        return False


def float_arith_reason(kind: str = "arithmetic") -> str:
    return (f"device float {kind} is not bit-identical to CPU on this "
            "backend (TPU f64 is emulated); set "
            "spark.rapids.sql.incompatibleOps.enabled=true to allow")
