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
import logging

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


@functools.lru_cache(maxsize=None)
def pallas_mode():
    """How the Pallas kernel tier (spark_rapids_tpu/kernels/) runs on
    the default backend: ``"interpret"`` on the CPU platform only
    (interpreter-mode emulation — tier-1 exercises every kernel path
    through it), ``"native"`` on an accelerator where ``pl.pallas_call``
    lowers and executes for real, ``None`` when the probe fails
    (kernels stay disabled and every op keeps its XLA-op oracle
    composition). An accelerator never answers ``"interpret"``: a
    kernel emulated op by op on the chip is slower than its oracle."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    mode = "interpret" if jax.default_backend() == "cpu" else "native"
    # one native int32 tile: the smallest shape Mosaic always accepts
    x = np.arange(8 * 128, dtype=np.int32).reshape(8, 128)
    try:
        # .lower().compile() forces REAL lowering even when the first
        # probe call happens inside an outer trace (a plain call would
        # inline the pallas_call into the outer jaxpr and "succeed"
        # without ever testing the backend)
        # tpu-lint: disable=jit-direct(one-shot lru_cached capability probe, never re-compiled)
        fn = jax.jit(lambda v: pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
            interpret=mode == "interpret")(v))
        out = fn.lower(x).compile()(x)
    except Exception as e:  # noqa: BLE001 - reported, kernel tier off
        logging.getLogger("spark_rapids_tpu.device_caps").warning(
            "Pallas %s probe failed on %s; kernel tier disabled: %s",
            mode, jax.default_backend(), e)
        return None
    return mode if np.array_equal(np.asarray(out), x * 2) else None


def pallas_interpret() -> bool:
    """True when kernels must pass ``interpret=True`` to pallas_call."""
    return pallas_mode() == "interpret"


def float_arith_reason(kind: str = "arithmetic") -> str:
    return (f"device float {kind} is not bit-identical to CPU on this "
            "backend (TPU f64 is emulated); set "
            "spark.rapids.sql.incompatibleOps.enabled=true to allow")
