"""Pallas kernel tier: hand-written device kernels for the traced hot
loops, behind the existing JitCache keys (SURVEY.md §2.4: the
cuDF-equivalent kernel library must be *built* — the reference's speed
comes from purpose-built device kernels; this package is ours).

Model (docs/kernels.md):

- every kernel has an XLA-op composition **oracle** — the code path
  that existed before the kernel — and must be bit-identical to it.
  Kernels therefore only take shapes where bit-identity is provable
  (integer/decimal accumulation, exact min/max ranks, the literal
  murmur3 arithmetic); anything else stays on the oracle.
- kernels are **traced functions**: they run inside the op's existing
  jitted program, so the JitCache key simply gains a kernel flag —
  enable-state changes can never reuse a stale trace.
- per-kernel enable confs ``spark.rapids.sql.kernel.<name>.enabled``
  plus a master ``spark.rapids.sql.kernel.enabled``; with everything
  off the oracle path is byte-for-byte what shipped before this tier.
- ``device_caps.pallas_mode()`` picks real lowering on an accelerator
  or ``interpret=True`` emulation on the CPU platform, so tier-1
  exercises every kernel path without hardware. A kernel the native
  compiler refuses is listed in ``NATIVE_REFUSED`` with the compiler's
  message and gated off there, so the stock path never attempts a
  compile that is known to fail.
- **fallback**: a kernel program that fails to lower/compile/execute
  (anything that is not the retry protocol's OOM/chip-failure
  traffic) poisons its structural key — the reason is logged and kept
  (``poisoned()``) — and the call re-runs on the oracle, counted as
  ``kernelFallbacks.<name>``. The group-by kernel additionally reports
  hash-table overflow as a device flag; the exec re-runs overflowed
  batches on the oracle (same counter, no poison).
"""

from __future__ import annotations

import contextlib as _contextlib
import logging
import threading
from typing import Dict

from spark_rapids_tpu import metrics as M

# kernel name -> one-line description (docs/kernels.md table; the
# per-kernel conf entries live in conf.py like every other knob)
KERNELS: Dict[str, str] = {
    "groupbyHash": "single-pass open-addressed hash-table group-by "
                   "(partial-mode SUM/COUNT/MIN/MAX)",
    "joinProbe": "hash-table build/probe gather map (semi/anti joins "
                 "+ the FK unique-build-key fast path)",
    "murmur3": "fused Spark Murmur3_x86_32 partition hashing",
    "decodeFused": "single-program fused Parquet page decode "
                   "(RLE/bit-unpack + dict gather + validity expansion "
                   "+ string offsets/chars)",
}

_CONF_OF = {
    "groupbyHash": "spark.rapids.sql.kernel.groupbyHash.enabled",
    "joinProbe": "spark.rapids.sql.kernel.joinProbe.enabled",
    "murmur3": "spark.rapids.sql.kernel.murmur3.enabled",
    "decodeFused": "spark.rapids.sql.kernel.decodeFused.enabled",
}


# Kernels the native (Mosaic) compiler refuses: name -> the first line
# of what the lowering raised, as chip_smoke.py's kernel leg printed it
# on a TPU v5 lite (jax 0.9.0, libtpu 0.0.34; docs/kernels.md has the
# shapes and where each error comes from). All four carry 64-bit lanes,
# which Pallas's TPU lowering has no rules for — the design itself is
# refused, so ROADMAP S4/D1/D2 decide redesign or removal. The gate
# below answers False for these wherever Pallas lowers natively, so the
# stock path performs no failed kernel compile and poisons no key;
# interpret mode (CPU) still runs them. A kernel that is made to lower
# leaves this table (chip_smoke.py fails while the two disagree).
NATIVE_REFUSED: Dict[str, str] = {
    "groupbyHash": "ZeroDivisionError: integer modulo by zero",
    "joinProbe": "RecursionError: maximum recursion depth exceeded",
    "murmur3": "RecursionError: maximum recursion depth exceeded",
    "decodeFused": "ValueError: Only arrays with 32-bit element types "
                   "can be converted to scalars, but got: int64. Try "
                   "casting the input before squeezing the scalar.",
}

_log = logging.getLogger("spark_rapids_tpu.kernels")


class KernelDispatchError(RuntimeError):
    """Injected kernel failure (tests): routed to the oracle fallback
    exactly like a real lowering/compile failure."""


# structural keys whose kernel build/dispatch failed once: the kernel
# is not retried for that structure (the oracle handles it for the
# process lifetime; a conf flip or restart clears the set). Bounded:
# distinct plan structures, not per-batch.
_POISON_LOCK = threading.Lock()
_POISONED: Dict[tuple, str] = {}  # (name, key) -> first line of the error
_POISON_CAP = 4096

# test hook: kernel names whose next dispatches raise (FaultInjector
# style, but for the lowering-failure path which never fires on a
# backend where the kernels actually work)
_FAIL_INJECT: set = set()


def first_line(exc: BaseException) -> str:
    """``Type: first line of the message`` — how a refusal is recorded
    (poison reasons, ``NATIVE_REFUSED``, chip_smoke.py's verdicts)."""
    lines = str(exc).strip().splitlines()
    return f"{type(exc).__name__}: {lines[0] if lines else ''}"


def poison(name: str, key, exc: BaseException) -> None:
    reason = first_line(exc)
    _log.warning("kernel %s failed and is off for this structure "
                 "(oracle composition takes over): %s", name, reason,
                 exc_info=exc)
    with _POISON_LOCK:
        if len(_POISONED) < _POISON_CAP:
            _POISONED[(name, key)] = reason


def is_poisoned(name: str, key) -> bool:
    with _POISON_LOCK:
        return (name, key) in _POISONED


def clear_poison() -> None:
    with _POISON_LOCK:
        _POISONED.clear()


def poisoned() -> Dict[tuple, str]:
    """Snapshot of the poisoned ``(name, key) -> reason`` entries."""
    with _POISON_LOCK:
        return dict(_POISONED)


def inject_failure(name: str, on: bool = True) -> None:
    """Tests: make every ``check_injected_failure(name)`` site raise."""
    if on:
        _FAIL_INJECT.add(name)
    else:
        _FAIL_INJECT.discard(name)


def check_injected_failure(name: str) -> None:
    if name in _FAIL_INJECT:
        raise KernelDispatchError(f"injected kernel failure: {name}")


def kernel_enabled(conf, name: str) -> bool:
    """Conf + backend gate for one kernel (structure checks are the
    caller's — each op knows its own supported shapes)."""
    if conf is None:
        return False
    from spark_rapids_tpu import device_caps as DC
    from spark_rapids_tpu.conf import KERNEL_ENABLED
    if not bool(conf.get(KERNEL_ENABLED)):
        return False
    if not conf.is_op_enabled(_CONF_OF[name], default=True):
        return False
    mode = DC.pallas_mode()
    if mode == "native":
        return name not in NATIVE_REFUSED
    return mode is not None


def interpret() -> bool:
    from spark_rapids_tpu import device_caps as DC
    return DC.pallas_interpret()


def is_oracle_fallback_error(exc: BaseException) -> bool:
    """True when a kernel-path failure should fall back to the oracle
    composition; False for the retry protocol's own traffic (OOM /
    split / chip failure must keep riding PR 4's state machine)."""
    from spark_rapids_tpu.retry import (TpuChipFailure, TpuRetryOOM,
                                        is_oom_error)
    if isinstance(exc, (TpuRetryOOM, TpuChipFailure, KeyboardInterrupt,
                        SystemExit)):
        return False
    # raw backend HBM OOM: the retry wrappers translate it. A compile
    # error that names VMEM is not one (retry.is_vmem_refusal): the
    # kernel does not fit the core, and the oracle takes over
    return not is_oom_error(exc)


def count_dispatch(metrics, name: str) -> None:
    if metrics is not None:
        metrics.create(f"kernelDispatchCount.{name}", M.MODERATE).add(1)


def count_fallback(metrics, name: str) -> None:
    if metrics is not None:
        metrics.create(f"kernelFallbacks.{name}", M.ESSENTIAL).add(1)


@_contextlib.contextmanager
def dispatch_span(name: str, chip=None, **attrs):
    """Trace span for one kernel dispatch (`kernel=<name>` attr + chip
    id), so profiles attribute kernel vs oracle time (docs/kernels.md).
    Extra attrs (shape bucket, tuned flag) ride along for the hotspots
    per-bucket split."""
    from spark_rapids_tpu import trace as TR
    with TR.span("kernelDispatch", chip=chip, kernel=name, **attrs):
        yield


def table_slots(conf, cap: int, slots_mult: int = 1) -> int:
    """Group-by table capacity: the conf bound (scaled by the
    autotuner's per-bucket multiplier), shrunk toward the batch (a
    64-row batch cannot have 1024 groups) and rounded to a power of
    two (the kernel masks slot indices)."""
    from spark_rapids_tpu.conf import KERNEL_GROUPBY_TABLE_SLOTS
    want = min(int(conf.get(KERNEL_GROUPBY_TABLE_SLOTS))
               * max(1, int(slots_mult)),
               max(2 * cap, 64))
    t = 64
    while t < want:
        t <<= 1
    return t
