"""Task-level OOM retry & split-and-retry framework + fault injection.

The reference survives memory pressure with two cooperating pieces:
``DeviceMemoryEventHandler.onAllocFailure`` spills the device store and
retries the allocation, and the retry framework (RmmRapidsRetryIterator
.scala:243 withRetry / withRetryNoSplit) wraps every operator-held
allocation so a ``GpuRetryOOM`` re-attempts after the store drains and a
``GpuSplitAndRetryOOM`` splits the operator's input in half and
processes the pieces independently.  This module is the TPU twin:

- ``with_retry(fn, conf, metrics)`` — run one device operation under
  the retry protocol: on :class:`TpuRetryOOM` spill the DeviceStore
  down, sleep a bounded exponential backoff, and re-attempt up to
  ``spark.rapids.sql.retry.maxRetries`` times, then re-raise.
- ``with_split_retry(batch, fn, conf, metrics)`` — the split-and-retry
  combinator: when retries exhaust (or the failure explicitly asks for
  a split), the input batch splits in half BY ROWS and each half runs
  independently; results concat downstream to a bit-identical whole.
- ``io_with_retry(fn, conf, metrics)`` — bounded-backoff retry for
  transient reader IO errors, re-raising the original after
  ``spark.rapids.sql.reader.maxRetries``.

Fault injection (SURVEY.md:377-385 names the missing piece): a
deterministic, seeded :class:`FaultInjector` driven by the
``spark.rapids.sql.test.injectOOM`` / ``injectIOError`` /
``injectChipFailure`` confs throws synthetic OOMs at the Nth wrapped
allocation, IO errors at the Nth reader access, and dispatch failures
on named mesh chips.  Chip failures degrade the mesh (parallel/mesh.py
``mark_chip_failed``) instead of failing the query; see
docs/robustness.md for the full state machine.
"""

from __future__ import annotations

import contextlib
import random
import threading
import time
from typing import Any, Callable, List, Optional, TypeVar

from spark_rapids_tpu import metrics as M

T = TypeVar("T")


# ---------------------------------------------------------------------------
# Exceptions (GpuRetryOOM / GpuSplitAndRetryOOM / shuffle-fetch-failure twins)
# ---------------------------------------------------------------------------

class TpuRetryOOM(MemoryError):
    """Retryable device allocation failure: the caller should make its
    held batches spillable, spill the store down, and re-attempt."""


class TpuSplitAndRetryOOM(TpuRetryOOM):
    """Retrying at the same size will not help: split the input batch
    in half by rows and process the halves independently."""


class TpuChipFailure(RuntimeError):
    """A device program could not be dispatched on a mesh chip. Handled
    by degrading the mesh to the surviving chips (the Spark analogue is
    a fetch-failure driving stage re-execution on healthy executors)."""

    def __init__(self, chip_id: int, msg: str = ""):
        super().__init__(msg or f"dispatch failure on mesh chip {chip_id}")
        self.chip_id = chip_id


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Resource exhausted",
                "Out of memory", "out of memory",
                "Failed to allocate", "OOM")


def is_vmem_refusal(e: BaseException) -> bool:
    """A compile-time ``RESOURCE_EXHAUSTED`` that names VMEM: the
    program does not fit the chip's on-core vector memory. Spilling
    HBM or splitting the batch cannot change a static program
    footprint, so this is a compiler refusal, never traffic for the
    retry ladder."""
    s = str(e)
    return "vmem" in s.lower() and any(m in s for m in _OOM_MARKERS)


def is_oom_error(e: BaseException) -> bool:
    """Heuristic: does a raw backend error look like an HBM allocation
    failure (XLA surfaces RESOURCE_EXHAUSTED through generic
    RuntimeError/XlaRuntimeError types)?"""
    if isinstance(e, TpuRetryOOM):
        return True
    s = str(e)
    return any(m in s for m in _OOM_MARKERS) and not is_vmem_refusal(e)


# ---------------------------------------------------------------------------
# Recovery-path injection suppression (the retry machinery's own spill /
# split / fallback work must never recurse into another injected fault)
# ---------------------------------------------------------------------------

_tls = threading.local()


def _suppressed() -> bool:
    return getattr(_tls, "suppress", 0) > 0


@contextlib.contextmanager
def suppress_injection():
    _tls.suppress = getattr(_tls, "suppress", 0) + 1
    try:
        yield
    finally:
        _tls.suppress -= 1


# ---------------------------------------------------------------------------
# Deterministic fault injector
# ---------------------------------------------------------------------------

class _Schedule:
    """Parsed injection spec. Grammar (docs/robustness.md):

    - ``"N"``        fire once at every Nth event
    - ``"N:K"``      at every Nth event, fail K CONSECUTIVE attempts
                     (K > retry.maxRetries forces split-and-retry)
    - ``"split:N"``  throw TpuSplitAndRetryOOM at every Nth event
    - ``"seed:S:P"`` seeded random: each event fails with probability P
    - ``"site:NAME:SPEC"`` scope any of the above to events tagged
      with site NAME (e.g. ``site:upload:2`` fails every 2nd scan
      upload-ahead; untagged sites never count against the schedule).
      ``site:cancel:SPEC`` is special: it counts LIFECYCLE
      cancellation checkpoints and injects a cooperative cancel of
      the live query's token instead of an OOM (docs/robustness.md
      site catalog). ``site:budget:SPEC`` is the planning leg: it
      counts budget-ORACLE queries and makes the firing query report
      half the real headroom (docs/out_of_core.md) — never an error
    """

    __slots__ = ("every_n", "streak", "split", "seed", "prob", "rng",
                 "site")

    def __init__(self, every_n=0, streak=1, split=False, seed=0,
                 prob=0.0, site=""):
        self.every_n = every_n
        self.streak = max(1, streak)
        self.split = split
        self.seed = seed
        self.prob = prob
        self.site = site
        # per-schedule RNG: a seeded OOM schedule and a seeded IO
        # schedule must each follow their OWN deterministic stream
        self.rng = random.Random(seed) if prob > 0.0 else None


def _parse_schedule(spec: str) -> Optional[_Schedule]:
    s = str(spec or "").strip().lower()
    if not s or s in ("0", "false", "off", "none"):
        return None
    if s.startswith("site:"):
        _, name, rest = s.split(":", 2)
        sched = _parse_schedule(rest)
        if sched is not None:
            sched.site = name
        return sched
    if s.startswith("split:"):
        return _Schedule(every_n=int(s[len("split:"):]), split=True)
    if s.startswith("seed:"):
        _, seed, prob = s.split(":")
        return _Schedule(seed=int(seed), prob=float(prob))
    if ":" in s:
        n, k = s.split(":")
        return _Schedule(every_n=int(n), streak=int(k))
    return _Schedule(every_n=int(s))


class FaultInjector:
    """Deterministic synthetic-fault source. One instance per distinct
    injection conf (process-wide, like the DeviceStore); counters are
    shared across sessions so a schedule is a property of the process
    timeline, exactly like the reference's RMM inject-OOM hook."""

    def __init__(self, oom_spec: str = "", io_spec: str = "",
                 chip_spec: str = ""):
        self._oom = _parse_schedule(oom_spec)
        # `site:cancel:N` is the LIFECYCLE leg of the grammar
        # (docs/robustness.md): the schedule counts cancellation
        # CHECKPOINTS (lifecycle.checkpoint) instead of allocations,
        # and the injected fault is a cooperative cancel of the live
        # query's token — never an OOM
        self._cancel = None
        if self._oom is not None and self._oom.site == "cancel":
            self._cancel, self._oom = self._oom, None
        # `site:budget:N` is the PLANNING leg (docs/robustness.md,
        # docs/out_of_core.md): the schedule counts budget-ORACLE
        # queries instead of allocations, and the injected fault is a
        # halved headroom report — never a raised error — so the
        # planned out-of-core tier's escalation path (more partitions,
        # zero retries) is deterministically testable
        self._budget = None
        if self._oom is not None and self._oom.site == "budget":
            self._budget, self._oom = self._oom, None
        # `site:tuning:N` is the FEEDBACK-CONTROL leg (docs/tuning.md):
        # the schedule counts TuningController scan ticks, and the
        # injected fault is a deliberately harmful synthetic action —
        # never an error — so the guardrail's auto-revert path is
        # deterministically testable end to end
        self._tuning = None
        if self._oom is not None and self._oom.site == "tuning":
            self._tuning, self._oom = self._oom, None
        self._io = _parse_schedule(io_spec)
        self._chips = set()
        for part in str(chip_spec or "").split(","):
            part = part.strip()
            if part:
                self._chips.add(int(part))
        self._lock = threading.Lock()
        self._alloc_count = 0
        self._oom_streak = 0
        self._io_count = 0
        self._io_streak = 0
        self._cancel_count = 0
        self._budget_count = 0
        self._tuning_count = 0
        # observability (bench detail.robustness, tests)
        self.oom_injected = 0
        self.io_injected = 0
        self.chip_failures_injected = 0
        self.cancels_injected = 0
        self.budget_faults_injected = 0
        self.tuning_faults_injected = 0

    def _fire(self, sched: _Schedule, count: int) -> bool:
        if sched.prob > 0.0:
            return sched.rng.random() < sched.prob
        return sched.every_n > 0 and count % sched.every_n == 0

    def on_alloc(self, site: str = "") -> None:
        """Checkpoint at one wrapped device allocation attempt. ``site``
        tags named allocation classes (``upload`` = the scan pipeline's
        prefetched raw-chunk upload) so a ``site:NAME:...`` schedule
        can target exactly one of them."""
        if self._oom is None or _suppressed():
            return
        if self._oom.site and self._oom.site != site:
            return
        with self._lock:
            if self._oom_streak > 0:
                self._oom_streak -= 1
                self.oom_injected += 1
                raise TpuRetryOOM("injected OOM (consecutive-failure "
                                  "streak, spark.rapids.sql.test.injectOOM)")
            self._alloc_count += 1
            if not self._fire(self._oom, self._alloc_count):
                return
            self.oom_injected += 1
            if self._oom.split:
                raise TpuSplitAndRetryOOM(
                    f"injected split-OOM at allocation {self._alloc_count} "
                    "(spark.rapids.sql.test.injectOOM)")
            self._oom_streak = self._oom.streak - 1
            raise TpuRetryOOM(
                f"injected OOM at allocation {self._alloc_count} "
                "(spark.rapids.sql.test.injectOOM)")

    def on_io(self, path: str = "") -> None:
        """Checkpoint at one reader IO attempt."""
        if self._io is None or _suppressed():
            return
        with self._lock:
            if self._io_streak > 0:
                self._io_streak -= 1
                self.io_injected += 1
                raise IOError(f"injected IO error reading {path!r} "
                              "(spark.rapids.sql.test.injectIOError)")
            self._io_count += 1
            if not self._fire(self._io, self._io_count):
                return
            self.io_injected += 1
            self._io_streak = self._io.streak - 1
            raise IOError(f"injected IO error reading {path!r} "
                          "(spark.rapids.sql.test.injectIOError)")

    def on_chip(self, chip_id: int) -> None:
        """Checkpoint before dispatching device work onto a mesh chip.
        Injected failures are PERSISTENT per chip — the degrade loop
        stops consulting a chip once it is marked failed, which is what
        ends the failure stream (a real dead chip behaves the same)."""
        if chip_id in self._chips:
            with self._lock:
                self.chip_failures_injected += 1
            raise TpuChipFailure(chip_id)

    def on_cancel_point(self, token, site: str = "") -> None:
        """Checkpoint at one lifecycle cancellation checkpoint
        (lifecycle.checkpoint). A ``site:cancel:N`` schedule cancels
        the live query's token at the Nth checkpoint — the fault it
        injects IS a cancellation, so the query unwinds through the
        cooperative-cancel protocol, not the retry protocol. Recovery
        paths are exempt like every other injection site."""
        if self._cancel is None or token is None or _suppressed():
            return
        with self._lock:
            self._cancel_count += 1
            if not self._fire(self._cancel, self._cancel_count):
                return
            self.cancels_injected += 1
        from spark_rapids_tpu.lifecycle import REASON_INJECTED
        token.cancel(REASON_INJECTED)

    def on_budget_query(self) -> bool:
        """Checkpoint at one budget-oracle headroom query. A
        ``site:budget:N`` schedule returns True at the Nth query — the
        oracle then reports HALF the real headroom, so planning sees
        synthetic memory pressure and escalates its partition count
        (never an error: the fault exercises the planned path, not the
        retry backstop). Recovery paths are exempt like every other
        injection site."""
        if self._budget is None or _suppressed():
            return False
        with self._lock:
            self._budget_count += 1
            if not self._fire(self._budget, self._budget_count):
                return False
            self.budget_faults_injected += 1
            return True

    def on_tuning_tick(self) -> bool:
        """Checkpoint at one TuningController scan tick. A
        ``site:tuning:N`` schedule returns True at the Nth tick — the
        controller then applies a deliberately HARMFUL synthetic action
        (docs/tuning.md) so the guardrail's observe-and-revert loop is
        exercised without waiting for a real bad decision (never an
        error: the fault is a bad action, and reverting it IS the
        behavior under test)."""
        if self._tuning is None or _suppressed():
            return False
        with self._lock:
            self._tuning_count += 1
            if not self._fire(self._tuning, self._tuning_count):
                return False
            self.tuning_faults_injected += 1
            return True

    def stats(self) -> dict:
        with self._lock:
            return {"allocations": self._alloc_count,
                    "oomInjected": self.oom_injected,
                    "ioInjected": self.io_injected,
                    "chipFailuresInjected": self.chip_failures_injected,
                    "cancelsInjected": self.cancels_injected,
                    "budgetFaultsInjected": self.budget_faults_injected,
                    "tuningFaultsInjected": self.tuning_faults_injected}


_INJECTOR: Optional[FaultInjector] = None
_INJECTOR_KEY: Optional[tuple] = None
_INJECTOR_LOCK = threading.Lock()


def get_fault_injector(conf) -> Optional[FaultInjector]:
    """Process-wide injector for the session's injection confs; None
    (zero overhead) when injection is off. Rebuilt — with fresh,
    deterministic counters — whenever the injection confs change."""
    if conf is None:
        return None
    from spark_rapids_tpu.conf import (INJECT_CHIP_FAILURE, INJECT_IO_ERROR,
                                       INJECT_OOM)
    key = (str(conf.get(INJECT_OOM) or ""),
           str(conf.get(INJECT_IO_ERROR) or ""),
           str(conf.get(INJECT_CHIP_FAILURE) or ""))
    if key == ("", "", ""):
        return None
    global _INJECTOR, _INJECTOR_KEY
    with _INJECTOR_LOCK:
        if _INJECTOR is None or _INJECTOR_KEY != key:
            _INJECTOR = FaultInjector(*key)
            _INJECTOR_KEY = key
        return _INJECTOR


def reset_fault_injection() -> None:
    """Drop the injector singleton so the next query sees a fresh,
    deterministic schedule (tests call this between runs)."""
    global _INJECTOR, _INJECTOR_KEY
    with _INJECTOR_LOCK:
        _INJECTOR = None
        _INJECTOR_KEY = None


def degrade_on_chip_failure(attempt: Callable[[], T],
                            metrics=None) -> T:
    """The chip-failure degrade loop (docs/robustness.md ladder), shared
    by the exchange materializer and the driver-level collect so the
    retry-vs-reraise protocol lives in ONE place. Snapshot the failed
    set BEFORE each attempt: a failure on a chip that was already
    demoted when the attempt began means the failure is elsewhere and
    re-raises (bounding the loop by the chip count); a chip another
    thread demoted mid-attempt still retries on the survivors."""
    from spark_rapids_tpu.parallel.mesh import (failed_chips,
                                                mark_chip_failed)
    while True:
        already = failed_chips()
        try:
            return attempt()
        except TpuChipFailure as e:
            if e.chip_id in already:
                raise
            from spark_rapids_tpu import trace as TR
            TR.instant("chipFailure", chip=e.chip_id)
            if mark_chip_failed(e.chip_id) and metrics is not None:
                metrics.create(M.DEGRADED_CHIPS, M.ESSENTIAL).add(1)


def chip_checkpoint(conf, device) -> None:
    """Raise TpuChipFailure when dispatch onto ``device`` is injected
    to fail (called at mesh upload / mesh exchange dispatch points)."""
    inj = get_fault_injector(conf)
    if inj is not None:
        inj.on_chip(device.id if hasattr(device, "id") else int(device))


# ---------------------------------------------------------------------------
# Retry combinators
# ---------------------------------------------------------------------------

def _retry_limits(conf) -> tuple:
    if conf is None:
        return 3, 1, 100
    from spark_rapids_tpu.conf import (RETRY_BACKOFF_MS, RETRY_MAX_BACKOFF_MS,
                                       RETRY_MAX_RETRIES)
    return (int(conf.get(RETRY_MAX_RETRIES)),
            int(conf.get(RETRY_BACKOFF_MS)),
            int(conf.get(RETRY_MAX_BACKOFF_MS)))


def _recover(conf, metrics, attempt: int, backoff_ms: int,
             max_backoff_ms: int) -> None:
    """One OOM recovery step: spill the device store down (the
    DeviceMemoryEventHandler.onAllocFailure role), then block for a
    bounded exponential backoff so concurrent tasks' frees land. Traced
    as an instant ``retryOOM`` marker plus a nested ``retryBlock`` span
    over the SAME interval the retryBlockTime metric reads — the
    offline analyzer subtracts the nested span from enclosing operator
    spans, undoing the documented retryBlockTime-inside-opTime double
    count at the reporting layer (docs/observability.md)."""
    from spark_rapids_tpu import trace as TR
    from spark_rapids_tpu.telemetry import triggers as TEL
    TR.instant("retryOOM", attempt=attempt)
    # retry-STORM telemetry is evaluated here, at retry time, so a
    # storm surfaces while it is happening (one boolean check when the
    # engine is unarmed; docs/observability.md "Live telemetry")
    TEL.on_retry()
    freed = 0
    with TR.span("retryBlock", metrics=metrics,
                 attempt=attempt) as sp, suppress_injection():
        if conf is not None:
            from spark_rapids_tpu.memory import get_device_store
            store = get_device_store(conf)
        else:
            # conf-less wrap sites (columnar helpers without a plan
            # context): best-effort spill of the live process store —
            # backoff alone rarely frees HBM
            from spark_rapids_tpu import memory
            store = memory._STORE
        if store is not None:
            # escalate: first retry frees half the device tier (handles
            # the operation touches next stay resident instead of
            # thrashing a full device->host->device round trip), later
            # retries drain it completely
            target = store.device_bytes // 2 if attempt == 1 else 0
            freed = store.spill_device_down(target)
        delay = min(backoff_ms * (1 << (attempt - 1)), max_backoff_ms)
        if delay > 0:
            # cancellation-aware backoff (docs/serving.md "Query
            # lifecycle"): a cancelled/timed-out query must not sleep
            # through its deadline inside the retry protocol
            from spark_rapids_tpu.lifecycle import cancellable_sleep
            cancellable_sleep(delay / 1000.0, site="retryBackoff")
        sp.attrs["freedBytes"] = freed
    if metrics is not None:
        metrics.create(M.RETRY_COUNT, M.ESSENTIAL).add(1)
        if freed:
            metrics.create(M.SPILL_BYTES_ON_RETRY, M.ESSENTIAL).add(freed)
        metrics.create(M.RETRY_BLOCK_TIME).add(sp.t1 - sp.t0)


def with_retry(fn: Callable[[], T], conf=None, metrics=None, *,
               splittable: bool = False,
               translate_real: bool = True, site: str = "") -> T:
    """Run ``fn`` under the OOM-retry protocol (withRetryNoSplit role).

    On :class:`TpuRetryOOM` — injected, or a real backend
    RESOURCE_EXHAUSTED when ``translate_real`` — spill the DeviceStore
    down, back off (bounded exponential), and re-attempt up to
    ``spark.rapids.sql.retry.maxRetries`` times before re-raising.
    ``fn`` must be safe to re-execute (callers with donated input
    buffers pass ``translate_real=False``: a real OOM may have consumed
    the inputs mid-program, so only pre-dispatch injected faults — which
    leave inputs intact — are retried there).

    ``splittable=True`` (set by :func:`with_split_retry`) propagates
    :class:`TpuSplitAndRetryOOM` to the caller instead of degrading it
    to a plain retry.
    """
    inj = get_fault_injector(conf)
    max_retries, backoff_ms, max_backoff_ms = _retry_limits(conf)
    attempt = 0
    while True:
        try:
            if inj is not None:
                inj.on_alloc(site)
            return fn()
        except TpuSplitAndRetryOOM:
            if splittable:
                raise
            # no split support at this site: degrade to a plain retry
            attempt += 1
            if attempt > max_retries:
                raise
        except TpuRetryOOM:
            attempt += 1
            if attempt > max_retries:
                raise
        except TpuChipFailure:
            raise  # handled by the mesh degrade loop, never retried here
        except Exception as e:
            from spark_rapids_tpu.lifecycle import TpuQueryCancelled
            if isinstance(e, TpuQueryCancelled):
                raise  # cooperative cancel unwinds, never retried
            if not translate_real or not is_oom_error(e):
                raise
            attempt += 1
            if attempt > max_retries:
                raise TpuRetryOOM(f"device OOM after {max_retries} "
                                  f"retries: {e}") from e
        _recover(conf, metrics, attempt, backoff_ms, max_backoff_ms)


def with_split_retry(batch, fn: Callable[[Any], T], conf=None,
                     metrics=None, *, split=None,
                     translate_real: bool = True,
                     split_first: bool = False) -> List[T]:
    """Split-and-retry combinator (RmmRapidsRetryIterator.withRetry with
    the splitSpillableInHalfByRows policy): process ``batch`` with
    ``fn``; when the per-piece retry protocol exhausts — or the failure
    explicitly demands a split — the piece splits in half by rows and
    the halves are processed independently, recursively. Returns the
    per-piece results IN ROW ORDER, so concatenating them downstream is
    bit-identical to the unsplit whole (for the row-wise operators this
    wraps). Raises when a piece of <= 1 row still cannot complete.
    """
    if split is None:
        split = split_device_batch
    stack = [batch]
    out: List[T] = []
    first = True
    while stack:
        b = stack.pop()
        if first and split_first:
            first = False
            halves = _split_piece(b, split, metrics)
            if halves is None:
                stack.append(b)  # cannot split: one plain attempt
            else:
                stack.extend(reversed(halves))
            continue
        first = False
        try:
            out.append(with_retry(lambda: fn(b), conf, metrics,
                                  splittable=True,
                                  translate_real=translate_real))
        except TpuRetryOOM:
            halves = _split_piece(b, split, metrics)
            if halves is None:
                # unsplittable piece (single row, array/map columns):
                # last resort is the plain retry protocol — spilling
                # the store down may still free enough HBM for the
                # piece to fit; re-raises after maxRetries
                out.append(with_retry(lambda: fn(b), conf, metrics,
                                      splittable=False,
                                      translate_real=translate_real))
                continue
            stack.extend(reversed(halves))
    return out


def _split_piece(b, split, metrics) -> Optional[list]:
    with suppress_injection():
        halves = split(b)
    if not halves or len(halves) < 2:
        return None
    if metrics is not None:
        metrics.create(M.SPLIT_RETRY_COUNT, M.ESSENTIAL).add(1)
    from spark_rapids_tpu import trace as TR
    TR.instant("splitRetry", pieces=len(halves))
    return halves


def io_with_retry(fn: Callable[[], T], conf=None, metrics=None,
                  path: str = "") -> T:
    """Bounded-exponential-backoff retry for transient reader IO
    errors; the ORIGINAL error re-raises after
    ``spark.rapids.sql.reader.maxRetries`` attempts."""
    inj = get_fault_injector(conf)
    if conf is not None:
        from spark_rapids_tpu.conf import (READER_MAX_RETRIES,
                                           READER_RETRY_BACKOFF_MS)
        max_retries = int(conf.get(READER_MAX_RETRIES))
        backoff_ms = int(conf.get(READER_RETRY_BACKOFF_MS))
    else:
        max_retries, backoff_ms = 3, 1
    attempt = 0
    first_err: Optional[OSError] = None
    while True:
        try:
            if inj is not None:
                inj.on_io(path)
            return fn()
        except OSError as e:
            if first_err is None:
                first_err = e  # the root cause, not the last retry's
            attempt += 1
            if attempt > max_retries:
                raise first_err
            from spark_rapids_tpu import trace as TR
            TR.instant("ioRetry", path=path, attempt=attempt)
            if metrics is not None:
                metrics.create(M.IO_RETRY_COUNT, M.ESSENTIAL).add(1)
            t0 = time.perf_counter_ns()
            from spark_rapids_tpu.lifecycle import cancellable_sleep
            cancellable_sleep(
                min(backoff_ms * (1 << (attempt - 1)), 1000) / 1000.0,
                site="retryBackoff")
            if metrics is not None:
                metrics.create(M.RETRY_BLOCK_TIME).add(
                    time.perf_counter_ns() - t0)


# ---------------------------------------------------------------------------
# Split policies
# ---------------------------------------------------------------------------

def split_host_batch(hb) -> Optional[list]:
    """HostBatch -> two halves by rows (the R2C upload split policy)."""
    n = hb.num_rows
    if n <= 1:
        return None
    return [hb.slice(0, n // 2), hb.slice(n // 2, n)]


def split_device_batch(b) -> Optional[list]:
    """DeviceBatch -> halves with ~equal ACTIVE rows, original order
    preserved (the splitSpillableInHalfByRows policy). Reuses the
    exchange's one-program sort-split (split_by_pid), so each half
    compacts to its own smaller capacity bucket — the memory actually
    shrinks. Nested array/map columns carry element pools the row-sort
    cannot ride; those batches report unsplittable (None)."""
    from spark_rapids_tpu.sql import types as T
    for f in b.schema.fields:
        if isinstance(f.data_type, (T.ArrayType, T.MapType)):
            return None
    n = b.row_count()  # recovery path: a blocking count sync is fine
    if n <= 1:
        return None
    from spark_rapids_tpu.exec.exchange import split_by_pid
    parts = split_by_pid(b, _half_pids()(b.active), 2)
    return [p for p in parts if p is not None]


_HALF_PIDS = None


def _half_pids():
    """Jitted half-point pid assignment (compiled once per capacity
    bucket by jax's own cache; the builder itself is built once)."""
    global _HALF_PIDS
    if _HALF_PIDS is None:
        import jax
        import jax.numpy as jnp

        def _fn(active):
            rank = jnp.cumsum(active.astype(jnp.int64)) - 1
            total = jnp.sum(active.astype(jnp.int64))
            return jnp.where(rank * 2 < total, 0, 1).astype(jnp.int32)
        from spark_rapids_tpu.jit_cache import named_jit
        # tpu-lint: disable=jit-direct(one lazily-built fixed split program — bounded by construction)
        _HALF_PIDS = named_jit("srt_retry_half_pids", _fn)
    return _HALF_PIDS
