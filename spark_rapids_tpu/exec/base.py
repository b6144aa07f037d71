"""TpuExec base + row/columnar transitions (GpuExec.scala:196,
GpuRowToColumnarExec.scala, GpuColumnarToRowExec.scala twins).

Execution model mirrors the CPU engine's ``partitions() -> [thunk]`` shape,
with a device-side channel: every TpuExec produces ``device_partitions()``
yielding HBM-resident ``DeviceBatch``es; ``partitions()`` (rows-for-CPU
view) is derived by gathering to host, which is exactly what the plugin's
``GpuColumnarToRowExec`` transition does. The rewrite engine inserts
explicit transition nodes so plans show the same boundaries the reference
plans do.
"""

from __future__ import annotations

from typing import Callable, Iterator, List, Optional

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu.columnar.device import (
    DeviceBatch, bucket_capacity, concat_device, shrink_to_bucket)
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu.conf import TpuConf, METRICS_LEVEL
from spark_rapids_tpu.resource import get_semaphore
from spark_rapids_tpu.sql import physical as P

DevicePartitionThunk = Callable[[], Iterator[DeviceBatch]]


class TpuExec(P.PhysicalPlan):
    """Base of all device operators. Subclasses implement
    ``device_partitions``; the host-row view is derived via to_host the way
    GpuColumnarToRowExec derives rows (the rewrite inserts an explicit
    TpuColumnarToRowExec at real boundaries — partitions() here only backs
    execute_collect on nested/driver paths)."""

    def __init__(self, conf: TpuConf):
        self.conf = conf
        # owner labels this exec's trace spans "<Exec>.<metric>"
        self.metrics = M.MetricRegistry(str(conf.get(METRICS_LEVEL)),
                                        owner=type(self).__name__)
        # pre-created so an op that saw 0 rows logs numOutputRows: 0 —
        # distinguishable from a metric that never existed (event-log
        # v2 contract, docs/observability.md)
        self.metrics.create(M.NUM_OUTPUT_ROWS, M.ESSENTIAL)

    def device_partitions(self) -> List[DevicePartitionThunk]:
        raise NotImplementedError

    def register_spillable(self, store, batch: DeviceBatch):
        """Register a batch this operator holds across yields, tagged
        with the operator as the owning allocator: the store's per-op
        HBM ledger (live/peak bytes, spill attribution) and this exec's
        peakDeviceMemory/spillBytes metrics all hang off this tag
        (docs/observability.md, per-op profile accounting)."""
        return store.register(batch, owner=type(self).__name__,
                              metrics=self.metrics)

    def partitions(self) -> List[P.PartitionThunk]:
        def make(thunk: DevicePartitionThunk) -> P.PartitionThunk:
            def run() -> Iterator[HostBatch]:
                for b in thunk():
                    yield b.to_host()
            return run
        return [make(t) for t in self.device_partitions()]


def device_channel(plan: P.PhysicalPlan) -> List[DevicePartitionThunk]:
    """Child's device batches: direct when the child is a TpuExec, else it
    is a bug in the rewrite (transitions must have been inserted)."""
    assert isinstance(plan, TpuExec), (
        f"device operator consuming non-device child {plan.simple_string()}; "
        "the rewrite engine must insert TpuRowToColumnarExec")
    return plan.device_partitions()


class TpuRowToColumnarExec(TpuExec):
    """CPU rows -> device batches (GpuRowToColumnarExec.scala:830).

    Uploads each HostBatch into HBM with power-of-two capacity bucketing,
    coalescing consecutive small host batches up to the goal row count
    first (the reference reaches its goal via GpuCoalesceBatches; here the
    upload itself batches, which keeps one HBM copy per goal batch).
    Acquires the TpuSemaphore before touching the device.
    """

    def __init__(self, child: P.PhysicalPlan, conf: TpuConf,
                 goal_rows: Optional[int] = None):
        super().__init__(conf)
        self.children = [child]
        self.goal_rows = goal_rows or conf.batch_size_rows

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def device_partitions(self) -> List[DevicePartitionThunk]:
        sem = get_semaphore(self.conf)
        metrics = self.metrics
        # this transition is the scan's direct consumer: allow the scan
        # to hand us still-encoded parquet pages for device decode
        # (decided here, at execution time, so plan rewrites that splice
        # CPU operators in between never see EncodedBatch objects)
        if hasattr(self.child, "emit_encoded"):
            self.child.emit_encoded = True
        # mesh scan handshake (docs/multichip.md): hand the scan the
        # active mesh's devices so it plans one reader stream per chip;
        # each stream's batches then upload DIRECTLY to that chip's HBM
        # (finish_upload pins the device_put) — no gather to chip 0
        if hasattr(self.child, "set_scan_mesh"):
            from spark_rapids_tpu.parallel.mesh import mesh_scan_devices
            self.child.set_scan_mesh(mesh_scan_devices(self.conf))
        parts = self.child.partitions()
        devices = list(getattr(self.child, "partition_devices", []))
        devices += [None] * (len(parts) - len(devices))
        from spark_rapids_tpu.conf import \
            PARQUET_DEVICE_DECODE_MAX_IN_FLIGHT
        depth = int(self.conf.get(PARQUET_DEVICE_DECODE_MAX_IN_FLIGHT))

        def make(thunk: P.PartitionThunk, device) -> DevicePartitionThunk:
            if depth <= 0:
                return self._make_sync(thunk, sem, metrics, device)
            return self._make_pipelined(thunk, sem, metrics, device,
                                        depth)
        return [make(t, d) for t, d in zip(parts, devices)]

    def _make_sync(self, thunk, sem, metrics,
                   device) -> DevicePartitionThunk:
        """Fully synchronous upload loop (deviceDecode.maxInFlight=0):
        read -> prepare -> upload -> decode, one batch at a time on the
        task thread. The unpipelined A/B baseline bench.py measures."""
        def run() -> Iterator[DeviceBatch]:
            from spark_rapids_tpu.io.device_decode import EncodedBatch

            def one(payload):
                return self._finish(self._prepare(payload, metrics),
                                    sem, metrics, device)
            pending: List[HostBatch] = []
            rows = 0
            for b in thunk():
                if isinstance(b, EncodedBatch):
                    if pending:
                        yield from one(pending)
                        pending, rows = [], 0
                    yield from one(b)
                    continue
                if b.num_rows == 0:
                    continue
                pending.append(b)
                rows += b.num_rows
                if rows >= self.goal_rows:
                    yield from one(pending)
                    pending, rows = [], 0
            if pending:
                yield from one(pending)
        return run

    def _make_pipelined(self, thunk, sem, metrics, device,
                        depth: int) -> DevicePartitionThunk:
        """The async read -> decode -> compute scan pipeline
        (docs/scan.md): a producer thread pulls reader batches (file
        IO, decompress, header parse), coalesces and packs them —
        bounded by a prefetch ring of ``depth`` staged batches — while
        the task thread issues each batch's raw-chunk device upload
        AHEAD of the previous batch's decode program, so the upload of
        batch k+1 overlaps the compute of batch k and the read of
        batch k+2. One ring per reader stream; on the mesh scan each
        stream's uploads target its own chip's HBM."""
        def run() -> Iterator[DeviceBatch]:
            import queue as _q
            import threading

            from spark_rapids_tpu import trace as _trace
            from spark_rapids_tpu.io.device_decode import EncodedBatch

            q: "_q.Queue" = _q.Queue(maxsize=depth)
            stop = threading.Event()
            scope = _trace.scope_of(metrics)
            chip = device.id if device is not None else None

            def put_bounded(item) -> bool:
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.05)
                        return True
                    except _q.Full:
                        continue
                return False

            def producer() -> None:
                err = None
                gen = thunk()
                try:
                    def emit(payload) -> bool:
                        # interval-union metric: N streams' overlapping
                        # prefetch work counts wall once (the PR 1
                        # decodeTime>wall audit applies to these
                        # threads too), mirrored as a scanPrefetch span
                        m = metrics.create("scanPrefetchTime")
                        with _trace.span("scanPrefetch", scope=scope,
                                         chip=chip):
                            m.enter_wall()
                            try:
                                prep = self._prepare(payload, metrics)
                            finally:
                                m.exit_wall()
                        return put_bounded(("batch", prep))

                    pending: List[HostBatch] = []
                    rows = 0
                    for b in gen:
                        if stop.is_set():
                            return
                        if isinstance(b, EncodedBatch):
                            # a device-decode batch is already a whole
                            # row group: never coalesced; flush queued
                            # host batches first to keep order
                            if pending:
                                if not emit(pending):
                                    return
                                pending, rows = [], 0
                            if not emit(b):
                                return
                            continue
                        if b.num_rows == 0:
                            continue
                        pending.append(b)
                        rows += b.num_rows
                        if rows >= self.goal_rows:
                            if not emit(pending):
                                return
                            pending, rows = [], 0
                    if pending:
                        emit(pending)
                except BaseException as e:  # surfaced on the task thread
                    err = e
                finally:
                    # a closed/failed consumer must not leak reader
                    # prefetch work: closing the generator runs the
                    # reader's finally (cancels pool futures)
                    try:
                        gen.close()
                    except Exception:
                        pass
                    put_bounded(("error", err) if err is not None
                                else ("done",))

            def producer_scoped() -> None:
                with _trace.attach(scope):
                    producer()

            t = threading.Thread(target=producer_scoped, daemon=True,
                                 name="srt-scan-prefetch")
            t.start()
            ring: List = []

            def get_item():
                try:
                    return q.get_nowait()
                except _q.Empty:
                    pass
                # the ring is empty: a scanWait span, and one more of
                # the query's open streams waiting; a cancelled query
                # must not park here (the raise runs the finally below,
                # which stops and joins the producer)
                from spark_rapids_tpu.lifecycle import checkpoint
                if scope is not None:
                    scope.scan_streams(waiting=1)
                try:
                    with _trace.span("scanWait", scope=scope, chip=chip):
                        while True:
                            try:
                                return q.get(timeout=0.05)
                            except _q.Empty:
                                checkpoint("prefetch")
                finally:
                    if scope is not None:
                        scope.scan_streams(waiting=-1)

            # open from the first pull until its last batch is handed on
            if scope is not None:
                scope.scan_streams(opened=1)
            try:
                while True:
                    item = get_item()
                    if item[0] == "done":
                        break
                    if item[0] == "error":
                        raise item[1]
                    prep = item[1]
                    entry = self._start_ahead(prep, sem, metrics, device)
                    if entry is None:
                        # OOM on the prefetched upload: SHRINK the ring
                        # — complete and yield the older in-flight
                        # batches (their raw buffers free with them),
                        # then run this batch through the synchronous
                        # spill/retry/host-fallback protocol
                        metrics.create("prefetchRingShrinks").add(1)
                        while ring:
                            yield from self._complete_ahead(
                                ring.pop(0), metrics)
                        yield from self._finish(prep, sem, metrics,
                                                device)
                        continue
                    ring.append(entry)
                    while len(ring) >= depth:
                        yield from self._complete_ahead(ring.pop(0),
                                                        metrics)
                while ring:
                    yield from self._complete_ahead(ring.pop(0), metrics)
            finally:
                if scope is not None:
                    scope.scan_streams(opened=-1)
                stop.set()
                try:
                    while True:
                        q.get_nowait()
                except _q.Empty:
                    pass
                t.join(timeout=10.0)
        return run

    def _start_ahead(self, prepared, sem, metrics, device):
        """Issue one prepared batch's raw-buffer device_put (async) —
        the upload-ahead half of the pipeline. Returns a ring entry, or
        None on OOM so the caller can shrink the ring first (the
        prefetched buffers are not yet store-registered, so completing
        the older in-flight uploads IS the spill here)."""
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu import trace as _trace
        from spark_rapids_tpu.columnar.transfer import start_upload
        num_rows, staged, src = prepared
        sem.acquire_if_necessary(metrics)
        if device is not None:
            # mesh scan: an injected/real dispatch failure on this chip
            # surfaces here; the exchange's degrade loop (or the
            # driver-level task retry) re-plans on the survivors
            R.chip_checkpoint(self.conf, device)
        inj = R.get_fault_injector(self.conf)
        try:
            with _trace.span("uploadAhead", mode=staged[0],
                             chip=(device.id if device is not None
                                   else None), rows=num_rows):
                if inj is not None:
                    inj.on_alloc("upload")
                # tpu-lint: disable=retry-coverage(deliberately unretried: OOM returns None and the caller shrinks the upload-ahead ring, docs/scan.md)
                tok = start_upload(staged, device)
            metrics.create("uploadAheadBatches").add(1)
            return (num_rows, tok, src, device)
        except R.TpuRetryOOM:
            return None
        except Exception as e:
            if R.is_oom_error(e):
                return None
            raise

    def _complete_ahead(self, entry, metrics) -> List[DeviceBatch]:
        """Run a ring entry's decode program and emit its batches; OOM
        falls back per batch exactly like the synchronous path."""
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu.columnar.transfer import finish_started
        from spark_rapids_tpu.lifecycle import checkpoint
        # per-scan-batch cancellation point: the upload loop is the
        # highest-frequency batch loop in the engine
        checkpoint("batch")
        num_rows, tok, src, device = entry
        try:
            with metrics.timed(M.COPY_TO_DEVICE_TIME,
                               chip=(device.id if device is not None
                                     else None), rows=num_rows):
                out = [R.with_retry(lambda: finish_started(tok),
                                    self.conf, metrics, splittable=True)]
        except (R.TpuSplitAndRetryOOM, R.TpuRetryOOM):
            out = self._upload_degraded(src, device, metrics)
        metrics.create(M.NUM_OUTPUT_ROWS, M.ESSENTIAL).add(num_rows)
        metrics.create(M.NUM_OUTPUT_BATCHES, M.ESSENTIAL).add(len(out))
        return out

    def _prepare(self, batches, metrics):
        from spark_rapids_tpu.columnar.transfer import prepare_upload
        if isinstance(batches, list):
            whole = (batches[0] if len(batches) == 1
                     else HostBatch.concat(batches))
        else:
            whole = batches  # an EncodedBatch stages as itself
        cap = bucket_capacity(max(1, whole.num_rows))
        # separate metric: pack overlaps the previous batch's transfer,
        # so folding it into copyToDeviceTime would double-count wall
        with metrics.timed(M.PACK_TIME):
            # the source rides along for OOM recovery: a HostBatch can
            # split in half by rows, an EncodedBatch can fall back to
            # its pyarrow host decode (docs/robustness.md). Host-memory
            # cost: at most one extra host copy per in-flight upload
            # (the 1-deep prefetch bounds this at 2 per stream), freed
            # as soon as _finish returns
            return whole.num_rows, prepare_upload(
                whole, cap, metrics=metrics), whole

    def _finish(self, prepared, sem, metrics,
                device=None) -> List[DeviceBatch]:
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu.columnar.transfer import finish_upload
        from spark_rapids_tpu.lifecycle import checkpoint
        checkpoint("batch")
        num_rows, staged, src = prepared
        sem.acquire_if_necessary(metrics)
        if device is not None:
            # mesh scan: an injected/real dispatch failure on this chip
            # surfaces here; the exchange's degrade loop (or the
            # driver-level task retry) re-plans on the survivors
            R.chip_checkpoint(self.conf, device)
        try:
            with metrics.timed(M.COPY_TO_DEVICE_TIME,
                               chip=(device.id if device is not None
                                     else None), rows=num_rows):
                # mesh scan: each stream's batches land on THEIR chip
                out = [R.with_retry(
                    lambda: finish_upload(staged, device),
                    self.conf, metrics, splittable=True)]
        except (R.TpuSplitAndRetryOOM, R.TpuRetryOOM):
            out = self._upload_degraded(src, device, metrics)
        metrics.create(M.NUM_OUTPUT_ROWS, M.ESSENTIAL).add(num_rows)
        metrics.create(M.NUM_OUTPUT_BATCHES, M.ESSENTIAL).add(len(out))
        return out

    def _upload_degraded(self, src, device, metrics) -> List[DeviceBatch]:
        """OOM recovery for one upload: an EncodedBatch falls back to
        its pyarrow per-column host decode for this batch; a HostBatch
        splits in half by rows and the halves upload independently
        (downstream consumers see the halves in order — results stay
        bit-identical to the unsplit whole)."""
        from spark_rapids_tpu import retry as R
        from spark_rapids_tpu.columnar.transfer import upload_batch
        from spark_rapids_tpu.io.device_decode import EncodedBatch

        def upload_host(hb):
            return upload_batch(hb, bucket_capacity(max(1, hb.num_rows)),
                                device)

        if isinstance(src, EncodedBatch):
            if src.host_fallback is None:
                raise  # no host decode attached (unit-test batches)
            metrics.create(M.DEVICE_DECODE_OOM_FALLBACKS,
                           M.ESSENTIAL).add(1)
            with R.suppress_injection():
                hbs = [hb for hb in src.host_fallback() if hb.num_rows]
                # the HBM pressure that forced this fallback is still
                # live: the replacement uploads get the same retry/
                # split protection (suppression keeps injected faults
                # out; real OOMs spill the store and halve the batch)
                return [d for hb in hbs
                        for d in R.with_split_retry(
                            hb, upload_host, self.conf, metrics,
                            split=R.split_host_batch)]
        return R.with_split_retry(src, upload_host, self.conf, metrics,
                                  split=R.split_host_batch,
                                  split_first=True)


    def simple_string(self):
        return "TpuRowToColumnar"


class TpuColumnarToRowExec(P.PhysicalPlan):
    """Device batches -> CPU rows (GpuColumnarToRowExec.scala:358); releases
    the semaphore once a partition's device data is exhausted."""

    def __init__(self, child: TpuExec, conf: TpuConf):
        self.children = [child]
        self.conf = conf
        self.metrics = M.MetricRegistry(str(conf.get(METRICS_LEVEL)),
                                        owner=type(self).__name__)

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def partitions(self) -> List[P.PartitionThunk]:
        sem = get_semaphore(self.conf)
        metrics = self.metrics

        def make(thunk: DevicePartitionThunk) -> P.PartitionThunk:
            def run() -> Iterator[HostBatch]:
                from spark_rapids_tpu.columnar.device import finish_to_host
                from spark_rapids_tpu.lifecycle import checkpoint
                try:
                    # 1-ahead: batch k+1's pack program + async D2H
                    # copies are in flight while batch k converts on
                    # the host — the flat fetch latency overlaps
                    prev = None
                    for b in thunk():
                        checkpoint("batch")
                        tok = b.start_to_host()
                        if prev is not None:
                            with metrics.timed(M.COPY_FROM_DEVICE_TIME):
                                h = finish_to_host(prev)
                            metrics.create(M.NUM_OUTPUT_ROWS,
                                           M.ESSENTIAL).add(h.num_rows)
                            yield h
                        prev = tok
                    if prev is not None:
                        with metrics.timed(M.COPY_FROM_DEVICE_TIME):
                            h = finish_to_host(prev)
                        metrics.create(M.NUM_OUTPUT_ROWS,
                                       M.ESSENTIAL).add(h.num_rows)
                        yield h
                finally:
                    sem.release_if_necessary()
            return run
        return [make(t) for t in self.child.device_partitions()]

    def simple_string(self):
        return "TpuColumnarToRow"


class TpuCoalesceBatchesExec(TpuExec):
    """Concats small device batches up to the goal (GpuCoalesceBatches.scala
    :519; goal algebra at :143-177). ``require_single_batch`` is the
    RequireSingleBatch goal used by ops that need the whole partition."""

    def __init__(self, child: TpuExec, conf: TpuConf,
                 goal_rows: Optional[int] = None,
                 require_single_batch: bool = False):
        super().__init__(conf)
        self.children = [child]
        self.goal_rows = goal_rows or conf.batch_size_rows
        self.require_single_batch = require_single_batch

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def device_partitions(self) -> List[DevicePartitionThunk]:
        metrics = self.metrics

        def make(thunk: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                pending: List[DeviceBatch] = []
                rows = 0
                for b in thunk():
                    n = b.row_count()
                    if n == 0:
                        continue
                    pending.append(b)
                    rows += n
                    if not self.require_single_batch and \
                            rows >= self.goal_rows:
                        yield self._emit(pending, metrics)
                        pending, rows = [], 0
                if pending:
                    yield self._emit(pending, metrics)
            return run
        return [make(t) for t in self.child.device_partitions()]

    def _emit(self, pending: List[DeviceBatch], metrics) -> DeviceBatch:
        with metrics.timed(M.CONCAT_TIME):
            out = pending[0] if len(pending) == 1 else concat_device(pending)
        metrics.create(M.NUM_OUTPUT_BATCHES, M.ESSENTIAL).add(1)
        metrics.create(M.NUM_OUTPUT_ROWS, M.ESSENTIAL).add(out.row_count())
        return out

    def simple_string(self):
        goal = ("RequireSingleBatch" if self.require_single_batch
                else f"TargetSize({self.goal_rows})")
        return f"TpuCoalesceBatches {goal}"
