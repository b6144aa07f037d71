"""Device memory store + spill tiers (RapidsBufferCatalog.scala:40,
SpillableColumnarBatch.scala:29, DeviceMemoryEventHandler.scala:43 twins).

A byte-budget pool over HBM-resident batches. Operators that hold batches
across yields (exchange materialization, aggregation staging) register
them as ``SpillableBatch`` handles; when the pool exceeds its budget the
least-recently-used handles are demoted device -> host (numpy) -> disk
(the columnar/serde.py format under spark.rapids.memory.spillDirectory,
optionally compressed per spark.rapids.shuffle.compression.codec), and
transparently
re-promoted on access — the reference's 3-tier store collapsed onto the
JAX transfer primitives (to_host/from_host ARE the spill copies).

Lifecycle: handles release deterministically via ``close()``; a dropped
handle (operator GC'd with its plan) auto-releases through a weakref
finalizer, so the process-wide store never pins batches whose owner died
(the reference ties this to Spark's TaskCompletionListener).

Note: a spill round-trip COMPACTS the batch (to_host gathers active rows,
from_host rebuilds prefix-active at a possibly smaller capacity bucket) —
active row ORDER is preserved, but per-slot layouts are not. Callers that
pair a batch with precomputed per-slot arrays must check
``ever_spilled``/capacity and remap (see the range exchange).

The pool cannot intercept XLA's own allocations (scratch inside a fused
program); like the reference's RMM pool it bounds what the framework
retains between kernels, which is where multi-batch operators hold the
bytes that matter.
"""

from __future__ import annotations

import logging
import os
import threading
import uuid
import weakref
from collections import OrderedDict
from typing import Dict, Optional

from spark_rapids_tpu import trace as _trace
from spark_rapids_tpu.telemetry import triggers as _telemetry
from spark_rapids_tpu.columnar.device import DeviceBatch
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu.conf import (DEVICE_MEMORY_LIMIT,
                                   HOST_SPILL_STORAGE_SIZE, MEMORY_DEBUG,
                                   SPILL_DIR, TpuConf)

# spark.rapids.memory.tpu.debug: log every store transition
# (register/spill/promote/release) the way the reference's
# MEMORY_DEBUG logs RMM allocation events (RapidsConf.scala:307)
_log = logging.getLogger("spark_rapids_tpu.memory")

# host emulation only (XLA:CPU reports no memory stats): an accelerator
# that reports none is an error, never this default
_CPU_EMULATION_BUDGET = 8 << 30

TIER_DEVICE = "device"
TIER_HOST = "host"
TIER_DISK = "disk"

# owner label for registrations that did not attribute themselves (the
# profile's accounting still balances: unattributed bytes are a bucket,
# not a leak)
UNATTRIBUTED = "(unattributed)"

# ---------------------------------------------------------------------------
# Tenant attribution (docs/serving.md): the serving layer executes each
# query under a tenant, and every SpillableBatch registered during that
# query bills to the tenant's HBM ledger. Attribution rides on the
# registering exec's METRIC REGISTRY (``stamp_plan_tenant`` tags every
# registry of the executing plan before collect), because the registry
# object travels with the exec's closures into whatever pool thread
# performs the registration — a thread-local could not follow the work
# across the task/reader/pack pools. A thread-local scope remains as
# the fallback for registrations without a registry.
# ---------------------------------------------------------------------------

_TENANT_TLS = threading.local()


def current_tenant() -> Optional[str]:
    """The calling thread's fallback tenant (None = untenanted; only
    the serving layer sets this, for registrations without metrics)."""
    return getattr(_TENANT_TLS, "name", None)


import contextlib  # noqa: E402  (scope helper belongs with the TLS)


@contextlib.contextmanager
def tenant_scope(name: Optional[str]):
    """Thread-local fallback tenant for registrations that carry no
    metric registry (no-op for None)."""
    if name is None:
        yield
        return
    prev = getattr(_TENANT_TLS, "name", None)
    _TENANT_TLS.name = name
    try:
        yield
    finally:
        _TENANT_TLS.name = prev


def stamp_plan_tenant(physical, tenant: Optional[str]) -> None:
    """Tag every metric registry in ``physical`` (fused constituents
    included) with the owning tenant, so store registrations made from
    ANY pool thread bill to the right per-tenant ledger. Called by
    ``execute_plan`` before the collect when the session carries a
    tenant id (docs/serving.md)."""
    if tenant is None:
        return

    from spark_rapids_tpu.metrics import plan_registries
    for reg in plan_registries(physical):
        reg._tenant = tenant


class _State:
    """Per-handle storage owned by the store (survives handle GC so the
    finalizer can release whatever tier the data sits in)."""

    __slots__ = ("tier", "device", "host", "disk_path", "device_bytes",
                 "host_bytes", "closed", "rows", "ever_spilled", "owner",
                 "metrics_ref", "tenant", "cache_entry")

    def __init__(self, batch: DeviceBatch, owner: str = UNATTRIBUTED,
                 metrics=None, cache_entry: bool = False):
        self.tier = TIER_DEVICE
        self.device: Optional[DeviceBatch] = batch
        self.host: Optional[HostBatch] = None
        self.disk_path: Optional[str] = None
        self.device_bytes = batch.sizeof()
        self.host_bytes = 0
        self.closed = False
        # lazy: forcing a D2H count here is a device sync per
        # registration; producers that know their counts (splits)
        # attach them, others resolve on first use
        self.rows: Optional[int] = batch._num_rows
        self.ever_spilled = False
        # owner-attributed HBM accounting (docs/observability.md): the
        # exec that registered the batch; the registry is held weakly so
        # accounting never pins a released plan's metrics
        self.owner = owner
        self.metrics_ref = (weakref.ref(metrics)
                            if metrics is not None else None)
        # tenant attribution: the registry's stamp (stamp_plan_tenant)
        # wins because it follows the work across pool threads; the
        # thread-local scope is the metric-less fallback
        self.tenant: Optional[str] = (
            getattr(metrics, "_tenant", None) if metrics is not None
            else None) or current_tenant()
        # cache-tier entry (docs/caching.md): reconstructible data a
        # serve-tier cache registered opportunistically. Under pool
        # pressure these DROP (release outright, never demote to
        # host/disk) and drop FIRST — before any live query's batch
        # spills — because the cache can always rebuild from source
        self.cache_entry = cache_entry


class SpillableBatch:
    """Handle over a batch the store may demote (SpillableColumnarBatch)."""

    def __init__(self, store: "DeviceStore", state: _State,
                 handle_id: int):
        self._store = store
        self._state = state
        self._id = handle_id
        weakref.finalize(self, store._release_id, handle_id)

    def get(self) -> DeviceBatch:
        """The device batch, re-promoted through the tiers if spilled."""
        return self._store._access(self._id)

    def row_count(self, site: str = "rowCount") -> int:
        """Row count; cached when the producer attached one, resolved
        (one D2H sync booked to ``deviceSync site=``, or free from the
        host tier) otherwise."""
        st = self._state
        if st.rows is None:
            if st.tier == TIER_DEVICE:
                st.rows = st.device.row_count(site)
            elif st.tier == TIER_HOST:
                st.rows = st.host.num_rows
            else:
                st.rows = self._store._access(self._id).row_count(site)
        return st.rows

    @property
    def rows(self) -> int:
        return self.row_count()

    @property
    def capacity_hint(self) -> Optional[int]:
        """Device capacity WITHOUT promoting a spilled batch; None when
        the data is off-device (callers treat that conservatively)."""
        st = self._state
        if st.tier == TIER_DEVICE and st.device is not None:
            return st.device.capacity
        return None

    @property
    def ever_spilled(self) -> bool:
        """True once the batch has been demoted at least once — its slot
        layout/capacity may differ from the originally registered batch."""
        return self._state.ever_spilled

    def sizeof(self) -> int:
        return self._state.device_bytes

    @property
    def closed(self) -> bool:
        return self._state.closed

    def close(self) -> None:
        self._store._release_id(self._id)

    def __repr__(self) -> str:
        return f"SpillableBatch(id={self._id}, tier={self._state.tier})"


class DeviceStore:
    """The catalog: tracks handles, enforces the HBM budget via LRU
    spill, and accounts host-tier bytes against the host budget."""

    def __init__(self, device_budget: int, host_budget: int,
                 spill_dir: str, debug: bool = False,
                 codec: str = "none"):
        self.device_budget = device_budget
        self.host_budget = host_budget
        self.spill_dir = spill_dir
        self.debug = debug
        self.codec = codec
        self._lock = threading.RLock()
        self._states: "OrderedDict[int, _State]" = OrderedDict()
        self._next_id = 0
        self.device_bytes = 0
        self.host_bytes = 0
        # observability (surfaced by bench + tests)
        self.spill_count = 0
        self.spilled_device_bytes = 0
        self.disk_spill_count = 0
        self.peak_device_bytes = 0
        # owner-attributed accounting: live/peak HBM bytes per
        # registering operator. Invariant (asserted by the profile
        # tests): sum(owner_live.values()) == device_bytes at all
        # times, so the per-op view always reconciles with the pool
        self.owner_live: Dict[str, int] = {}
        self.owner_peak: Dict[str, int] = {}
        # tenant-attributed ledger (docs/serving.md): live/peak HBM and
        # spilled bytes per serving tenant. Invariant mirrored from the
        # owner ledger: sum(tenant_live) == device bytes registered
        # under ANY tenant (untenanted bytes are outside the ledger).
        self.tenant_live: Dict[str, int] = {}
        self.tenant_peak: Dict[str, int] = {}
        self.tenant_spill: Dict[str, int] = {}
        # fair-share HBM arbitration: a tenant whose live bytes exceed
        # factor * (budget / live tenants) is "over share" — its
        # handles spill FIRST when the pool needs room, so the spill
        # bills to the offending tenant, not whichever victim happened
        # to be least-recently used (spark.rapids.sql.serve
        # .fairShareFactor; set in place by get_device_store)
        self.fair_share_factor = 1.5
        # disk-tier hygiene: every spill file carries this store's
        # prefix so close() can sweep stragglers without touching other
        # stores sharing the directory; diskFilesLive tracks files the
        # store believes exist (leak detector for tests/stats)
        self._file_prefix = f"spill-{uuid.uuid4().hex[:8]}"
        self.disk_files_live = 0
        self._closed = False
        # cache-tier accounting (docs/caching.md): entries the pool
        # dropped under pressure (released, not spilled)
        self.cache_drop_count = 0
        self.cache_dropped_bytes = 0

    # -- owner accounting + occupancy timeline -----------------------------

    def _owner_delta(self, st: _State, delta: int) -> None:
        """Move ``delta`` HBM bytes on the owner's ledger (call under
        the lock). Peaks are monotone. The per-INSTANCE peak is tracked
        on the registering exec's own metric registry (a plan with two
        exchanges must not report each other's bytes as its
        peakDeviceMemory), while the store ledger aggregates by owner
        class name."""
        live = self.owner_live.get(st.owner, 0) + delta
        self.owner_live[st.owner] = live
        if delta > 0 and live > self.owner_peak.get(st.owner, 0):
            self.owner_peak[st.owner] = live
        if st.tenant is not None:
            tlive = self.tenant_live.get(st.tenant, 0) + delta
            self.tenant_live[st.tenant] = tlive
            if delta > 0 and tlive > self.tenant_peak.get(st.tenant, 0):
                self.tenant_peak[st.tenant] = tlive
        m = st.metrics_ref() if st.metrics_ref is not None else None
        if m is not None:
            # instance-live rides on the registry object itself; all
            # mutations happen under this store lock, so the
            # read-modify-write is safe
            inst = getattr(m, "_store_live_bytes", 0) + delta
            m._store_live_bytes = inst
            if delta > 0:
                from spark_rapids_tpu import metrics as M
                m.create(M.PEAK_DEVICE_MEMORY, M.ESSENTIAL).set_max(inst)

    def _sample_counters(self) -> None:
        """Pool occupancy sample into the active trace (Chrome "C"
        counter events -> the Perfetto HBM timeline) and the telemetry
        HBM-watermark trigger. One None/bool check each when off; the
        trigger hook only ENQUEUES (no IO under this store's lock)."""
        _telemetry.on_store_sample(self.device_bytes,
                                   self.device_budget)
        qt = _trace._ACTIVE
        if qt is not None:
            qt.count("deviceStoreBytes", self.device_bytes)
            qt.count("hostStoreBytes", self.host_bytes)

    # -- registration ------------------------------------------------------

    def register(self, batch: DeviceBatch, owner: str = UNATTRIBUTED,
                 metrics=None, cache_entry: bool = False) -> SpillableBatch:
        """Track ``batch`` as spillable. ``owner`` names the creating
        operator for the per-op HBM ledger (execs call this through
        ``TpuExec.register_spillable``, which threads their class name
        and metric registry). ``cache_entry`` marks reconstructible
        cache data that drops FIRST under pool pressure instead of
        spilling (docs/caching.md)."""
        with self._lock:
            st = _State(batch, owner=owner, metrics=metrics,
                        cache_entry=cache_entry)
            hid = self._next_id
            self._next_id += 1
            self._states[hid] = st
            self.device_bytes += st.device_bytes
            self.peak_device_bytes = max(self.peak_device_bytes,
                                         self.device_bytes)
            self._owner_delta(st, st.device_bytes)
            self._sample_counters()
            self._enforce(exclude=hid)
            return SpillableBatch(self, st, hid)

    # -- internal tier movement --------------------------------------------

    def _access(self, hid: int) -> DeviceBatch:
        with self._lock:
            st = self._states.get(hid)
            assert st is not None and not st.closed, \
                "SpillableBatch used after close"
            if st.tier == TIER_DISK:
                from spark_rapids_tpu.columnar import serde
                with _trace.span("promoteFromDisk"), \
                        open(st.disk_path, "rb") as f:
                    st.host = serde.deserialize_batch(f.read())
                os.unlink(st.disk_path)
                self.disk_files_live -= 1
                st.disk_path = None
                st.tier = TIER_HOST
                st.host_bytes = _host_sizeof(st.host)
                self.host_bytes += st.host_bytes
            if st.tier == TIER_HOST:
                if self.debug:
                    _log.info("promote host->device: %d bytes",
                              st.host_bytes)
                with _trace.span("promoteToDevice", bytes=st.host_bytes):
                    st.device = DeviceBatch.from_host(st.host)
                self.host_bytes -= st.host_bytes
                st.host, st.host_bytes = None, 0
                st.tier = TIER_DEVICE
                st.device_bytes = st.device.sizeof()
                self.device_bytes += st.device_bytes
                self.peak_device_bytes = max(self.peak_device_bytes,
                                             self.device_bytes)
                self._owner_delta(st, st.device_bytes)
                self._sample_counters()
            self._states.move_to_end(hid)
            self._enforce(exclude=hid)
            return st.device

    def _over_share_tenants(self) -> Dict[str, int]:
        """Tenants whose live HBM exceeds ``fair_share_factor`` times
        the equal share of the budget (budget / live tenants), most
        over-share first. Call under the lock."""
        live = {t: v for t, v in self.tenant_live.items() if v > 0}
        if len(live) < 2:
            # a lone tenant cannot crowd anyone; plain LRU applies
            return {}
        share = self.device_budget / len(live)
        limit = self.fair_share_factor * share
        over = {t: v for t, v in live.items() if v > limit}
        return dict(sorted(over.items(), key=lambda kv: -kv[1]))

    def _device_spill_order(self, exclude: int) -> list:
        """Handle ids in the order the pool should demote them:
        cache-tier entries FIRST (reconstructible data never outranks a
        live query's batches, docs/caching.md), then over-share
        tenants' handles (most-over tenant first, LRU within), then
        plain LRU — the fair-share arbitration that bills spill
        pressure to the tenant causing it (docs/serving.md)."""
        over = self._over_share_tenants()
        if not over:
            return sorted(
                (h for h in self._states if h != exclude),
                key=lambda h: 0 if self._states[h].cache_entry else 1)
        rank = {t: i for i, t in enumerate(over)}
        ordered = sorted(
            (h for h in self._states if h != exclude),
            key=lambda h: (0 if self._states[h].cache_entry else 1,
                           rank.get(self._states[h].tenant, len(rank))))
        return ordered

    def _enforce(self, exclude: int) -> None:
        if self.device_bytes > self.device_budget:
            for hid in self._device_spill_order(exclude):
                if self.device_bytes <= self.device_budget:
                    break
                st = self._states[hid]
                if st.tier != TIER_DEVICE:
                    continue
                if st.cache_entry:
                    self._drop_cache_entry(hid, st)
                else:
                    self._spill_to_host(st)
        if self.host_bytes > self.host_budget:
            for hid in list(self._states):
                if self.host_bytes <= self.host_budget:
                    break
                st = self._states[hid]
                if st.tier == TIER_HOST:
                    self._spill_to_disk(st)

    def _spill_to_host(self, st: _State) -> None:
        if self.debug:
            _log.info("spill device->host: %d bytes (pool %d/%d)",
                      st.device_bytes, self.device_bytes,
                      self.device_budget)
        with _trace.span("spillToHost", bytes=st.device_bytes):
            st.host = st.device.to_host()
        st.rows = st.host.num_rows
        st.device = None
        self.device_bytes -= st.device_bytes
        st.host_bytes = _host_sizeof(st.host)
        self.host_bytes += st.host_bytes
        st.tier = TIER_HOST
        st.ever_spilled = True
        self.spill_count += 1
        self.spilled_device_bytes += st.device_bytes
        if st.tenant is not None:
            # the demotion bills the OWNING tenant's spill ledger (the
            # fair-share ordering below makes the owner usually the
            # over-share offender, never an arbitrary victim)
            self.tenant_spill[st.tenant] = (
                self.tenant_spill.get(st.tenant, 0) + st.device_bytes)
        self._owner_delta(st, -st.device_bytes)
        # the demotion is billed to the OWNING operator, not whichever
        # task happened to trip the budget (per-op spillBytes)
        m = st.metrics_ref() if st.metrics_ref is not None else None
        if m is not None:
            from spark_rapids_tpu import metrics as M
            m.create(M.SPILL_BYTES, M.ESSENTIAL).add(st.device_bytes)
        self._sample_counters()

    def _drop_cache_entry(self, hid: int, st: _State) -> None:
        """Release a cache-tier entry outright under pool pressure
        (docs/caching.md): the data is reconstructible from source, so
        demoting it to host/disk would spend spill bandwidth preserving
        bytes nobody is owed. The owning cache observes the closed
        handle on its next lookup and forgets the entry."""
        dropped = st.device_bytes
        with _trace.span("cacheEntryDrop", bytes=dropped,
                         owner=st.owner):
            self._release_id(hid)
        self.cache_drop_count += 1
        self.cache_dropped_bytes += dropped

    def _spill_to_disk(self, st: _State) -> None:
        if self.debug:
            _log.info("spill host->disk: %d bytes (host %d/%d)",
                      st.host_bytes, self.host_bytes, self.host_budget)
        os.makedirs(self.spill_dir, exist_ok=True)
        path = os.path.join(
            self.spill_dir,
            f"{self._file_prefix}-{uuid.uuid4().hex[:16]}.bin")
        from spark_rapids_tpu.columnar import serde
        with _trace.span("spillToDisk", bytes=st.host_bytes), \
                open(path, "wb") as f:
            f.write(serde.serialize_batch(st.host, self.codec))
        self.host_bytes -= st.host_bytes
        st.host, st.host_bytes = None, 0
        st.disk_path = path
        st.tier = TIER_DISK
        self.disk_spill_count += 1
        self.disk_files_live += 1
        self._sample_counters()

    def _release_id(self, hid: int) -> None:
        with self._lock:
            st = self._states.pop(hid, None)
            if st is None or st.closed:
                return
            st.closed = True
            if st.tier == TIER_DEVICE:
                self.device_bytes -= st.device_bytes
                self._owner_delta(st, -st.device_bytes)
                self._sample_counters()
            elif st.tier == TIER_HOST:
                self.host_bytes -= st.host_bytes
                self._sample_counters()
            elif st.disk_path:
                try:
                    os.unlink(st.disk_path)
                    self.disk_files_live -= 1
                except OSError:
                    pass
                st.disk_path = None
            st.device = None
            st.host = None

    # -- OOM-retry hook + lifecycle ----------------------------------------

    def release_for_registries(self, reg_ids) -> int:
        """Close every live handle registered under one of the given
        metric-registry ids (the cancellation path: a cancelled query's
        plan is dead, so its HBM frees NOW instead of at GC — the
        weakref finalizers remain the backstop). Returns the number of
        handles released."""
        with self._lock:
            victims = []
            for hid, st in self._states.items():
                if st.closed or st.metrics_ref is None:
                    continue
                m = st.metrics_ref()
                if m is not None and id(m) in reg_ids:
                    victims.append(hid)
            for hid in victims:
                self._release_id(hid)
        return len(victims)

    def spill_device_down(self, target_bytes: int = 0) -> int:
        """Demote device-tier handles (LRU first) until at most
        ``target_bytes`` remain in HBM — the retry framework's
        spill-the-store-and-retry step
        (DeviceMemoryEventHandler.onAllocFailure role). Returns the
        HBM bytes freed."""
        freed = 0
        with self._lock:
            # same fair-share ordering as budget enforcement: a retry
            # spill under multi-tenant pressure demotes the over-share
            # tenant's working set first (docs/serving.md)
            for hid in self._device_spill_order(exclude=-1):
                if self.device_bytes <= target_bytes:
                    break
                st = self._states[hid]
                if st.tier == TIER_DEVICE and not st.closed:
                    freed += st.device_bytes
                    if st.cache_entry:
                        self._drop_cache_entry(hid, st)
                    else:
                        self._spill_to_host(st)
        return freed

    def close(self) -> None:
        """Release every handle and sweep this store's disk-tier files
        (spill files are scratch — nothing must survive the store;
        registered atexit for the process singleton so interpreter exit
        never leaks /tmp spill files)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            for hid in list(self._states):
                self._release_id(hid)
            # stragglers (crash paths, files orphaned mid-transition)
            try:
                import glob
                for path in glob.glob(os.path.join(
                        self.spill_dir, f"{self._file_prefix}-*.bin")):
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
            except Exception:
                pass
            self.disk_files_live = 0

    def stats(self) -> Dict[str, int]:
        return {
            "deviceBytes": self.device_bytes,
            "peakDeviceBytes": self.peak_device_bytes,
            "hostBytes": self.host_bytes,
            "spillCount": self.spill_count,
            "spilledDeviceBytes": self.spilled_device_bytes,
            "diskSpillCount": self.disk_spill_count,
            "diskFilesLive": self.disk_files_live,
            "cacheDropCount": self.cache_drop_count,
            "cacheDroppedBytes": self.cache_dropped_bytes,
        }

    def owner_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-operator HBM ledger: live and peak bytes for every owner
        that registered batches (the profile's memory section and the
        event log's memoryByOperator field)."""
        with self._lock:
            owners = set(self.owner_live) | set(self.owner_peak)
            return {o: {"liveBytes": self.owner_live.get(o, 0),
                        "peakBytes": self.owner_peak.get(o, 0)}
                    for o in sorted(owners)}

    def over_share_tenants(self) -> Dict[str, int]:
        """Public snapshot of the fair-share offenders (live bytes per
        over-share tenant, most over first) — the admission
        controller's throttle signal (docs/serving.md)."""
        with self._lock:
            return self._over_share_tenants()

    def tenant_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-tenant HBM ledger: live/peak/spilled bytes for every
        serving tenant that registered batches (the admission
        controller's fair-share signal and the server's per-tenant
        stats surface, docs/serving.md)."""
        with self._lock:
            tenants = (set(self.tenant_live) | set(self.tenant_peak)
                       | set(self.tenant_spill))
            return {t: {"liveBytes": self.tenant_live.get(t, 0),
                        "peakBytes": self.tenant_peak.get(t, 0),
                        "spillBytes": self.tenant_spill.get(t, 0)}
                    for t in sorted(tenants)}

    def reset_peaks(self) -> None:
        """Re-base the pool and per-owner high-watermarks at the current
        live occupancy. Bench detail legs call this (with
        metrics.begin_epoch) so each leg's profile reports its OWN
        peaks, not a high-watermark inherited from an earlier leg."""
        with self._lock:
            self.peak_device_bytes = self.device_bytes
            self.owner_live = {o: v for o, v in self.owner_live.items()
                               if v}
            self.owner_peak = dict(self.owner_live)
            self.tenant_live = {t: v for t, v
                                in self.tenant_live.items() if v}
            self.tenant_peak = dict(self.tenant_live)
            self.tenant_spill = {}


def _host_sizeof(b: HostBatch) -> int:
    total = 0
    for c in b.columns:
        if c.data.dtype == object:
            total += sum(len(str(v)) for v in c.data) + len(c.data)
        else:
            total += c.data.nbytes
        total += c.validity.nbytes
    return total


def _default_budget() -> int:
    import jax

    from spark_rapids_tpu import device_manager
    limit = device_manager.device_memory_bytes()
    if limit:
        return int(limit * 0.8)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{jax.default_backend()} device reports no HBM bytes_limit; "
            "set spark.rapids.memory.tpu.poolSize explicitly")
    return _CPU_EMULATION_BUDGET


_STORE: Optional[DeviceStore] = None
_STORE_KEY: Optional[tuple] = None
_STORE_LOCK = threading.Lock()
# every store this process built (the keyed rebuild replaces _STORE but
# older stores may still back live handles): atexit closes them ALL so
# no disk-tier spill file survives the interpreter
_ALL_STORES: list = []


def _close_stores_at_exit() -> None:
    for s in _ALL_STORES:
        try:
            s.close()
        except Exception:
            pass


import atexit  # noqa: E402  (registration belongs with the registry)

atexit.register(_close_stores_at_exit)


def get_device_store(conf: TpuConf) -> DeviceStore:
    """Process-wide store (GpuDeviceManager owns one RMM pool per
    executor); rebuilt when the configured budget changes (tests)."""
    global _STORE, _STORE_KEY
    from spark_rapids_tpu.conf import SHUFFLE_COMPRESSION_CODEC
    budget = int(conf.get(DEVICE_MEMORY_LIMIT)) or _default_budget()
    host_budget = int(conf.get(HOST_SPILL_STORAGE_SIZE))
    spill_dir = str(conf.get(SPILL_DIR))
    codec = str(conf.get(SHUFFLE_COMPRESSION_CODEC)).lower()
    from spark_rapids_tpu.columnar import serde
    if codec not in serde._CODECS:
        raise ValueError(
            f"spark.rapids.shuffle.compression.codec={codec!r}: "
            f"supported codecs are {sorted(serde._CODECS)}")
    key = (budget, host_budget, spill_dir, codec)
    with _STORE_LOCK:
        if _STORE is None or _STORE_KEY != key:
            _STORE = DeviceStore(budget, host_budget, spill_dir,
                                 codec=codec)
            _STORE_KEY = key
            _ALL_STORES.append(_STORE)
        # toggled in place so a flip never replaces the live store (two
        # stores would account one HBM independently): debug logging and
        # the serving fair-share factor are both policy, not identity
        _STORE.debug = bool(conf.get(MEMORY_DEBUG))
        from spark_rapids_tpu.conf import SERVE_FAIR_SHARE_FACTOR
        _STORE.fair_share_factor = float(conf.get(SERVE_FAIR_SHARE_FACTOR))
        return _STORE


def reset_store_peaks() -> None:
    """Re-base the process store's high-watermarks (no-op without a
    store); the bench leg / test hook pairing metrics.begin_epoch."""
    if _STORE is not None:
        _STORE.reset_peaks()


def release_plan_handles(physical) -> int:
    """Deterministically close every store handle registered by the
    given physical plan's metric registries (fused constituents
    included). The cancellation path calls this so a cancelled /
    timed-out query's HBM ledger and spillable handles free at the
    cancel, not at plan GC (docs/serving.md 'Query lifecycle')."""
    store = _STORE
    if store is None or physical is None:
        return 0
    regs = set()

    def walk(p) -> None:
        m = getattr(p, "metrics", None)
        if m is not None:
            regs.add(id(m))
        for op in getattr(p, "fused_ops", []) or []:
            fm = getattr(op, "metrics", None)
            if fm is not None:
                regs.add(id(fm))
        for c in getattr(p, "children", []):
            walk(c)

    walk(physical)
    return store.release_for_registries(regs)


def store_owner_stats() -> Dict[str, Dict[str, int]]:
    """The process store's per-operator HBM ledger ({} without a
    store) — the profile writer's and event log's data source."""
    return _STORE.owner_stats() if _STORE is not None else {}


def store_tenant_stats() -> Dict[str, Dict[str, int]]:
    """The process store's per-tenant HBM ledger ({} without a store)
    — the admission controller's and server stats' data source."""
    return _STORE.tenant_stats() if _STORE is not None else {}


# ---------------------------------------------------------------------------
# Planned out-of-core budget oracle (docs/out_of_core.md). Operators
# query it BEFORE materializing a working set: a join build side or
# aggregation estimated over its budget share partitions/spills up
# front (sized pow2 partition counts) instead of discovering the
# overflow inside the OOM-retry protocol. The reactive retry ladder
# stays as the backstop for estimates that lie.
# ---------------------------------------------------------------------------

class BudgetOracle:
    """Per-query view over the planned out-of-core budget confs plus
    the live store occupancy. Cheap to construct (a handful of conf
    reads); operators build one per materialization decision so conf
    changes and injected budget faults always apply."""

    def __init__(self, conf: TpuConf):
        from spark_rapids_tpu.conf import (DEVICE_BUDGET_BYTES,
                                           OUT_OF_CORE_BUDGET_SHARE,
                                           OUT_OF_CORE_ENABLED,
                                           OUT_OF_CORE_MAX_PARTITIONS,
                                           OUT_OF_CORE_MAX_RECURSION)
        self.conf = conf
        self.enabled = bool(conf.get(OUT_OF_CORE_ENABLED))
        self.budget = (int(conf.get(DEVICE_BUDGET_BYTES))
                       or _default_budget())
        self.share_fraction = float(conf.get(OUT_OF_CORE_BUDGET_SHARE))
        self.max_partitions = max(
            2, int(conf.get(OUT_OF_CORE_MAX_PARTITIONS)))
        self.max_recursion = max(
            0, int(conf.get(OUT_OF_CORE_MAX_RECURSION)))

    def headroom(self) -> int:
        """Bytes of budget left over the store's live occupancy. A
        firing ``site:budget:N`` schedule HALVES the report (synthetic
        memory pressure for the escalation tests — the fault is a lie,
        never an error, so the planned path absorbs it by planning
        more partitions, not by retrying)."""
        live = _STORE.device_bytes if _STORE is not None else 0
        room = max(0, self.budget - live)
        from spark_rapids_tpu import retry as R
        inj = R.get_fault_injector(self.conf)
        if inj is not None and inj.on_budget_query():
            room //= 2
        return room

    def operator_share(self) -> int:
        """Working-set bytes ONE operator may plan to hold resident at
        once (several operators hold batches concurrently under
        taskParallelism, so nobody plans for the whole headroom)."""
        return max(1, int(self.headroom() * self.share_fraction))

    def plan_partitions(self, estimate_bytes: int, metrics=None,
                        share: Optional[int] = None) -> int:
        """Spill-backed partition count for a working set of
        ``estimate_bytes``: 1 when it fits the operator share (the
        in-memory path), else estimate/share pow2-rounded UP and
        clamped to outOfCore.maxPartitions. Records the
        plannedPartitions / budgetPressurePeak metric family on
        ``metrics`` when given."""
        if share is None:
            share = self.operator_share()
        n = 1
        if self.enabled and estimate_bytes > share:
            n = 2
            while n * share < estimate_bytes and n < self.max_partitions:
                n <<= 1
        if metrics is not None:
            from spark_rapids_tpu import metrics as M
            metrics.create(M.BUDGET_PRESSURE_PEAK, M.ESSENTIAL).set_max(
                int(estimate_bytes * 100 // max(1, share)))
            if n > 1:
                metrics.create(M.PLANNED_PARTITIONS,
                               M.ESSENTIAL).add(n)
        return n


def get_budget_oracle(conf: TpuConf) -> BudgetOracle:
    """A fresh oracle view for one materialization decision."""
    return BudgetOracle(conf)
