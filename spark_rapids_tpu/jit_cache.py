"""Bounded LRU caches for compiled device programs.

Every structurally-keyed jit cache in the package (project/filter
programs, aggregation programs, fetch-pack/concat shape programs, the
window/exchange/sort kernels) goes through a ``JitCache`` instead of a
bare module dict: long-running sessions that plan many distinct query
shapes would otherwise grow the compile caches without limit (each
entry pins an XLA executable). Eviction drops the *oldest-used* entry;
a re-planned query simply recompiles (and, on backends with the
persistent XLA cache, reloads the serialized executable cheaply).

Hit/miss counters are kept per cache and surfaced two ways: execs that
own a cache mirror the counts into their metric registries
(``compileCacheHits`` / ``compileCacheMisses``), and ``cache_stats()``
returns the whole registry for the bench's JSON detail.
"""

from __future__ import annotations

import os
import re
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

# Large enough that no single query ever thrashes (q1 compiles ~10
# distinct programs per operator family), small enough that thousands
# of distinct plan shapes cannot pin unbounded executables.
DEFAULT_CAPACITY = int(os.environ.get(
    "SPARK_RAPIDS_TPU_JIT_CACHE_CAPACITY", "256"))

PROGRAM_NAME_MAX = 48
_NAME_OK = re.compile(r"[A-Za-z0-9_]+\Z")


def program_name(family: str, *tags) -> str:
    """``srt_<family>[_<tag>...]``: the name a device program carries
    in XLA (module ``jit_srt_...``), in profiler traces and in compile
    spans. At most 48 characters of ``[A-Za-z0-9_]``. Every tag must be
    a function of the program's STRUCTURAL key and nothing else — no
    literal value, capacity, hash(), counter or address: JAX's
    persistent compilation cache keys on the module name, so a name
    that varies from process to process misses there every time."""
    parts = [family] + [str(t) for t in tags if t not in (None, "")]
    name = "srt_" + re.sub(r"[^A-Za-z0-9]+", "_", "_".join(parts))
    return name[:PROGRAM_NAME_MAX].rstrip("_")


def named_jit(name: str, fn: Callable, **jit_kwargs) -> Callable:
    """``jax.jit(fn, **jit_kwargs)`` under a stable program name: sets
    ``fn.__name__``/``__qualname__`` (what XLA names the module after)
    and returns the jitted function itself — no wrapper around the
    call, so a dispatch costs what ``jax.jit`` costs. The one way the
    package builds a device program (the ``jit-direct`` lint rule
    holds every other ``jax.jit`` to a reasoned suppression). The name
    is readable back as ``program_of(jitted)``."""
    if len(name) > PROGRAM_NAME_MAX or not name.startswith("srt_") \
            or _NAME_OK.match(name) is None:
        raise ValueError(
            f"program name {name!r}: want srt_<family>[_<tag>], at most "
            f"{PROGRAM_NAME_MAX} characters of [A-Za-z0-9_]")
    import jax
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


def program_of(jitted) -> Optional[str]:
    """The ``named_jit`` name of a jitted callable (None for anything
    else): what dispatch spans and ``firstDispatch`` record as
    ``program=``."""
    name = getattr(jitted, "__name__", None)
    return name if isinstance(name, str) and name.startswith("srt_") \
        else None


def program_in(value) -> Optional[str]:
    """The program name of a cache value: a jitted callable, or a
    tuple holding one (fetchPack keeps ``(fn, order)``)."""
    if isinstance(value, tuple):
        for v in value:
            name = program_of(v)
            if name is not None:
                return name
        return None
    return program_of(value)


_CACHES: Dict[str, "JitCache"] = {}
_REG_LOCK = threading.Lock()


class JitCache:
    """Thread-safe LRU mapping structural keys -> compiled callables."""

    def __init__(self, name: str, capacity: int = 0):
        self.name = name
        self.capacity = capacity or DEFAULT_CAPACITY
        self._data: "OrderedDict[Any, Any]" = OrderedDict()
        self._lock = threading.Lock()
        # single-flight (docs/serving.md): keys whose build is in
        # progress map to the Event concurrent requesters wait on, so
        # two queries sharing a shape never compile the same program
        # twice nor corrupt LRU order racing a duplicate put
        self._building: Dict[Any, Any] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.contention = 0  # threads that blocked on an in-progress build
        # per-thread (miss time, key) so the build between a miss and
        # its put traces as one `compile` span (best-effort: only the
        # get->put pattern on one thread is covered, which is every
        # caller in the package)
        self._miss_tls = threading.local()
        # pre-warm protection (docs/tuning.md): an optional predicate
        # over keys; protected entries are evicted LAST, so a
        # storm-prone signature's programs survive capacity churn. The
        # capacity bound always wins — when every resident entry is
        # protected, plain LRU eviction resumes
        self._protector: Optional[Callable[[Any], bool]] = None
        with _REG_LOCK:
            _CACHES[name] = self

    def set_protector(self,
                      pred: Optional[Callable[[Any], bool]]) -> None:
        """Install (or clear, with None) the eviction-protection
        predicate. The predicate runs under the cache lock — keep it
        cheap (set membership)."""
        with self._lock:
            self._protector = pred

    def _evict_locked(self) -> None:
        while len(self._data) > self.capacity:
            victim = None
            if self._protector is not None:
                for k in self._data:  # oldest-used first
                    try:
                        if not self._protector(k):
                            victim = k
                            break
                    except Exception:
                        victim = k
                        break
            if victim is None:
                self._data.popitem(last=False)
            else:
                del self._data[victim]
            self.evictions += 1

    def get(self, key) -> Optional[Any]:
        """Lookup, counting a hit or a miss; refreshes LRU order."""
        with self._lock:
            val = self._data.get(key)
            if val is None:
                self.misses += 1
                from spark_rapids_tpu import trace as _trace
                if _trace._ACTIVE is not None:
                    import time
                    self._miss_tls.pending = (time.perf_counter_ns(), key)
                return None
            self._data.move_to_end(key)
            self.hits += 1
            return val

    def put(self, key, value) -> Any:
        pending = getattr(self._miss_tls, "pending", None)
        if pending is not None and pending[1] == key:
            self._miss_tls.pending = None
            from spark_rapids_tpu import trace as _trace
            qt = _trace._ACTIVE
            if qt is not None:
                # host stream only: the interval ran get -> put with
                # no block to annotate (get_or_build's does both)
                import time
                _trace.record(qt, "compile", pending[0],
                              time.perf_counter_ns(),
                              _trace.current_scope(),
                              attrs={"cache": self.name,
                                     "program": program_in(value)})
        with self._lock:
            self._data[key] = value
            self._data.move_to_end(key)
            self._evict_locked()
        return value

    def get_or_build(self, key, build: Callable[[], Any]
                     ) -> Tuple[Any, bool]:
        """Returns ``(value, was_miss)``. SINGLE-FLIGHT: exactly one
        thread builds a missing key; concurrent requesters of the SAME
        key block on the builder's Event (counted as ``contention`` in
        the stats and a ``compileCacheContention`` trace instant) and
        then read the finished value — no duplicate compiles under
        concurrent queries sharing a shape. The build itself runs
        OUTSIDE the lock (tracing can be slow and may re-enter other
        caches). If a build raises, its waiters re-race: one becomes
        the new builder, so a transient failure never wedges the key."""
        from spark_rapids_tpu import trace as _trace
        while True:
            wait_ev = None
            with self._lock:
                val = self._data.get(key)
                if val is not None:
                    self._data.move_to_end(key)
                    self.hits += 1
                    return val, False
                ev = self._building.get(key)
                if ev is None:
                    self.misses += 1
                    my_ev = self._building[key] = threading.Event()
                    break
                self.contention += 1
                wait_ev = ev
            _trace.instant("compileCacheContention", cache=self.name)
            # cancellation-aware single-flight wait: a cancelled query
            # parked behind another thread's compile unwinds instead
            # of waiting the build out (the builder is unaffected)
            from spark_rapids_tpu.lifecycle import cancellable_wait
            cancellable_wait(wait_ev, site="jitWait")
        try:
            with _trace.span("compile", cache=self.name) as sp:
                val = build()
                sp.attrs["program"] = program_in(val)
            with self._lock:
                self._data[key] = val
                self._data.move_to_end(key)
                self._evict_locked()
            return val, True
        finally:
            with self._lock:
                self._building.pop(key, None)
            my_ev.set()

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"size": len(self._data), "capacity": self.capacity,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "contention": self.contention}


def cache_stats() -> Dict[str, Dict[str, int]]:
    """Snapshot of every registered compile cache (bench detail JSON)."""
    with _REG_LOCK:
        caches = list(_CACHES.values())
    return {c.name: c.stats() for c in caches}


def mirror_to_metrics(cache: JitCache, metrics, was_miss: bool) -> None:
    """Mirror one lookup's outcome into an exec's metric registry."""
    from spark_rapids_tpu import metrics as M
    name = M.COMPILE_CACHE_MISSES if was_miss else M.COMPILE_CACHE_HITS
    metrics.create(name, M.MODERATE).add(1)
