"""Device bootstrap: the GpuDeviceManager twin (GpuDeviceManager.scala:36).

The reference's executor plugin initializes the device and the RMM pool
once per process (initializeGpuAndMemory, GpuDeviceManager.scala:125).
XLA owns the HBM allocator on TPU, so initialization here is:

- enable the persistent XLA compilation cache (compiled programs survive
  process restarts — the analogue of CUDA's on-disk kernel cache);
- discover device/backend facts used for memory accounting (HBM bytes)
  and capability gating (device_caps probes exactness separately);
- register the process's one ``gc.callbacks`` hook
  (``trace.install_gc_hook``: gcPauseTime, gcCollections.<gen>).

Cache placement: where ``JAX_COMPILATION_CACHE_DIR`` is set (or the
caller configured ``jax_compilation_cache_dir`` itself), JAX already
holds the directory and this module sets none. Otherwise the cache goes to
``<checkout>/.xla_cache/<backend>`` — a fixed path computed from the
package's own location (the path is part of JAX's cache key, so a
directory that moves never hits). The one ``<backend>`` level keeps the
sandbox's XLA:CPU entries apart from the chip machine's.

Idempotent and cheap; every TpuSparkSession calls ``initialize()``.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Optional

_LOCK = threading.Lock()
_INITIALIZED = False

DEFAULT_CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".xla_cache")


def initialize() -> None:
    global _INITIALIZED
    with _LOCK:
        if _INITIALIZED:
            return
        import jax
        # jax_enable_compilation_cache is JAX's own off switch
        # (tests/conftest.py): nothing is placed while it is off
        if jax.config.jax_enable_compilation_cache:
            if jax.config.jax_compilation_cache_dir is None:
                cache_dir = os.path.join(DEFAULT_CACHE_ROOT,
                                         jax.default_backend())
                os.makedirs(cache_dir, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir", cache_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.5)
        from spark_rapids_tpu import trace
        trace.install_gc_hook()
        _INITIALIZED = True


@functools.lru_cache(maxsize=None)
def device_memory_bytes() -> Optional[int]:
    """Reported HBM size of one chip: the smallest ``bytes_limit`` over
    the local devices (the store budgets every chip alike). None when
    the backend does not expose it (CPU). Asked once: the limit is
    fixed for the life of the process, and the budget oracle reads it
    at every materialization decision."""
    import jax
    limits = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if not stats.get("bytes_limit"):
            return None
        limits.append(int(stats["bytes_limit"]))
    return min(limits)
