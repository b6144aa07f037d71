"""Persistent per-kernel autotuner.

The reference tunes its CUDA kernels per-architecture at build time;
TPU generations differ just as much (VMEM size, megacore count, DMA
latency), so the winning block shapes are a property of (kernel,
shape bucket, device kind) — and they do not change between runs on
the same machine. This module sweeps a bounded parameter grid ONCE
per such key, validates every candidate bit-exactly against the
kernel's oracle before timing it, and persists the winner in a
crash-safe JSON-lines table so production servers never re-tune:

- ``params_for(conf, kernel, cap)`` is the one entry point. A warm
  table hit returns the recorded winner with zero device work; a miss
  sweeps only when ``spark.rapids.sql.kernel.autotune.enabled`` is on
  (off = read-only: recorded winners still apply) and the budget
  (``...autotune.budgetMs``) allows. Untuned keys return ``{}`` —
  the kernel's built-in defaults.
- a candidate that fails oracle validation is rejected (counted),
  never timed, never recorded: a tuning table can make kernels
  *slower* but never *wrong*.
- a sweep whose best candidate is the default is recorded with
  ``applied: false`` — the sweep is remembered (no re-sweep) but the
  defaults stay in force.
- the table file (``kernel-autotune.jsonl`` under
  ``...autotune.dir``) is append-only one-JSON-object-per-line; the
  loader skips unparseable lines, so a torn write from a crash mid-
  append costs one entry, not the table. Last entry per key wins.
  An empty dir conf keeps the table in memory only.

Stats surface through ``jit_cache.cache_stats()['kernelAutotune']``
(JitCache-shaped: hits = warm lookups, misses = sweeps), which the
server's ``/stats`` and Prometheus endpoints already export.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu import jit_cache as JC

_FILE = "kernel-autotune.jsonl"

_LOCK = threading.Lock()
# dir conf value -> {(kernel, bucket, device): entry}; "" = memory-only
_TABLES: Dict[str, Dict[Tuple, dict]] = {}
_COUNTERS = {"hits": 0, "sweeps": 0, "loaded": 0, "rejected": 0,
             "torn": 0}

# bounded per-kernel candidate grids; the first entry MUST be {} so
# the default is always validated+timed and a winner has a baseline
_GRIDS: Dict[str, List[dict]] = {
    "groupbyHash": [{}, {"blockRows": 1024}, {"blockRows": 2048},
                    {"laneGroups": 2}, {"slotsMult": 2},
                    {"blockRows": 1024, "laneGroups": 2}],
    "decodeFused": [{}, {"charChunk": 2048}, {"charChunk": 8192}],
}


def _device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def _bucket(cap: int) -> int:
    return int(cap)


def _key(kernel: str, cap: int) -> Tuple:
    return (kernel, _bucket(cap), _device_kind())


def _path(dir_: str) -> str:
    return os.path.join(dir_, _FILE)


def _load_locked(dir_: str) -> Dict[Tuple, dict]:
    tbl: Dict[Tuple, dict] = {}
    if dir_:
        try:
            with open(_path(dir_), "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        e = json.loads(line)
                        k = (str(e["kernel"]), int(e["bucket"]),
                             str(e["device"]))
                        dict(e["params"])
                    except Exception:
                        _COUNTERS["torn"] += 1
                        continue
                    tbl[k] = e
                    _COUNTERS["loaded"] += 1
        except OSError:
            pass
    return tbl


def _table(dir_: str) -> Dict[Tuple, dict]:
    with _LOCK:
        tbl = _TABLES.get(dir_)
        if tbl is None:
            tbl = _TABLES[dir_] = _load_locked(dir_)
        return tbl


def _record(dir_: str, key: Tuple, entry: dict) -> None:
    with _LOCK:
        _TABLES.setdefault(dir_, {})[key] = entry
        if not dir_:
            return
        try:
            os.makedirs(dir_, exist_ok=True)
            with open(_path(dir_), "a", encoding="utf-8") as f:
                f.write(json.dumps(entry, sort_keys=True) + "\n")
                f.flush()
                os.fsync(f.fileno())
        except OSError:
            pass  # an unwritable dir degrades to memory-only tuning


def _probe_decode_fused(params: dict) -> bool:
    """Oracle validation for a decodeFused candidate: the only tuned
    knob is charChunk, whose contract is byte-identity of the chunked
    char gather — check it on synthetic data covering padding and
    clipped offsets."""
    import numpy as np

    import jax.numpy as jnp
    from spark_rapids_tpu.ops import rle as R
    rng = np.random.RandomState(11)
    nb, n, char_cap = 4096, 1024, 16
    bytes_all = jnp.asarray(rng.randint(0, 256, size=nb), jnp.int32)
    starts = jnp.asarray(rng.randint(0, nb, size=n), jnp.int64)
    lengths = jnp.asarray(rng.randint(0, char_cap + 1, size=n),
                          jnp.int32)
    chunk = int(params.get("charChunk", 0))
    got = R.gather_chars_chunked(bytes_all, starts, lengths, char_cap,
                                 chunk)
    want = R.gather_chars(bytes_all, starts, lengths, char_cap)
    return bool(jnp.array_equal(got, want))


def _probe_groupby(params: dict) -> bool:
    from spark_rapids_tpu.kernels import groupby_hash as GK
    return GK.autotune_probe(params)


def _run_candidate(kernel: str, cap: int, params: dict
                   ) -> Tuple[bool, float]:
    """Validate one candidate against its oracle and time it; returns
    ``(ok, elapsed_ms)``. Module-level so tests can monkeypatch in a
    deliberately-broken candidate and assert it is rejected."""
    t0 = time.perf_counter()
    if kernel == "decodeFused":
        ok = _probe_decode_fused(params)
    elif kernel == "groupbyHash":
        ok = _probe_groupby(params)
    else:
        ok = False
    return ok, (time.perf_counter() - t0) * 1000.0


def _sweep(conf, kernel: str, cap: int, dir_: str, key: Tuple
           ) -> Tuple[dict, bool]:
    from spark_rapids_tpu.conf import KERNEL_AUTOTUNE_BUDGET_MS
    budget_ms = int(conf.get(KERNEL_AUTOTUNE_BUDGET_MS))
    with _LOCK:
        _COUNTERS["sweeps"] += 1
    t0 = time.perf_counter()
    default_ms: Optional[float] = None
    best_params: dict = {}
    best_ms: Optional[float] = None
    for params in _GRIDS.get(kernel, [{}]):
        # the default always runs (the baseline); later candidates
        # stop when the budget is spent — a partial sweep still
        # records, so the budget bounds cost per key per process life
        if default_ms is not None and \
                (time.perf_counter() - t0) * 1000.0 > budget_ms:
            break
        ok, ms = _run_candidate(kernel, cap, params)
        if not ok:
            with _LOCK:
                _COUNTERS["rejected"] += 1
            continue
        if not params:
            default_ms = ms
        if best_ms is None or ms < best_ms:
            best_params, best_ms = dict(params), ms
    applied = bool(best_params)
    _record(dir_, key, {
        "kernel": kernel, "bucket": _bucket(cap),
        "device": _device_kind(), "params": best_params,
        "applied": applied, "defaultMs": default_ms, "bestMs": best_ms,
        "ts": time.time()})
    return (dict(best_params), True) if applied else ({}, False)


def params_for(conf, kernel: str, cap: int) -> Tuple[dict, bool]:
    """Tuned parameters for one (kernel, capacity bucket) on this
    device: ``(params, tuned)``. ``params == {}`` means built-in
    defaults; ``tuned`` is True only when a recorded winner is in
    force (drives the hotspots report's untuned flag)."""
    if conf is None:
        return {}, False
    from spark_rapids_tpu.conf import (KERNEL_AUTOTUNE_DIR,
                                       KERNEL_AUTOTUNE_ENABLED)
    dir_ = str(conf.get(KERNEL_AUTOTUNE_DIR) or "")
    key = _key(kernel, cap)
    ent = _table(dir_).get(key)
    if ent is not None:
        with _LOCK:
            _COUNTERS["hits"] += 1
        if ent.get("applied") and ent.get("params"):
            return dict(ent["params"]), True
        return {}, False
    if not bool(conf.get(KERNEL_AUTOTUNE_ENABLED)):
        return {}, False
    return _sweep(conf, kernel, cap, dir_, key)


def stats() -> Dict[str, int]:
    """JitCache-shaped snapshot (the Prometheus renderer reads the
    size/capacity/hits/misses/evictions/contention keys of every
    ``cache_stats()`` entry unconditionally)."""
    with _LOCK:
        size = sum(len(t) for t in _TABLES.values())
        return {"size": size, "capacity": 4096,
                "hits": _COUNTERS["hits"],
                "misses": _COUNTERS["sweeps"],
                "evictions": 0, "contention": 0,
                "sweeps": _COUNTERS["sweeps"],
                "loaded": _COUNTERS["loaded"],
                "rejected": _COUNTERS["rejected"],
                "torn": _COUNTERS["torn"]}


def reset_for_tests() -> None:
    """Drop the in-memory tables and counters (simulates a process
    restart: the next ``params_for`` re-loads from disk)."""
    with _LOCK:
        _TABLES.clear()
        for k in _COUNTERS:
            _COUNTERS[k] = 0


JC.register_stats_provider("kernelAutotune", stats)
