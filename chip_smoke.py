#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Run from the root of a checkout, in ONE process that owns the TPU:

    python chip_smoke.py

It drives the main path once, through the entry points a user calls, at
the size the repo's first deployment names (BASELINE.json): TPC-H q1 at
SF1 (6,001,215 ``lineitem`` rows, real decimal(15,2) money columns, 8
Parquet partitions written by the engine's own writer from the fixed
seed) and the TPC-DS q3-shaped star join over 2M fact rows —

- directly, ``TpuSparkSession(...).sql(...).collect()`` under bench.py's
  conf, cold then warm, bit-identical to the CPU engine
  (``spark.rapids.sql.enabled=false``), which is this system's plain
  reference;
- served: one ``QueryServer`` in this process, two tenants sending q1
  and q3 through ``ServeClient``, every payload equal to the reference
  rows, a clean drain with the store empty;
- one ``mapInPandas`` call: the python worker is the only child the
  package starts, and it must answer without touching the chip;
- with more than one chip visible: q1 and the join with both sides
  shuffled under ``spark.rapids.shuffle.mode=ici`` over every chip.

It refuses to start without a TPU, never reports a failure as a skip,
and exits non-zero if any leg failed, a retry counter moved, a
fallback report was not empty, or a warm run compiled.
The walls and compile seconds it prints are information for the next
PR, not a benchmark. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# rebuilt from the seed on every run: a marker left by an earlier
# writer is never trusted (the chip tool copies the disk as it stands)
DATA_ROOT = os.path.join(ROOT, ".bench-data", "chip_smoke")

SF1_LINEITEM_ROWS = 6_001_215
STAR_FACT_ROWS = 2_000_000

# printed per query; the second group must stay zero
_REPORTED = ("deviceFallbackUnits", "deviceFallbackColumns")
_MUST_BE_ZERO = ("deviceDecodeOomFallbacks", "retryCount",
                 "splitRetryCount")


def say(section: str, obj) -> None:
    print(f"[smoke] {section}: "
          + (obj if isinstance(obj, str) else json.dumps(obj, sort_keys=True)),
          flush=True)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# measurement plumbing: JAX's own compile events + the engine's counters
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts what JAX itself reports: every backend compile (or
    persistent-cache load) with its seconds, and the persistent cache's
    hits and misses. Stricter than the engine's jit-cache misses: it
    also sees a stray eager op compiling."""

    def __init__(self):
        from jax import monitoring
        self._lock = threading.Lock()
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.slowest: List[Tuple[float, str]] = []
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1
                self.seconds += secs
                self.slowest = sorted(
                    self.slowest + [(round(secs, 1),
                                     str(kw.get("fun_name", "?")))],
                    reverse=True)[:8]

    def _event(self, name: str, **_kw) -> None:
        with self._lock:
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> Tuple[int, float, int, int]:
        with self._lock:
            return self.compiles, self.seconds, self.hits, self.misses


def process_totals() -> Dict[str, int]:
    """Monotone totals over every metric registry the process ever made
    (live plans + retired ones): a superset of any captured plan, and
    the only view that covers queries the server ran."""
    from spark_rapids_tpu.telemetry.prometheus import aggregator
    return aggregator().scrape()[0]


def jit_cache_misses() -> int:
    from spark_rapids_tpu.jit_cache import cache_stats
    return sum(int(s.get("misses", 0)) for s in cache_stats().values())


class Probe:
    """Counter/compile deltas over a ``with`` block."""

    def __init__(self, watch: CompileWatch):
        self.watch = watch
        self.report: Dict = {}

    def __enter__(self) -> "Probe":
        self._totals = process_totals()
        self._watch = self.watch.snapshot()
        self._misses = jit_cache_misses()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        wall = time.perf_counter() - self._t0
        after = process_totals()
        delta = {k: v - self._totals.get(k, 0) for k, v in after.items()}
        c0, s0, h0, m0 = self._watch
        c1, s1, h1, m1 = self.watch.snapshot()
        self.report = {
            "wall_s": round(wall, 3),
            "stageCompileTime_s": round(
                delta.get("stageCompileTime", 0) / 1e9, 3),
            "jitCacheMisses": jit_cache_misses() - self._misses,
            "xlaCompiles": c1 - c0,
            "xlaCompile_s": round(s1 - s0, 3),
            "persistentCacheHits": h1 - h0,
            "persistentCacheMisses": m1 - m0,
            "counters": {
                k: v for k, v in sorted(delta.items())
                if v and (k.startswith(_REPORTED) or k in _MUST_BE_ZERO)},
        }


def check_counters(label: str, report: Dict) -> None:
    moved = {k: v for k, v in report["counters"].items()
             if k in _MUST_BE_ZERO}
    require(not moved, f"{label}: counters that must stay 0 moved: {moved}")


def first_difference(want: List[tuple], got: List[tuple]) -> str:
    if len(want) != len(got):
        return f"{len(want)} reference rows vs {len(got)}"
    for i, (w, g) in enumerate(zip(want, got)):
        if w != g:
            return f"row {i}: reference {w!r} vs {g!r}"
    return "equal"


def require_rows(label: str, want: List[tuple], got: List[tuple]) -> None:
    require(want == got, f"{label}: rows differ from the CPU engine's: "
            + first_difference(want, got))


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def device_leg() -> Dict:
    """Refuse anything but a TPU that says how much HBM it has."""
    import jax
    devs = jax.devices()
    limits = []
    for d in devs:
        stats = d.memory_stats() or {}
        require(bool(stats.get("bytes_limit")),
                f"device {d} reports no HBM bytes_limit: {stats}")
        limits.append(int(stats["bytes_limit"]))
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device", {**info, "hbm_bytes_limit": limits,
                   "jax": jax.__version__,
                   "compile_cache_env": os.environ.get(
                       "JAX_COMPILATION_CACHE_DIR", "")})
    return info


def data_leg(root: str, lineitem_rows: int, fact_rows: int) -> Dict[str, str]:
    """Both datasets from the seed, through the engine's own writer."""
    import bench
    from spark_rapids_tpu.sql.session import TpuSparkSession
    if os.path.exists(root):
        shutil.rmtree(root)
    paths = {"lineitem": os.path.join(root, "lineitem"),
             "tpcds": os.path.join(root, "tpcds")}
    gen = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        bench.write_lineitem(gen, paths["lineitem"], lineitem_rows)
        bench.write_tpcds(gen, paths["tpcds"], fact_rows)
    finally:
        gen.stop()
    files = sum(len(fs) for _d, _s, fs in os.walk(root))
    size = sum(os.path.getsize(os.path.join(d, f))
               for d, _s, fs in os.walk(root) for f in fs)
    say("data", {"lineitem_rows": lineitem_rows, "fact_rows": fact_rows,
                 "files": files, "bytes": size})
    return paths


def register_views(target, paths: Dict[str, str]) -> None:
    """``target`` is a session (views from ``read.parquet``) or a
    QueryServer (``register_view``)."""
    views = {"lineitem": paths["lineitem"]}
    for name in ("item", "date_dim", "store_sales"):
        views[name] = os.path.join(paths["tpcds"], name)
    for name, path in views.items():
        if hasattr(target, "register_view"):
            target.register_view(name, path)
        else:
            target.read.parquet(path).createOrReplaceTempView(name)


def queries() -> Dict[str, str]:
    import bench
    return {"q1": bench.Q1, "q3": bench.TPCDS_Q3}


def reference_leg(paths: Dict[str, str]) -> Dict[str, List[tuple]]:
    """The CPU engine's rows: the plain reference everything else must
    equal bit for bit."""
    from spark_rapids_tpu.sql.session import TpuSparkSession
    cpu = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        register_views(cpu, paths)
        ref = {}
        for name, sql in queries().items():
            t0 = time.perf_counter()
            ref[name] = [tuple(r) for r in cpu.sql(sql).collect()]
            say(f"reference.{name}",
                {"rows": len(ref[name]),
                 "cpu_engine_wall_s": round(time.perf_counter() - t0, 3)})
            require(len(ref[name]) > 0, f"reference {name} is empty")
    finally:
        cpu.stop()
    return ref


def device_conf(extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """bench.py's conf and nothing else, plus the fallback report."""
    import bench
    conf = dict(bench.TPU_CONF)
    conf["spark.rapids.sql.explain"] = "NOT_ON_GPU"
    conf.update(extra or {})
    return conf


def checked_collect(label: str, spark, q, want: List[tuple]) -> None:
    """One ``collect()``: rows bit-identical to the reference, the
    fallback report (spark.rapids.sql.explain=NOT_ON_GPU) empty."""
    rows = [tuple(r) for r in q.collect()]
    fallbacks = list(spark.last_rewrite_report.fallbacks)
    require_rows(label, want, rows)
    require(not fallbacks,
            f"{label}: fallback report not empty: {fallbacks}")


def require_no_compile(label: str, report: Dict) -> None:
    require(report["xlaCompiles"] == 0 and report["jitCacheMisses"] == 0
            and report["stageCompileTime_s"] == 0,
            f"{label}: the warm run compiled: {report}")


def direct_leg(paths: Dict[str, str], ref: Dict[str, List[tuple]],
               watch: CompileWatch, device_kind: str) -> Dict:
    """q1 and q3 through ``TpuSparkSession(...).sql(...).collect()``,
    cold then warm. The cold runs go side by side, one thread and one
    session each: a cold wall on this chip is XLA compile time (up to
    108 s for one program on a v5e, PERF.md), the two queries share no
    program, and compiling them one after the other would not leave the
    smoke inside its time limit. The warm runs go one at a time and
    must compile nothing."""
    from spark_rapids_tpu.sql.session import TpuSparkSession
    sqls = queries()
    sessions = {name: TpuSparkSession(device_conf()) for name in sqls}
    out: Dict = {}
    try:
        qs = {}
        for name, spark in sessions.items():
            register_views(spark, paths)
            qs[name] = spark.sql(sqls[name])
        walls: Dict[str, float] = {}
        errors: List[str] = []

        def cold(name: str) -> None:
            t0 = time.perf_counter()
            try:
                checked_collect(f"direct {name} cold", sessions[name],
                                qs[name], ref[name])
                walls[name] = round(time.perf_counter() - t0, 3)
            except Exception:  # noqa: BLE001 - re-raised by the leg below
                errors.append(f"{name}: {traceback.format_exc()}")

        with Probe(watch) as p:
            threads = [threading.Thread(target=cold, args=(n,),
                                        name=f"smoke-cold-{n}")
                       for n in qs]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        out["cold"] = {**p.report, "wall_s_by_query": walls}
        say(f"direct.cold (q1 and q3 side by side) on {device_kind}",
            out["cold"])
        require(not errors, "direct cold: " + "\n".join(errors))
        check_counters("direct cold", p.report)
        for name, q in qs.items():
            with Probe(watch) as p:
                checked_collect(f"direct {name} warm", sessions[name], q,
                                ref[name])
            out[f"{name}.warm"] = p.report
            say(f"direct.{name}.warm on {device_kind}", p.report)
            check_counters(f"direct {name} warm", p.report)
            require_no_compile(f"direct {name}", p.report)
    finally:
        for spark in sessions.values():
            spark.stop()
    return out


# each tenant sends q1 once and q3 twice; the first two rounds pair a
# q1 with a q3, the last pairs two q3s
_TENANT_REQUESTS = {"tenantA": ("q1", "q3", "q3"),
                    "tenantB": ("q3", "q1", "q3")}


def served_leg(paths: Dict[str, str], ref: Dict[str, List[tuple]],
               watch: CompileWatch) -> Dict:
    """One QueryServer in this process; two tenants, one thread and one
    ServeClient each, send a few q1 and q3 requests."""
    from spark_rapids_tpu import memory as MEM
    from spark_rapids_tpu.serve import QueryServer, ServeClient
    sqls = queries()
    errors: List[str] = []
    latencies: Dict[str, List[float]] = {"q1": [], "q3": []}
    lock = threading.Lock()
    srv = QueryServer(device_conf()).start()
    drained = False
    try:
        register_views(srv, paths)

        def tenant(tenant_id: str) -> None:
            try:
                with ServeClient(srv.port, tenant=tenant_id) as c:
                    for i, kind in enumerate(_TENANT_REQUESTS[tenant_id]):
                        t0 = time.perf_counter()
                        rows = c.collect(sqls[kind])
                        dt = time.perf_counter() - t0
                        require_rows(f"served {tenant_id} {kind} #{i}",
                                     ref[kind], rows)
                        with lock:
                            latencies[kind].append(round(dt, 3))
            except Exception:  # noqa: BLE001 - re-raised by the leg below
                with lock:
                    errors.append(f"{tenant_id}: {traceback.format_exc()}")

        with Probe(watch) as p:
            threads = [threading.Thread(target=tenant, args=(t,),
                                        name=f"smoke-{t}")
                       for t in _TENANT_REQUESTS]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            alive = [t.name for t in threads if t.is_alive()]
        stats = srv.stats()
    finally:
        drained = srv.shutdown()
    gc.collect()
    store = MEM._STORE
    n_requests = sum(len(v) for v in _TENANT_REQUESTS.values())
    report = {
        **p.report, "requests": n_requests,
        "latency_s": latencies, "queriesOk": stats["queriesOk"],
        "queriesErr": stats["queriesErr"], "drained": drained,
        "storeDeviceBytes": store.device_bytes if store else 0,
        "storeHostBytes": store.host_bytes if store else 0}
    say("served", report)
    require(not alive, f"served: client threads still running: {alive}")
    require(not errors, "served: " + "\n".join(errors))
    require(stats["queriesOk"] == n_requests
            and stats["queriesErr"] == 0,
            f"served: server counted {stats['queriesOk']} ok / "
            f"{stats['queriesErr']} failed")
    require(drained is True, "served: shutdown() did not drain")
    require(report["storeDeviceBytes"] == 0
            and report["storeHostBytes"] == 0,
            "served: the store is not empty after the drain")
    check_counters("served", p.report)
    return report


def _worker_backend(batches):
    """mapInPandas body: runs in the python worker, which must see the
    CPU platform (its parent holds the chip)."""
    import jax
    for pdf in batches:
        pdf["worker_backend"] = jax.default_backend()
        pdf["doubled"] = pdf["v"] * 2
        yield pdf


def python_worker_leg() -> Dict:
    """The one child the package starts (python/pool.py): started by a
    parent that holds the chip, it answers and sits on the CPU."""
    from spark_rapids_tpu.python.pool import shutdown_worker_pool
    from spark_rapids_tpu.sql.session import TpuSparkSession
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "true"})
    try:
        df = spark.createDataFrame({"v": list(range(1000))}, "v long")
        rows = df.mapInPandas(
            _worker_backend,
            "v long, worker_backend string, doubled long").collect()
    finally:
        spark.stop()
        shutdown_worker_pool()
    backends = sorted({r[1] for r in rows})
    say("python_worker", {"rows": len(rows), "worker_backend": backends})
    require(sorted((r[0], r[2]) for r in rows)
            == [(i, 2 * i) for i in range(1000)],
            "python worker returned wrong rows")
    require(backends == ["cpu"],
            f"python worker ran on {backends}, not the CPU")
    return {"worker_backend": backends}


def small_join_tables():
    """A 3000-row fact and a 300-row dimension (the shapes
    tests/test_device_join.py joins): small enough that the multi-chip
    leg's shuffled join costs little to compile on every chip."""
    import numpy as np

    from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
    from spark_rapids_tpu.sql import types as T
    rng = np.random.default_rng(13)
    m, n = 300, 3000
    dim = HostBatch(
        T.StructType([T.StructField("pk", T.LongT),
                      T.StructField("nm", T.StringT)]),
        [HostColumn.all_valid(np.arange(1, m + 1), T.LongT),
         HostColumn.all_valid(np.array([f"n{i % 7}" for i in range(m)],
                                       dtype=object), T.StringT)], m)
    fact = HostBatch(
        T.StructType([T.StructField("fk", T.LongT),
                      T.StructField("v", T.LongT)]),
        [HostColumn(T.LongT, rng.integers(1, m + 120, n),
                    rng.random(n) > 0.1).normalized(),
         HostColumn.all_valid(rng.integers(0, 50, n), T.LongT)], n)
    return {"fact": (fact, 4), "dim": (dim, 2)}


_SHUFFLED_JOIN = ("SELECT nm, count(*) AS c, sum(v) AS sv FROM fact "
                  "JOIN dim ON fk = pk GROUP BY nm ORDER BY nm")


def multichip_leg(paths: Dict[str, str], ref: Dict[str, List[tuple]],
                  watch: CompileWatch, device_kind: str) -> Dict:
    """q1 at full size, and a join with
    ``autoBroadcastJoinThreshold=-1`` so both sides shuffle, under the
    ICI exchange over every visible chip; every chip must dispatch and
    scan. Every per-chip program compiles once per chip, so the join
    runs on small tables."""
    import jax

    from spark_rapids_tpu.metrics import sum_plan_metrics
    from spark_rapids_tpu.parallel.mesh import get_active_mesh, mesh_size
    from spark_rapids_tpu.sql.session import TpuSparkSession
    n = len(jax.devices())
    tables = small_join_tables()

    def load_small(s) -> None:
        for name, (hb, parts) in tables.items():
            s.createDataFrame(hb, num_partitions=parts) \
                .createOrReplaceTempView(name)

    cpu = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        load_small(cpu)
        join_ref = [tuple(r) for r in cpu.sql(_SHUFFLED_JOIN).collect()]
    finally:
        cpu.stop()
    require(len(join_ref) > 0, "the shuffled join's reference is empty")
    spark = TpuSparkSession(device_conf({
        "spark.rapids.shuffle.mode": "ici",
        "spark.rapids.sql.autoBroadcastJoinThreshold": "-1"}))
    out = {}
    try:
        require(mesh_size(get_active_mesh()) == n,
                f"mesh spans {mesh_size(get_active_mesh())} of {n} chips")
        register_views(spark, paths)
        load_small(spark)
        spark.start_capture()
        for name, sql, want in (("q1", queries()["q1"], ref["q1"]),
                                ("shuffledJoin", _SHUFFLED_JOIN, join_ref)):
            with Probe(watch) as p:
                checked_collect(f"multichip {name}", spark, spark.sql(sql),
                                want)
            out[name] = p.report
            say(f"multichip.{name}.cold on {n} x {device_kind}", p.report)
            check_counters(f"multichip {name}", p.report)
        plans = spark.get_captured_plans()
        dispatch = sum_plan_metrics(plans, "dispatchCount.chip")
        scanned = sum_plan_metrics(plans, "meshScanUnits.chip")
        ici = sum_plan_metrics(plans, "numIciExchanges")
    finally:
        spark.stop()
    say("multichip", {"chips": n, **dispatch, **scanned, **ici})
    require(ici.get("numIciExchanges", 0) >= 3,
            "q1's exchange and both sides of the join must ride the ICI "
            f"all-to-all: {ici}")
    for d in jax.devices():
        require(dispatch.get(f"dispatchCount.chip{d.id}", 0) > 0,
                f"chip {d.id} dispatched nothing: {dispatch}")
        require(scanned.get(f"meshScanUnits.chip{d.id}", 0) > 0,
                f"chip {d.id} scanned nothing: {scanned}")
    return {"chips": n, "runs": out}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_legs(device: Dict, lineitem_rows: int, fact_rows: int,
             data_root: str) -> List[str]:
    """Every leg in order; a leg that raises is recorded (never
    skipped) and the rest still run, so one chip call reports all it
    can. Returns the names of the legs that failed."""
    failed: List[str] = []

    def leg(name: str, fn: Callable, *args):
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:  # noqa: BLE001 - recorded; the exit code says so
            traceback.print_exc()
            sys.stderr.flush()
            failed.append(name)
            say(f"leg.{name}", f"FAILED after "
                f"{time.perf_counter() - t0:.1f}s (traceback on stderr)")
            return None
        say(f"leg.{name}", f"ok in {time.perf_counter() - t0:.1f}s")
        return out

    import jax
    watch = CompileWatch()
    kind = device["kind"]
    paths = leg("data", data_leg, data_root, lineitem_rows, fact_rows)
    ref = leg("reference", reference_leg, paths) if paths else None
    if ref is None:
        return failed  # nothing to compare against: data or reference failed
    leg("direct", direct_leg, paths, ref, watch, kind)
    leg("served", served_leg, paths, ref, watch)
    leg("python_worker", python_worker_leg)
    if len(jax.devices()) > 1:
        leg("multichip", multichip_leg, paths, ref, watch, kind)
    else:
        say("leg.multichip", "not run: one chip visible")
    c, s, h, m = watch.snapshot()
    say(f"compile totals on {kind}",
        {"xlaCompiles": c, "xlaCompile_s": round(s, 3),
         "persistentCacheHits": h, "persistentCacheMisses": m,
         "slowest": watch.slowest,
         "cacheDir": jax.config.jax_compilation_cache_dir})
    return failed


def main() -> int:
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX's default backend is "
              f"{jax.default_backend()!r}, not 'tpu'; this script only "
              "runs on the chip", file=sys.stderr)
        return 2
    # a directory that holds this script and nothing else of the repo
    # fails here, before anything is printed
    import bench  # noqa: F401
    import spark_rapids_tpu  # noqa: F401
    t0 = time.perf_counter()
    device = device_leg()  # raises: nothing runs on a chip it cannot size
    try:
        failed = run_legs(device, SF1_LINEITEM_ROWS, STAR_FACT_ROWS,
                          DATA_ROOT)
    finally:
        shutil.rmtree(DATA_ROOT, ignore_errors=True)
    say("total_wall_s", str(round(time.perf_counter() - t0, 1)))
    if failed:  # no result line: stdout ends with the legs' own lines
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
