#!/usr/bin/env python
"""Driver benchmark: TPC-H q1 at SF1, from Parquet files.

BASELINE.md's first target config: ``parquet scan -> filter -> groupBy
aggregate, single host``. A seeded SF1 ``lineitem`` (6,001,215 rows —
the TPC-H SF1 cardinality) is generated ONCE into ``.bench-data/`` and
written as Parquet through the engine's own writer; the timed query is
the full q1 — scan, date filter, arithmetic projections, 2-key groupBy
with 8 aggregates, orderBy — run through ``spark.sql`` on this engine's
CPU path (the stand-in for "CPU Spark", which the reference's 3x-7x /
"4x typical" claim is measured against, /root/reference/docs/FAQ.md:
104-105) and on the TPU path with every operator force-placed on device.

Prints ONE JSON line:
  {"metric": ..., "value": rows/s on device, "unit": "rows/s",
   "vs_baseline": device_speedup_over_cpu / 4.0}

so vs_baseline >= 1.0 means matching the reference's typical published
speedup on its own terms. Correctness is asserted before timing: with
the real decimal(15,2) money columns (round 4), every aggregate is
exact integer arithmetic, so ALL columns must match bit-for-bit —
no float tolerance carve-out applies to q1 anymore.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Multichip leg on emulated devices: BENCH_MULTICHIP_DEVICES=8 forces N
# virtual CPU devices (same emulation tests/conftest.py uses) so the
# detail.multichip section can run without TPU hardware. Must be set
# BEFORE the first jax import; on real multi-chip backends leave unset.
_mc_emu = int(os.environ.get("BENCH_MULTICHIP_DEVICES", "0"))
if _mc_emu > 1:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count={_mc_emu}"
        ).strip()

import numpy as np  # noqa: E402

SF1_ROWS = 6_001_215
N_ROWS = int(os.environ.get("BENCH_ROWS", SF1_ROWS))
N_PARTITIONS = 8
REFERENCE_TYPICAL_SPEEDUP = 4.0
DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench-data", f"lineitem_dec_{N_ROWS}")

Q1 = """
SELECT
    l_returnflag,
    l_linestatus,
    sum(l_quantity) AS sum_qty,
    sum(l_extendedprice) AS sum_base_price,
    sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
    sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
    avg(l_quantity) AS avg_qty,
    avg(l_extendedprice) AS avg_price,
    avg(l_discount) AS avg_disc,
    count(*) AS count_order
FROM lineitem
WHERE l_shipdate <= date '1998-09-02'
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus
"""


def make_lineitem(n: int = N_ROWS):
    """Seeded SF1-shaped lineitem with the REAL TPC-H schema: the money
    columns are decimal(15,2) (dbgen 4.2.2.13 domains), generated as
    unscaled int64 directly."""
    from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
    from spark_rapids_tpu.sql import types as T

    DEC = T.DecimalType(15, 2)
    rng = np.random.default_rng(20260730)
    quantity = rng.integers(1, 51, n) * 100          # 1.00 .. 50.00
    extendedprice = rng.integers(90100, 10494951, n)  # 901.00..104949.50
    discount = rng.integers(0, 11, n)                 # 0.00 .. 0.10
    tax = rng.integers(0, 9, n)                       # 0.00 .. 0.08
    returnflag = np.array(["A", "N", "R"], dtype=object)[
        rng.integers(0, 3, n)]
    linestatus = np.array(["O", "F"], dtype=object)[rng.integers(0, 2, n)]
    # 1992-01-02 .. 1998-12-01 as days since epoch
    lo = (np.datetime64("1992-01-02") - np.datetime64("1970-01-01")).astype(int)
    hi = (np.datetime64("1998-12-01") - np.datetime64("1970-01-01")).astype(int)
    shipdate = rng.integers(lo, hi + 1, n).astype(np.int32)
    schema = T.StructType([
        T.StructField("l_quantity", DEC),
        T.StructField("l_extendedprice", DEC),
        T.StructField("l_discount", DEC),
        T.StructField("l_tax", DEC),
        T.StructField("l_returnflag", T.StringT),
        T.StructField("l_linestatus", T.StringT),
        T.StructField("l_shipdate", T.DateT),
    ])
    cols = [HostColumn.all_valid(c, f.data_type)
            for c, f in zip([quantity, extendedprice, discount, tax,
                             returnflag, linestatus, shipdate],
                            schema.fields)]
    return HostBatch(schema, cols, n)


def write_lineitem(spark, path: str, n: int = N_ROWS) -> None:
    """Generate lineitem from the seed and write it as N_PARTITIONS
    Parquet files through the engine's own writer."""
    df = spark.createDataFrame(make_lineitem(n),
                               num_partitions=N_PARTITIONS)
    df.write.mode("overwrite").parquet(path)


def ensure_data(spark) -> str:
    marker = os.path.join(DATA_DIR, "_SUCCESS.bench")
    if os.path.exists(marker):
        return DATA_DIR
    if os.path.exists(DATA_DIR):
        shutil.rmtree(DATA_DIR)
    write_lineitem(spark, DATA_DIR)
    with open(marker, "w") as f:
        f.write("ok\n")
    return DATA_DIR


def build_query(spark):
    spark.read.parquet(DATA_DIR).createOrReplaceTempView("lineitem")
    return spark.sql(Q1)


def run_once(q):
    t0 = time.perf_counter()
    rows = q.collect()
    return time.perf_counter() - t0, rows


def assert_rows_match(cpu_rows, tpu_rows):
    assert len(cpu_rows) == len(tpu_rows), \
        (len(cpu_rows), len(tpu_rows))
    for rc, rt in zip(cpu_rows, tpu_rows):
        for vc, vt in zip(rc, rt):
            if isinstance(vc, float):
                assert vt == vc or abs(vt - vc) <= 1e-9 * max(
                    abs(vc), abs(vt)), (vc, vt)
            else:
                assert vc == vt, (vc, vt)


TPCDS_Q3 = """
SELECT d_year, i_brand_id brand_id, i_brand brand,
       sum(ss_ext_sales_price) sum_agg
FROM store_sales
JOIN date_dim ON d_date_sk = ss_sold_date_sk
JOIN item ON ss_item_sk = i_item_sk
WHERE i_manufact_id = 128 AND d_moy = 11
GROUP BY d_year, i_brand_id, i_brand
ORDER BY d_year, sum_agg DESC, brand_id
LIMIT 100
"""

TPCDS_ROWS = int(os.environ.get("BENCH_TPCDS_ROWS", 2_000_000))
TPCDS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".bench-data", f"tpcds_{TPCDS_ROWS}")


def ensure_tpcds_data(spark) -> None:
    marker = os.path.join(TPCDS_DIR, "_SUCCESS.bench")
    if os.path.exists(marker):
        return
    if os.path.exists(TPCDS_DIR):
        shutil.rmtree(TPCDS_DIR)
    write_tpcds(spark, TPCDS_DIR)
    with open(marker, "w") as f:
        f.write("ok\n")


def write_tpcds(spark, path: str, n: int = TPCDS_ROWS) -> None:
    """Synthetic TPC-DS star-schema slice for q3 (BASELINE config 2),
    generated from the seed: store_sales fact (``n`` rows) +
    item/date_dim dimensions, decimal money, one Parquet directory per
    table under ``path``."""
    from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
    from spark_rapids_tpu.sql import types as T
    rng = np.random.default_rng(20260731)
    DEC = T.DecimalType(7, 2)

    n_item = 20_000
    item = HostBatch(T.StructType([
        T.StructField("i_item_sk", T.LongT),
        T.StructField("i_brand_id", T.IntegerT),
        T.StructField("i_brand", T.StringT),
        T.StructField("i_manufact_id", T.IntegerT),
    ]), [
        HostColumn.all_valid(np.arange(1, n_item + 1), T.LongT),
        HostColumn.all_valid(
            rng.integers(1, 1000, n_item).astype(np.int32), T.IntegerT),
        HostColumn.all_valid(np.array(
            [f"brand#{i % 997:03d}" for i in range(n_item)],
            dtype=object), T.StringT),
        HostColumn.all_valid(
            rng.integers(1, 1001, n_item).astype(np.int32), T.IntegerT),
    ], n_item)

    n_date = 73_049
    date_dim = HostBatch(T.StructType([
        T.StructField("d_date_sk", T.LongT),
        T.StructField("d_year", T.IntegerT),
        T.StructField("d_moy", T.IntegerT),
    ]), [
        HostColumn.all_valid(np.arange(1, n_date + 1), T.LongT),
        HostColumn.all_valid(
            (1998 + (np.arange(n_date) // 365) % 7).astype(np.int32),
            T.IntegerT),
        HostColumn.all_valid(
            (1 + (np.arange(n_date) // 30) % 12).astype(np.int32),
            T.IntegerT),
    ], n_date)

    store_sales = HostBatch(T.StructType([
        T.StructField("ss_sold_date_sk", T.LongT),
        T.StructField("ss_item_sk", T.LongT),
        T.StructField("ss_ext_sales_price", DEC),
    ]), [
        HostColumn.all_valid(rng.integers(1, n_date + 1, n), T.LongT),
        HostColumn.all_valid(rng.integers(1, n_item + 1, n), T.LongT),
        HostColumn.all_valid(rng.integers(100, 1_000_000, n), DEC),
    ], n)

    for name, batch, parts in (("item", item, 1), ("date_dim", date_dim, 1),
                               ("store_sales", store_sales, 8)):
        spark.createDataFrame(batch, num_partitions=parts).write \
            .mode("overwrite").parquet(os.path.join(path, name))


def run_tpcds_q3(spark, capture=False):
    for name in ("item", "date_dim", "store_sales"):
        spark.read.parquet(os.path.join(TPCDS_DIR, name)) \
            .createOrReplaceTempView(name)
    q = spark.sql(TPCDS_Q3)
    run_once(q)  # warm
    times, rows, stages, decode = [], None, None, None
    for i in range(2):
        if capture and i == 1:
            spark.start_capture()
        dt, rows = run_once(q)
        times.append(dt)
    if capture:
        plans = spark.get_captured_plans()
        stages = stage_breakdown(plans)
        decode = decode_breakdown(plans)
    return min(times), rows, stages, decode


def stage_breakdown(plans) -> dict:
    """Aggregate per-operator time metrics from the captured physical
    plan of the LAST timed run (VERDICT r3 weak #10: publish where the
    wall time goes, not just its total). Fused stages fan their metrics
    back to their constituent execs, so the breakdown keeps the same
    per-operator stage keys whether or not fusion is enabled."""
    out: dict = {}

    def visit(p):
        ms = getattr(p, "metrics", None)
        if ms is None:
            return
        name = p.simple_string().split()[0]
        for k, v in ms.snapshot().items():
            if "Time" in k and v:
                key = f"{name}.{k}"
                out[key] = round(out.get(key, 0.0) + v / 1e9, 3)

    def walk(p):
        visit(p)
        for op in getattr(p, "fused_ops", []):
            visit(op)  # shallow: child links point back into the chain
        for c in p.children:
            walk(c)

    for plan in plans or []:
        walk(plan)
    return out


def collect_counters(plans, names) -> dict:
    """Named metric counters across every exec of the captured plans —
    one registry_snapshot call (metrics.py owns the walk; fused
    constituents included)."""
    from spark_rapids_tpu.metrics import registry_snapshot
    snap = registry_snapshot(plans)["metrics"]
    return {n: snap.get(n, 0) for n in names}


def decode_breakdown(plans) -> dict:
    """Per-encoding scan decode attribution: host decodeTime vs
    deviceDecodeTime (the host-side IO/plan half of the device path),
    how many values each Parquet encoding contributed on DEVICE vs the
    per-column HOST fallbacks, and the scan pipeline's prefetch /
    upload-ahead counters (docs/scan.md)."""
    out = {"hostDecodeTime_s": 0.0, "deviceDecodeTime_s": 0.0,
           "scanPrefetchTime_s": 0.0, "deviceDecodedBatches": 0,
           "deviceFallbackUnits": 0, "deviceFallbackColumns": 0,
           "uploadAheadBatches": 0, "prefetchRingShrinks": 0,
           "valuesByEncoding": {}, "hostValuesByEncoding": {}}

    def walk(p):
        name = type(p).__name__
        if name == "CpuFileScanExec":
            snap = p.metrics.snapshot()
            out["hostDecodeTime_s"] = round(
                out["hostDecodeTime_s"] + snap.get("decodeTime", 0) / 1e9,
                3)
            out["deviceDecodeTime_s"] = round(
                out["deviceDecodeTime_s"]
                + snap.get("deviceDecodeTime", 0) / 1e9, 3)
            for k in ("deviceDecodedBatches", "deviceFallbackUnits",
                      "deviceFallbackColumns"):
                out[k] += snap.get(k, 0)
            for k, v in snap.items():
                if k.startswith("deviceDecodedValues."):
                    enc = k.split(".", 1)[1]
                    out["valuesByEncoding"][enc] = \
                        out["valuesByEncoding"].get(enc, 0) + v
                elif k.startswith("hostDecodedValues."):
                    enc = k.split(".", 1)[1]
                    out["hostValuesByEncoding"][enc] = \
                        out["hostValuesByEncoding"].get(enc, 0) + v
        elif name == "TpuRowToColumnarExec":
            snap = p.metrics.snapshot()
            out["scanPrefetchTime_s"] = round(
                out["scanPrefetchTime_s"]
                + snap.get("scanPrefetchTime", 0) / 1e9, 3)
            out["uploadAheadBatches"] += snap.get("uploadAheadBatches", 0)
            out["prefetchRingShrinks"] += snap.get(
                "prefetchRingShrinks", 0)
        for c in p.children:
            walk(c)

    for plan in plans or []:
        walk(plan)
    return out


def fresh_leg() -> int:
    """Scope a detail leg's process-wide observability state: start a
    new metric-registry epoch and re-base the device store's pool +
    per-owner peak watermarks, so each leg's snapshot/profile reports
    its OWN run instead of inheriting earlier legs' registries and
    high-watermarks."""
    from spark_rapids_tpu import memory
    from spark_rapids_tpu.metrics import begin_epoch
    memory.reset_store_peaks()
    return begin_epoch()


TPU_CONF = {
    "spark.rapids.sql.enabled": "true",
    "spark.rapids.sql.test.forceDevice": "true",  # fail on any fallback
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
    # TPU executes f64 via emulation (not bit-identical rounding);
    # q1's double arithmetic opts in exactly like the reference's
    # .incompat() ops, and the result assert holds doubles to 1e-9
    "spark.rapids.sql.incompatibleOps.enabled": "true",
    # overlap per-task host round trips with device compute
    "spark.rapids.sql.taskParallelism": "4",
    "spark.rapids.sql.concurrentGpuTasks": "4",
    # device parquet decode + the async scan pipeline are ON BY
    # DEFAULT (ISSUE 9); the bench runs the stock configuration and
    # detail.decode A/B-measures the host-decode / unpipelined legs
}

DEVICE_DECODE_CONF = \
    "spark.rapids.sql.format.parquet.deviceDecode.enabled"
MAX_IN_FLIGHT_CONF = \
    "spark.rapids.sql.format.parquet.deviceDecode.maxInFlight"

_COUNTERS = ("dispatchCount", "stageCompileTime", "fusedOps")


def run_tpu(fusion_enabled: bool) -> dict:
    """One full TPU pass (q1 warm + 3 timed, q3) with stage fusion on
    or off — the fused-vs-unfused comparison runs in the SAME bench
    invocation so the walls are directly comparable."""
    from spark_rapids_tpu.sql.session import TpuSparkSession
    fresh_leg()
    conf = dict(TPU_CONF)
    conf["spark.rapids.sql.stageFusion.enabled"] = str(
        fusion_enabled).lower()
    tpu = TpuSparkSession(conf)
    q_tpu = build_query(tpu)
    tpu.start_capture()
    run_once(q_tpu)  # jit compile warm-up
    warm_counters = collect_counters(tpu.get_captured_plans(), _COUNTERS)
    times, rows = [], None
    for i in range(3):
        if i == 2:
            tpu.start_capture()
        dt, rows = run_once(q_tpu)
        times.append(dt)
    captured = tpu.get_captured_plans()
    counters = collect_counters(captured, _COUNTERS)
    out = {
        "wall_s": round(min(times), 4),
        "rows": rows,
        "stages": stage_breakdown(captured),
        "decode": decode_breakdown(captured),
        "dispatchCount": counters["dispatchCount"],
        "fusedOps": counters["fusedOps"],
        "stageCompileTime_s": round(
            warm_counters["stageCompileTime"] / 1e9, 3),
    }
    q3_t, q3_rows, q3_stages, q3_decode = run_tpcds_q3(tpu, capture=True)
    out["q3"] = {"wall_s": round(q3_t, 4), "rows": q3_rows,
                 "stages": q3_stages, "decode": q3_decode}
    tpu.stop()
    return out


def run_decode_ab(pipelined_wall: float, cpu_rows) -> dict:
    """detail.decode A/B legs (like detail.fusion): q1 with the HOST
    decode (deviceDecode off) and with device decode but the scan
    pipeline fully synchronous (maxInFlight=0), against the default
    pipelined wall — so the device-decode win and the pipeline win are
    separately attributable. Both legs assert bit-identical rows."""
    from spark_rapids_tpu.sql.session import TpuSparkSession
    out = {"pipelined_wall_s": round(pipelined_wall, 4)}
    for name, extra in (("hostDecode", {DEVICE_DECODE_CONF: "false"}),
                        ("unpipelined", {MAX_IN_FLIGHT_CONF: "0"})):
        fresh_leg()
        conf = dict(TPU_CONF)
        conf.update(extra)
        tpu = TpuSparkSession(conf)
        try:
            q = build_query(tpu)
            run_once(q)  # warm
            times, rows = [], None
            for i in range(2):
                if i == 1:
                    tpu.start_capture()
                dt, rows = run_once(q)
                times.append(dt)
            assert_rows_match(cpu_rows, rows)
            out[name] = {
                "wall_s": round(min(times), 4),
                "decode": decode_breakdown(tpu.get_captured_plans()),
            }
        finally:
            tpu.stop()
    out["pipelineSpeedup"] = round(
        out["unpipelined"]["wall_s"] / pipelined_wall, 4)
    out["deviceDecodeSpeedup"] = round(
        out["hostDecode"]["wall_s"] / pipelined_wall, 4)
    return out


def run_multichip(single_chip_wall: float, cpu_rows) -> dict:
    """q1 end-to-end with shuffle.mode=ici over every visible device:
    the mesh-sharded scan runs one reader stream per chip, fused stages
    execute on each chip's resident batches, and the exchange consumes
    them without a host gather (docs/multichip.md). Skips gracefully
    when fewer than 2 devices are visible. The mesh size honors
    spark.rapids.shuffle.ici.devices (0 = all visible)."""
    import jax
    n_vis = len(jax.devices())
    if n_vis < 2:
        return {"skipped": True,
                "reason": f"{n_vis} device visible (need >= 2; set "
                          "BENCH_MULTICHIP_DEVICES=8 to emulate)"}
    from spark_rapids_tpu.sql.session import TpuSparkSession
    fresh_leg()
    conf = dict(TPU_CONF)
    conf["spark.rapids.shuffle.mode"] = "ici"
    # 0 = all visible devices (resolved by the session's mesh wiring)
    conf["spark.rapids.shuffle.ici.devices"] = os.environ.get(
        "BENCH_ICI_DEVICES", "0")
    tpu = TpuSparkSession(conf)
    try:
        from spark_rapids_tpu.parallel.mesh import get_active_mesh, mesh_size
        n_chips = mesh_size(get_active_mesh())
        q = build_query(tpu)
        run_once(q)  # jit compile warm-up
        times, rows = [], None
        for i in range(2):
            if i == 1:
                tpu.start_capture()
            dt, rows = run_once(q)
            times.append(dt)
        from spark_rapids_tpu.metrics import sum_plan_metrics
        captured = tpu.get_captured_plans()
        assert_rows_match(cpu_rows, rows)
        wall = min(times)
        dispatch = sum_plan_metrics(captured, "dispatchCount.chip")
        units = sum_plan_metrics(captured, "meshScanUnits.chip")
        pad = sum_plan_metrics(captured, "meshPadWaste")
        return {
            "skipped": False,
            "n_chips": n_chips,
            "wall_s": round(wall, 4),
            "single_chip_wall_s": round(single_chip_wall, 4),
            "speedup_vs_single_chip": round(single_chip_wall / wall, 4),
            "perChipDispatchCount": dispatch,
            "chipsDispatching": sum(1 for v in dispatch.values() if v),
            "scanUnitsPerChip": units,
            "meshPadWaste": pad.get("meshPadWaste", 0),
        }
    finally:
        tpu.stop()


_ROBUSTNESS_COUNTERS = ("retryCount", "splitRetryCount",
                        "spillBytesOnRetry", "retryBlockTime",
                        "ioRetryCount", "degradedChips",
                        "prefetchRingShrinks", "uploadAheadBatches",
                        "deviceDecodeOomFallbacks")


def run_robustness(clean_wall: float, cpu_rows) -> dict:
    """q1 under deterministic fault injection (docs/robustness.md): one
    leg per failure mode — every-Nth OOM (retry), split-OOM (split-and-
    retry), and a persistently failing mesh chip (graceful degradation)
    — asserting bit-identical results and reporting the retry/split/
    spill counters plus the degraded-mode walls against the clean wall.
    Skips gracefully when injection is off (BENCH_INJECT=0)."""
    if os.environ.get("BENCH_INJECT", "1").lower() in ("0", "false",
                                                       "off"):
        return {"skipped": True, "reason": "injection off (BENCH_INJECT=0)"}
    from spark_rapids_tpu import retry as RT
    from spark_rapids_tpu.sql.session import TpuSparkSession
    legs = [
        ("oomEveryN", {"spark.rapids.sql.test.injectOOM": "5"}, {}),
        ("splitOom", {"spark.rapids.sql.test.injectOOM": "split:7"}, {}),
        # OOM targeted at the scan pipeline's prefetched uploads: the
        # in-flight ring must SHRINK (drain + synchronous retry), not
        # deadlock, under with_retry spills (docs/scan.md)
        ("prefetchOom",
         {"spark.rapids.sql.test.injectOOM": "site:upload:3"}, {}),
    ]
    import jax
    if len(jax.devices()) >= 2:
        legs.append(("chipFailure",
                     {"spark.rapids.sql.test.injectChipFailure":
                      str(jax.devices()[0].id)},
                     {"spark.rapids.shuffle.mode": "ici"}))
    out = {"skipped": False, "clean_wall_s": round(clean_wall, 4),
           "legs": {}}
    for name, inject, extra in legs:
        RT.reset_fault_injection()
        fresh_leg()
        conf = dict(TPU_CONF)
        conf.update(inject)
        conf.update(extra)
        tpu = TpuSparkSession(conf)
        try:
            q = build_query(tpu)
            # capture BOTH runs: one-time events (chip degradation
            # happens once per session) land in the warm run, while the
            # second run's wall is the degraded-mode steady state
            tpu.start_capture()
            run_once(q)
            RT.reset_fault_injection()
            dt, rows = run_once(q)
            assert_rows_match(cpu_rows, rows)
            counters = collect_counters(tpu.get_captured_plans(),
                                        _ROBUSTNESS_COUNTERS)
            inj = RT.get_fault_injector(tpu.conf_obj)
            out["legs"][name] = {
                "wall_s": round(dt, 4),
                "slowdown_vs_clean": round(dt / clean_wall, 4),
                "retryCount": counters["retryCount"],
                "splitRetryCount": counters["splitRetryCount"],
                "spillBytesOnRetry": counters["spillBytesOnRetry"],
                "retryBlockTime_s": round(
                    counters["retryBlockTime"] / 1e9, 4),
                "degradedChips": counters["degradedChips"],
                "prefetchRingShrinks": counters["prefetchRingShrinks"],
                "deviceDecodeOomFallbacks":
                    counters["deviceDecodeOomFallbacks"],
                "injected": inj.stats() if inj is not None else {},
            }
        finally:
            tpu.stop()
    RT.reset_fault_injection()
    return out


_OOC_COUNTERS = ("retryCount", "splitRetryCount", "plannedPartitions",
                 "plannedOutOfCoreEscalations", "budgetPressurePeak")


def run_out_of_core(clean_wall: float, cpu_rows) -> dict:
    """detail.outOfCore (docs/out_of_core.md): q1 with the planning
    budget pinned at 1x / 4x / 10x UNDER the clean run's peak HBM, so
    the planned partitioned tier absorbs the pressure. The acceptance
    number is plannedPathClean: 1.0 means every over-budget leg stayed
    bit-identical with retryCount == 0 and splitRetryCount == 0 — the
    degradation ladder never fell past its first two rungs."""
    from spark_rapids_tpu import retry as RT
    from spark_rapids_tpu.memory import get_device_store
    from spark_rapids_tpu.sql.session import TpuSparkSession

    # probe: the clean run's peak HBM is the working-set estimate the
    # over-budget legs divide down from
    fresh_leg()
    tpu = TpuSparkSession(dict(TPU_CONF))
    try:
        q = build_query(tpu)
        run_once(q)
        peak = int(get_device_store(tpu.conf_obj)
                   .stats()["peakDeviceBytes"])
    finally:
        tpu.stop()
    if peak <= 0:
        return {"skipped": True,
                "reason": f"clean peakDeviceBytes={peak}: no working "
                          f"set to budget against"}

    out = {"skipped": False, "clean_wall_s": round(clean_wall, 4),
           "workingSetBytes": peak, "legs": {}}
    clean_path = True
    for name, divisor in (("budget1x", 1), ("budget4x", 4),
                          ("budget10x", 10)):
        RT.reset_fault_injection()
        fresh_leg()
        conf = dict(TPU_CONF)
        budget = max(1, peak // divisor)
        conf["spark.rapids.sql.memory.deviceBudgetBytes"] = str(budget)
        tpu = TpuSparkSession(conf)
        try:
            q = build_query(tpu)
            run_once(q)  # warm: compiles at this budget's plan shape
            tpu.start_capture()
            dt, rows = run_once(q)
            assert_rows_match(cpu_rows, rows)
            counters = collect_counters(tpu.get_captured_plans(),
                                        _OOC_COUNTERS)
            store_peak = int(get_device_store(tpu.conf_obj)
                             .stats()["peakDeviceBytes"])
            retried = (counters["retryCount"]
                       + counters["splitRetryCount"]) > 0
            clean_path = clean_path and not retried
            out["legs"][name] = {
                "wall_s": round(dt, 4),
                "slowdown_vs_clean": round(dt / clean_wall, 4),
                "budgetBytes": budget,
                "peakDeviceBytes": store_peak,
                "retryCount": counters["retryCount"],
                "splitRetryCount": counters["splitRetryCount"],
                "plannedPartitions": counters["plannedPartitions"],
                "plannedOutOfCoreEscalations":
                    counters["plannedOutOfCoreEscalations"],
                "budgetPressurePeak": counters["budgetPressurePeak"],
            }
        finally:
            tpu.stop()
    out["plannedPathClean"] = 1.0 if clean_path else 0.0
    return out


def run_trace(clean_wall: float, cpu_rows) -> dict:
    """q1 with span tracing on (docs/observability.md): emits one
    Chrome-trace file per run under .bench-data/traces, reports the
    per-chip occupancy + critical-path breakdown from the last run's
    trace, and measures the tracing overhead against the untraced
    wall (budget: <= 15% on the smoke input, tests/test_trace.py)."""
    import glob

    from spark_rapids_tpu import trace as TR
    from spark_rapids_tpu.sql.session import TpuSparkSession
    from spark_rapids_tpu.tools import analyze_trace
    tdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench-data", "traces")
    shutil.rmtree(tdir, ignore_errors=True)
    TR.reset_tracing()
    fresh_leg()
    conf = dict(TPU_CONF)
    conf["spark.rapids.sql.trace.enabled"] = "true"
    conf["spark.rapids.sql.trace.dir"] = tdir
    tpu = TpuSparkSession(conf)
    try:
        q = build_query(tpu)
        run_once(q)  # jit compile warm-up
        times, rows = [], None
        for i in range(2):
            if i == 1:
                tpu.start_capture()
            dt, rows = run_once(q)
            times.append(dt)
        assert_rows_match(cpu_rows, rows)
        wall = min(times)
        files = sorted(glob.glob(os.path.join(tdir, "trace-*.json")))
        analysis = analyze_trace(files[-1]) if files else {}
        cp = analysis.get("criticalPath_s", {})
        # decode-overlap ratio (ISSUE 9 acceptance): how much of the
        # scan's wall (host decode plan + prefetch threads) hid under
        # device compute — 1.0 means the scan never held the critical
        # path, and FileScan.decodeTime off the critical path is the
        # flip's proof
        dec = decode_breakdown(tpu.get_captured_plans())
        scan_total = (dec["hostDecodeTime_s"] + dec["deviceDecodeTime_s"]
                      + dec["scanPrefetchTime_s"])
        scan_critical = sum(v for k, v in cp.items() if k in (
            "FileScan.decodeTime", "FileScan.deviceDecodeTime",
            "scanPrefetch", "uploadAhead"))
        overlap = {
            "scanTotal_s": round(scan_total, 4),
            "scanOnCriticalPath_s": round(scan_critical, 4),
            "overlapRatio": round(
                max(0.0, 1.0 - scan_critical / scan_total), 4)
            if scan_total > 0 else 1.0,
            "decodeTimeOnCriticalPath":
                "FileScan.decodeTime" in cp,
        }
        return {
            "skipped": False,
            "wall_s": round(wall, 4),
            "untraced_wall_s": round(clean_wall, 4),
            "tracingOverhead": round(wall / clean_wall, 4),
            "traceFiles": len(files),
            "spanCount": analysis.get("spanCount", 0),
            "criticalPath_s": cp,
            "criticalPathIdle_s": analysis.get("criticalPathIdle_s", 0),
            "occupancy": analysis.get("occupancy", {}),
            "topSpans": analysis.get("topSpans", []),
            "scanOverlap": overlap,
        }
    finally:
        tpu.stop()
        TR.reset_tracing()


def run_profile(clean_wall: float, cpu_rows) -> dict:
    """q1 + q3 with the profile subsystem on (docs/observability.md
    "Reading a query profile"): per-op peak HBM from each query's
    artifact (checked against the pool watermark), explain coverage
    counts, and the measured profiling overhead vs the clean wall
    (acceptance: <= 1.15x on the smoke input)."""
    from spark_rapids_tpu.profile import read_profiles
    from spark_rapids_tpu.sql.session import TpuSparkSession
    pdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench-data", "profiles")
    shutil.rmtree(pdir, ignore_errors=True)
    conf = dict(TPU_CONF)
    # no forceDevice: the explain section should report REAL coverage
    # (a forced-fallback query would abort under forceDevice)
    conf.pop("spark.rapids.sql.test.forceDevice", None)
    conf["spark.rapids.sql.profile.enabled"] = "true"
    conf["spark.rapids.sql.profile.dir"] = pdir

    def leg(run_query, check_rows) -> dict:
        epoch = fresh_leg()
        tpu = TpuSparkSession(conf)
        try:
            wall, rows, path = run_query(tpu)
            if check_rows is not None:
                assert_rows_match(check_rows, rows)
            prof = list(read_profiles(path))[0]
            ops = prof["memory"]["operators"]
            pool = prof["memory"]["pool"]
            ex = prof.get("explain", {})
            # consistency: the pool watermark is bounded by the sum of
            # per-op peaks (acceptance criterion)
            sum_peaks = sum(st["peakBytes"] for st in ops.values())
            assert pool.get("peakDeviceBytes", 0) <= sum_peaks or \
                not ops, (pool, ops)
            # epoch-scoped process-wide snapshot: only THIS leg's
            # registries contribute (the registry-bleed satellite)
            from spark_rapids_tpu.metrics import registry_snapshot
            leg_metrics = registry_snapshot(epoch=epoch)["metrics"]
            return {
                "wall_s": round(wall, 4),
                "perOpPeakHBM": {o: st["peakBytes"]
                                 for o, st in sorted(ops.items())},
                "poolPeakHBM": pool.get("peakDeviceBytes", 0),
                "deviceOps": len(ex.get("deviceOps", [])),
                "fallbacks": len(ex.get("fallbacks", [])),
                "coverage": ex.get("coverage", 1.0),
                "legSpillBytes": leg_metrics.get("spillBytes", 0),
                "legRetryCount": leg_metrics.get("retryCount", 0),
            }
        finally:
            tpu.stop()

    def q1_run(tpu):
        q = build_query(tpu)
        run_once(q)  # warm
        times, rows = [], None
        for _ in range(2):
            dt, rows = run_once(q)
            times.append(dt)
        return min(times), rows, tpu.last_profile_path

    def q3_run(tpu):
        t, rows, _stages, _decode = run_tpcds_q3(tpu)
        return t, rows, tpu.last_profile_path

    q1_leg = leg(q1_run, cpu_rows)
    q3_leg = leg(q3_run, None)
    return {
        "skipped": False,
        "clean_wall_s": round(clean_wall, 4),
        "profilingOverhead": round(q1_leg["wall_s"] / clean_wall, 4),
        "q1": q1_leg,
        "q3": q3_leg,
    }


def run_serving(clean_wall: float, cpu_rows, q3_cpu_rows) -> dict:
    """Mixed q1/q3 workload through the query server
    (docs/serving.md): sustained QPS and p50/p99 latency at
    concurrency 1/4/16, plan-cache and jit-cache hit rates warm vs
    cold, per-tenant queue waits. Results are asserted bit-identical
    to the CPU oracle on every request. Skips gracefully when the
    server cannot bind."""
    import threading

    from spark_rapids_tpu.plan_cache import PLAN_CACHE
    from spark_rapids_tpu.serve import QueryServer, ServeClient
    from spark_rapids_tpu.serve.scheduler import percentile
    fresh_leg()
    conf = dict(TPU_CONF)
    # admission sized for the c=16 leg: queries queue rather than reject
    conf.update({
        "spark.rapids.sql.serve.maxConcurrentQueries": "4",
        "spark.rapids.sql.serve.maxQueued": "64",
        "spark.rapids.sql.serve.maxConcurrentPerTenant": "4",
    })
    try:
        srv = QueryServer(conf).start()
    except OSError as e:
        return {"skipped": True, "reason": f"cannot bind: {e!r}"}
    try:
        srv.register_view("lineitem", DATA_DIR)
        for name in ("item", "date_dim", "store_sales"):
            srv.register_view(name, os.path.join(TPCDS_DIR, name))

        def check(kind, rows):
            assert_rows_match(cpu_rows if kind == "q1" else q3_cpu_rows,
                              rows)

        # cold: first submission of each shape populates plan cache +
        # jit caches through the server path
        cold_stats = {"hits0": PLAN_CACHE.hits,
                      "misses0": PLAN_CACHE.misses}
        t0 = time.perf_counter()
        with ServeClient(srv.port, tenant="warmup") as c:
            b, _ = c.sql(Q1)
            check("q1", [tuple(r) for r in b.rows()])
            b, _ = c.sql(TPCDS_Q3)
            check("q3", [tuple(r) for r in b.rows()])
        cold_s = time.perf_counter() - t0
        cold = {
            "wall_s": round(cold_s, 4),
            "planCacheMisses": PLAN_CACHE.misses - cold_stats["misses0"],
            "planCacheHits": PLAN_CACHE.hits - cold_stats["hits0"],
        }

        legs = {}
        n_queries = int(os.environ.get("BENCH_SERVE_QUERIES", "8"))
        for concurrency in (1, 4, 16):
            h0, m0 = PLAN_CACHE.hits, PLAN_CACHE.misses
            total = max(n_queries, concurrency)
            lat: list = []
            errors: list = []
            lat_lock = threading.Lock()

            def worker(i):
                try:
                    with ServeClient(srv.port,
                                     tenant=f"t{i % 4}") as c:
                        kind = "q1" if i % 2 == 0 else "q3"
                        tq = time.perf_counter()
                        b, _h = c.sql(Q1 if kind == "q1" else TPCDS_Q3)
                        dt = time.perf_counter() - tq
                        check(kind, [tuple(r) for r in b.rows()])
                        with lat_lock:
                            lat.append(dt)
                except Exception as e:  # noqa: BLE001 - reported below
                    errors.append(repr(e))

            t0 = time.perf_counter()
            threads = []
            for i in range(total):
                t = threading.Thread(target=worker, args=(i,))
                t.start()
                threads.append(t)
                # cap live threads at the leg's concurrency
                while sum(1 for x in threads if x.is_alive()) \
                        >= concurrency:
                    time.sleep(0.005)
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                # a served request that failed (or returned rows that
                # differ from the oracle) fails the leg, not one cell
                raise RuntimeError(
                    f"serving c={concurrency}: {len(errors)} of {total} "
                    f"requests failed: {errors[:3]}")
            hits = PLAN_CACHE.hits - h0
            misses = PLAN_CACHE.misses - m0
            legs[f"c{concurrency}"] = {
                "queries": total,
                "wall_s": round(wall, 4),
                "qps": round(total / wall, 4),
                "latency_s": {
                    "p50": round(percentile(lat, 0.50), 4),
                    "p99": round(percentile(lat, 0.99), 4),
                },
                "planCacheHitRate": round(
                    hits / max(1, hits + misses), 4),
            }
        st = srv.stats()
        jit = st["jitCaches"]
        warm_hit_rates = {
            name: round(s["hits"] / max(1, s["hits"] + s["misses"]), 4)
            for name, s in sorted(jit.items())
            if s["hits"] + s["misses"] > 0}
        return {
            "skipped": False,
            "clean_wall_s": round(clean_wall, 4),
            "cold": cold,
            "concurrency": legs,
            "admission": st["admission"],
            "tenantsHBM": st["tenantsHBM"],
            "jitCacheHitRates": warm_hit_rates,
        }
    finally:
        srv.shutdown()


def run_result_cache(clean_wall: float, cpu_rows, q3_cpu_rows) -> dict:
    """detail.resultCache (docs/caching.md): dashboard-replay QPS at
    c=16 — the same mixed q1/q3 workload replayed against a cache-off
    server (cold: every query executes) and a result-cache server after
    one priming pass per shape (warm: hits serve payload bytes from
    memory) — plus the subplan-cache join build-time delta on repeated
    q3. Every response, cached or executed, is asserted bit-identical
    to the CPU oracle. Skips gracefully when the server cannot bind."""
    import threading

    from spark_rapids_tpu.serve import QueryServer, ServeClient

    def check(kind, rows):
        assert_rows_match(cpu_rows if kind == "q1" else q3_cpu_rows,
                          rows)

    def serve(extra: dict) -> "QueryServer":
        conf = dict(TPU_CONF)
        conf.update({
            "spark.rapids.sql.serve.maxConcurrentQueries": "4",
            "spark.rapids.sql.serve.maxQueued": "64",
            "spark.rapids.sql.serve.maxConcurrentPerTenant": "4",
        })
        conf.update(extra)
        srv = QueryServer(conf).start()
        srv.register_view("lineitem", DATA_DIR)
        for name in ("item", "date_dim", "store_sales"):
            srv.register_view(name, os.path.join(TPCDS_DIR, name))
        return srv

    def replay(port: int, total: int, concurrency: int = 16):
        errors: list = []

        def worker(i):
            try:
                with ServeClient(port, tenant=f"dash{i % 4}") as c:
                    kind = "q1" if i % 2 == 0 else "q3"
                    b, _ = c.sql(Q1 if kind == "q1" else TPCDS_Q3)
                    check(kind, [tuple(r) for r in b.rows()])
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(repr(e))

        t0 = time.perf_counter()
        threads = []
        for i in range(total):
            t = threading.Thread(target=worker, args=(i,))
            t.start()
            threads.append(t)
            while sum(1 for x in threads if x.is_alive()) \
                    >= concurrency:
                time.sleep(0.005)
        for t in threads:
            t.join()
        return time.perf_counter() - t0, errors

    fresh_leg()
    total = int(os.environ.get("BENCH_REPLAY_QUERIES", "32"))

    # cold side: caches off — every replayed query admits and executes
    try:
        srv = serve({})
    except OSError as e:
        return {"skipped": True, "reason": f"cannot bind: {e!r}"}
    try:
        cold_wall, errors = replay(srv.port, total)
        if errors:
            return {"skipped": True, "reason": errors[:3]}
    finally:
        srv.shutdown()

    # warm side: result cache on — one priming pass per shape, then
    # the identical replay; hits bypass admission and device work
    srv = serve({
        "spark.rapids.sql.resultCache.enabled": "true",
        "spark.rapids.sql.subplanCache.enabled": "true",
    })
    try:
        with ServeClient(srv.port, tenant="prime") as c:
            b, _ = c.sql(Q1)
            check("q1", [tuple(r) for r in b.rows()])
            b, _ = c.sql(TPCDS_Q3)
            check("q3", [tuple(r) for r in b.rows()])
        warm_wall, errors = replay(srv.port, total)
        if errors:
            return {"skipped": True, "reason": errors[:3]}
        rc = srv.stats().get("cache", {}).get("result", {})
    finally:
        srv.shutdown()
    probes = rc.get("hits", 0) + rc.get("misses", 0)
    out = {
        "skipped": False,
        "clean_wall_s": round(clean_wall, 4),
        "replay": {
            "queries": total,
            "coldWall_s": round(cold_wall, 4),
            "coldQps": round(total / cold_wall, 4),
            "warmWall_s": round(warm_wall, 4),
            "warmQps": round(total / warm_wall, 4),
            "qpsSpeedup": round(cold_wall / max(1e-9, warm_wall), 4),
            "hitRate": round(rc.get("hits", 0) / max(1, probes), 4),
            "result": rc,
        },
    }

    # subplan leg: result cache OFF so repeats re-execute, subplan
    # cache ON so the q3 join build tables are reused — the wall delta
    # between the first (building) and best repeated run is the
    # build-time saving
    from spark_rapids_tpu.serve import result_cache as RC
    RC.reset_subplan_cache()
    srv = serve({"spark.rapids.sql.subplanCache.enabled": "true"})
    try:
        walls = []
        with ServeClient(srv.port, tenant="sub") as c:
            for _ in range(3):
                tq = time.perf_counter()
                b, _ = c.sql(TPCDS_Q3)
                walls.append(time.perf_counter() - tq)
                check("q3", [tuple(r) for r in b.rows()])
        sp = srv.stats().get("cache", {}).get("subplan", {})
    finally:
        srv.shutdown()
    out["subplan"] = {
        "buildWall_s": round(walls[0], 4),
        "reuseWall_s": round(min(walls[1:]), 4),
        "buildSpeedup": round(
            walls[0] / max(1e-9, min(walls[1:])), 4),
        "stats": sp,
    }
    return out


def run_lifecycle(clean_wall: float, cpu_rows) -> dict:
    """detail.lifecycle (docs/serving.md "Query lifecycle"): cancel
    latency p50/p99 (cancel verb fired against a running q1; latency =
    cancel send -> status:cancelled on the submitter's wire), a
    deadline leg asserting the cancelled response lands within the
    deadline + one batch interval, graceful-drain wall with in-flight
    queries, and the poison-query quarantine's fail-fast behavior."""
    import threading

    from spark_rapids_tpu import lifecycle as LC
    from spark_rapids_tpu import retry as R
    from spark_rapids_tpu.serve import QueryServer, ServeClient
    from spark_rapids_tpu.serve.client import ServeCancelled, ServeError
    from spark_rapids_tpu.serve.scheduler import percentile
    fresh_leg()
    conf = dict(TPU_CONF)
    conf.update({
        "spark.rapids.sql.serve.maxConcurrentQueries": "4",
        "spark.rapids.sql.serve.maxQueued": "16",
        "spark.rapids.sql.serve.maxConcurrentPerTenant": "4",
    })
    try:
        srv = QueryServer(conf).start()
    except OSError as e:
        return {"skipped": True, "reason": f"cannot bind: {e!r}"}
    cancel_lat: list = []
    completed_before_cancel = 0
    deadline_leg = {}
    try:
        srv.register_view("lineitem", DATA_DIR)
        with ServeClient(srv.port, tenant="warm") as c:
            b, _ = c.sql(Q1)
            assert_rows_match(cpu_rows, [tuple(r) for r in b.rows()])

        # -- cancel latency: q1 runs multiple seconds at SF1, so a
        # cancel fired shortly after submit lands mid-execution
        for i in range(5):
            state = {}
            done = threading.Event()

            def submit(qid=f"bench-cancel-{i}"):
                try:
                    with ServeClient(srv.port, tenant="cancelme") as c:
                        c.sql(Q1, query_id=qid)
                        state["outcome"] = "ok"
                except ServeCancelled:
                    state["t_resp"] = time.perf_counter()
                    state["outcome"] = "cancelled"
                except ServeError as e:
                    state["outcome"] = f"error: {e}"
                finally:
                    done.set()

            t = threading.Thread(target=submit)
            t.start()
            time.sleep(0.3)
            t_cancel = time.perf_counter()
            with ServeClient(srv.port) as cc:
                n = cc.cancel(query_id=f"bench-cancel-{i}",
                              tenant="cancelme")
            done.wait(timeout=120)
            t.join(timeout=10)
            if n and state.get("outcome") == "cancelled":
                cancel_lat.append(state["t_resp"] - t_cancel)
            else:
                completed_before_cancel += 1

        # -- deadline: the cancelled response must land within the
        # deadline + one batch interval (acceptance criterion)
        deadline_ms = 400
        t0 = time.perf_counter()
        try:
            with ServeClient(srv.port, tenant="deadline") as c:
                c.sql(Q1, timeout_ms=deadline_ms)
            deadline_leg = {"outcome": "completed under deadline"}
        except ServeCancelled as e:
            resp_ms = (time.perf_counter() - t0) * 1e3
            deadline_leg = {
                "outcome": "cancelled",
                "reason": e.reason,
                "deadlineMs": deadline_ms,
                "responseMs": round(resp_ms, 1),
                # one batch interval of slack: the checkpoint slice is
                # 50ms; generous bound for the verdict flag
                "withinBound": resp_ms <= deadline_ms + 1000,
            }

        # -- graceful drain with in-flight queries
        def drain_worker(i: int) -> None:
            try:
                with ServeClient(srv.port, tenant=f"drain{i}") as c:
                    c.sql(Q1)
            except ServeError:
                pass  # a straggler cancel is a valid drain outcome

        inflight = []
        for i in range(2):
            t = threading.Thread(target=drain_worker, args=(i,))
            t.start()
            inflight.append(t)
        time.sleep(0.3)
        t0 = time.perf_counter()
        drained = srv.shutdown(timeout=120)
        drain_s = time.perf_counter() - t0
        for t in inflight:
            t.join(timeout=30)
        drain_leg = {"drained": drained, "drain_s": round(drain_s, 3)}
    finally:
        srv.shutdown(timeout=10)

    # -- quarantine: a signature that fails K consecutive times fails
    # fast afterwards (fresh server; IO injection makes every scan
    # runtime-fatal quickly and deterministically)
    R.reset_fault_injection()
    LC.reset_lifecycle()
    qconf = dict(TPU_CONF)
    qconf.update({
        "spark.rapids.sql.test.injectIOError": "1:99",
        "spark.rapids.sql.reader.maxRetries": "1",
        "spark.rapids.sql.serve.quarantineThreshold": "2",
    })
    quarantine = {}
    try:
        qsrv = QueryServer(qconf).start()
        try:
            qsrv.register_view("lineitem", DATA_DIR)
            statuses = []
            fail_fast_ms = None
            for i in range(3):
                t0 = time.perf_counter()
                try:
                    with ServeClient(qsrv.port, tenant="poison") as c:
                        c.sql(Q1)
                    statuses.append("ok")
                except ServeError as e:
                    statuses.append(type(e).__name__)
                    if i == 2:
                        fail_fast_ms = round(
                            (time.perf_counter() - t0) * 1e3, 1)
            quarantine = {
                "statuses": statuses,
                "thirdFailedFast": statuses[2:] == ["ServeQuarantined"],
                "failFastMs": fail_fast_ms,
            }
        finally:
            qsrv.shutdown(timeout=30)
    except OSError as e:
        quarantine = {"skipped": True, "reason": f"cannot bind: {e!r}"}
    finally:
        R.reset_fault_injection()
        LC.reset_lifecycle()

    return {
        "skipped": False,
        "clean_wall_s": round(clean_wall, 4),
        "cancelLatency": {
            "samples": len(cancel_lat),
            "completedBeforeCancel": completed_before_cancel,
            "p50_s": round(percentile(cancel_lat, 0.50), 4),
            "p99_s": round(percentile(cancel_lat, 0.99), 4),
        },
        "deadline": deadline_leg,
        "drain": drain_leg,
        "quarantine": quarantine,
    }


def run_telemetry(clean_wall: float, cpu_rows) -> dict:
    """detail.telemetry (docs/observability.md "Live telemetry"): the
    q1 ring-recorder overhead ratio vs trace fully off (budget
    <= 1.05x — INTERLEAVED walls so machine drift can't masquerade as
    recorder overhead), the Prometheus endpoint's scrape latency while
    c=4 queries run, and one forced slow-query bundle round trip (ring
    dump loads in the trace analyzer, bundle names its condition)."""
    import glob
    import threading

    from spark_rapids_tpu import trace as TR
    from spark_rapids_tpu.sql.session import TpuSparkSession
    from spark_rapids_tpu.telemetry import triggers as TEL
    from spark_rapids_tpu.tools import analyze_trace

    # -- ring-recorder overhead (interleaved best-of) ----------------------
    TR.reset_tracing()
    fresh_leg()
    off = TpuSparkSession(dict(TPU_CONF))
    on = TpuSparkSession({**TPU_CONF,
                          "spark.rapids.sql.trace.enabled": "true",
                          "spark.rapids.sql.trace.mode": "ring"})
    try:
        q_off, q_on = build_query(off), build_query(on)
        run_once(q_off)  # warm (compile caches are process-wide)
        run_once(q_on)
        offs, ons = [], []
        for _ in range(2):
            dt, rows_off = run_once(q_off)
            offs.append(dt)
            dt, rows_on = run_once(q_on)
            ons.append(dt)
        assert_rows_match(cpu_rows, rows_off)
        assert_rows_match(cpu_rows, rows_on)
        ring = TR.ring_active()
        ring_counts = ring.record_counts() if ring is not None else {}
    finally:
        on.stop()
        off.stop()
        TR.reset_tracing()
    out = {
        "skipped": False,
        "clean_wall_s": round(clean_wall, 4),
        "ringWall_s": round(min(ons), 4),
        "offWall_s": round(min(offs), 4),
        "ringOverhead": round(min(ons) / min(offs), 4),
        "ringOverheadBudget": 1.05,
        "ringRecordCounts": ring_counts,
    }

    # -- endpoint scrape under load + forced slow-query bundle -------------
    tdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench-data", "telemetry")
    shutil.rmtree(tdir, ignore_errors=True)
    from spark_rapids_tpu.serve import QueryServer, ServeClient
    TEL.engine().reset()
    conf = dict(TPU_CONF)
    conf.update({
        "spark.rapids.sql.telemetry.dir": tdir,
        # every query is "slow": one forced bundle, then rate-limited
        "spark.rapids.sql.telemetry.slowQueryMs": "1",
        "spark.rapids.sql.telemetry.triggerMinIntervalS": "3600",
        "spark.rapids.sql.profile.enabled": "true",
        "spark.rapids.sql.profile.dir": os.path.join(tdir, "profiles"),
    })
    try:
        srv = QueryServer(conf).start()
    except OSError as e:
        out["endpoint"] = {"skipped": True,
                           "reason": f"cannot bind: {e!r}"}
        return out
    try:
        srv.register_view("lineitem", DATA_DIR)
        stop = threading.Event()
        errors: list = []

        def load_worker(i):
            try:
                with ServeClient(srv.port, tenant=f"t{i % 2}") as c:
                    while not stop.is_set():
                        c.sql(Q1)
            except Exception as e:  # noqa: BLE001 - reported below
                if not stop.is_set():
                    errors.append(repr(e))

        workers = [threading.Thread(target=load_worker, args=(i,))
                   for i in range(4)]
        for w in workers:
            w.start()
        time.sleep(0.5)  # let the first queries land
        scrape_lat = []
        with ServeClient(srv.port, tenant="scraper") as sc:
            for _ in range(20):
                t0 = time.perf_counter()
                text = sc.metrics()
                scrape_lat.append(time.perf_counter() - t0)
        stop.set()
        for w in workers:
            w.join(timeout=120)
        from spark_rapids_tpu.serve.scheduler import percentile
        out["endpoint"] = {
            "scrapes": len(scrape_lat),
            "scrapeLatencyMs": {
                "p50": round(percentile(scrape_lat, 0.50) * 1e3, 3),
                "p99": round(percentile(scrape_lat, 0.99) * 1e3, 3),
            },
            "families": sum(1 for ln in text.splitlines()
                            if ln.startswith("# TYPE ")),
            "loadErrors": errors[:3],
        }
        TEL.engine().drain(timeout=30)
        bundles = sorted(glob.glob(os.path.join(tdir, "bundle-*.json")))
        bundle_leg = {"bundles": len(bundles)}
        if bundles:
            with open(bundles[0]) as f:
                b = json.load(f)
            bundle_leg["trigger"] = b.get("trigger")
            bundle_leg["condition"] = b.get("condition")
            bundle_leg["hasProfile"] = bool(b.get("profile"))
            bundle_leg["hasServerStats"] = bool(b.get("serverStats"))
            ring_dump = b.get("ringDump")
            if ring_dump and os.path.exists(ring_dump):
                analysis = analyze_trace(ring_dump)
                bundle_leg["ringDumpSpans"] = analysis.get(
                    "spanCount", 0)
        out["slowQueryBundle"] = bundle_leg
        out["triggerStats"] = TEL.engine().stats()
        out["triggerStats"].pop("bundles", None)
    finally:
        srv.shutdown()
        TEL.engine().reset()
        TR.reset_tracing()
    return out


def run_history(clean_wall: float, cpu_rows) -> dict:
    """detail.history (docs/observability.md "Query history"): the q1
    history-append overhead ratio (interleaved on/off walls, budget
    <= 1.05x), a doctor round trip on a FORCED slow query (OOM storm
    injected via the process injector while the session conf — and so
    the plan signature — stays identical to the baseline runs), and a
    warm-start leg proving the watchdog p99 is available with ZERO
    fresh samples after a lifecycle reset."""
    from spark_rapids_tpu import lifecycle as LC
    from spark_rapids_tpu import retry as R
    from spark_rapids_tpu.sql.session import TpuSparkSession
    from spark_rapids_tpu.telemetry import history as H
    from spark_rapids_tpu.telemetry.doctor import diagnose

    hdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench-data", "history")
    shutil.rmtree(hdir, ignore_errors=True)
    H.reset_history()
    LC.reset_lifecycle()
    R.reset_fault_injection()
    fresh_leg()

    # -- append overhead (interleaved best-of; the sessions differ in
    # ONE variable — history.dir — so the ratio measures the append
    # path alone, not profile writing or plan-cache savings) ---------------
    off = TpuSparkSession({
        **TPU_CONF,
        "spark.rapids.sql.planCache.enabled": "true",
    })
    on = TpuSparkSession({
        **TPU_CONF,
        "spark.rapids.sql.planCache.enabled": "true",
        "spark.rapids.sql.telemetry.history.dir": hdir,
    })
    prof_conf = {
        **TPU_CONF,
        "spark.rapids.sql.planCache.enabled": "true",
        "spark.rapids.sql.telemetry.history.dir": hdir,
        "spark.rapids.sql.profile.enabled": "true",
        "spark.rapids.sql.profile.dir": os.path.join(hdir, "profiles"),
        # consulted only when retries happen — harmless on the clean
        # baseline runs, but it must live in the BASELINE conf too so
        # the storm session's plan signature matches
        "spark.rapids.sql.retry.backoffMs": "20",
        "spark.rapids.sql.retry.maxBackoffMs": "200",
    }
    prof = TpuSparkSession(prof_conf)
    try:
        q_off, q_on = build_query(off), build_query(on)
        run_once(q_off)  # warm
        run_once(q_on)
        offs, ons = [], []
        for _ in range(2):
            dt, rows_off = run_once(q_off)
            offs.append(dt)
            dt, rows_on = run_once(q_on)
            ons.append(dt)
        assert_rows_match(cpu_rows, rows_off)
        assert_rows_match(cpu_rows, rows_on)

        # -- doctor round trip on a forced slow query ----------------------
        # baseline runs with profile artifacts (the doctor's stage
        # source), then the storm on a session whose conf adds ONLY
        # the injection schedule — test.inject* keys are excluded from
        # the plan signature, so the storm query diffs against these
        # baselines, exactly the situation `tools doctor` exists for
        q_prof = build_query(prof)
        # 4 baselines + the storm = 5 finished records for this
        # signature, the watchdog's minimum sample count — so the
        # warm-start leg below proves p99 availability
        for _ in range(4):
            _, base_rows = run_once(q_prof)
        assert_rows_match(cpu_rows, base_rows)
        storm_sess = TpuSparkSession({
            **prof_conf,
            "spark.rapids.sql.test.injectOOM": "4:2",
        })
        try:
            q_storm = build_query(storm_sess)
            t0 = time.perf_counter()
            _, storm_rows = run_once(q_storm)
            storm_wall = time.perf_counter() - t0
            assert_rows_match(cpu_rows, storm_rows)
        finally:
            storm_sess.stop()
            R.reset_fault_injection()
        recs = H.read_records(hdir)
        storm = recs[-1]
        t0 = time.perf_counter()
        diag = diagnose(hdir, str(storm.get("queryId")))
        doctor_ms = (time.perf_counter() - t0) * 1e3
        doctor_leg = {
            "records": len(recs),
            "stormWall_s": round(storm_wall, 4),
            "stormRetries": storm.get("retryCount", 0),
            "verdict": diag.get("verdict"),
            "divergentStage": diag.get("divergentStage"),
            "roundTripMs": round(doctor_ms, 1),
        }

        # -- warm-start: watchdog p99 with zero fresh samples --------------
        sig = storm.get("signature")
        LC.reset_lifecycle()  # the "restart"
        assert LC.signature_p99(sig) is None
        ws = H.warm_start(on.conf_obj)
        warm_leg = {
            "summary": ws,
            "p99AvailableWithZeroFreshSamples":
                LC.signature_p99(sig) is not None,
        }
    finally:
        prof.stop()
        on.stop()
        off.stop()
        R.reset_fault_injection()
        LC.reset_lifecycle()
        H.reset_history()
    return {
        "skipped": False,
        "clean_wall_s": round(clean_wall, 4),
        "historyWall_s": round(min(ons), 4),
        "offWall_s": round(min(offs), 4),
        "appendOverhead": round(min(ons) / min(offs), 4),
        "appendOverheadBudget": 1.05,
        "doctor": doctor_leg,
        "warmStart": warm_leg,
    }


def run_tuning(clean_wall: float, cpu_rows) -> dict:
    """detail.tuning (docs/tuning.md): the feedback-control loop end
    to end. A forced compileStorm verdict (synthetic regressed record
    with a jit-miss storm) puts the q1 signature in the pre-warm
    ledger and a server RESTART serves the first request from the
    pre-warmed plan cache; a site:tuning injected harmful action
    auto-reverts within the guard window (visible in the stats, the
    history store, srt_tuning_* and the `tools tuning` table).
    The controller tick interval is parked at 3600s so the LEG drives
    every tick — each phase is deterministic, not timing-dependent."""
    from spark_rapids_tpu import lifecycle as LC
    from spark_rapids_tpu import plan_cache as PC
    from spark_rapids_tpu import retry as R
    from spark_rapids_tpu.plan_cache import PLAN_CACHE
    from spark_rapids_tpu.serve import QueryServer, ServeClient
    from spark_rapids_tpu.telemetry import history as H
    from spark_rapids_tpu.telemetry import tuning as T

    hdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench-data", "tuning")
    shutil.rmtree(hdir, ignore_errors=True)
    H.reset_history()
    R.reset_fault_injection()
    fresh_leg()
    conf = {
        **TPU_CONF,
        "spark.rapids.sql.planCache.enabled": "true",
        "spark.rapids.sql.telemetry.history.dir": hdir,
        "spark.rapids.sql.serve.tuning.enabled": "true",
        "spark.rapids.sql.serve.tuning.intervalS": "3600",
        "spark.rapids.sql.serve.tuning.guardWindowQueries": "2",
        # the 3rd scan tick applies the synthetic harmful action
        "spark.rapids.sql.test.injectOOM": "site:tuning:3",
    }

    def new_server():
        srv = QueryServer(dict(conf))
        srv.register_view("lineitem", DATA_DIR)
        return srv.start()

    def run_q1(client):
        t0 = time.perf_counter()
        b, _h = client.sql(Q1)
        dt = time.perf_counter() - t0
        assert_rows_match(cpu_rows, [tuple(r) for r in b.rows()])
        return dt

    try:
        srv = new_server()  # tick 1: empty history, no actions
    except OSError as e:
        return {"skipped": True, "reason": f"cannot bind: {e!r}"}
    try:
        # -- learn: q1 records + the sql<->signature pairing ---------------
        with ServeClient(srv.port, tenant="bench") as c:
            cold_first_s = run_q1(c)
            for _ in range(2):
                run_q1(c)
        tun = srv._tuning
        sig = tun.signature_hint(Q1)
        store = H.HistoryStore(hdir, 1 << 30, 14)
        walls = sorted(float(r.get("wallSeconds", 0))
                       for r in H.read_records(hdir)
                       if r.get("signature") == sig)
        p50 = walls[len(walls) // 2]

        # -- forced compileStorm: a synthetic regressed record with a
        # jit-miss storm makes the doctor verdict deterministic ------------
        store.append({"version": 1, "ts": time.time(), "signature": sig,
                      "status": "finished",
                      "wallSeconds": 3 * p50 + 0.05,
                      "queueWaitSeconds": 0.0, "outputRows": 4,
                      "jitMisses": 64})
        tun.tick()  # tick 2: applies prewarmCaches for sig
        prewarmed = sig in (T.load_state(hdir).get("prewarm") or {})

        tun.tick()  # tick 3: site:tuning fires -> harmful clamp on sig
        injected = [a for a in tun.actions()
                    if (a.get("evidence") or {}).get("injected")]
        clamped = srv._admission.signature_limit(sig)

        # guard window: two clean post-action q1 runs, then the judge
        with ServeClient(srv.port, tenant="bench") as c:
            for _ in range(2):
                run_q1(c)
        tun.tick()  # tick 4: guardrail reverts the injected action
        reverted = [a for a in tun.actions()
                    if (a.get("evidence") or {}).get("injected")
                    and a.get("state") == "reverted"]
        guard = {
            "injected": len(injected),
            "clampApplied": clamped == 1,
            "autoReverted": 1.0 if reverted else 0.0,
            "clampCleared": srv._admission.signature_limit(sig) is None,
            "revertVisible": {
                "metrics": "srt_tuning_reverts_total 1"
                           in srv.metrics_text(),
                "history": any(r.get("status") == "revert"
                               for r in H.read_records(hdir)),
                "cli": "reverted" in T.format_tuning(T.load_state(hdir)),
            },
        }

        stats_before_restart = srv.stats().get("tuning") or {}
    finally:
        srv.shutdown()

    # -- restart: persisted actions re-apply, the pre-warm ledger
    # replays, and the FIRST request hits the plan cache ------------------
    PLAN_CACHE.clear()
    LC.reset_lifecycle()
    R.reset_fault_injection()
    try:
        srv = new_server()
        try:
            replayed = srv._tuning.prewarm_replayed
            h0 = PLAN_CACHE.hits
            with ServeClient(srv.port, tenant="bench") as c:
                warm_first_s = run_q1(c)
            hit = PLAN_CACHE.hits - h0
            prewarm_leg = {
                "ledgered": prewarmed,
                "replayed": replayed,
                "hitOnRestart": 1.0 if hit >= 1 else 0.0,
                "firstRequestCold_s": round(cold_first_s, 4),
                "firstRequestWarm_s": round(warm_first_s, 4),
                "restartSpeedup": round(cold_first_s / warm_first_s, 4),
            }
        finally:
            srv.shutdown()
    finally:
        PC.set_prewarm_digests(set())
        PLAN_CACHE.clear()
        LC.reset_lifecycle()
        R.reset_fault_injection()
        H.reset_history()
    return {
        "skipped": False,
        "clean_wall_s": round(clean_wall, 4),
        "prewarm": prewarm_leg,
        "guard": guard,
        "controller": stats_before_restart,
    }


def _adaptive_skew_query(spark):
    """A shuffled join with ONE hot key at ~20x the median partition
    (48 base keys spread the other partitions; the right side is small
    but broadcast is disabled in the leg conf, so the skew-split replan
    is the adaptive action under test)."""
    rep = 24
    lk = [100 + (i % 48) for i in range(48 * rep)]
    lk += [7] * (rep * 12 * 20)
    lv = list(range(len(lk)))
    rk = list(range(100, 148)) * 2 + [7, 7]
    rw = [i * 10 for i in range(len(rk))]
    left = spark.createDataFrame({"k": lk, "v": lv}, "k int, v long",
                                 num_partitions=3)
    right = spark.createDataFrame({"k2": rk, "w": rw},
                                  "k2 int, w long", num_partitions=2)
    from spark_rapids_tpu.sql import functions as F
    return (left.join(right, left["k"] == right["k2"], "inner")
            .groupBy("k").agg(F.sum("v").alias("sv"),
                              F.sum("w").alias("sw"),
                              F.count("*").alias("c"))
            .orderBy("k"))


def run_adaptive(clean_wall: float) -> dict:
    """detail.adaptive (docs/adaptive.md): (a) skewed-join wall A/B —
    the adaptive run skew-splits the hot partition and completes clean
    (retryCount == 0) while the unadaptive run of the same shape rides
    an injected OOM storm (the CPU backend's DeviceStore spills instead
    of raising, so the deterministic storm stands in for the monolithic
    hot partition blowing HBM on real hardware, exactly like
    detail.robustness) — both bit-identical to the CPU oracle;
    (b) AQE partition coalescing on a mostly-empty exchange: dispatch
    count adaptive-on vs adaptive-off; (c) same-signature serving: 16
    concurrent same-template queries (distinct literal bindings)
    through the server with batch fusion on vs off under ONE saturated
    admission slot, bit-identical per member."""
    import threading

    from spark_rapids_tpu import retry as RT
    from spark_rapids_tpu.sql.session import TpuSparkSession

    out = {"skipped": False, "clean_wall_s": round(clean_wall, 4)}

    # -- (a) skewed-join wall A/B -------------------------------------
    skew_conf = dict(TPU_CONF)
    skew_conf.update({
        "spark.rapids.sql.autoBroadcastJoinThreshold": "-1",
        "spark.rapids.sql.shuffle.devicePartitions": "4",
        "spark.rapids.sql.batchSizeRows": "512",
        "spark.rapids.sql.retry.backoffMs": "40",
        "spark.rapids.sql.retry.maxBackoffMs": "400",
    })
    cpu = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        _, skew_oracle = run_once(_adaptive_skew_query(cpu))
    finally:
        cpu.stop()

    def skew_leg(extra):
        RT.reset_fault_injection()
        fresh_leg()
        conf = dict(skew_conf)
        conf.update(extra)
        spark = TpuSparkSession(conf)
        try:
            q = _adaptive_skew_query(spark)
            run_once(q)  # warm compile caches
            RT.reset_fault_injection()
            spark.start_capture()
            dt, rows = run_once(q)
            assert_rows_match(skew_oracle, rows)
            counters = collect_counters(
                spark.get_captured_plans(),
                ("retryCount", "splitRetryCount", "aqeReplans",
                 "aqeSkewSplits", "aqeBroadcastFlip"))
        finally:
            spark.stop()
            RT.reset_fault_injection()
        return dt, counters

    on_dt, on_c = skew_leg({})
    off_dt, off_c = skew_leg({
        "spark.rapids.sql.adaptive.enabled": "false",
        "spark.rapids.sql.test.injectOOM": "5"})
    assert on_c["retryCount"] == 0, on_c
    assert on_c["aqeSkewSplits"] > 0, on_c
    out["skew"] = {
        "adaptive_wall_s": round(on_dt, 4),
        "unadaptive_wall_s": round(off_dt, 4),
        "speedup": round(off_dt / on_dt, 4),
        "retryCount_adaptive": on_c["retryCount"],
        "retryCount_unadaptive": off_c["retryCount"],
        "aqeSkewSplits": on_c["aqeSkewSplits"],
        "aqeReplans": on_c["aqeReplans"],
    }

    # -- (b) coalesce dispatch delta ----------------------------------
    coalesce_conf = dict(TPU_CONF)
    coalesce_conf.update({
        "spark.rapids.sql.shuffle.devicePartitions": "8",
        "spark.rapids.sql.batchSizeRows": "512",
    })

    def coalesce_query(spark):
        from spark_rapids_tpu.sql import functions as F
        df = spark.createDataFrame(
            {"g": [i % 3 for i in range(3000)],
             "v": list(range(3000))}, "g int, v long",
            num_partitions=4)
        return df.groupBy("g").agg(F.sum("v").alias("sv")) \
                 .orderBy("g")

    def coalesce_leg(extra):
        fresh_leg()
        conf = dict(coalesce_conf)
        conf.update(extra)
        spark = TpuSparkSession(conf)
        try:
            q = coalesce_query(spark)
            run_once(q)
            spark.start_capture()
            dt, rows = run_once(q)
            counters = collect_counters(
                spark.get_captured_plans(),
                ("dispatchCount", "aqeCoalescedPartitions"))
        finally:
            spark.stop()
        return dt, rows, counters

    c_on_dt, c_on_rows, c_on = coalesce_leg({})
    c_off_dt, c_off_rows, c_off = coalesce_leg(
        {"spark.rapids.sql.adaptive.enabled": "false"})
    assert_rows_match(c_off_rows, c_on_rows)
    out["coalesce"] = {
        "adaptive_wall_s": round(c_on_dt, 4),
        "unadaptive_wall_s": round(c_off_dt, 4),
        "dispatchCount_adaptive": c_on["dispatchCount"],
        "dispatchCount_unadaptive": c_off["dispatchCount"],
        "dispatchDelta": c_off["dispatchCount"] - c_on["dispatchCount"],
        "aqeCoalescedPartitions": c_on["aqeCoalescedPartitions"],
    }

    # -- (c) same-signature batch fusion QPS A/B ----------------------
    from spark_rapids_tpu.serve import QueryServer, ServeClient

    def variant(i):
        return ("SELECT l_returnflag, count(*) AS c, "
                "sum(l_quantity) AS sq FROM lineitem "
                f"WHERE l_quantity > {i}00 "
                "GROUP BY l_returnflag ORDER BY l_returnflag")

    def fusion_leg(enabled):
        fresh_leg()
        conf = dict(TPU_CONF)
        conf.update({
            "spark.rapids.sql.serve.maxConcurrentQueries": "1",
            "spark.rapids.sql.serve.maxQueued": "64",
            "spark.rapids.sql.serve.maxConcurrentPerTenant": "32",
            "spark.rapids.sql.serve.batchFusion.enabled":
                "true" if enabled else "false",
            "spark.rapids.sql.serve.batchFusion.windowMs": "50",
            "spark.rapids.sql.serve.batchFusion.maxBatch": "16",
        })
        try:
            srv = QueryServer(conf).start()
        except OSError as e:
            return None, {"skipped": True,
                          "reason": f"cannot bind: {e!r}"}
        results: dict = {}
        errors: list = []
        try:
            srv.register_view("lineitem", DATA_DIR)
            with ServeClient(srv.port, tenant="warmup") as c:
                for i in range(4):
                    results[f"warm{i}"] = c.collect(variant(i))

            def worker(i):
                try:
                    with ServeClient(srv.port,
                                     tenant=f"t{i % 4}") as c:
                        results[i] = c.collect(variant(i % 4))
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))

            t0 = time.perf_counter()
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errors:
                return None, {"errors": errors[:3]}
            for i in range(16):
                assert results[i] == results[f"warm{i % 4}"], (
                    f"fusion={enabled}: member {i} diverged")
            st = srv.stats()
            leg = {"wall_s": round(wall, 4),
                   "qps": round(16 / wall, 4)}
            if enabled:
                leg["batchFusion"] = st.get("batchFusion", {})
            return results, leg
        finally:
            srv.shutdown()

    r_off, leg_off = fusion_leg(False)
    r_on, leg_on = fusion_leg(True)
    fusion = {"off": leg_off, "on": leg_on}
    if r_on is not None and r_off is not None:
        for i in range(16):
            assert r_on[i] == r_off[i], (
                f"fusion on/off diverged on member {i}")
        fusion["qpsSpeedup"] = round(
            leg_on["qps"] / leg_off["qps"], 4)
    out["batchFusion"] = fusion
    return out


def run_bench_diff(current: dict) -> dict:
    """Regression tracking: diff THIS run's output against the newest
    BENCH_r0*.json in the repo (docs/observability.md 'Live
    telemetry'); the machine verdict rides in the bench JSON so the
    round trajectory is an enforced curve, not loose files."""
    from spark_rapids_tpu.telemetry.bench_diff import (bench_diff,
                                                      latest_bench_file)
    prev = latest_bench_file(os.path.dirname(os.path.abspath(__file__)))
    if prev is None:
        return {"skipped": True, "reason": "no previous BENCH_r*.json"}
    report = bench_diff(prev, current)
    return {
        "skipped": False,
        "baseline": os.path.basename(prev),
        "verdict": report["verdict"],
        "regressed": report["regressed"],
        "improved": report["improved"],
        "compared": len(report["checks"]),
        "notComparable": len(report["missing"]),
    }


def run_leg(failed: list, label: str, fn, *args) -> dict:
    """Fault-isolated detail leg: a leg that raises must not discard
    the measured primary results, so its failure rides in the JSON —
    but it is a failure, not a skip: its label joins ``failed`` and
    main() exits non-zero."""
    t0 = time.perf_counter()
    try:
        out = fn(*args)
    except Exception as e:  # noqa: BLE001 - reported and exit code set
        import traceback
        traceback.print_exc()
        failed.append(label)
        out = {"failed": True, "reason": f"{label} failed: {e!r}"}
    # progress on stderr: a run that is cut short still says how far it got
    state = ("FAILED" if out.get("failed") else
             "skipped" if out.get("skipped") else "ok")
    print(f"[bench] {label}: {state} in {time.perf_counter() - t0:.1f}s",
          file=sys.stderr, flush=True)
    return out


def main():
    import jax

    from spark_rapids_tpu.metrics import registry_snapshot
    from spark_rapids_tpu.sql.session import TpuSparkSession

    t_start = time.perf_counter()
    failed_legs: list = []

    def progress(phase: str) -> None:
        print(f"[bench] {phase} done at "
              f"{time.perf_counter() - t_start:.1f}s",
              file=sys.stderr, flush=True)

    gen = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    ensure_data(gen)
    ensure_tpcds_data(gen)
    gen.stop()
    progress("data")

    cpu = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    q_cpu = build_query(cpu)
    run_once(q_cpu)  # warm (footer caches, numpy paths)
    cpu_times, cpu_rows = [], None
    for _ in range(3):
        dt, cpu_rows = run_once(q_cpu)
        cpu_times.append(dt)
    q3_cpu_t, q3_cpu_rows, _, _ = run_tpcds_q3(cpu)
    cpu.stop()
    progress("CPU engine passes")

    # unfused FIRST (its compile misses don't warm fused-stage
    # programs; the fused pass compiles its own)
    unfused = run_tpu(fusion_enabled=False)
    progress("unfused device pass")
    fused = run_tpu(fusion_enabled=True)
    progress("fused device pass")

    assert_rows_match(cpu_rows, fused["rows"])
    assert_rows_match(cpu_rows, unfused["rows"])
    assert_rows_match(q3_cpu_rows, fused["q3"]["rows"])
    assert_rows_match(q3_cpu_rows, unfused["q3"]["rows"])

    # decode A/B legs (host decode / unpipelined), fault-isolated like
    # every other detail leg
    decode_ab = run_leg(failed_legs, "decode A/B leg", run_decode_ab,
                        fused["wall_s"], cpu_rows)

    # AFTER the primary asserts, and fault-isolated: a multichip-leg
    # failure must not discard the measured single-chip results
    multichip = run_leg(failed_legs, "multichip leg", run_multichip,
                        fused["wall_s"], cpu_rows)

    # robustness sweep, equally fault-isolated
    robustness = run_leg(failed_legs, "robustness leg", run_robustness,
                         fused["wall_s"], cpu_rows)

    # planned out-of-core sweep (docs/out_of_core.md): 1x/4x/10x over
    # budget, gated on the planned path staying retry-free
    out_of_core_leg = run_leg(failed_legs, "out-of-core leg", run_out_of_core,
                              fused["wall_s"], cpu_rows)

    # span-tracing leg (docs/observability.md), equally fault-isolated
    trace_leg = run_leg(failed_legs, "trace leg", run_trace, fused["wall_s"],
                        cpu_rows)

    # query-profile leg (per-op peak HBM + explain coverage)
    profile_leg = run_leg(failed_legs, "profile leg", run_profile,
                          fused["wall_s"], cpu_rows)

    # serving leg (docs/serving.md): QPS/latency through the query
    # server at concurrency 1/4/16, equally fault-isolated
    serving = run_leg(failed_legs, "serving leg", run_serving, fused["wall_s"],
                      cpu_rows, q3_cpu_rows)

    # live-telemetry leg (docs/observability.md "Live telemetry"):
    # ring-recorder overhead, endpoint scrape-under-load latency, one
    # forced slow-query bundle round trip — equally fault-isolated
    telemetry_leg = run_leg(failed_legs, "telemetry leg", run_telemetry,
                            fused["wall_s"], cpu_rows)

    # query-lifecycle leg (docs/serving.md "Query lifecycle"): cancel
    # latency, deadline bound, drain wall, quarantine fail-fast
    lifecycle_leg = run_leg(failed_legs, "lifecycle leg", run_lifecycle,
                            fused["wall_s"], cpu_rows)

    # query-history leg (docs/observability.md "Query history"):
    # append overhead, doctor round trip on a forced slow query,
    # warm-start watchdog availability — equally fault-isolated
    history_leg = run_leg(failed_legs, "history leg", run_history,
                          fused["wall_s"], cpu_rows)

    # self-tuning leg (docs/tuning.md): forced compileStorm pre-warm
    # hit on restart, injected harmful action auto-reverted by the
    # guardrail
    tuning_leg = run_leg(failed_legs, "tuning leg", run_tuning,
                         fused["wall_s"], cpu_rows)

    # adaptive-execution leg (docs/adaptive.md): skewed-join replan
    # A/B, coalesce dispatch delta, same-signature batch-fusion QPS
    adaptive_leg = run_leg(failed_legs, "adaptive leg", run_adaptive,
                           fused["wall_s"])

    # result + subplan cache leg (docs/caching.md): dashboard-replay
    # warm-vs-cold QPS at c=16, hit rates, join build reuse delta
    result_cache_leg = run_leg(failed_legs, "result-cache leg",
                               run_result_cache, fused["wall_s"], cpu_rows,
                               q3_cpu_rows)

    cpu_t = min(cpu_times)
    tpu_t = fused["wall_s"]
    q3_tpu_t = fused["q3"]["wall_s"]
    speedup = cpu_t / tpu_t
    result = {
        "metric": "tpch_q1_sf1_parquet",
        "value": round(N_ROWS / tpu_t, 1),
        "unit": "rows/s",
        "vs_baseline": round(speedup / REFERENCE_TYPICAL_SPEEDUP, 4),
        "detail": {
            "device_wall_s": round(tpu_t, 4),
            "cpu_engine_wall_s": round(cpu_t, 4),
            "speedup_vs_cpu_engine": round(speedup, 4),
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
            "rows": N_ROWS,
            "stages": fused["stages"],
            "decode": {**fused["decode"], "ab": decode_ab,
                       "overlap": trace_leg.get("scanOverlap")},
            "fusion": {
                "q1_fused_wall_s": fused["wall_s"],
                "q1_unfused_wall_s": unfused["wall_s"],
                "q1_fusion_speedup": round(
                    unfused["wall_s"] / fused["wall_s"], 4),
                "q3_fused_wall_s": fused["q3"]["wall_s"],
                "q3_unfused_wall_s": unfused["q3"]["wall_s"],
                "q3_fusion_speedup": round(
                    unfused["q3"]["wall_s"] / fused["q3"]["wall_s"], 4),
                "dispatchCount_fused": fused["dispatchCount"],
                "dispatchCount_unfused": unfused["dispatchCount"],
                "fusedOps": fused["fusedOps"],
                "stageCompileTime_s": fused["stageCompileTime_s"],
                "unfused_stages": unfused["stages"],
            },
            "multichip": multichip,
            "robustness": robustness,
            "outOfCore": out_of_core_leg,
            "trace": trace_leg,
            "profile": profile_leg,
            "serving": serving,
            "telemetry": telemetry_leg,
            "lifecycle": lifecycle_leg,
            "history": history_leg,
            "tuning": tuning_leg,
            "adaptive": adaptive_leg,
            "resultCache": result_cache_leg,
            "jitCaches": registry_snapshot()["jitCaches"],
            "tpcds_q3": {
                "device_wall_s": round(q3_tpu_t, 4),
                "cpu_engine_wall_s": round(q3_cpu_t, 4),
                "speedup_vs_cpu_engine": round(q3_cpu_t / q3_tpu_t, 4),
                "rows": TPCDS_ROWS,
                "stages": fused["q3"]["stages"],
                "decode": fused["q3"]["decode"],
            },
        },
    }
    # regression verdict vs the previous round rides IN the output
    # (fault-isolated: a differ failure must not discard the results)
    telemetry_leg["benchDiff"] = run_leg(failed_legs, "bench-diff",
                                         run_bench_diff, result)
    result["detail"]["failedLegs"] = failed_legs
    print(json.dumps(result))
    if failed_legs:
        sys.exit(1)


if __name__ == "__main__":
    main()
