"""PR 36: every row of query 51's windows and full outer join held to the plain
reference at the cell's own size. ``correct`` compares the statement's 100
LIMIT rows, the first dozen of 18,000 items; a fault far down the sorted
stream (a carry, a prefix, a lost unmatched row) would pass it. This runs the
statement once without its WHERE, ORDER BY and LIMIT, some 683 thousand rows
of ``y``, under the cell's conf, and compares every row with
``reference.whole_relation``. Exits non-zero on any difference or fallback.

    chiprun -- python3 docs/profiles/pr36/chip_full_relation.py 2147484301
(``JAX_PLATFORMS=cpu SCALE=0.02`` rehearses it.)"""
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())

SEED = int(sys.argv[1]) if len(sys.argv) > 1 else 2147484301
CUT = "where web_cumulative > store_cumulative"


def main() -> int:
    import jax

    from benchmarks.harness import cell as C
    from benchmarks.harness.compare import first_difference, normal_rows
    from spark_rapids_tpu.sql.session import TpuSparkSession

    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    print("device", jax.devices()[0], flush=True)
    cell = C.load_cell("q51_sf1_batch", SEED,
                       float(os.environ.get("SCALE", "1.0")) if rehearsal
                       else 1.0)
    data_root = os.path.join(".bench-data", "perf", "q51-whole")
    shutil.rmtree(data_root, ignore_errors=True)
    cell.generate()
    cell.write(data_root)
    text = cell.sql(0)
    assert text.count(CUT) == 1
    text = text[:text.index(CUT)]
    spark = TpuSparkSession(dict(cell.config["conf"]))
    try:
        for table, path in cell.paths.items():
            spark.read.parquet(path).createOrReplaceTempView(table)
        t = time.perf_counter()
        got = sorted(normal_rows(
            [tuple(r) for r in spark.sql(text).collect()]),
            key=lambda r: r[:2])
        t_engine = time.perf_counter() - t
        fallbacks = [str(f) for f in spark.last_rewrite_report.fallbacks]
    finally:
        spark.stop()
        shutil.rmtree(data_root, ignore_errors=True)
    ref = C.load_module(os.path.join(cell.config_dir, "reference.py"),
                        "q51_reference")
    t = time.perf_counter()
    want = ref.whole_relation(cell.tables, cell.bindings[0])
    t_ref = time.perf_counter() - t
    wrong = sum(w != g for w, g in zip(want, got))
    print(f"seed {SEED}: {len(got)} rows of y from the engine in "
          f"{t_engine:.1f} s (the first run compiles), {len(want)} from the "
          f"reference in {t_ref:.1f} s; web-only {sum(r[3] is None for r in want)}"
          f", store-only {sum(r[2] is None for r in want)}, both "
          f"{sum(r[2] is not None and r[3] is not None for r in want)}; null "
          f"web_cumulative {sum(r[4] is None for r in want)}; greatest "
          f"unscaled sum {max(r[5][0] for r in want if r[5])}; rows that "
          f"differ {wrong}; fallbacks {len(fallbacks)}", flush=True)
    if len(want) != len(got) or wrong or fallbacks:
        print("NOT EQUAL:", first_difference(want, got), fallbacks[:3])
        return 1
    print("every row equal")
    return 0


if __name__ == "__main__":
    sys.exit(main())
