#!/usr/bin/env python3
"""Two readings of ``q67_sf1_batch`` on the chip that the cell's own run
cannot give, in one process over one seed's tables:

1. **The whole relation.** ``correct`` compares the statement's 100 LIMIT
   rows: the null category's partition, the coarse half of the nine
   grouping levels. This runs the statement once without ``where rk <=
   100``, its ORDER BY and LIMIT (``dw2``: every group of every level,
   ranked, about 0.76 million rows) under the cell's conf, and compares
   every row with ``reference.ranked(reference.levels(...))`` as a
   multiset: keys, sum and rank. It prints the rows by level (the number
   of rolled-up or null keys) and by partition.
2. **The stall hunt.** The statement back to back for ``HUNT_SECONDS``
   (default 420), each query under its own ``jax.profiler`` trace and
   held to ``reference.answer``. A query slower than ``STALL_SHARE``
   (default 1.15) times the median before it keeps its profile (the
   first ``MAX_KEPT``, default 6), as does the third query, the
   baseline; every other profile is deleted. For
   each kept profile it prints busy and idle seconds, idle by the host
   span that owns it, the longest gaps and device seconds by program,
   the stalled query's beside the baseline's, and writes the ``tools
   trace`` report to ``chiprun_out/q67_sf1/hunt.<n>.txt``.

On the chip, from the root of a checkout (the whole relation's first run
compiles its programs, some four minutes, then ``HUNT_SECONDS``):

    python3 docs/profiles/q67_sf1/chip_witness.py 2147500201

Exits non-zero on any difference, wrong answer or fallback.
``JAX_PLATFORMS=cpu SCALE=0.02 HUNT_SECONDS=20`` rehearses it."""
import json
import os
import shutil
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

SEED = int(sys.argv[1]) if len(sys.argv) > 1 else 2147500201
CUT = "where rk <= 100"
OUT = os.path.join("chiprun_out", "q67_sf1")


def sort_key(row) -> tuple:
    return tuple((0, 0) if v is None else (1, v) for v in row)


def whole_relation(spark, cell, ref) -> int:
    text = cell.sql(0)
    assert text.count(CUT) == 1
    text = text[:text.index(CUT)]
    from benchmarks.harness.compare import first_difference, normal_rows
    t = time.perf_counter()
    got = sorted(normal_rows([tuple(r) for r in spark.sql(text).collect()]),
                 key=sort_key)
    t_engine = time.perf_counter() - t
    fallbacks = [str(f) for f in spark.last_rewrite_report.fallbacks]
    t = time.perf_counter()
    rows = ref.ranked(ref.levels(cell.tables, cell.bindings[0]))
    want = sorted((tuple(r[:8]) + ((r[8], ref.SCALE), r[9]) for r in rows),
                  key=sort_key)
    t_ref = time.perf_counter() - t
    by_nulls, by_part = {}, {}
    for r in want:
        n = sum(v is None for v in r[:8])
        by_nulls[n] = by_nulls.get(n, 0) + 1
        by_part[str(r[0])] = by_part.get(str(r[0]), 0) + 1
    wrong = sum(w != g for w, g in zip(want, got))
    print(json.dumps({
        "witness": "dw2", "seed": SEED, "engine_rows": len(got),
        "reference_rows": len(want), "engine_s": round(t_engine, 3),
        "reference_s": round(t_ref, 3), "rows_differ": wrong,
        "fallbacks": len(fallbacks),
        "rows_by_null_keys": dict(sorted(by_nulls.items())),
        "rows_by_partition": by_part,
        "largest_rank": max(r[9] for r in want)}), flush=True)
    if len(want) != len(got) or wrong or fallbacks:
        print("NOT EQUAL:", first_difference(want, got), fallbacks[:3])
        return 1
    print("every row of dw2 equal", flush=True)
    return 0


def summary(profile_dir: str) -> dict:
    from spark_rapids_tpu import tools as TL
    files = TL.profile_files(profile_dir)
    a = TL.analyze_profile(files[-1])
    if not a["chips"]:
        return {"file": files[-1], "chips": 0}
    c = next(iter(a["chips"].values()))
    progs = c["device"]["byProgram"]
    return {"file": files[-1], "busy_s": c["busy_s"], "idle_s": c["idle_s"],
            "idle_by_kind_s": dict(list(c["idleByKind_s"].items())[:6]),
            "longest_gaps": c["longestGaps"][:4],
            "device_s_by_program": dict(sorted(
                progs.items(), key=lambda kv: -kv[1])[:12])}


def hunt(spark, cell, ref) -> int:
    import jax

    from benchmarks.harness.compare import normal_rows
    from spark_rapids_tpu import tools as TL
    seconds = float(os.environ.get("HUNT_SECONDS", "420"))
    share = float(os.environ.get("STALL_SHARE", "1.15"))
    max_kept = int(os.environ.get("MAX_KEPT", "6"))
    text = cell.sql(0)
    want = ref.answer(cell.tables, cell.bindings[0])
    root = os.path.join(".bench-data", "perf", "q67-hunt")
    shutil.rmtree(root, ignore_errors=True)
    lats, kept, bad = [], [], 0
    for _ in range(2):                           # warm, untraced
        spark.sql(text).collect()
    t_end = time.perf_counter() + seconds
    i = 0
    while time.perf_counter() < t_end:
        d = os.path.join(root, f"q{i}")
        jax.profiler.start_trace(d)
        t = time.perf_counter()
        rows = normal_rows([tuple(r) for r in spark.sql(text).collect()])
        lat = time.perf_counter() - t
        jax.profiler.stop_trace()
        ok = rows == want
        bad += not ok
        med = statistics.median(lats) if len(lats) >= 4 else None
        stalled = med is not None and lat > share * med
        lats.append(lat)
        print(json.dumps({"q": i, "lat_s": round(lat, 4), "ok": ok,
                          "stalled": stalled}), flush=True)
        if (stalled and len(kept) <= max_kept) or i == 2:
            s = summary(d)
            s.update(q=i, lat_s=lat, median_before_s=med, stalled=stalled)
            kept.append(s)
            with open(os.path.join(OUT, f"hunt.{i}.txt"), "w") as f:
                f.write(TL.format_profile_report(
                    TL.profile_files(d)[-1], top=30) + "\n")
            print("[hunt] " + json.dumps(s), flush=True)
        shutil.rmtree(d, ignore_errors=True)
        i += 1
    q = statistics.quantiles(lats, n=4) if len(lats) >= 4 else lats
    print(json.dumps({"hunt": "done", "queries": len(lats), "wrong": bad,
                      "stalls": sum(lat > share * statistics.median(
                          lats[:j]) for j, lat in enumerate(lats) if j >= 4),
                      "median_s": statistics.median(lats) if lats else None,
                      "quartiles_s": q, "max_s": max(lats, default=None)}),
          flush=True)
    return 1 if bad else 0


def main() -> int:
    import jax

    from benchmarks.harness import cell as C
    from spark_rapids_tpu.sql.session import TpuSparkSession

    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    print("device", jax.devices()[0], flush=True)
    os.makedirs(OUT, exist_ok=True)
    cell = C.load_cell("q67_sf1_batch", SEED,
                       float(os.environ.get("SCALE", "1.0")) if rehearsal
                       else 1.0)
    data_root = os.path.join(".bench-data", "perf", "q67-whole")
    shutil.rmtree(data_root, ignore_errors=True)
    cell.generate()
    cell.write(data_root)
    ref = C.load_module(os.path.join(cell.config_dir, "reference.py"),
                        "q67_reference")
    spark = TpuSparkSession(dict(cell.config["conf"]))
    try:
        for table, path in cell.paths.items():
            spark.read.parquet(path).createOrReplaceTempView(table)
        rc = whole_relation(spark, cell, ref)
        rc = hunt(spark, cell, ref) or rc
    finally:
        spark.stop()
        shutil.rmtree(data_root, ignore_errors=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
