#!/usr/bin/env python3
"""Op counts of q1's ``jit_srt_agg_partial`` before XLA's optimisation, by
named scope, on the CPU at 2% of scale factor 1 (120,024 rows).

    JAX_PLATFORMS=cpu python3 docs/profiles/pr44/filter_scope_ops.py \
        <tree> <dump-dir>

``<tree>`` is a checkout of the engine (``.`` or a ``git archive`` of
another commit); ``<dump-dir>`` is a new directory for XLA's dump and the
generated tables. Runs the configuration ``tpch_q1_sf1``'s statement once
through the device path on the CPU, prints its first two rows, then the
ops of every dumped ``jit_srt_agg_partial`` module in the fused stage's
``Filter`` scope and outside it, the Filter scope's ops by kind, and its
``u8`` arrays. The counts are the program's, not a device's time."""
import collections
import glob
import os
import re
import sys


def run(tree: str, dump: str) -> None:
    os.environ["XLA_FLAGS"] = f"--xla_dump_to={dump}"
    # a program loaded from the persistent cache is not compiled or dumped
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    sys.path.insert(0, tree)
    os.chdir(tree)
    from benchmarks.harness import cell as C
    from benchmarks.harness import tables as TB
    from spark_rapids_tpu.sql.session import TpuSparkSession
    d = os.path.join(C.BENCH_DIR, "configs", "tpch_q1_sf1")
    cfg = C.load_json(os.path.join(d, "config.json"))
    gen = C.load_module(os.path.join(d, "generator.py"), "q1_generator")
    rows = {"lineitem": 120024}
    specs = {t: dict(s, rows=rows[t]) for t, s in cfg["tables"].items()}
    for s in specs.values():
        s.pop("layout", None)
    paths = TB.write_tables(gen.generate(2147500441, rows), specs,
                            dump + "_data")
    spark = TpuSparkSession(dict(cfg["conf"]))
    for t, p in paths.items():
        spark.read.parquet(p).createOrReplaceTempView(t)
    with open(os.path.join(d, cfg["statement_file"])) as f:
        text = f.read().format(**cfg["default_binding"])
    print(spark.sql(text).collect()[:2])
    spark.stop()


def count(dump: str) -> None:
    pattern = "*jit_srt_agg_partial*before_optimizations.txt"
    for path in sorted(glob.glob(os.path.join(dump, pattern))):
        scopes, kinds, u8 = collections.Counter(), collections.Counter(), 0
        with open(path) as f:
            for line in f:
                head, _, meta = line.partition("metadata=")
                m = re.search(r'op_name="([^"]*)"', meta)
                if not m or "=" not in head:
                    continue
                scope = "Filter" if "/Filter" in m.group(1) else "other"
                scopes[scope] += 1
                if scope == "Filter":
                    k = re.search(r"=\s*\S+\s+([a-z\-]+)\(", head)
                    if k:
                        kinds[k.group(1)] += 1
                    u8 += bool(re.search(r"= u8\[", head))
        print(os.path.basename(path), dict(scopes))
        print("  Filter ops by kind:", dict(kinds.most_common()))
        print("  u8 arrays in the Filter scope:", u8)


if __name__ == "__main__":
    run(os.path.abspath(sys.argv[1]), os.path.abspath(sys.argv[2]))
    count(os.path.abspath(sys.argv[2]))
