"""PR 36: the decimal window aggregates that query 51 does not reach, run on
the chip against the CPU engine. q51 sums an int64 source and takes the
maximum of a two-limb one in running frames; this runs sum / min / max /
count / first / last over ``decimal(7,2)``, ``decimal(17,2)`` and
``decimal(27,2)`` in the whole, running and bounded frames, and the sum that
passes ``decimal(38, 0)``, under the cells' conf (``forceDevice``), 60,000
rows in partitions of about 30. Exits non-zero on any difference.

    chiprun -- python3 docs/profiles/pr36/chip_windows.py
(``JAX_PLATFORMS=cpu ROWS=3000`` rehearses it.)"""
import os
import random
import sys
import time
from decimal import Decimal

sys.path.insert(0, os.getcwd())
import jax  # noqa: E402

from spark_rapids_tpu.sql.session import TpuSparkSession  # noqa: E402

ROWS = int(os.environ.get("ROWS", "60000"))
CONF = {"spark.rapids.sql.enabled": "true",
        "spark.rapids.sql.test.forceDevice": "true",
        "spark.rapids.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.sql.incompatibleOps.enabled": "true",
        "spark.rapids.sql.explain": "NOT_ON_GPU"}
FRAMES = {
    "whole": "rows between unbounded preceding and unbounded following",
    "running": "rows between unbounded preceding and current row",
    "bounded": "rows between 2 preceding and 1 following"}
QUERIES = [
    ("decimal(27,2)", "running", "sum min max count first last"),
    ("decimal(17,2)", "whole", "sum min max count first last"),
    ("decimal(27,2)", "bounded", "sum min max count"),
    ("decimal(7,2)", "bounded", "sum min max"),
]


def rows_of(precision: int):
    rng = random.Random(precision)
    top = 10 ** precision - 1
    return {"k": [rng.randrange(ROWS // 30) for _ in range(ROWS)],
            "o": rng.sample(range(ROWS), ROWS),
            "v": [None if rng.random() < 0.2
                  else Decimal(rng.randint(-top, top)).scaleb(-2)
                  for _ in range(ROWS)]}


def main() -> int:
    print("device", jax.devices()[0], flush=True)
    cpu = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    dev = TpuSparkSession(dict(CONF))
    bad = 0
    for spark in (cpu, dev):
        for p in (7, 17, 27):
            spark.createDataFrame(rows_of(p), f"k int, o int, v decimal({p},2)"
                                  ).createOrReplaceTempView(f"t{p}")
        big = Decimal(9 * 10 ** 37)
        spark.createDataFrame(
            {"k": [1] * 4 + [2] * 4, "o": list(range(8)),
             "v": [big, big, big, -big, -big, -big, -big, big]},
            "k int, o int, v decimal(38,0)").createOrReplaceTempView("t38")
    texts = []
    for dec, frame, aggs in QUERIES:
        over = f"over (partition by k order by o {FRAMES[frame]})"
        cols = ", ".join(f"{a}(v) {over} a_{a}" for a in aggs.split())
        texts.append((f"{dec} {frame}", f"select k, o, {cols} from "
                      f"t{dec[8:dec.index(',')]} order by k, o"))
    texts.append(("decimal(38,0) overflow", "select k, o, sum(v) over "
                  f"(partition by k order by o {FRAMES['running']}) s, "
                  f"sum(v) over (partition by k order by o {FRAMES['bounded']})"
                  " b from t38 order by k, o"))
    for name, text in texts:
        t0 = time.time()
        want = [tuple(r) for r in cpu.sql(text).collect()]
        t1 = time.time()
        got = [tuple(r) for r in dev.sql(text).collect()]
        fallbacks = list(dev.last_rewrite_report.fallbacks)
        ok = got == want and not fallbacks
        bad += not ok
        print(f"{name:24s} rows {len(want):6d} equal {got == want} "
              f"fallbacks {len(fallbacks)} cpu {t1 - t0:.1f} s device "
              f"{time.time() - t1:.1f} s", flush=True)
        if name.endswith("overflow"):
            print("   ", [r[2:] for r in got], flush=True)
    cpu.stop()
    dev.stop()
    print("ok" if not bad else f"{bad} quer(ies) differ", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
