"""``tpcds_q67_sf1``: the pinned split of ``store_sales`` packs one way into
scan partitions at the cell's own size with this table's five column types,
the generator's calendar columns are the specification's, the plain
reference agrees with a brute-force loop, all four controls are refused at
full size, the grouping levels hold the groups ``config.json`` and
``PERF.md`` quote, and a rehearsed run of the cell ends ``correct`` with its
per-layer metrics.

Beside ``test_layout.py`` and ``test_control.py``, which a ``model_config``
PR may not edit (``test_control.py`` finds this configuration's four
``*control_answer`` functions itself)."""

import bisect
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import cell as C
from benchmarks.harness import layout as LY
from benchmarks.harness.compare import (compared_numbers, first_difference,
                                        is_correct)

CONFIG = "tpcds_q67_sf1"
CONFIG_FILE = os.path.join(C.BENCH_DIR, "configs", CONFIG, "config.json")
SEEDS = (2147483659, 2147483693, 3000000019)
OPEN_COST = 4 << 20          # spark.sql.files.openCostInBytes unless set
PINNED_PACKING = {"store_sales": [2, 2, 1, 1, 1, 1]}
MARGIN_BYTES = 10_000
# groups of each level by the keys it keeps (the grand total first), for
# uniform draws over 18,000 items x 12 months x 12 store rows of ~577 K
# rows: a null string is a group of its own beside the 10 categories, 100
# classes and 1,000 brands, and the 12 store rows hold 6 store ids
LEVEL_GROUPS = (1, 11, 134, 1_065, 18_000, 18_000, 72_000, 201_000, 452_000)


def make(share: float, seed: int):
    cell = C.make_cell("q67", 1, CONFIG, CONFIG_FILE, "closed_direct_c1",
                       seed, share)
    cell.generate()
    return cell


def reference():
    return C.load_module(os.path.join(C.BENCH_DIR, "configs", CONFIG,
                                      "reference.py"), "q67ref")


@pytest.fixture(scope="module", params=SEEDS[:2])
def full(request, tmp_path_factory):
    cell = make(1.0, request.param)
    cell.write(str(tmp_path_factory.mktemp(f"q67-{request.param}")))
    return cell


@pytest.mark.parametrize("table", sorted(PINNED_PACKING))
def test_the_pinned_split_packs_one_way_with_a_margin(full, table):
    """As ``test_layout.py`` holds q1's: from the footers, every decision of
    the packing clears the threshold by ``MARGIN_BYTES``; and the engine's
    own scan, under the cell's conf, packs the files so."""
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.io.readers import CpuFileScanExec
    spec = full.config["tables"][table]
    line = LY.tables_layout(full.paths)[table]
    assert line["rows"] == spec["layout"]["partition_rows"]
    assert line["row_groups"] == [1] * spec["partitions"]
    weights = [b + OPEN_COST for b in line["total_byte_size"]]
    threshold = sum(weights) // int(
        full.config["conf"]["spark.rapids.sql.taskParallelism"])
    packing, held = [0], 0
    for w in weights:
        if packing[-1]:
            assert abs(threshold - (held + w)) >= MARGIN_BYTES, (held, w)
            if held + w > threshold:
                packing.append(0)
                held = 0
        packing[-1] += 1
        held += w
    assert packing == PINNED_PACKING[table]
    scan = CpuFileScanExec([], "parquet", [full.paths[table]], {},
                           TpuConf(dict(full.config["conf"])))
    assert [len(part) for part in scan._parts] == PINNED_PACKING[table]


def test_the_planner_sees_every_table_on_one_side_of_its_threshold(full):
    """Which joins are planned as broadcasts follows the files' bytes on
    disk against ``autoBroadcastJoinThreshold``: no table may sit so near
    it that the seed decides the plan; the three dimensions broadcast."""
    from spark_rapids_tpu.conf import AUTO_BROADCAST_JOIN_THRESHOLD, TpuConf
    threshold = int(TpuConf(dict(full.config["conf"])).get(
        AUTO_BROADCAST_JOIN_THRESHOLD))
    line = LY.tables_layout(full.paths)
    for table in full.config["tables"]:
        size = sum(line[table]["file_bytes"])
        assert abs(size - threshold) > 0.2 * threshold, (table, size)
    assert sum(line["store_sales"]["file_bytes"]) > threshold
    for table in ("date_dim", "store", "item"):
        assert sum(line[table]["file_bytes"]) < threshold, table


def test_the_calendar_columns_are_the_specifications():
    date = make(0.001, 7).tables["date_dim"]
    epoch = np.datetime64("1900-01-02")
    days = epoch + (date["d_date_sk"] - 2415022)
    months = days.astype("datetime64[M]").astype(int)
    assert (date["d_year"] == days.astype("datetime64[Y]").astype(int)
            + 1970).all()
    assert (date["d_moy"] == months % 12 + 1).all()
    assert (date["d_qoy"] == (months % 12) // 3 + 1).all()
    year = (date["d_month_seq"] >= 1200) & (date["d_month_seq"] <= 1211)
    assert year.sum() == 366 and set(date["d_year"][year].tolist()) == {2000}
    assert sorted(set(date["d_qoy"][year].tolist())) == [1, 2, 3, 4]


def brute_force(tables: dict, dms: int, rollup=True, partitioned=True,
                nulls_first=True) -> list:
    """Query 67 row by row: dictionaries and Python integers, no numpy
    grouping, no sort of codes."""
    date, store, item = tables["date_dim"], tables["store"], tables["item"]
    day = {sk: (y, q, m) for sk, seq, y, q, m in zip(
        *(date[k].tolist() for k in ("d_date_sk", "d_month_seq", "d_year",
                                     "d_qoy", "d_moy")))
        if dms <= seq <= dms + 11}
    sid = dict(zip(store["s_store_sk"].tolist(),
                   store["s_store_id"].tolist()))
    its = {sk: (c, k, b, p) for sk, c, k, b, p in zip(
        *(item[k].tolist() for k in ("i_item_sk", "i_category", "i_class",
                                     "i_brand", "i_product_name")))}
    fact = tables["store_sales"]
    sums: dict = {}
    for d, i, s, q, p in zip(*(fact[k].tolist() for k in (
            "ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_quantity",
            "ss_sales_price"))):
        if d in day and i in its and s in sid:
            key = its[i] + day[d] + (sid[s],)
            for n in (range(9) if rollup else (8,)):
                rolled = (n, key[:n] + (None,) * (8 - n))
                sums[rolled] = sums.get(rolled, 0) + p * q
    rows = [(key, total) for (_, key), total in sums.items()]
    by_part: dict = {}
    for key, total in rows:
        by_part.setdefault(key[0] if partitioned else 0, []).append(total)
    for totals in by_part.values():
        totals.sort()
    out = []
    for key, total in rows:
        totals = by_part[key[0] if partitioned else 0]
        rank = 1 + len(totals) - bisect.bisect_right(totals, total)
        if rank <= 100:
            out.append(key + ((total, 2), rank))

    def order(r):
        return tuple((0 if nulls_first else 2, 0) if v is None else (1, v)
                     for v in r)
    return sorted(out, key=order)[:100]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_against_a_brute_force_loop(seed):
    cell = make(0.01, seed)
    small = dict(cell.tables)
    ref = reference()
    for dms in (1200, 1176, 1224):
        want = brute_force(small, dms)
        assert ref.answer(small, {"dms": dms}) == want
        assert len(want) == 100
    assert ref.finest_only_control_answer(small, {"dms": 1200}) == \
        brute_force(small, 1200, rollup=False)
    assert ref.unpartitioned_rank_control_answer(small, {"dms": 1200}) == \
        brute_force(small, 1200, partitioned=False)
    assert ref.nulls_last_control_answer(small, {"dms": 1200}) == \
        brute_force(small, 1200, nulls_first=False)


@pytest.mark.parametrize("seed", SEEDS)
def test_all_four_controls_are_refused_at_the_cells_own_size(seed):
    """``control_answer`` (float32 sums), ``finest_only_control_answer``
    (no ROLLUP), ``unpartitioned_rank_control_answer`` (one partition) and
    ``nulls_last_control_answer``: each gives 100 rows, and the comparison
    refuses it. Held to the binding the cell sends."""
    cell = make(1.0, seed)
    ref = reference()
    (binding,) = cell.bindings
    want = ref.answer(cell.tables, binding)
    assert len(want) == 100 and want[0][:8] == (None,) * 8
    # the guarantee float32 breaks is one a run can show: sums past 2**24
    assert max(r[8][0] for r in want) > 1 << 24
    for broken in (ref.control_answer, ref.finest_only_control_answer,
                   ref.unpartitioned_rank_control_answer,
                   ref.nulls_last_control_answer):
        got = broken(cell.tables, binding)
        assert len(got) == len(want), broken
        assert first_difference(want, got) != "equal", broken
        record = C.QueryRecord(client=0, binding=0, t_start=0.0, t_end=1.0,
                               ok=got == want, differs=got != want)
        assert not is_correct(compared_numbers([record], []))


def test_the_cell_sees_what_the_configuration_says_it_sees():
    """The sizes ``config.json`` and ``PERF.md`` quote: rows of the year,
    the Expand's rows, groups of each level within a few percent."""
    cell = make(1.0, SEEDS[0])
    ref = reference()
    (binding,) = cell.bindings
    year_rows = len(ref.joined(cell.tables, binding)[2])
    assert 570_000 < year_rows < 585_000
    assert 5_130_000 < 9 * year_rows < 5_265_000
    counts = ref.level_groups(cell.tables, binding)
    for got, near in zip(counts, LEVEL_GROUPS):
        assert abs(got - near) <= 0.03 * near, (counts, LEVEL_GROUPS)
    assert 750_000 < sum(counts) < 775_000
    item = cell.tables["item"]
    for k in ("i_category", "i_class", "i_brand", "i_product_name"):
        assert 20 < sum(v is None for v in item[k].tolist()) < 100, k
    assert len(set(cell.tables["store"]["s_store_id"].tolist())) == 6
    # the null category's subtotal is compared beside the grand total
    want = ref.answer(cell.tables, binding)
    assert sum(r[:8] == (None,) * 8 for r in want) >= 2


def test_a_rehearsed_run_ends_correct_with_its_per_layer_metrics():
    p = subprocess.run(
        [sys.executable, os.path.join(C.BENCH_DIR, "run.py"), "--workload",
         "q67_sf1_batch", "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--scale-rows", "0.02"],
        cwd=C.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["fallback_reports"] == {"value": 0, "limit": 0}
    for metric in ("expand_host_s", "expand_rows", "agg_key_words",
                   "dispatch_count", "plan_host_s", "agg_host_s"):
        assert metric in result["metrics"], sorted(result["metrics"])
    assert result["metrics"]["expand_rows"]["value"] > 90_000
    assert result["metrics"]["agg_key_words"]["value"] > 2_000_000
