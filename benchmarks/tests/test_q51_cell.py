"""``tpcds_q51_sf1``: the pinned splits of ``store_sales`` and ``web_sales``
pack one way into scan partitions at the cell's own size, the generator's
calendar is the specification's, the plain reference agrees with a
brute-force loop, all three controls are refused at full size, and a
rehearsed run of the cell ends ``correct`` with its per-layer metrics.

Beside ``test_layout.py`` and ``test_control.py``, which a ``model_config``
PR may not edit: they hold q1 and the star join to the same things (and
``test_control.py`` finds this configuration's ``control_answer`` and
``outer_control_answer`` itself)."""

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

CONFIG = "tpcds_q51_sf1"
CONFIG_FILE = os.path.join(C.BENCH_DIR, "configs", CONFIG, "config.json")
SEEDS = (2147483659, 2147483693, 3000000019)
OPEN_COST = 4 << 20          # spark.sql.files.openCostInBytes unless set
PINNED_PACKING = {"store_sales": [2, 2, 1, 1, 1, 1], "web_sales": [1, 1]}
MARGIN_BYTES = 10_000


def make(share: float, seed: int):
    cell = C.make_cell("q51", 1, CONFIG, CONFIG_FILE, "closed_direct_c1",
                       seed, share)
    cell.generate()
    return cell


def reference():
    return C.load_module(os.path.join(C.BENCH_DIR, "configs", CONFIG,
                                      "reference.py"), "q51ref")


@pytest.fixture(scope="module", params=SEEDS[:2])
def full(request, tmp_path_factory):
    cell = make(1.0, request.param)
    cell.write(str(tmp_path_factory.mktemp(f"q51-{request.param}")))
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
    disk against ``autoBroadcastJoinThreshold``: no table of this
    configuration may sit so near it that the seed decides the plan."""
    from spark_rapids_tpu.conf import AUTO_BROADCAST_JOIN_THRESHOLD, TpuConf
    threshold = int(TpuConf(dict(full.config["conf"])).get(
        AUTO_BROADCAST_JOIN_THRESHOLD))
    line = LY.tables_layout(full.paths)
    for table in full.config["tables"]:
        size = sum(line[table]["file_bytes"])
        assert abs(size - threshold) > 0.2 * threshold, (table, size)
    assert sum(line["store_sales"]["file_bytes"]) > threshold
    assert sum(line["web_sales"]["file_bytes"]) < threshold
    assert sum(line["date_dim"]["file_bytes"]) < threshold


def test_the_calendar_is_the_specifications():
    date = make(0.001, 7).tables["date_dim"]
    epoch = np.datetime64("1970-01-01")
    at = {int(sk): i for i, sk in enumerate(date["d_date_sk"].tolist())}
    assert len(at) == 73049 and min(at) == 2415022

    def day(sk):
        return str(epoch + int(date["d_date"][at[sk]]))
    assert day(2415022) == "1900-01-02"
    assert day(2450816) == "1998-01-02" and day(2452642) == "2003-01-02"
    seq = date["d_month_seq"]
    assert seq[at[2415022]] == 0
    year_2000 = [sk for sk in at if 1200 <= seq[at[sk]] <= 1211]
    assert len(year_2000) == 366
    assert day(min(year_2000)) == "2000-01-01"
    assert day(max(year_2000)) == "2000-12-31"
    # every binding keeps its twelve months inside the five years of sales
    # (1998-01-01, the day before the first sale, is the one day outside)
    lo, hi = C.load_json(CONFIG_FILE)["binding_domains"]["dms"]
    in_domain = [sk for sk in at if lo <= seq[at[sk]] <= hi + 11]
    assert 2450815 <= min(in_domain) and max(in_domain) <= 2452642


def brute_force(tables: dict, dms: int, running=True, outer=True,
                whole=False) -> list:
    """Query 51 row by row: no numpy, no running state carried along.
    ``whole``: every row of ``y``, without the WHERE and the LIMIT."""
    date = tables["date_dim"]
    day_of = {sk: day for sk, day, seq in zip(
        date["d_date_sk"].tolist(), date["d_date"].tolist(),
        date["d_month_seq"].tolist()) if dms <= seq <= dms + 11}

    def cume(fact: dict, prefix: str) -> dict:
        sums: dict = {}
        for sk, item, price in zip(fact[f"{prefix}_sold_date_sk"].tolist(),
                                   fact[f"{prefix}_item_sk"].tolist(),
                                   fact[f"{prefix}_sales_price"].tolist()):
            if sk in day_of:
                key = (item, day_of[sk])
                sums[key] = sums.get(key, 0) + price
        by_item: dict = {}
        for (item, day), v in sums.items():
            by_item.setdefault(item, []).append((day, v))
        return {(item, day): sum(v for d2, v in days
                                 if d2 <= day or not running)
                for item, days in by_item.items() for day, _ in days}

    web = cume(tables["web_sales"], "ws")
    store = cume(tables["store_sales"], "ss")
    keys = set(web) | set(store) if outer else set(web) & set(store)
    by_item: dict = {}
    for item, day in keys:
        by_item.setdefault(item, []).append(day)
    out = []
    for item, day in sorted(keys):
        seen = [(web.get((item, d2)), store.get((item, d2)))
                for d2 in by_item[item] if d2 <= day or not running]
        web_best = max((w for w, _ in seen if w is not None), default=None)
        store_best = max((s for _, s in seen if s is not None), default=None)
        if whole or (web_best is not None and store_best is not None
                     and web_best > store_best):
            out.append((item, day) + tuple(
                None if v is None else (v, 2)
                for v in (web.get((item, day)), store.get((item, day)),
                          web_best, store_best)))
    return out if whole else out[:100]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_references_whole_relation_against_a_brute_force_loop(seed):
    """What ``docs/profiles/pr36/chip_full_relation.py`` holds the engine to
    on the chip: every row of ``y``, store-only and web-only rows too."""
    cell = make(0.05, seed)
    want = brute_force(cell.tables, 1200, whole=True)
    assert reference().whole_relation(cell.tables, {"dms": 1200}) == want
    assert len(want) > 30_000
    assert any(r[2] is None for r in want) and any(r[3] is None for r in want)
    assert any(r[4] is None for r in want) and any(r[5] is None for r in want)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_against_a_brute_force_loop(seed):
    cell = make(0.05, seed)
    ref = reference()
    for dms in (1200, 1176, 1224):
        want = brute_force(cell.tables, dms)
        assert ref.answer(cell.tables, {"dms": dms}) == want
        assert len(want) >= 5
    assert ref.whole_frame_answer(cell.tables, {"dms": 1200}) == brute_force(
        cell.tables, 1200, running=False)
    assert ref.outer_control_answer(
        cell.tables, {"dms": 1200}) == brute_force(cell.tables, 1200,
                                                   outer=False)


@pytest.mark.parametrize("seed", SEEDS)
def test_all_three_controls_are_refused_at_the_cells_own_size(seed):
    """``control_answer`` (float32 sums) and ``outer_control_answer`` (an
    inner join), which ``test_control.py`` lists too, and the control it
    cannot list (it leaves fewer rows): every window over its whole
    partition. Held to the binding the cell sends."""
    cell = make(1.0, seed)
    ref = reference()
    (binding,) = cell.bindings
    want = ref.answer(cell.tables, binding)
    assert len(want) == 100
    assert any(r[2] is None for r in want) and any(r[3] is None for r in want)
    # the guarantee the control breaks is one a run can show: sums past 2**24
    assert max(r[4][0] for r in want) > 1 << 24
    for broken in (ref.control_answer, ref.whole_frame_answer,
                   ref.outer_control_answer):
        got = broken(cell.tables, binding)
        assert first_difference(want, got) != "equal", broken
        record = C.QueryRecord(client=0, binding=0, t_start=0.0, t_end=1.0,
                               ok=got == want, differs=got != want)
        assert not is_correct(compared_numbers([record], []))
    assert len(ref.control_answer(cell.tables, binding)) == len(want)
    assert len(ref.outer_control_answer(cell.tables, binding)) == len(want)


def test_the_cell_sees_what_the_configuration_says_it_sees():
    """The sizes ``config.json`` and ``PERF.md`` quote: rows of the year,
    groups of each channel, pairs both channels have."""
    tables = make(1.0, SEEDS[0]).tables
    date = tables["date_dim"]
    year = set(date["d_date_sk"][(date["d_month_seq"] >= 1200)
                                 & (date["d_month_seq"] <= 1211)].tolist())

    def groups(fact, prefix):
        hit = np.isin(fact[f"{prefix}_sold_date_sk"], list(year))
        pairs = set(zip(fact[f"{prefix}_sold_date_sk"][hit].tolist(),
                        fact[f"{prefix}_item_sk"][hit].tolist()))
        return int(hit.sum()), pairs
    store_rows, store = groups(tables["store_sales"], "ss")
    web_rows, web = groups(tables["web_sales"], "ws")
    assert 570_000 < store_rows < 585_000 and 141_000 < web_rows < 147_000
    assert 548_000 < len(store) < 558_000 and 139_000 < len(web) < 145_000
    assert 11_000 < len(store & web) < 13_000
    assert 675_000 < len(store | web) < 690_000


def test_a_rehearsed_run_ends_correct_with_its_per_layer_metrics():
    p = subprocess.run(
        [sys.executable, os.path.join(C.BENCH_DIR, "run.py"), "--workload",
         "q51_sf1_batch", "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--scale-rows", "0.02"],
        cwd=C.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["fallback_reports"] == {"value": 0, "limit": 0}
    for metric in ("window_host_s", "window_rows", "join_host_s",
                   "agg_merge_count", "dispatch_count", "plan_host_s"):
        assert metric in result["metrics"], sorted(result["metrics"])
    assert result["metrics"]["window_rows"]["value"] > 20_000
