"""``tpch_q18_sf1``: the pinned split of ``lineitem`` and of ``orders`` packs
one way into scan partitions at the cell's own size, the plain reference
agrees with a brute-force loop, both controls are refused at full size, and
a rehearsed run of the cell ends ``correct`` with its per-layer metrics.

Beside ``test_layout.py`` and ``test_control.py``, which a ``model_config``
PR may not edit: they hold q1 and the star join to the same things (and
``test_control.py`` finds this configuration's ``control_answer`` itself)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import cell as C
from benchmarks.harness import layout as LY
from benchmarks.harness.compare import (compared_numbers, first_difference,
                                        is_correct)

CONFIG = "tpch_q18_sf1"
CONFIG_FILE = os.path.join(C.BENCH_DIR, "configs", CONFIG, "config.json")
SEEDS = (2147483659, 2147483693, 3000000019)
OPEN_COST = 4 << 20          # spark.sql.files.openCostInBytes unless set
PINNED_PACKING = {"lineitem": [2, 2, 1, 1, 1, 1], "orders": [1, 1, 1, 1]}
MARGIN_BYTES = 10_000


def make(share: float, seed: int):
    cell = C.make_cell("q18", 1, CONFIG, CONFIG_FILE, "closed_direct_c1",
                       seed, share)
    cell.generate()
    return cell


@pytest.fixture(scope="module", params=SEEDS[:2])
def full(request, tmp_path_factory):
    cell = make(1.0, request.param)
    cell.write(str(tmp_path_factory.mktemp(f"q18-{request.param}")))
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


def brute_force(tables: dict, quantity: int) -> list:
    """Q18 row by row: no numpy, no shortcut through the keys."""
    lines = list(zip(tables["lineitem"]["l_orderkey"].tolist(),
                     tables["lineitem"]["l_quantity"].tolist()))
    sums: dict = {}
    for key, qty in lines:
        sums[key] = sums.get(key, 0) + qty
    large = {key for key, total in sums.items() if total > quantity * 100}
    customers = list(zip(tables["customer"]["c_custkey"].tolist(),
                         tables["customer"]["c_name"].tolist()))
    groups: dict = {}
    o = tables["orders"]
    for okey, ckey, price, day in zip(
            o["o_orderkey"].tolist(), o["o_custkey"].tolist(),
            o["o_totalprice"].tolist(), o["o_orderdate"].tolist()):
        if okey not in large:
            continue
        for c_custkey, c_name in customers:
            if c_custkey != ckey:
                continue
            for l_orderkey, qty in lines:
                if l_orderkey == okey:
                    group = (str(c_name), ckey, okey, day, price)
                    groups[group] = groups.get(group, 0) + qty
    ordered = sorted(groups.items(), key=lambda kv: (-kv[0][4], kv[0][3]))
    return [(name, ckey, okey, day, (price, 2), (qty, 2))
            for (name, ckey, okey, day, price), qty in ordered[:100]]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_against_a_brute_force_loop(seed):
    cell = make(0.01, seed)
    ref = C.load_module(os.path.join(cell.config_dir, "reference.py"), "q18ref")
    for quantity in (260, 300):
        want = brute_force(cell.tables, quantity)
        assert ref.answer(cell.tables, {"quantity": quantity}) == want
        if quantity == 260:
            assert len(want) >= 5


@pytest.mark.parametrize("seed", SEEDS)
def test_or_equal_for_greater_is_refused_at_the_cells_own_size(seed):
    """The control ``test_control.py`` cannot list (it adds rows): an order
    whose lines sum to exactly QUANTITY must not get in. Held, beside the
    float32 control that test lists too, to the binding the cell sends."""
    cell = make(1.0, seed)
    ref = C.load_module(os.path.join(cell.config_dir, "reference.py"), "q18ref")
    (binding,) = cell.bindings
    want = ref.answer(cell.tables, binding)
    assert 20 <= len(want) < 100      # LIMIT 100 cuts nothing away
    for broken in (ref.having_or_equal_answer, ref.control_answer):
        got = broken(cell.tables, binding)
        assert first_difference(want, got) != "equal", broken
        record = C.QueryRecord(client=0, binding=0, t_start=0.0, t_end=1.0,
                               ok=got == want, differs=got != want)
        assert not is_correct(compared_numbers([record], []))
    assert len(ref.having_or_equal_answer(cell.tables, binding)) > len(want)


def test_a_rehearsed_run_ends_correct_with_its_per_layer_metrics():
    p = subprocess.run(
        [sys.executable, os.path.join(C.BENCH_DIR, "run.py"), "--workload",
         "q18_sf1_batch", "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--scale-rows", "0.05"],
        cwd=C.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["fallback_reports"] == {"value": 0, "limit": 0}
    for metric in ("agg_host_s", "join_host_s", "agg_merge_count",
                   "dispatch_count", "plan_host_s"):
        assert metric in result["metrics"], sorted(result["metrics"])
    assert result["metrics"]["agg_merge_count"]["value"] >= 1
