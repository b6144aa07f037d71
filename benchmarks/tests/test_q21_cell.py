"""``tpch_q21_sf1``: the pinned split of ``lineitem`` and of ``orders`` packs
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

CONFIG = "tpch_q21_sf1"
CONFIG_FILE = os.path.join(C.BENCH_DIR, "configs", CONFIG, "config.json")
SEEDS = (2147483659, 2147483693, 3000000019)
OPEN_COST = 4 << 20          # spark.sql.files.openCostInBytes unless set
PINNED_PACKING = {"lineitem": [2, 2, 1, 1, 1, 1], "orders": [1, 1, 1, 1]}
MARGIN_BYTES = 10_000
NATION = "SAUDI ARABIA"


def make(share: float, seed: int):
    cell = C.make_cell("q21", 1, CONFIG, CONFIG_FILE, "closed_direct_c1",
                       seed, share)
    cell.generate()
    return cell


@pytest.fixture(scope="module", params=SEEDS[:2])
def full(request, tmp_path_factory):
    cell = make(1.0, request.param)
    cell.write(str(tmp_path_factory.mktemp(f"q21-{request.param}")))
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


def test_the_planner_sees_every_build_side_on_one_side_of_its_threshold(full):
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
    assert sum(line["lineitem"]["file_bytes"]) > threshold
    assert sum(line["supplier"]["file_bytes"]) < threshold


def brute_force(tables: dict, nation: str, exists_another_supplier=True,
                others_late_only=True) -> list:
    """Q21 line by line over the lines of each order: no numpy, no shortcut
    through the suppliers' extremes."""
    li, orders, supp, nat = (tables["lineitem"], tables["orders"],
                             tables["supplier"], tables["nation"])
    lines: dict = {}
    for okey, skey, commit, receipt in zip(
            li["l_orderkey"].tolist(), li["l_suppkey"].tolist(),
            li["l_commitdate"].tolist(), li["l_receiptdate"].tolist()):
        lines.setdefault(okey, []).append((skey, receipt > commit))
    numwait: dict = {}
    for okey, status in zip(orders["o_orderkey"].tolist(),
                            orders["o_orderstatus"].tolist()):
        if status != "F":
            continue
        for skey, late in lines.get(okey, []):
            if not late:
                continue
            if exists_another_supplier and not any(
                    s2 != skey for s2, _ in lines[okey]):
                continue
            if any(s3 != skey and (late3 or not others_late_only)
                   for s3, late3 in lines[okey]):
                continue
            for s_suppkey, s_name, s_nationkey in zip(
                    supp["s_suppkey"].tolist(), supp["s_name"].tolist(),
                    supp["s_nationkey"].tolist()):
                if s_suppkey != skey:
                    continue
                for n_nationkey, n_name in zip(nat["n_nationkey"].tolist(),
                                               nat["n_name"].tolist()):
                    if n_nationkey == s_nationkey and n_name == nation:
                        numwait[str(s_name)] = numwait.get(str(s_name), 0) + 1
    return sorted(numwait.items(), key=lambda nc: (-nc[1], nc[0]))[:100]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_against_a_brute_force_loop(seed):
    cell = make(0.01, seed)
    ref = C.load_module(os.path.join(cell.config_dir, "reference.py"), "q21ref")
    for nation in (NATION, "FRANCE"):
        want = brute_force(cell.tables, nation)
        assert ref.answer(cell.tables, {"nation": nation}) == want
        assert len(want) >= 5
    assert ref.control_answer(cell.tables, {"nation": NATION}) == brute_force(
        cell.tables, NATION, exists_another_supplier=False)
    assert ref.not_exists_any_other_answer(
        cell.tables, {"nation": NATION}) == brute_force(
            cell.tables, NATION, others_late_only=False) == []


@pytest.mark.parametrize("seed", SEEDS)
def test_both_controls_are_refused_at_the_cells_own_size(seed):
    """``control_answer`` (the EXISTS without its ``<>``), which
    ``test_control.py`` lists too, and the control it cannot list (it leaves
    no row): the NOT EXISTS without ``l3``'s lateness filter. Held to the
    binding the cell sends."""
    cell = make(1.0, seed)
    ref = C.load_module(os.path.join(cell.config_dir, "reference.py"), "q21ref")
    (binding,) = cell.bindings
    want = ref.answer(cell.tables, binding)
    assert len(want) == 100           # some 400 suppliers wait; LIMIT cuts
    assert want[0][1] > want[-1][1] >= 5
    for broken in (ref.control_answer, ref.not_exists_any_other_answer):
        got = broken(cell.tables, binding)
        assert first_difference(want, got) != "equal", broken
        record = C.QueryRecord(client=0, binding=0, t_start=0.0, t_end=1.0,
                               ok=got == want, differs=got != want)
        assert not is_correct(compared_numbers([record], []))
    assert len(ref.control_answer(cell.tables, binding)) == len(want)
    assert ref.not_exists_any_other_answer(cell.tables, binding) == []


def test_a_rehearsed_run_ends_correct_with_its_per_layer_metrics():
    p = subprocess.run(
        [sys.executable, os.path.join(C.BENCH_DIR, "run.py"), "--workload",
         "q21_sf1_batch", "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--scale-rows", "0.02"],
        cwd=C.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["fallback_reports"] == {"value": 0, "limit": 0}
    for metric in ("join_host_s", "join_condition_pairs",
                   "join_condition_host_s", "dispatch_count", "plan_host_s"):
        assert metric in result["metrics"], sorted(result["metrics"])
    assert result["metrics"]["join_condition_pairs"]["value"] > 100_000
