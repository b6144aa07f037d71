"""``tpch_q13_sf1``: the tables hold their rows, the comments their lengths,
distinctness and measured match share, the pinned split of ``orders`` packs
one way into scan partitions with a margin and keeps the join shuffled,
every control is refused at full size, and a rehearsed run of the cell
ends ``correct`` with its per-layer metrics.

Beside ``test_layout.py`` and ``test_control.py``, which a ``model_config``
PR may not edit (``test_control.py`` finds this configuration's
``control_answer`` itself)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import cell as C
from benchmarks.harness import layout as LY
from benchmarks.harness.compare import (compared_numbers, first_difference,
                                        is_correct)

CONFIG = "tpch_q13_sf1"
CONFIG_FILE = os.path.join(C.BENCH_DIR, "configs", CONFIG, "config.json")
SEEDS = (2147483659, 2147483693, 3000000019)
# comments that match '%special%requests%', as config.json records them
MATCHES = {2147483659: 16041, 2147483693: 16203, 3000000019: 15952}
OPEN_COST = 4 << 20          # spark.sql.files.openCostInBytes unless set
PINNED_PACKING = {"customer": [1], "orders": [1, 1, 1, 1]}
MARGIN_BYTES = 10_000
CONTROLS = ("control_answer", "inner_join_answer", "filter_after_join_answer",
            "count_star_answer", "either_word_answer")


def make(share: float, seed: int):
    cell = C.make_cell("q13", 1, CONFIG, CONFIG_FILE, "closed_direct_c1",
                       seed, share)
    cell.generate()
    return cell


def reference():
    return C.load_module(os.path.join(C.BENCH_DIR, "configs", CONFIG,
                                      "reference.py"), "q13ref")


@pytest.fixture(scope="module", params=SEEDS[:2])
def full(request, tmp_path_factory):
    cell = make(1.0, request.param)
    cell.write(str(tmp_path_factory.mktemp(f"q13-{request.param}")))
    return cell


def test_the_tables_hold_their_rows_and_the_comments_their_shape(full):
    assert {t: len(next(iter(cols.values())))
            for t, cols in full.tables.items()} == {"customer": 150_000,
                                                   "orders": 1_500_000}
    comments = full.tables["orders"]["o_comment"].tolist()
    lengths = [len(s) for s in comments]
    assert min(lengths) == 19 and max(lengths) == 78
    assert len(set(comments)) == len(comments)
    custkey = full.tables["orders"]["o_custkey"]
    assert (custkey % 3 != 0).all() and custkey.max() <= 150_000


@pytest.mark.parametrize("seed", SEEDS)
def test_the_match_share_is_the_one_recorded(seed):
    cell = make(1.0, seed)
    ref = reference()
    (binding,) = cell.bindings
    hits = sum(ref.matches(s, binding["word1"], binding["word2"])
               for s in cell.tables["orders"]["o_comment"].tolist())
    assert hits == MATCHES[seed]
    assert f"{100 * hits / 1_500_000:.4f}%" in " ".join(
        cell.config["assumed"])


@pytest.mark.parametrize("table", sorted(PINNED_PACKING))
def test_the_pinned_split_packs_one_way_with_a_margin(full, table):
    """As ``test_layout.py`` holds q1's: from the footers, every decision of
    the packing clears the threshold by ``MARGIN_BYTES``; and the engine's
    own scan, under the cell's conf, packs the files so."""
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.io.readers import CpuFileScanExec
    spec = full.config["tables"][table]
    line = LY.tables_layout(full.paths)[table]
    assert line["rows"] == spec.get("layout", {}).get(
        "partition_rows", [spec["rows"]])
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


def test_the_outer_join_stays_shuffled_by_a_margin(full):
    """``orders`` is the outer join's build side: its files lie far over
    ``autoBroadcastJoinThreshold``, ``customer``'s far under it."""
    from spark_rapids_tpu.conf import AUTO_BROADCAST_JOIN_THRESHOLD, TpuConf
    threshold = int(TpuConf(dict(full.config["conf"])).get(
        AUTO_BROADCAST_JOIN_THRESHOLD))
    line = LY.tables_layout(full.paths)
    for table in full.config["tables"]:
        size = sum(line[table]["file_bytes"])
        assert abs(size - threshold) > 0.2 * threshold, (table, size)
    assert sum(line["orders"]["file_bytes"]) > 2 * threshold
    assert sum(line["customer"]["file_bytes"]) < threshold


def test_the_comments_go_to_plain_pages(full):
    """Distinct comments overflow the writer's dictionary: each chunk has
    a dictionary page, then PLAIN byte-array pages."""
    import pyarrow.parquet as pq
    directory = full.paths["orders"]
    for name in sorted(os.listdir(directory)):
        meta = pq.ParquetFile(os.path.join(directory, name)).metadata
        col = meta.row_group(0).column(2)
        assert col.path_in_schema == "o_comment"
        assert set(col.encodings) == {"PLAIN", "RLE", "RLE_DICTIONARY"}


@pytest.mark.parametrize("seed", SEEDS)
def test_every_control_is_refused_at_the_cells_own_size(seed):
    cell = make(1.0, seed)
    ref = reference()
    (binding,) = cell.bindings
    want = ref.answer(cell.tables, binding)
    assert want[0] == (0, 50_000)      # the customers with no order first
    for name in CONTROLS:
        got = getattr(ref, name)(cell.tables, binding)
        assert first_difference(want, got) != "equal", name
        record = C.QueryRecord(client=0, binding=0, t_start=0.0, t_end=1.0,
                               ok=got == want, differs=got != want)
        assert not is_correct(compared_numbers([record], [])), name
    assert len(ref.control_answer(cell.tables, binding)) == len(want)


def test_a_rehearsed_run_ends_correct_with_its_per_layer_metrics():
    p = subprocess.run(
        [sys.executable, os.path.join(C.BENCH_DIR, "run.py"), "--workload",
         "q13_sf1_batch", "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--scale-rows", "0.02"],
        cwd=C.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["fallback_reports"] == {"value": 0, "limit": 0}
    for metric in ("string_lengths_host_s", "string_decode_bytes",
                   "like_char_bytes", "dispatch_count", "plan_host_s"):
        assert metric in result["metrics"], sorted(result["metrics"])
    assert result["metrics"]["like_char_bytes"]["value"] == \
        result["metrics"]["string_decode_bytes"]["value"] > 0
