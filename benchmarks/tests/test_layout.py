"""The ``[bench] layout`` line says what pyarrow's own metadata says, the
pinned split of rows is the one written and packs one way into scan
partitions at the cell's own size, and the files are byte for byte those the
engine's writer gives."""

import hashlib
import os

import numpy as np
import pyarrow.parquet as pq
import pytest

from benchmarks.harness import cell as C
from benchmarks.harness import layout as LY
from benchmarks.harness import tables as TB


def make(config: str, share: float, seed: int = 2147483693):
    cell = C.make_cell("layout", 1, config, os.path.join(
        C.BENCH_DIR, "configs", config, "config.json"), "closed_direct_c1",
        seed, share)
    cell.generate()
    return cell


@pytest.mark.parametrize("config,share", [("tpch_q1_sf1", 0.05),
                                          ("tpcds_star_2m", 0.2)])
def test_layout_line_against_pyarrows_metadata(config, share, tmp_path):
    cell = make(config, share)
    cell.write(str(tmp_path))
    line = LY.tables_layout(cell.paths)
    assert set(line) == set(cell.config["tables"])
    for table, spec in cell.config["tables"].items():
        files = sorted(f for f in os.listdir(cell.paths[table])
                       if f.endswith(".parquet"))
        assert line[table]["partitions"] == len(files) == spec["partitions"]
        assert sum(line[table]["rows"]) == cell.rows[table]
        assert line[table]["rows"] == TB.partition_rows(spec, cell.rows[table])
        metas = [pq.ParquetFile(os.path.join(cell.paths[table], f)).metadata
                 for f in files]
        assert line[table]["rows"] == [m.num_rows for m in metas]
        assert line[table]["row_groups"] == [m.num_row_groups for m in metas]
        assert line[table]["total_byte_size"] == [
            m.row_group(g).total_byte_size
            for m in metas for g in range(m.num_row_groups)]
        assert line[table]["file_bytes"] == [
            os.path.getsize(os.path.join(cell.paths[table], f)) for f in files]


# what the pins were set against: the engine weighs a row group by its
# total_byte_size plus spark.sql.files.openCostInBytes (4 MiB unless set) and
# cuts a partition at (all weights) // spark.rapids.sql.taskParallelism
OPEN_COST = 4 << 20
PINNED_PACKING = [2, 2, 1, 1, 1, 1]
MARGIN_BYTES = 10_000   # the seed's values move a row group by 0.5-3 KB


@pytest.mark.parametrize("seed", [2147483693, 3000000019])
@pytest.mark.parametrize("config", ["tpch_q1_sf1", "tpcds_star_2m"])
def test_the_pinned_split_packs_one_way_with_a_margin(config, seed, tmp_path):
    """At the cell's own size, from the footers: every seed's files pack into
    scan partitions of 2, 2, 1, 1, 1 and 1 files, no decision closer to the
    threshold than ``MARGIN_BYTES``; and the engine's own scan, under the
    cell's conf, packs them so. A change of the threshold (openCostInBytes,
    taskParallelism, the weights) fails here and does not move the numbers
    in silence."""
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.io.readers import CpuFileScanExec
    cell = make(config, 1.0, seed)
    cell.write(str(tmp_path))
    (fact,) = [t for t, spec in cell.config["tables"].items()
               if "layout" in spec]
    weights = [b + OPEN_COST
               for b in LY.tables_layout(cell.paths)[fact]["total_byte_size"]]
    threshold = sum(weights) // int(
        cell.config["conf"]["spark.rapids.sql.taskParallelism"])
    packing, held = [0], 0
    for w in weights:
        if packing[-1]:             # does this file still fit the partition?
            assert abs(threshold - (held + w)) >= MARGIN_BYTES, (held, w)
            if held + w > threshold:
                packing.append(0)
                held = 0
        packing[-1] += 1
        held += w
    assert packing == PINNED_PACKING
    scan = CpuFileScanExec([], "parquet", [cell.paths[fact]], {},
                           TpuConf(dict(cell.config["conf"])))
    assert [len(part) for part in scan._parts] == PINNED_PACKING


def test_partition_rows_pinned_and_scaled():
    spec = {"rows": 8000, "partitions": 4,
            "layout": {"partition_rows": [1900, 1900, 2100, 2100]}}
    assert TB.partition_rows(spec, 8000) == [1900, 1900, 2100, 2100]
    assert TB.partition_rows(spec, 800) == [190, 190, 210, 210]
    scaled = TB.partition_rows(spec, 1001)
    assert sum(scaled) == 1001 and len(scaled) == 4
    assert TB.partition_rows({"rows": 10, "partitions": 4}, 10) == [3, 3, 3, 1]
    with pytest.raises(ValueError):
        TB.partition_rows(dict(spec, layout={"partition_rows": [1, 2]}), 8000)
    with pytest.raises(ValueError):
        TB.write_table({}, dict(spec, columns=[], layout={"row_group_size": 1}), "/nowhere")


@pytest.mark.parametrize("config", ["tpch_q1_sf1", "tpcds_star_2m"])
def test_every_configuration_pins_the_same_split_for_every_seed(config, tmp_path):
    """Two seeds, one layout: files, rows and row groups agree; only the
    bytes follow the values."""
    lines = []
    for seed in (5, 3000000019):
        cell = make(config, 0.05, seed)
        cell.write(str(tmp_path / str(seed)))
        lines.append(LY.tables_layout(cell.paths))
    a, b = lines
    for table in a:
        for key in ("partitions", "rows", "row_groups"):
            assert a[table][key] == b[table][key]


def test_files_are_the_engine_writers_byte_for_byte(tmp_path):
    """Without a pinned split the files equal those of the engine's own
    writer (``createDataFrame(batch, n).write.parquet``), which is
    ``pq.write_table(table, path, compression="snappy")`` and nothing else."""
    from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
    from spark_rapids_tpu.sql import types as T
    from spark_rapids_tpu.sql.session import TpuSparkSession
    cell = make("tpch_q1_sf1", 0.01)
    spec = dict(cell.config["tables"]["lineitem"], rows=cell.rows["lineitem"])
    spec.pop("layout", None)
    TB.write_table(cell.tables["lineitem"], spec, str(tmp_path / "ours"))
    plain = {"string": T.StringT, "date": T.DateT}
    fields, cols = [], []
    for name, text in spec["columns"]:
        dt = plain.get(text) or T.DecimalType(15, 2)
        arr = cell.tables["lineitem"][name]
        fields.append(T.StructField(name, dt))
        cols.append(HostColumn.all_valid(
            arr.astype(object) if text == "string" else arr, dt))
    writer = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        writer.createDataFrame(
            HostBatch(T.StructType(fields), cols, spec["rows"]),
            num_partitions=spec["partitions"]
        ).write.mode("overwrite").parquet(str(tmp_path / "theirs"))
    finally:
        writer.stop()

    def digests(d):
        return [hashlib.sha256(open(os.path.join(d, f), "rb").read()).hexdigest()
                for f in sorted(os.listdir(d)) if f.endswith(".parquet")]
    ours, theirs = digests(tmp_path / "ours"), digests(tmp_path / "theirs")
    assert len(ours) == spec["partitions"] and ours == theirs
