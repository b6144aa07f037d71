"""From the generator's numpy arrays to Parquet, through the engine's own
writer (the CPU engine: writing is set-up, not the system under test)."""

import os
import re
from typing import Dict

import numpy as np


def _engine_type(text: str):
    from spark_rapids_tpu.sql import types as T
    plain = {"string": T.StringT, "long": T.LongT, "int": T.IntegerT,
             "date": T.DateT}
    if text in plain:
        return plain[text]
    m = re.fullmatch(r"decimal\((\d+),(\d+)\)", text)
    if not m:
        raise ValueError(f"column type {text!r} is not one the benchmark writes")
    return T.DecimalType(int(m.group(1)), int(m.group(2)))


def write_tables(tables: Dict[str, Dict[str, np.ndarray]], layout: Dict,
                 root: str) -> Dict[str, str]:
    """Write every table as ``layout[name]["partitions"]`` Parquet files under
    ``root/<name>``; returns table -> directory."""
    from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
    from spark_rapids_tpu.sql import types as T
    from spark_rapids_tpu.sql.session import TpuSparkSession
    paths = {}
    writer = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    try:
        for name, spec in layout.items():
            fields, cols = [], []
            for col, type_text in spec["columns"]:
                dt = _engine_type(type_text)
                arr = tables[name][col]
                if type_text == "string":
                    arr = arr.astype(object)
                fields.append(T.StructField(col, dt))
                cols.append(HostColumn.all_valid(arr, dt))
            n = len(tables[name][spec["columns"][0][0]])
            batch = HostBatch(T.StructType(fields), cols, n)
            paths[name] = os.path.join(root, name)
            writer.createDataFrame(batch, num_partitions=spec["partitions"]) \
                .write.mode("overwrite").parquet(paths[name])
    finally:
        writer.stop()
    return paths
