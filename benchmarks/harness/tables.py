"""From the generator's numpy arrays to Parquet files, with pyarrow alone.

The files are written by the very call the engine's writer makes,
``pq.write_table(table, path, compression="snappy")``, over the split of rows
``createDataFrame(batch, num_partitions)`` makes: byte for byte the files that
writer gave (PR 28 compared them). Written here, a table's description can
pin, under ``layout``, the one thing the engine's writer takes no option for:
which rows go to which partition file (``partition_rows``, with its ``why``).
The values are the generator's either way.
"""

import os
import re
import shutil
from typing import Dict, List

import numpy as np

_DECIMAL = re.compile(r"decimal\((\d+),(\d+)\)")


def even_split(rows: int, partitions: int) -> List[int]:
    """Rows per partition as ``createDataFrame(batch, num_partitions)``
    splits them: equal shares rounded up, the last one short."""
    partitions = max(1, min(partitions, max(1, rows)))
    per = -(-rows // partitions)
    return [min(per, rows - i * per) for i in range(partitions)
            if rows - i * per > 0]


def partition_rows(spec: Dict, rows: int) -> List[int]:
    """The pinned split, brought to ``rows`` where a rehearsal has shrunk the
    table: every share in proportion, the last taking what is left."""
    pinned = spec.get("layout", {}).get("partition_rows")
    if pinned is None:
        return even_split(rows, spec["partitions"])
    if len(pinned) != spec["partitions"] or sum(pinned) != spec["rows"]:
        raise ValueError(f"layout.partition_rows {pinned} must have "
                         f"{spec['partitions']} shares that sum to {spec['rows']}")
    if rows == spec["rows"]:
        return list(pinned)
    shares = [max(1, r * rows // spec["rows"]) for r in pinned[:-1]]
    return shares + [rows - sum(shares)]


def _arrow_column(arr: np.ndarray, type_text: str):
    """One all-valid column in the Arrow type the engine's writer gives it."""
    import pyarrow as pa
    if type_text == "string":
        return pa.array(arr.astype(object), type=pa.string())
    if type_text == "long":
        return pa.array(arr.astype(np.int64))
    if type_text == "int":
        return pa.array(arr.astype(np.int32))
    if type_text == "date":
        return pa.array(arr.astype(np.int32)).cast(pa.date32())
    m = _DECIMAL.fullmatch(type_text)
    if not m:
        raise ValueError(f"column type {type_text!r} is not one the benchmark writes")
    # decimal128 from the unscaled int64: little-endian low word, sign word
    low = arr.astype("<i8")
    words = np.stack([low, low >> 63], axis=1)
    return pa.Array.from_buffers(
        pa.decimal128(int(m.group(1)), int(m.group(2))), len(low),
        [None, pa.py_buffer(words.tobytes())])


def write_table(columns: Dict[str, np.ndarray], spec: Dict, directory: str
                ) -> None:
    """One table as ``spec["partitions"]`` files ``part-<i>.parquet``."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    unknown = set(spec.get("layout", {})) - {"partition_rows", "why"}
    if unknown:
        raise ValueError(f"layout keys {sorted(unknown)} are not known; a "
                         "layout takes partition_rows and why")
    table = pa.Table.from_arrays(
        [_arrow_column(columns[col], text) for col, text in spec["columns"]],
        names=[col for col, _ in spec["columns"]])
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    start = 0
    for i, n in enumerate(partition_rows(spec, table.num_rows)):
        pq.write_table(table.slice(start, n),
                       os.path.join(directory, f"part-{i:05d}.parquet"),
                       compression="snappy")
        start += n


def write_tables(tables: Dict[str, Dict[str, np.ndarray]], specs: Dict,
                 root: str) -> Dict[str, str]:
    """Write every table under ``root/<name>``; returns table -> directory."""
    paths = {name: os.path.join(root, name) for name in specs}
    for name, spec in specs.items():
        write_table(tables[name], spec, paths[name])
    return paths
