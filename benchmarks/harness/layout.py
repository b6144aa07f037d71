"""What the set-up wrote, read back from the Parquet footers: per table its
partition files, and per file the rows, the row groups and each row group's
``total_byte_size``, the uncompressed bytes a scan planner weighs it by and
the one quantity of the files that the seed's values move (PERF.md 7, PR 28:
pages and encodings were the same for every seed). pyarrow's metadata only;
nothing of the engine. It is set-up and costs a few milliseconds."""

import os
from typing import Dict, List


def file_layout(path: str) -> Dict:
    import pyarrow.parquet as pq
    meta = pq.ParquetFile(path).metadata
    return {"rows": meta.num_rows, "file_bytes": os.path.getsize(path),
            "total_byte_size": [meta.row_group(g).total_byte_size
                                for g in range(meta.num_row_groups)]}


def tables_layout(paths: Dict[str, str]) -> Dict[str, Dict[str, List]]:
    """The ``[bench] layout`` line: table -> its partition files in name
    order, as lists of their rows, row groups, bytes on disk and, row group
    by row group, ``total_byte_size``."""
    out = {}
    for table, directory in paths.items():
        files = [file_layout(os.path.join(directory, name))
                 for name in sorted(os.listdir(directory))
                 if name.endswith(".parquet")]
        out[table] = {
            "partitions": len(files),
            "rows": [f["rows"] for f in files],
            "row_groups": [len(f["total_byte_size"]) for f in files],
            "file_bytes": [f["file_bytes"] for f in files],
            "total_byte_size": [b for f in files for b in f["total_byte_size"]]}
    return out
