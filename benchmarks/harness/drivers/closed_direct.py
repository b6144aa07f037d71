"""``closed_direct``: each client owns one ``TpuSparkSession`` and sends the
cell's statements back to back through ``sql(text).collect()``."""

from typing import List

from benchmarks.harness.cell import Cell
from benchmarks.harness.closed_loop import ClosedLoopDriver, Send


class Driver(ClosedLoopDriver):
    def __init__(self, cell: Cell):
        super().__init__(cell, int(cell.traffic["clients"]))
        self.sessions: List = []

    def start(self) -> None:
        from spark_rapids_tpu.sql.session import TpuSparkSession
        for _ in range(self.clients):
            spark = TpuSparkSession(dict(self.cell.config["conf"]))
            self.sessions.append(spark)
            for table, path in self.cell.paths.items():
                spark.read.parquet(path).createOrReplaceTempView(table)

    def _send(self, spark) -> Send:
        def send(sql: str):
            rows = [tuple(r) for r in spark.sql(sql).collect()]
            # spark.rapids.sql.explain=NOT_ON_GPU fills the report per statement
            self.fallbacks.extend(
                str(f) for f in spark.last_rewrite_report.fallbacks)
            return rows, None
        return send

    def sends(self) -> List[Send]:
        return [self._send(s) for s in self.sessions]

    def stop(self) -> None:
        for spark in self.sessions:
            spark.stop()
        self.sessions = []
