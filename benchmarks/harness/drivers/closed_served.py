"""``closed_served``: one ``QueryServer`` in this process (the one process
that holds the chip); ``tenants`` x ``clients_per_tenant`` ``ServeClient``s,
each a closed loop. Latency is taken on the client's side, around ``sql()``
and the decode of the payload into rows."""

from typing import Dict, List, Optional

from benchmarks.harness.cell import Cell
from benchmarks.harness.closed_loop import ClosedLoopDriver, Send


class Driver(ClosedLoopDriver):
    def __init__(self, cell: Cell):
        t = cell.traffic
        self.tenants = [f"tenant{i}" for i in range(int(t["tenants"]))
                        for _ in range(int(t["clients_per_tenant"]))]
        super().__init__(cell, len(self.tenants))
        self.server = None
        self.connections: List = []
        self.drained: Optional[bool] = None

    def start(self) -> None:
        from spark_rapids_tpu.serve import QueryServer, ServeClient
        conf = dict(self.cell.config["conf"])
        # a served statement has no fallback report to read: only this
        # setting, which makes a fallback a failed query, shows one
        if conf.get("spark.rapids.sql.test.forceDevice") != "true":
            raise ValueError("a served cell needs spark.rapids.sql.test."
                             "forceDevice=true in its configuration's conf")
        conf.update(self.cell.traffic.get("serve_conf", {}))
        self.server = QueryServer(conf).start()
        for table, path in self.cell.paths.items():
            self.server.register_view(table, path)
        self.connections = [ServeClient(self.server.port, tenant=tenant)
                            for tenant in self.tenants]

    @staticmethod
    def _send(connection) -> Send:
        def send(sql: str):
            batch, header = connection.sql(sql)
            return [tuple(r) for r in batch.rows()], header
        return send

    def sends(self) -> List[Send]:
        return [self._send(c) for c in self.connections]

    def stats(self) -> Dict:
        s = self.server.stats() if self.server else {}
        return {k: s[k] for k in ("queriesOk", "queriesErr", "batchFusion")
                if k in s}

    def stop(self) -> None:
        for c in self.connections:
            c.close()
        self.connections = []
        if self.server is not None:
            self.drained = self.server.shutdown()
            self.server = None
