"""The closed loop both drivers share: a client sends its next query only
when the last has returned, and starts none that would overrun the window.
Every answer is kept and held to the plain reference by ``judge`` once the
window has closed, so the reference's time is no part of the set-up."""

import contextlib
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

from benchmarks.harness.cell import Cell, QueryRecord
from benchmarks.harness.compare import first_difference, normal_rows
from benchmarks.harness.tracing import WindowTracer

# (rows, header of the response or None)
Send = Callable[[str], Tuple[List[tuple], Optional[Dict]]]
JOIN_SLACK_S = 240.0


def one_query(cell: Cell, send: Send, client: int, binding: int,
              tracer: Optional[WindowTracer]) -> QueryRecord:
    """Send one statement and keep its rows for ``judge``."""
    header, error, ok, got = None, "", False, None
    traced = bool(tracer and tracer.active)
    t_start = time.perf_counter()
    try:
        if traced:
            tracer.note_start(t_start)
        with tracer.query() if traced else contextlib.nullcontext():
            rows, header = send(cell.sql(binding))
        t_end = time.perf_counter()
        got = normal_rows(rows)
        ok = True
    except Exception:  # noqa: BLE001 - a failed query is counted, not fatal
        t_end = time.perf_counter()
        error = traceback.format_exc()
    header = header or {}
    return QueryRecord(client=client, binding=binding, t_start=t_start,
                       t_end=t_end, ok=ok, error=error, rows=got,
                       queue_wait_ms=header.get("queueWaitMs"),
                       exec_ms=header.get("execMs"), traced=traced)


def judge(cell: Cell, records: List[QueryRecord]) -> None:
    """Hold every answer to the reference's (``cell.compute_answers()`` has
    run): one that differs is no longer ``ok`` and says where it differs."""
    for r in records:
        if r.ok and r.rows != cell.answers[r.binding]:
            r.ok, r.differs = False, True
            r.error = ("rows differ from the reference: "
                       + first_difference(cell.answers[r.binding], r.rows))


def run_clients(cell: Cell, sends: List[Send], steps: Optional[int],
                seconds: float, last_latency: List[float],
                positions: List, tracer: Optional[WindowTracer],
                t0: Optional[float] = None) -> List[QueryRecord]:
    """Every client in a thread of its own. With ``steps`` each sends that
    many queries (warm-up); without, each sends while the time elapsed plus
    its own last latency stays within ``seconds``, and its first always."""
    t0 = time.perf_counter() if t0 is None else t0
    records: List[List[QueryRecord]] = [[] for _ in sends]

    def client(i: int) -> None:
        sent = 0
        while True:
            if steps is not None:
                if sent >= steps:
                    return
            elif sent and (time.perf_counter() - t0 + last_latency[i]
                           > seconds):
                return
            rec = one_query(cell, sends[i], i, next(positions[i]), tracer)
            records[i].append(rec)
            last_latency[i] = rec.latency_s
            sent += 1
            if tracer:
                tracer.query_done()

    # daemons, so that a client stuck in a query cannot keep a failed run alive
    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-client-{i}")
               for i in range(len(sends))]
    for t in threads:
        t.start()
    # warm-up may compile for many minutes and is cut by the caller's own
    # limit; the window's clients get the window and some slack
    deadline = (None if steps is not None
                else time.perf_counter() + seconds + JOIN_SLACK_S)
    for t in threads:
        t.join(timeout=None if deadline is None
               else max(0.0, deadline - time.perf_counter()))
    stuck = [t.name for t in threads if t.is_alive()]
    if stuck:
        raise TimeoutError(f"clients still running {JOIN_SLACK_S:.0f} s after "
                           f"the window: {stuck}")
    return sorted((r for rs in records for r in rs), key=lambda r: r.t_start)


class ClosedLoopDriver:
    """What the closed-loop drivers share: every client's place in its
    schedule and its last latency, carried from the warm-up into the window.
    A driver adds ``start``, ``sends`` (one ``Send`` per client) and ``stop``;
    ``fallbacks`` collects what its statements' fallback reports held."""

    def __init__(self, cell: Cell, clients: int):
        self.cell = cell
        self.clients = clients
        self.fallbacks: List[str] = []
        self._last = [0.0] * clients
        self._positions = [cell.schedule(i, clients) for i in range(clients)]

    def sends(self) -> List[Send]:
        raise NotImplementedError

    def warm_up(self) -> List[QueryRecord]:
        """Every statement the window will send, once for each round."""
        steps = (self.cell.warmup_steps(self.clients)
                 * int(self.cell.traffic["warmup_rounds"]))
        return run_clients(self.cell, self.sends(), steps, 0.0, self._last,
                           self._positions, None)

    def run_window(self, seconds: float, tracer: Optional[WindowTracer],
                   t0: float) -> List[QueryRecord]:
        return run_clients(self.cell, self.sends(), None, seconds, self._last,
                           self._positions, tracer, t0)

    def stats(self) -> Dict:
        return {}
