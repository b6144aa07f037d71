#!/usr/bin/env python3
"""Runs ``benchmarks/run.py`` of the current directory's tree with its own
arguments (PR 38's chip runs; the harness itself is untouched).

    python3 <repo>/docs/profiles/pr38/run_cell.py <out-prefix|-> \
        --workload ... --seed ... --seconds ... --trace 0|1

Prints the window's PR 38 counters a completed query as one ``[probe]``
line (the byte, page and collection counters have no metric). With
``--trace 1`` and an out-prefix, it reads the profile just before the
harness deletes it and writes ``<prefix>.scopes.txt`` (the ``tools trace``
report) and ``<prefix>.json``: per device plane the idle seconds a query
by the host span that owns them, the part under the scan path's spans
(ISSUE 38's list: ``FileScan.*``, ``scanPrefetch``, ``uploadAhead``,
``TpuRowToColumnarExec.packBatchTime`` and the new kinds), the engine
spans a query by kind, and every ``gcPause`` of 10 ms or more with the
idle time it overlaps; and how much of the time any consumer waited
(the union of the ``scanWait`` spans) the device was busy.

With ``PR38_CPU=1`` (the change's tree only) it also books, per host
step, the calls, their wall (summed over threads) and their thread CPU
(``time.thread_time_ns``), and prints the window's as one ``[probe] cpu``
line beside the process's CPU seconds and the window's wall: a step
whose wall far exceeds its CPU spent that time off the CPU, waiting for
the interpreter's lock or the scheduler."""
import json
import os
import runpy
import shutil
import sys
import threading
import time

out = sys.argv.pop(1)
sys.path.insert(0, os.getcwd())
SCAN_KINDS = ("scanPrefetch", "uploadAhead",
              "TpuRowToColumnarExec.packBatchTime", "scanRead",
              "scanInflate", "scanParse", "scanWait")


def scan_path(kind: str) -> bool:
    return kind.startswith("FileScan.") or kind in SCAN_KINDS


def summarize(profile_file: str) -> dict:
    from spark_rapids_tpu import tools as TL
    pr = TL.load_profile(profile_file)
    a = TL.analyze_profile(profile_file, loaded=pr)
    spans = pr["spans"]
    roots = [s for s in spans if s["name"] == "srt.query"]
    nq = max(1, len(roots))
    window = ((min(s["t0"] for s in roots), max(s["t1"] for s in roots))
              if roots else None)
    kinds = {}
    for s in spans:
        if window is None or window[0] <= s["t0"] < window[1]:
            kinds[s["name"]] = kinds.get(s["name"], 0) + 1
    res = {"file": profile_file, "queries": len(roots),
           "spans_a_query": {k: v / nq for k, v in sorted(
               kinds.items(), key=lambda kv: -kv[1])},
           "spans_a_query_total": sum(kinds.values()) / nq, "chips": {}}
    occ = TL.device_occupancy(pr["ops"], window)
    for plane, c in a["chips"].items():
        by_kind = c["idleByKind_s"]
        gaps = occ[plane]["gaps"]
        pauses = []
        for s in spans:
            if s["name"] != "gcPause" or s["t1"] - s["t0"] < 10_000:
                continue
            idle = sum(max(0.0, min(s["t1"], g1) - max(s["t0"], g0))
                       for g0, g1 in gaps)
            pauses.append({"at_s": s["t0"] / 1e6,
                           "s": (s["t1"] - s["t0"]) / 1e6,
                           "gen": s["args"].get("gen"),
                           "q": s["args"].get("q"),
                           "idle_overlap_s": idle / 1e6})
        waits = TL._union([(s["t0"], s["t1"]) for s in spans
                           if s["name"] == "scanWait"])
        wait_s = sum(b - a for a, b in waits) / 1e6
        wait_idle = sum(max(0.0, min(b, g1) - max(a, g0))
                        for a, b in waits for g0, g1 in gaps) / 1e6
        res["chips"][plane] = {
            "scan_wait_union_a_query_s": wait_s / nq,
            "scan_wait_union_device_busy_a_query_s":
                (wait_s - wait_idle) / nq,
            "busy_s": c["busy_s"], "idle_s": c["idle_s"],
            "idle_a_query_s": c["idle_s"] / nq,
            "idle_by_kind_a_query_s": {k: v / nq
                                       for k, v in by_kind.items()},
            "scan_path_idle_a_query_s": sum(
                v for k, v in by_kind.items() if scan_path(k)) / nq,
            "longest_gaps": c["longestGaps"],
            "gc_pauses_over_10ms": pauses}
    return res


_rmtree = shutil.rmtree


def rmtree(path, *a, **k):
    if out != "-" and "perf-trace" in str(path) \
            and os.path.isdir(os.path.join(str(path), "plugins")):
        try:
            from spark_rapids_tpu import tools as TL
            files = TL.profile_files(str(path))
            text = "\n\n".join(TL.format_profile_report(fp, top=40)
                               for fp in files)
            with open(out + ".scopes.txt", "w") as f:
                f.write(text + "\n")
            with open(out + ".json", "w") as f:
                json.dump([summarize(fp) for fp in files], f, indent=1)
        except Exception as e:  # noqa: BLE001 - the run's result matters more
            print(f"[probe] tools trace failed: {e!r}", file=sys.stderr)
    return _rmtree(path, *a, **k)


shutil.rmtree = rmtree

from benchmarks.harness import watch  # noqa: E402

_delta = watch.delta
_totals = watch.process_totals
CPU = {}  # step -> [calls, wall ns, thread CPU ns]
_CPU_LOCK = threading.Lock()
_SNAPS = []  # (CPU copy, process CPU ns, wall ns) at each process_totals()


def _timed(step, fn):
    def timed(*a, **k):
        w0, c0 = time.perf_counter_ns(), time.thread_time_ns()
        try:
            return fn(*a, **k)
        finally:
            w = time.perf_counter_ns() - w0
            c = time.thread_time_ns() - c0
            with _CPU_LOCK:
                e = CPU.setdefault(step, [0, 0, 0])
                e[0] += 1
                e[1] += w
                e[2] += c
    return timed


if os.environ.get("PR38_CPU") == "1":
    from spark_rapids_tpu.io import device_decode as DD
    HS = DD._HostSteps
    HS.footer = _timed("read", HS.footer)
    HS.read = _timed("read", HS.read)
    HS.inflate = _timed("inflate", HS.inflate)
    # the parse is a column's plan less its inflate
    DD._plan_column = _timed("plan_column", DD._plan_column)
    DD.plan_unit_encoded = _timed("unit", DD.plan_unit_encoded)


def process_totals():
    with _CPU_LOCK:
        _SNAPS.append(({k: list(v) for k, v in CPU.items()},
                       time.process_time_ns(), time.perf_counter_ns()))
    return _totals()


def delta(after, before):
    d = _delta(after, before)
    keep = {k: v for k, v in d.items()
            if k.startswith(("scan", "gc", "deviceDecode", "decodeTime",
                             "firstDispatch"))}
    print("[probe] counters: " + json.dumps(keep, sort_keys=True),
          flush=True)
    if CPU and len(_SNAPS) >= 2:
        (c0, p0, w0), (c1, p1, w1) = _SNAPS[-2], _SNAPS[-1]
        steps = {k: [v[i] - c0.get(k, [0, 0, 0])[i] for i in range(3)]
                 for k, v in c1.items()}
        print("[probe] cpu: " + json.dumps(
            {"window_wall_ns": w1 - w0, "process_cpu_ns": p1 - p0,
             "steps_calls_wall_cpu_ns": steps}, sort_keys=True),
            flush=True)
    return d


watch.delta = delta
watch.process_totals = process_totals
sys.argv[0] = "benchmarks/run.py"
runpy.run_path("benchmarks/run.py", run_name="__main__")
