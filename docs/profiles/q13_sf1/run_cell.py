#!/usr/bin/env python3
"""Runs ``benchmarks/run.py`` of the current directory's tree with its own
arguments (the harness itself is untouched).

    python3 <repo>/docs/profiles/q13_sf1/run_cell.py <out-prefix|-> \
        --workload ... --seed ... --seconds ... --trace 0|1

Prints the window's string, LIKE and scan counters a completed query as
one ``[probe] counters`` line (a tree without a counter leaves it out).
With ``--trace 1`` and an out-prefix, it reads the profile just before the
harness deletes it and writes ``<prefix>.scopes.txt`` (the ``tools trace``
report: device seconds by program and scope, idle by the host span that
owns it) and ``<prefix>.json``: the queries the profile holds and the
engine's spans a query by kind.

With ``QPROBE=1`` it also prints one ``[probe] queries`` line: for every
query, warm-up included, its latency, the process's minor and major page
faults, context switches, user and system CPU seconds, resident bytes,
threads, Python collections by generation, the device allocator's state
after it, and every engine timer of a millisecond or more over that query
alone. ``QPROBE_NOGC=1`` turns Python's collector off for
the run. ``QPROBE_TRACEMALLOC=1`` traces the interpreter's and numpy's
allocations and prints, as ``[probe] retained``, the source lines whose
live bytes grew most between the end of the second query and the end of
the eighth (``tracemalloc`` slows every allocation: no timing of such a
run stands for the cell)."""
import json
import os
import runpy
import shutil
import sys

out = sys.argv.pop(1)
sys.path.insert(0, os.getcwd())
COUNTERS = ("likeCharBytes", "deviceDecodeStringBytes", "scanStrLengthsTime",
            "scanParseTime", "scanInflateTime", "scanReadTime",
            "scanStarvedTime", "scanPages", "joinConditionPushedCount",
            "joinTime", "joinStreamChunks", "joinBuildRows",
            "joinOutputRows", "computeAggTime", "aggMergeCount",
            "dispatchCount", "deviceSyncTime", "retryCount",
            "splitRetryCount")


def summarize(profile_file: str) -> dict:
    from spark_rapids_tpu import tools as TL
    spans = TL.load_profile(profile_file)["spans"]
    roots = [s for s in spans if s["name"] == "srt.query"]
    nq = max(1, len(roots))
    lo = min((s["t0"] for s in roots), default=float("-inf"))
    hi = max((s["t1"] for s in roots), default=float("inf"))
    kinds = {}
    for s in spans:
        if lo <= s["t0"] < hi:
            kinds[s["name"]] = kinds.get(s["name"], 0) + 1
    return {"file": profile_file, "queries": len(roots),
            "spans_a_query": {k: v / nq for k, v in sorted(
                kinds.items(), key=lambda kv: -kv[1])}}


_rmtree = shutil.rmtree


def rmtree(path, *a, **k):
    if out != "-" and "perf-trace" in str(path) \
            and os.path.isdir(os.path.join(str(path), "plugins")):
        try:
            from spark_rapids_tpu import tools as TL
            files = TL.profile_files(str(path))
            with open(out + ".scopes.txt", "w") as f:
                f.write("\n\n".join(TL.format_profile_report(fp, top=40)
                                    for fp in files) + "\n")
            with open(out + ".json", "w") as f:
                json.dump([summarize(fp) for fp in files], f, indent=1)
        except Exception as e:  # noqa: BLE001 - the run's result matters more
            print(f"[probe] tools trace failed: {e!r}", file=sys.stderr)
    return _rmtree(path, *a, **k)


shutil.rmtree = rmtree

from benchmarks.harness import watch  # noqa: E402

_delta = watch.delta


def delta(after, before):
    d = _delta(after, before)
    print("[probe] counters: " + json.dumps(
        {k: d[k] for k in COUNTERS if k in d}, sort_keys=True), flush=True)
    return d


watch.delta = delta

if os.environ.get("QPROBE"):
    import gc
    import resource
    import threading
    import time

    from benchmarks.harness import closed_loop as CL

    if os.environ.get("QPROBE_NOGC"):
        gc.disable()
    TRACEMALLOC = bool(os.environ.get("QPROBE_TRACEMALLOC"))
    if TRACEMALLOC:
        import tracemalloc
        tracemalloc.start(12)
    SNAPS = {}

    def maps_summary():
        """Mapped bytes by kind: anonymous writable, file-backed, other."""
        out = {"anon_mb": 0, "file_mb": 0, "other_mb": 0, "n": 0}
        with open("/proc/self/maps") as f:
            for ln in f:
                parts = ln.split()
                lo, hi = (int(x, 16) for x in parts[0].split("-"))
                kind = ("file_mb" if len(parts) > 5 and parts[5].startswith("/")
                        else "anon_mb" if "w" in parts[1] and len(parts) <= 5
                        else "other_mb")
                out[kind] += hi - lo
                out["n"] += 1
        return {k: v // 2**20 if k != "n" else v for k, v in out.items()}
    DEVICE = ("bytes_in_use", "num_allocs", "bytes_reserved",
              "largest_free_block_bytes")
    _one = CL.one_query
    QUERIES = []
    T0 = time.perf_counter()

    def sample():
        r = resource.getrusage(resource.RUSAGE_SELF)
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
        import pyarrow as pa
        pool = pa.default_memory_pool()
        status = {}
        with open("/proc/self/status") as f:
            for ln in f:
                k, _, v = ln.partition(":")
                if k in ("RssAnon", "RssFile", "RssShmem", "VmSize"):
                    status[k] = int(v.split()[0]) // 1024
        status.update(maps_summary())
        status.update(arrow_mb=pool.bytes_allocated() // 2**20,
                      arrow_max_mb=pool.max_memory() // 2**20,
                      arrow_pool=pool.backend_name)
        tot = watch.process_totals()
        import jax
        mem = jax.devices()[0].memory_stats() or {}
        return (r.ru_minflt, r.ru_majflt, r.ru_nvcsw, r.ru_nivcsw,
                r.ru_utime, r.ru_stime, rss,
                [s["collections"] for s in gc.get_stats()],
                {k: v for k, v in tot.items() if k.endswith("Time")},
                {k: mem[k] for k in DEVICE if k in mem}, status)

    def one_query(*a, **k):
        b = sample()
        rec = _one(*a, **k)
        e = sample()
        QUERIES.append({
            "t": round(rec.t_start - T0, 3), "lat": round(rec.latency_s, 4),
            "minflt": e[0] - b[0], "majflt": e[1] - b[1],
            "vcsw": e[2] - b[2], "ivcsw": e[3] - b[3],
            "utime": round(e[4] - b[4], 3), "stime": round(e[5] - b[5], 3),
            "rss_mb": round(e[6] / 2**20), "threads": threading.active_count(),
            "gc": [x - y for x, y in zip(e[7], b[7])],
            "device": e[9], "memory_mb": e[10],
            "timers_ms": {k[:-4]: round((v - b[8].get(k, 0)) / 1e6, 1)
                          for k, v in e[8].items()
                          if v - b[8].get(k, 0) >= 1e6}})
        if TRACEMALLOC and len(QUERIES) in (3, 9):
            SNAPS[len(QUERIES)] = tracemalloc.take_snapshot()
            if len(SNAPS) == 2:
                top = SNAPS[9].compare_to(SNAPS[3], "traceback")[:12]
                print("[probe] retained: " + json.dumps([
                    {"mb": round(st.size_diff / 2**20, 1),
                     "blocks": st.count_diff,
                     "at": [f"{fr.filename.split('/')[-1]}:{fr.lineno}"
                            for fr in st.traceback][-6:]}
                    for st in top]), flush=True)
        return rec

    CL.one_query = one_query
    _delta2 = watch.delta

    def delta_and_queries(after, before):
        print("[probe] queries: " + json.dumps(QUERIES), flush=True)
        return _delta2(after, before)

    watch.delta = delta_and_queries
sys.argv[0] = "benchmarks/run.py"
runpy.run_path("benchmarks/run.py", run_name="__main__")
