#!/usr/bin/env python3
"""Cell runs on the chip: runs ``run_cell.py`` once per argument, in turn,
each a process of its own that holds the chip alone.

    python3 docs/profiles/q13_sf1/chip_runs.py <tree>:<cell>:<seed>:<trace>[:<variant>] ...

``<tree>`` is ``.`` for the change or a directory holding the parent's
checkout (``git archive`` of the parent with the change's
``benchmarks/`` and ``BENCHMARK.json`` laid over it, as a paired
comparison runs it). Every run's output goes to ``chiprun_out/q13_sf1/<tag>.{out,err}`` (a traced run's
summary to ``<tag>.json`` and ``<tag>.scopes.txt``); one line a run is
printed: its metrics, ``correct``, and the counters ``run_cell.py`` prints.
``CELL_SECONDS`` sets the window (default 48); with ``STOP_ON_FAIL`` set,
a run of the change that fails or is not ``correct`` ends the list.
``<variant>`` names one of ``VARIANTS``: environment settings for that run
alone (the host allocators' and the per-query probe's)."""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
OUT = os.path.join(REPO, "chiprun_out", "q13_sf1")
# glibc: large blocks from the heap, never handed back to the kernel;
# pyarrow: glibc's allocator in place of its bundled mimalloc
HEAP = {"MALLOC_MMAP_THRESHOLD_": str(2**30),
        "MALLOC_TRIM_THRESHOLD_": str(2**34),
        "MALLOC_TOP_PAD_": str(2**28)}
VARIANTS = {
    "base": {},
    "probe": {"QPROBE": "1"},
    "heap": {"QPROBE": "1", **HEAP},
    "arrowsys": {"QPROBE": "1", "ARROW_DEFAULT_MEMORY_POOL": "system"},
    "heap_arrowsys": {"QPROBE": "1", "ARROW_DEFAULT_MEMORY_POOL": "system",
                      **HEAP},
    "nogc": {"QPROBE": "1", "QPROBE_NOGC": "1"},
    "arrowje": {"QPROBE": "1", "ARROW_DEFAULT_MEMORY_POOL": "jemalloc"},
    "tracemalloc": {"QPROBE": "1", "QPROBE_TRACEMALLOC": "1"},
}


def main(argv) -> int:
    os.makedirs(OUT, exist_ok=True)
    seconds = os.environ.get("CELL_SECONDS", "48")
    rc_all = 0
    for spec in argv:
        tree, cell, seed, trace, *variant = spec.split(":")
        variant = variant[0] if variant else "base"
        side = ("change" if tree == "." else
                os.path.basename(os.path.normpath(tree)))
        tag = f"{cell}.{side}.s{seed}.t{trace}" + (
            "" if variant == "base" else f".{variant}")
        env = dict(os.environ, **VARIANTS[variant])
        cmd = [sys.executable, os.path.join(HERE, "run_cell.py"),
               os.path.join(OUT, tag), "--workload", cell, "--seed", seed,
               "--seconds", seconds, "--trace", trace]
        t0 = time.time()
        with open(os.path.join(OUT, tag + ".out"), "w") as fo, \
                open(os.path.join(OUT, tag + ".err"), "w") as fe:
            rc = subprocess.call(cmd, cwd=os.path.join(REPO, tree),
                                 stdout=fo, stderr=fe, env=env)
        rc_all = rc_all or rc
        line, probe, queries = {}, "", ""
        with open(os.path.join(OUT, tag + ".out")) as f:
            for ln in f:
                if ln.startswith("[probe] counters: "):
                    probe = ln[len("[probe] counters: "):].strip()
                elif ln.startswith("[probe] retained: "):
                    print("  retained " + ln[len("[probe] retained: "):]
                          .strip()[:6000], flush=True)
                elif ln.startswith("[probe] queries: "):
                    queries = ln[len("[probe] queries: "):].strip()
                elif ln.startswith("{"):
                    try:
                        line = json.loads(ln)
                    except ValueError:
                        pass
        print(json.dumps({
            "run": tag, "rc": rc, "wall_s": round(time.time() - t0, 1),
            "correct": line.get("correct"),
            "attempted": line.get("attempted"),
            "failed": line.get("failed"),
            "metrics": {k: v["value"]
                        for k, v in line.get("metrics", {}).items()},
            "compared": {k: v.get("value")
                         for k, v in line.get("compared", {}).items()},
            "memory_peak_bytes": line.get("device", {}).get(
                "memory_peak_bytes")}), flush=True)
        print("  counters " + probe, flush=True)
        if queries:
            # the whole line is in the run's .out file
            print("  queries " + json.dumps([
                [q["lat"], q["rss_mb"], q["memory_mb"],
                 q["timers_ms"].get("scanStarved"),
                 q["timers_ms"].get("scanInflate"),
                 q["timers_ms"].get("scanStrLengths")]
                for q in json.loads(queries)]), flush=True)
        if tree == "." and (rc or line.get("correct") is not True) \
                and os.environ.get("STOP_ON_FAIL"):
            break
    return rc_all


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
