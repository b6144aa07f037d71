#!/usr/bin/env python3
"""Cell runs on the chip: runs ``run_cell.py`` once per argument, in turn,
each a process of its own that holds the chip alone.

    python3 docs/profiles/q67_sf1/chip_runs.py <tree>:<cell>:<seed>:<trace> ...

``<tree>`` is ``.`` for the change or a directory holding the parent's
checkout (``git archive`` of the parent with the change's
``benchmarks/`` and ``BENCHMARK.json`` laid over it, as a paired
comparison runs it). Every run's output goes to ``chiprun_out/q67_sf1/<tag>.{out,err}`` (a traced run's
summary to ``<tag>.json`` and ``<tag>.scopes.txt``); one line a run is
printed: its metrics, ``correct``, and the expansion and aggregate counters.
``CELL_SECONDS`` sets the window (default 48); ``CELL_SCALE`` (with
``JAX_PLATFORMS=cpu``) rehearses at that share of the fact table's rows."""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
OUT = os.path.join(REPO, "chiprun_out", "q67_sf1")


def main(argv) -> int:
    os.makedirs(OUT, exist_ok=True)
    seconds = os.environ.get("CELL_SECONDS", "48")
    rc_all = 0
    for spec in argv:
        tree, cell, seed, trace = spec.split(":")
        side = ("change" if tree == "." else
                os.path.basename(os.path.normpath(tree)))
        tag = f"{cell}.{side}.s{seed}.t{trace}"
        cmd = [sys.executable, os.path.join(HERE, "run_cell.py"),
               os.path.join(OUT, tag), "--workload", cell, "--seed", seed,
               "--seconds", seconds, "--trace", trace] + (
                   ["--scale-rows", os.environ["CELL_SCALE"]]
                   if os.environ.get("CELL_SCALE") else [])
        t0 = time.time()
        with open(os.path.join(OUT, tag + ".out"), "w") as fo, \
                open(os.path.join(OUT, tag + ".err"), "w") as fe:
            rc = subprocess.call(cmd, cwd=os.path.join(REPO, tree),
                                 stdout=fo, stderr=fe)
        rc_all = rc_all or rc
        line, probe = {}, ""
        with open(os.path.join(OUT, tag + ".out")) as f:
            for ln in f:
                if ln.startswith("[probe] counters: "):
                    probe = ln[len("[probe] counters: "):].strip()
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
    return rc_all


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
