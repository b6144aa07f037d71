#!/usr/bin/env python3
"""Prove one cell on the chip in one call: a first run (which compiles, or
loads what an earlier call compiled), one traced run, then ``--sets`` sets of
``--runs`` runs with the same seeds in every set; a cell whose first or traced
run failed gets no sets. Every run is a process
of its own, as the driver makes it, and all share one compile cache.

    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/prove_cell.py --workload q1_sf1_batch

This parent never imports JAX, so it never holds the chip. It writes every
run's information lines and result to ``chiprun_out/prove_<cell>.jsonl`` and
prints, per end-to-end metric and set, the median and the spread the bounds
are set from (first to third quartile of ``statistics.quantiles(n=4)`` as a
share of the median).
"""

import argparse
import json
import os
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from benchmarks.harness.stats import iqr_spread, median  # noqa: E402

# large, as the driver's are: a seed must not be assumed to fit 31 bits
SEEDS = (2147483659, 2147483693, 2147483713, 2147483743, 2147483777,
         2147483783, 2147483813, 2147483857)


def run_once(command, workload, seed, seconds, trace, log, label, timeout):
    argv = command[:] + ["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    try:
        proc = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True,
                              timeout=timeout)
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, *(
            t if isinstance(t, str) else (t or b"").decode(errors="replace")
            for t in (e.stdout, e.stderr))
    wall = time.time() - t0
    lines = [ln for ln in out.splitlines() if ln.strip()]
    result = None
    if rc == 0 and lines:
        try:
            result = json.loads(lines[-1])
        except ValueError:
            pass
    record = {"label": label, "workload": workload, "seed": seed,
              "trace": trace, "rc": rc, "wall_s": wall, "result": result,
              "info": [ln for ln in lines[:-1] if ln.startswith("[bench]")]}
    if result is None:
        record["stdout_tail"] = out[-3000:]
        record["stderr_tail"] = err[-6000:]
    log.write(json.dumps(record) + "\n")
    log.flush()
    values = ({k: v["value"] for k, v in result["metrics"].items()}
              if result else None)
    print(f"[prove] {label} seed={seed} rc={rc} wall={wall:.1f}s "
          f"correct={result and result['correct']} "
          f"attempted={result and result['attempted']} "
          f"failed={result and result['failed']} {json.dumps(values)}",
          flush=True)
    if result is None:
        print(err[-3000:], flush=True)
    return result


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--first", type=int, default=1, help="first runs before the sets")
    p.add_argument("--traced", type=int, default=1, help="traced runs before the sets")
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--seed-offset", type=int, default=0,
                   help="the sets start at this place in the list of seeds, "
                        "so that a later call can add runs to an earlier one's")
    p.add_argument("--timeout", type=float, default=1200.0)
    p.add_argument("--scale-rows", default=None,
                   help="passed on; run.py takes it under JAX_PLATFORMS=cpu only")
    args = p.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    command = manifest["command"]
    if args.scale_rows:
        command = command + ["--scale-rows", args.scale_rows]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    ok = True
    workload = args.workload
    path = os.path.join(ROOT, "chiprun_out", f"prove_{workload}.jsonl")
    sets = []
    with open(path, "a") as log:
        for i in range(args.first):
            r = run_once(command, workload, SEEDS[-1 - i], seconds, 0, log,
                         f"{workload} first{i}", args.timeout)
            ok &= bool(r and r["correct"])
        for i in range(args.traced):
            r = run_once(command, workload, SEEDS[i % len(SEEDS)], seconds, 1,
                         log, f"{workload} traced{i}", args.timeout)
            ok &= bool(r and r["correct"])
            if r:
                print("[prove] traced " + json.dumps(r), flush=True)
        if not ok:
            print(f"[prove] {workload}: a run failed, so no sets", flush=True)
        for s in range(args.sets if ok else 0):
            got = []
            for i in range(args.runs):
                seed = SEEDS[(args.seed_offset + i) % len(SEEDS)]
                r = run_once(command, workload, seed, seconds, 0, log,
                             f"{workload} set{s} run{i}", args.timeout)
                ok &= bool(r and r["correct"])
                if r:
                    got.append(r)
            sets.append(got)
    for n in sorted({n for got in sets for r in got for n in r["metrics"]}):
        for s, got in enumerate(sets):
            vals = [r["metrics"][n]["value"] for r in got if n in r["metrics"]]
            if vals:
                print(f"[prove] {workload} {n} set{s}: n={len(vals)} "
                      f"median={median(vals):.6g} "
                      f"spread={iqr_spread(vals)} values={vals}", flush=True)
    print(f"[prove] all correct: {ok}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
