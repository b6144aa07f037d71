#!/usr/bin/env python3
"""Prove one cell on the chip in one call: a first run (which compiles, or
loads what an earlier call compiled), one traced run, then ``--sets`` sets of
``--runs`` runs with the same seeds in every set; a cell whose first or traced
run failed gets no sets. Every run is a process
of its own, as the driver makes it, and all share one compile cache.

    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/prove_cell.py --workload q1_sf1_batch

This parent never imports JAX, so it never holds the chip. It writes every
run's information lines and result to ``chiprun_out/prove_<cell>.jsonl`` and
prints, per end-to-end metric and set, the median, the spread the bounds
are set from (first to third quartile of ``statistics.quantiles(n=4)`` as a
share of the median) and whether it is at or under half the manifest's bound
(``admissible: yes|no``); then per metric the bound that ``rule_bound`` gives
from the widest spread, so that the rule is computed by code and not by hand.
"""

import argparse
import json
import math
import os
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from benchmarks.harness.stats import iqr_spread, median  # noqa: E402

# large, as the driver's are: a seed must not be assumed to fit 31 bits
# the sets take the first six, traced runs the next ones, first runs the last
SEEDS = (2147483659, 2147483693, 2147483713, 2147483743, 2147483777,
         2147483783, 2147483813, 2147483869, 2147483887, 2147483929,
         2147483951, 2147483857)
SET_SEEDS = 6


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


def rule_bound(metric: str, widest_spread: float) -> float:
    """The bound PR 28's rule gives a metric from the widest spread any of
    its sets showed: twice it (the driver admits a cell whose spread is at
    most half the bound) plus half a percent for the driver's own seeds,
    rounded up to a multiple of half a percent, never under 1%. The set-up
    time keeps 0.25 and is judged on its median alone."""
    if metric == "setup_s":
        return 0.25
    steps = math.ceil(round((2 * widest_spread + 0.005) / 0.005, 9))
    return max(0.01, steps * 0.005)


def report_sets(workload, sets, entries) -> None:
    """Per metric and set the median, the spread and whether the spread is at
    or under half the manifest's bound (what the driver admits a cell on);
    then the widest spread, the bound the rule gives, and how the manifest's
    bound stands to it: too tight under twice the widest spread, too loose
    over eight times it (a bound of 1% is never too loose)."""
    names = sorted({n for got in sets for r in got for n in r["metrics"]})
    for n in names:
        bound = entries.get(n, {}).get("bound")
        spreads, medians = [], []
        for s, got in enumerate(sets):
            vals = [r["metrics"][n]["value"] for r in got if n in r["metrics"]]
            if len(vals) < 2:
                continue
            spread = iqr_spread(vals)
            spreads.append(spread)
            medians.append(median(vals))
            admissible = bound is not None and spread <= bound / 2
            print(f"[prove] {workload} {n} set{s}: n={len(vals)} "
                  f"median={median(vals):.6g} spread={spread:.5f} "
                  f"half_bound={bound and bound / 2} "
                  f"admissible: {'yes' if admissible else 'no'} "
                  f"values={vals}", flush=True)
        if not spreads:
            continue
        widest = max(spreads)
        verdict = "stands"
        if bound is not None and n != "setup_s":
            if widest > bound / 2:
                verdict = "too tight"
            elif bound > 0.01 and bound > 8 * widest:
                verdict = "too loose"
        drift = (abs(medians[-1] / medians[0] - 1) if len(medians) > 1
                 and medians[0] else 0.0)
        print(f"[prove] {workload} {n}: widest_spread={widest:.5f} "
              f"rule_bound={rule_bound(n, widest):.3f} manifest_bound={bound} "
              f"{verdict}; medians set to set differ by {drift:.5f}",
              flush=True)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--first", type=int, default=1, help="first runs before the sets")
    p.add_argument("--traced", type=int, default=1, help="traced runs before the sets")
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--seed-offset", type=int, default=0,
                   help="the sets start at this place among their six seeds, "
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
            r = run_once(command, workload,
                         SEEDS[(SET_SEEDS + i) % len(SEEDS)], seconds, 1,
                         log, f"{workload} traced{i}", args.timeout)
            ok &= bool(r and r["correct"])
            if r:
                print("[prove] traced " + json.dumps(r), flush=True)
        if not ok:
            print(f"[prove] {workload}: a run failed, so no sets", flush=True)
        for s in range(args.sets if ok else 0):
            got = []
            for i in range(args.runs):
                seed = SEEDS[(args.seed_offset + i) % SET_SEEDS]
                r = run_once(command, workload, seed, seconds, 0, log,
                             f"{workload} set{s} run{i}", args.timeout)
                ok &= bool(r and r["correct"])
                if r:
                    got.append(r)
            sets.append(got)
    report_sets(workload, sets, {m["name"]: m for m in manifest["end_to_end"]})
    print(f"[prove] all correct: {ok}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
