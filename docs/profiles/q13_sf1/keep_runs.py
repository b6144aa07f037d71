#!/usr/bin/env python3
"""Appends one line a run to ``runs.jsonl`` from a chip call's outputs in
``chiprun_out/q13_sf1/``: the result line's metrics and ``compared``
numbers, the window's latencies and counters, the peak device memory and,
where ``QPROBE`` was set, the per-query probe (``queries``) and the
``tracemalloc`` growth (``retained``).

    python3 docs/profiles/q13_sf1/keep_runs.py <call> <tag> ...

``<tag>`` is a run's file name in ``chiprun_out/q13_sf1/`` without
``.out``, as ``chip_runs.py`` names it."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
OUT = os.path.join(REPO, "chiprun_out", "q13_sf1")
PREFIXES = {"[probe] counters: ": "counters",
            "[probe] queries: ": "queries",
            "[probe] retained: ": "retained"}


def one(call: int, tag: str) -> dict:
    cell, side, seed, trace, *variant = tag.split(".")
    row = {"call": call, "cell": cell, "side": side,
           "seed": int(seed[1:]), "trace": int(trace[1:]),
           "variant": variant[0] if variant else "base"}
    with open(os.path.join(OUT, tag + ".out")) as f:
        for ln in f:
            for prefix, key in PREFIXES.items():
                if ln.startswith(prefix):
                    row[key] = json.loads(ln[len(prefix):])
            if ln.startswith("[bench] window: "):
                row["latency_s"] = [round(x, 4) for x in json.loads(
                    ln[len("[bench] window: "):])["latency_s"]]
            elif ln.startswith("{"):
                res = json.loads(ln)
                row["correct"] = res["correct"]
                row["metrics"] = {k: v["value"]
                                  for k, v in res["metrics"].items()}
                row["compared"] = {k: v["value"]
                                   for k, v in res["compared"].items()}
                row["memory_peak_bytes"] = res["device"].get(
                    "memory_peak_bytes")
    return row


def main(argv) -> int:
    call, tags = int(argv[0]), argv[1:]
    with open(os.path.join(HERE, "runs.jsonl"), "a") as f:
        for tag in tags:
            f.write(json.dumps(one(call, tag), sort_keys=True) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
