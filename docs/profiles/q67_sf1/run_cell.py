#!/usr/bin/env python3
"""Runs ``benchmarks/run.py`` of the current directory's tree with its own
arguments (the cell runs of ``chip_runs.py``; the harness itself is untouched).

    python3 <repo>/docs/profiles/q67_sf1/run_cell.py <out-prefix|-> \
        --workload ... --seed ... --seconds ... --trace 0|1

Prints the window's expansion, aggregate and window counters as one
``[probe] counters`` line (``aggGroupCount``, ``aggMergeCount`` beside the
three that ``expand_host_s``, ``expand_rows`` and ``agg_key_words`` read;
a tree without a counter leaves it out).
With ``--trace 1`` and an out-prefix, it reads the profile just before the
harness deletes it and writes ``<prefix>.scopes.txt`` (the ``tools trace``
report: device seconds by program and scope, idle by host span) and
``<prefix>.json``: the queries the profile holds and the engine's spans a
query by kind, ``deviceSync`` among them."""
import json
import os
import runpy
import shutil
import sys

out = sys.argv.pop(1)
sys.path.insert(0, os.getcwd())


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
    sites = {}
    for s in spans:
        if s["name"] == "deviceSync" and lo <= s["t0"] < hi:
            site = s["args"].get("site", "?")
            sites[site] = sites.get(site, 0) + 1
    return {"file": profile_file, "queries": len(roots),
            "spans_a_query": {k: v / nq for k, v in sorted(
                kinds.items(), key=lambda kv: -kv[1])},
            "device_sync_a_query_by_site": {k: v / nq for k, v in sorted(
                sites.items())}}


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
    keep = {k: v for k, v in d.items()
            if k in ("expandRows", "expandTime", "aggKeyWords",
                     "aggGroupCount", "aggMergeCount", "windowRows",
                     "windowTime", "computeAggTime", "dispatchCount",
                     "deviceSyncTime", "retryCount", "splitRetryCount")}
    print("[probe] counters: " + json.dumps(keep, sort_keys=True),
          flush=True)
    return d


watch.delta = delta
sys.argv[0] = "benchmarks/run.py"
runpy.run_path("benchmarks/run.py", run_name="__main__")
