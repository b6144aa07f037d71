#!/usr/bin/env python3
"""Runs ``benchmarks/run.py`` of the current directory's tree with its own
arguments (PR 37's chip runs; the harness itself is untouched).

    python3 <repo>/docs/profiles/pr37/run_cell.py <report|-> --workload ... \
        --seed ... --seconds 48 --trace 0|1

argv[1] is where the ``tools trace`` report of a ``--trace 1`` run goes (read
just before the harness deletes the profile; ``-`` for none). Prints, as
``[probe]`` lines, the window's join counters (``joinStreamChunks`` is in no
benchmark metric) and, with ``PROBE_SHAPES=1``, the lanes of every probe
(``device_join``'s stream and build capacities), once per new shape, and the
window's count of ``device_join`` calls: one a stream chunk, which is what
``joinStreamChunks`` counts, on a tree that has no such counter too.
``PROBE_CONF`` (a JSON object) is laid over the cell's session conf: a CPU
rehearsal at ``--scale-rows 0.02`` sets ``batchSizeRows`` to a fiftieth too,
so that the joins chunk as they do at full size; never set on the chip."""
import json
import os
import runpy
import shutil
import sys

out = sys.argv.pop(1)
sys.path.insert(0, os.getcwd())
_rmtree = shutil.rmtree


def rmtree(path, *a, **k):
    if out != "-" and "perf-trace" in str(path) \
            and os.path.isdir(os.path.join(str(path), "plugins")):
        try:
            from spark_rapids_tpu import tools
            text = "\n\n".join(tools.format_profile_report(fp, top=40)
                               for fp in tools.profile_files(str(path)))
            with open(out, "w") as f:
                f.write(text + "\n")
        except Exception as e:  # noqa: BLE001 - the run's result matters more
            print(f"[probe] tools trace failed: {e!r}", file=sys.stderr)
    return _rmtree(path, *a, **k)


shutil.rmtree = rmtree

from benchmarks.harness import watch  # noqa: E402

_delta = watch.delta


probes = {"calls": 0, "read_at": [0, 0]}
_totals = watch.process_totals


def process_totals():
    # the harness reads the totals at the window's start and at its end
    probes["read_at"] = [probes["read_at"][1], probes["calls"]]
    return _totals()


def delta(after, before):
    d = _delta(after, before)
    keep = {k: v for k, v in d.items()
            if k.startswith("join") and not k.endswith("Time")
            or k in ("dispatchCount", "retryCount", "splitRetryCount")}
    if os.environ.get("PROBE_SHAPES"):
        keep["device_join calls"] = (probes["read_at"][1]
                                     - probes["read_at"][0])
    print("[probe] counters: " + json.dumps(keep, sort_keys=True), flush=True)
    return d


watch.process_totals = process_totals
watch.delta = delta

extra = json.loads(os.environ.get("PROBE_CONF", "{}"))
if extra:
    from spark_rapids_tpu.sql import session as S
    _init = S.TpuSparkSession.__init__

    def init(self, conf=None, *a, **k):
        _init(self, dict(conf or {}, **extra), *a, **k)
    S.TpuSparkSession.__init__ = init

if os.environ.get("PROBE_SHAPES"):
    from spark_rapids_tpu.exec import join as J
    _join = J.device_join
    seen = set()

    def device_join(left, right, lk, rk, join_type, *a, **k):
        probes["calls"] += 1
        key = (join_type, left.capacity, right.capacity,
               k.get("condition") is not None)
        if key not in seen:
            seen.add(key)
            print(f"[probe] probe shape: {join_type} stream "
                  f"{left.capacity} + build {right.capacity} lanes"
                  f"{' (residual)' if key[3] else ''}", flush=True)
        return _join(left, right, lk, rk, join_type, *a, **k)
    J.device_join = device_join

sys.argv[0] = "benchmarks/run.py"
runpy.run_path("benchmarks/run.py", run_name="__main__")
