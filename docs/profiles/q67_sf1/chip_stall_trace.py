#!/usr/bin/env python3
"""One continuous device trace over the first queries of a ``q67_sf1_batch``
window, reduced query by query, to see what the chip did in a stalled one.

The stalls of the cell's runs (one query 0.5-2.0 s over the others' ~4.2 s)
came back to back, early in the window (the 3rd, 3rd, 5th and 7th query of
their runs), and never under ``chip_witness.py``'s hunt, which profiles each
query alone with a pause between them. So this script does what the harness
does (one seed's tables, the cell's conf, one warm-up query) and then runs
``QUERIES`` (default 7) queries back to back under ONE ``jax.profiler``
trace, with the harness's options (no Python frames, host annotations
only). It reads the trace with JAX's own ``ProfileData`` (the ``XLA
Modules`` line of the TPU plane and the host's annotations, not every
operation, so a 30 s trace is read in seconds) and prints one line
a query: its latency, the device's busy and idle seconds inside it, its
three longest idle gaps with the host annotations open at each gap's middle,
and device seconds by program; then the slowest query against the median
one, program by program. The trace is deleted.

On the chip, from the root of a checkout (about two minutes a seed):

    python3 docs/profiles/q67_sf1/chip_stall_trace.py 2147500301

``JAX_PLATFORMS=cpu SCALE=0.02`` rehearses it (a CPU trace has no device
plane: busy and programs read 0)."""
import glob
import json
import os
import re
import shutil
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

SEED = int(sys.argv[1]) if len(sys.argv) > 1 else 2147500301
DEVICE_PLANE = re.compile(r"^/device:TPU:0$")


def union(ivs):
    out = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def read_trace(trace_dir: str):
    """Module runs on the chip, the queries' annotations and every host
    event, each ``(name, start_ns, end_ns)`` on the profiler's clock."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = ProfileData.from_file(path)
    modules, queries, host = [], [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.extend((re.sub(r"\(\d+\)$", "", e.name),
                                    int(e.start_ns),
                                    int(e.start_ns + e.duration_ns))
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                    (queries if e.name == "srt.query" else host).append(ev)
    return (sorted(modules, key=lambda m: m[1]), sorted(queries,
            key=lambda q: q[1]), host, os.path.getsize(path))


def per_query(q, modules, host) -> dict:
    _, t0, t1 = q
    inside = [m for m in modules if t0 <= m[1] < t1]
    busy = union([[max(a, t0), min(b, t1)] for _, a, b in inside])
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < t1:
        gaps.append((at, t1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:3]
    programs = {}
    for name, a, b in inside:
        programs[name] = programs.get(name, 0) + (b - a) / 1e9
    return {
        "wall_s": (t1 - t0) / 1e9,
        "busy_s": sum(b - a for a, b in busy) / 1e9,
        "gaps": [{"at_s": (a - t0) / 1e9, "s": (b - a) / 1e9,
                  "host_open": sorted({n for n, x, y in host
                                       if x <= (a + b) // 2 < y})[:8]}
                 for a, b in gaps],
        "programs_s": dict(sorted(programs.items(), key=lambda kv: -kv[1])),
    }


def main() -> int:
    import jax

    from benchmarks.harness import cell as C
    from spark_rapids_tpu.sql.session import TpuSparkSession

    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    n = int(os.environ.get("QUERIES", "7"))
    print("device", jax.devices()[0], flush=True)
    cell = C.load_cell("q67_sf1_batch", SEED,
                       float(os.environ.get("SCALE", "1.0")) if rehearsal
                       else 1.0)
    root = os.path.join(".bench-data", "perf", f"q67-stall-{SEED}")
    shutil.rmtree(root, ignore_errors=True)
    cell.generate()
    cell.write(os.path.join(root, "data"))
    spark = TpuSparkSession(dict(cell.config["conf"]))
    text = cell.sql(0)
    lats = []
    try:
        for table, path in cell.paths.items():
            spark.read.parquet(path).createOrReplaceTempView(table)
        t = time.perf_counter()
        spark.sql(text).collect()                    # the warm-up round
        print(json.dumps({"warmup_s": time.perf_counter() - t}), flush=True)
        trace_dir = os.path.join(root, "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0          # the harness's options
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for _ in range(n):
            t = time.perf_counter()
            spark.sql(text).collect()
            lats.append(time.perf_counter() - t)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        stop_s = time.perf_counter() - t
    finally:
        spark.stop()
    t = time.perf_counter()
    modules, queries, host, size = read_trace(trace_dir)
    print(json.dumps({"seed": SEED, "latencies_s": lats, "stop_trace_s":
                      stop_s, "read_s": time.perf_counter() - t,
                      "trace_bytes": size, "queries_in_trace": len(queries),
                      "module_runs": len(modules),
                      "host_events": len(host)}), flush=True)
    rows = [per_query(q, modules, host) for q in queries]
    for i, r in enumerate(rows):
        print(json.dumps({"q": i, **{k: v for k, v in r.items()
                                     if k != "programs_s"},
                          "top_programs_s": dict(list(
                              r["programs_s"].items())[:6])}), flush=True)
    if rows:
        walls = [r["wall_s"] for r in rows]
        slow = max(range(len(rows)), key=lambda i: walls[i])
        mid = sorted(range(len(rows)), key=lambda i: walls[i])[len(rows) // 2]
        a, b = rows[slow]["programs_s"], rows[mid]["programs_s"]
        delta = {p: a.get(p, 0) - b.get(p, 0) for p in set(a) | set(b)}
        print(json.dumps({
            "slowest": slow, "median": mid,
            "wall_over_median_s": walls[slow] - walls[mid],
            "busy_over_median_s": rows[slow]["busy_s"] - rows[mid]["busy_s"],
            "median_wall_s": statistics.median(walls),
            "programs_over_median_s": dict(sorted(
                delta.items(), key=lambda kv: -abs(kv[1]))[:6])}),
            flush=True)
    shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
