"""PR 36: why do a cell's queries differ in latency? One process sets the cell
up as ``benchmarks/run.py`` does (data from the seed, one warm-up query), runs
``SETTLE`` queries, then ``QUERIES`` queries back to back under
``jax.profiler`` and takes the profile apart query by query: latency, device
busy and idle, the idle gaps of 5 ms and more with the host span (and the
file, where the span is a scan's) they fall under, and the device seconds of
every program. Programs whose seconds differ between queries by more than
``SHOW_MS`` are listed run by run.

    chiprun -- python3 docs/profiles/pr36/chip_query_profile.py q51_sf1_batch 2147484201

Writes ``chiprun_out/pr36/query_profile.<cell>.json`` (every program run and
gap of every query) for a later look. ``JAX_PLATFORMS=cpu SCALE=0.02``
rehearses it; a CPU profile has no device plane, so the rehearsal prints the
host's side only."""
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())

CELL = sys.argv[1] if len(sys.argv) > 1 else "q51_sf1_batch"
SEED = int(sys.argv[2]) if len(sys.argv) > 2 else 2147484201
QUERIES = int(os.environ.get("QUERIES", "10"))
SETTLE = int(os.environ.get("SETTLE", "2"))
SHOW_MS = float(os.environ.get("SHOW_MS", "10"))
GAP_MS = 5.0


def main() -> int:
    import jax

    from benchmarks.harness import cell as C
    from benchmarks.harness.drivers import closed_direct
    from spark_rapids_tpu import tools

    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    print("device", jax.devices()[0], flush=True)
    cell = C.load_cell(CELL, SEED, float(os.environ.get("SCALE", "1.0"))
                       if rehearsal else 1.0)
    data_root = os.path.join(".bench-data", "perf", "qprofile-" + CELL)
    trace_dir = os.path.join(".bench-data", "perf-trace", "qprofile-" + CELL)
    for d in (data_root, trace_dir):
        shutil.rmtree(d, ignore_errors=True)
    cell.generate()
    cell.write(data_root)
    driver = closed_direct.Driver(cell)
    driver.start()
    send = driver.sends()[0]
    sql = cell.sql(0)
    try:
        t = time.perf_counter()
        send(sql)
        print(f"warm-up {time.perf_counter() - t:.1f} s", flush=True)
        for _ in range(SETTLE):
            send(sql)
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        host_latency = []
        for _ in range(QUERIES):
            t = time.perf_counter()
            send(sql)
            host_latency.append(time.perf_counter() - t)
        jax.profiler.stop_trace()
    finally:
        driver.stop()
        shutil.rmtree(data_root, ignore_errors=True)
    print("host latencies", " ".join(f"{x:.4f}" for x in host_latency),
          flush=True)

    out = {"cell": CELL, "seed": SEED, "host_latency_s": host_latency,
           "queries": []}
    for path in tools.profile_files(trace_dir):
        if os.path.getsize(path) < 40 << 20:   # the raw profile, if it fits
            os.makedirs(os.path.join("chiprun_out", "pr36"), exist_ok=True)
            shutil.copy(path, os.path.join(
                "chiprun_out", "pr36", f"query_profile.{CELL}.xplane.pb"))
        pr = tools.load_profile(path)
        roots = sorted((s for s in pr["spans"] if s["name"] == "srt.query"),
                       key=lambda s: s["t0"])
        planes = sorted(pr["ops"])
        ops = pr["ops"][planes[0]] if planes else []
        modules = pr["modules"][planes[0]] if planes else []
        for root in roots:
            q, w0, w1 = root["args"].get("q"), root["t0"], root["t1"]
            spans = [s for s in pr["spans"] if s["args"].get("q") == q]
            occ = tools.device_occupancy({"d": ops}, (w0, w1)).get("d")
            rec = {"q": q, "latency_s": (w1 - w0) / 1e6, "runs": [],
                   "gaps": [], "scans": []}
            if occ:
                rec["busy_s"] = occ["busy_us"] / 1e6
                rec["idle_s"] = occ["idle_us"] / 1e6
                att = tools.attribute_gaps(occ["gaps"], spans, GAP_MS * 1e3)
                for g in att["gaps"]:
                    cover = [s for s in spans if s["name"] == g["kind"]
                             and s["t0"] < g["t1"] and s["t1"] > g["t0"]]
                    rec["gaps"].append({
                        "at_s": (g["t0"] - w0) / 1e6,
                        "s": (g["t1"] - g["t0"]) / 1e6, "kind": g["kind"],
                        "what": sorted({os.path.join(*str(
                            s["args"].get("path") or s["args"].get("site")
                            or s["args"].get("program") or "-"
                            ).split(os.sep)[-2:]) for s in cover})})
            for m in modules:
                if w0 <= m[1] < w1:
                    rec["runs"].append([m[0], (m[1] - w0) / 1e6,
                                        (m[2] - m[1]) / 1e6])
            for s in spans:
                if s["name"] == "FileScan.deviceDecodeTime":
                    rec["scans"].append([
                        os.path.join(*str(s["args"].get("path", "-")
                                          ).split(os.sep)[-2:]),
                        (s["t0"] - w0) / 1e6, (s["t1"] - s["t0"]) / 1e6,
                        s["tid"]])
            out["queries"].append(rec)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(os.path.join("chiprun_out", "pr36"), exist_ok=True)
    with open(os.path.join("chiprun_out", "pr36",
                           f"query_profile.{CELL}.json"), "w") as f:
        json.dump(out, f)
    report(out)
    return 0


def report(out) -> None:
    qs = out["queries"]
    print(f"\n{len(qs)} queries of {out['cell']}, seed {out['seed']}")
    print("    q  latency     busy     idle  gaps of 5 ms and more "
          "(at, seconds, span, what)")
    for r in qs:
        gaps = "; ".join(f"{g['at_s']:.3f} {g['s']:.3f} {g['kind']} "
                         f"{','.join(g['what'])}" for g in r["gaps"])
        print(f"{r['q']!s:>5} {r['latency_s']:8.4f} "
              f"{r.get('busy_s', 0):8.4f} {r.get('idle_s', 0):8.4f}  {gaps}")
    print("\nscans' host spans (file: start, seconds), by query")
    for r in qs:
        print(f"{r['q']!s:>5} " + " ".join(
            f"{p.split('/')[0][:5]}{p[-9:-8]}:{a:.3f}+{d:.3f}"
            for p, a, d, _tid in sorted(r["scans"], key=lambda x: x[1])))
    names = sorted({m[0] for r in qs for m in r["runs"]})
    print("\ndevice seconds by program (runs), by query; * differs by over "
          f"{SHOW_MS:.0f} ms")
    varying = []
    for n in names:
        tot = [sum(m[2] for m in r["runs"] if m[0] == n) for r in qs]
        cnt = [sum(1 for m in r["runs"] if m[0] == n) for r in qs]
        star = "*" if (max(tot) - min(tot)) * 1e3 > SHOW_MS \
            or len(set(cnt)) > 1 else " "
        if star == "*":
            varying.append(n)
        print(f" {star} {n[:58]:58s} " + " ".join(
            f"{t:.3f}({c})" for t, c in zip(tot, cnt)))
    for n in varying:
        print(f"\n{n}: every run's start and seconds, by query")
        for r in qs:
            print(f"{r['q']!s:>5} " + " ".join(
                f"{a:.3f}+{d:.4f}" for m, a, d in r["runs"] if m == n))
    print("\nall programs' seconds summed: " + " ".join(
        f"{sum(m[2] for m in r['runs']):.4f}" for r in qs))


if __name__ == "__main__":
    sys.exit(main())
