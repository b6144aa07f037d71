#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, as the one process that holds the chip. It
finds the cell in ``BENCHMARK.json``, and by the names there its
configuration (``configs/<config>/``), its traffic mix
(``traffic/<traffic>.json``), the mix's loop driver
(``harness/drivers/<driver>.py``) and one reader per metric
(``metrics/<metric>.py``). Set-up: data from ``--seed``, written as Parquet
(``harness/tables.py``) and read back as a ``[bench] layout`` line, one
warm-up pass over every statement the window will send. Then ``--seconds`` of
measurement; then, the window closed and the sessions stopped, the plain
reference's answers, to which every answer of the window is held. The last
line of standard output is the result; lines before it, marked ``[bench]``,
are information. The numbers ``correct`` rests on stand beside their limits
under the result's last key, ``compared``, and as the last lines of standard
error.

It refuses to start without a TPU holding the chips the cell asks for. Only
when the caller itself sets ``JAX_PLATFORMS=cpu`` does it run as a rehearsal
(``--scale-rows`` is accepted only then): the last line then names the CPU as
its device, and nothing it prints is a result.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def say(section: str, obj) -> None:
    print(f"[bench] {section}: "
          + (obj if isinstance(obj, str) else json.dumps(obj, sort_keys=True)),
          flush=True)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--scale-rows", type=float, default=1.0,
                   help="rehearsal only (JAX_PLATFORMS=cpu): the share of "
                        "each fact table's rows to generate")
    return p.parse_args(argv)


def device_or_exit(chips: int, rehearsal: bool):
    """The devices as JAX reports them; exits 2 without the accelerator."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if not rehearsal and (platform != "tpu" or len(devices) < chips):
        print(f"benchmarks/run.py: the cell asks for {chips} TPU chip(s) and "
              f"JAX finds {len(devices)} x {platform}; it measures only on "
              "the chip (JAX_PLATFORMS=cpu set by the caller rehearses)",
              file=sys.stderr)
        sys.exit(2)
    return devices


def metric_entries(manifest, cell_name: str, trace: int):
    """The cell's end-to-end metrics without a trace, its per-layer metrics
    with one. A metric without ``workloads`` belongs to every cell."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def main(argv=None) -> int:
    args = parse_args(argv)
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if args.scale_rows != 1.0 and not rehearsal:
        print("benchmarks/run.py: --scale-rows is for rehearsals under "
              "JAX_PLATFORMS=cpu; a cell is measured at its own size",
              file=sys.stderr)
        return 2

    from benchmarks.harness import cell as C
    manifest = C.load_json(C.MANIFEST)
    cell = C.load_cell(args.workload, args.seed, args.scale_rows)
    entries = metric_entries(manifest, cell.name, args.trace)
    readers = {m["name"]: C.load_module(
        os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"),
        "metric_" + m["name"]) for m in entries}
    driver_mod = C.load_module(
        os.path.join(BENCH_DIR, "harness", "drivers",
                     cell.traffic["driver"] + ".py"),
        "driver_" + cell.traffic["driver"])

    devices = device_or_exit(cell.chips, rehearsal)
    # the package must be there before any data is made: a directory with the
    # benchmark alone fails here, with no result printed
    import spark_rapids_tpu  # noqa: F401
    from benchmarks.harness import closed_loop as CL
    from benchmarks.harness import compare as CMP
    from benchmarks.harness import layout as LY
    from benchmarks.harness import profiler as PR
    from benchmarks.harness.peaks import peaks_for
    from benchmarks.harness.tracing import QUERY_ANNOTATION, WindowTracer
    from benchmarks.harness.watch import CompileWatch, delta, process_totals
    from benchmarks.harness.window import Window

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not rehearsal:
        peaks_for(device["kind"])  # an unknown chip is an error, not a default
    watch = CompileWatch()
    say("cell", {"workload": cell.name, "config": cell.config_name,
                 "traffic": cell.traffic_name, "seed": args.seed,
                 "seconds": args.seconds, "trace": args.trace,
                 "rows": cell.rows, "bindings": cell.bindings,
                 "device": device, "rehearsal": rehearsal})

    data_root = os.path.join(ROOT, ".bench-data", "perf", cell.name)
    trace_dir = os.path.join(ROOT, ".bench-data", "perf-trace", cell.name)
    shutil.rmtree(data_root, ignore_errors=True)
    driver = driver_mod.Driver(cell)
    failure = None
    try:
        t = time.perf_counter()
        cell.generate()
        t_gen = time.perf_counter() - t
        t = time.perf_counter()
        cell.write(data_root)
        t_write = time.perf_counter() - t
        t = time.perf_counter()
        say("layout", LY.tables_layout(cell.paths))
        t_layout = time.perf_counter() - t
        t = time.perf_counter()
        driver.start()
        warm = driver.warm_up()
        t_warm = time.perf_counter() - t
        bad = [r.error for r in warm if not r.ok]
        if bad:
            raise RuntimeError("a warm-up query failed, so nothing is "
                               "measured: " + bad[0])
        setup_compiles = watch.snapshot()
        import jax
        say("setup", {
            "generate_s": t_gen, "write_s": t_write, "layout_s": t_layout,
            "start_and_warmup_s": t_warm,
            "warmup_latency_s": [r.latency_s for r in warm],
            "programs_compiled_or_loaded": setup_compiles.compiles,
            "compile_or_load_s": setup_compiles.seconds,
            "persistent_cache_hits": setup_compiles.cache_hits,
            "persistent_cache_misses": setup_compiles.cache_misses,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir})

        tracer = None
        if args.trace:
            tracer = WindowTracer(trace_dir,
                                  float(cell.traffic["trace_min_seconds"]))
        gc.collect()
        totals0 = process_totals()
        if tracer:
            tracer.start()
        compiles0 = watch.snapshot()
        t0 = time.perf_counter()
        setup_s = t0 - T_PROCESS_START
        records = driver.run_window(args.seconds, tracer, t0)
        if tracer:
            tracer.finish()
        compiles = watch.snapshot().since(compiles0)
        counters = delta(process_totals(), totals0)
        driver_stats = driver.stats()
        device["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices)
    except Exception as e:  # noqa: BLE001 - reported; no result is printed
        import traceback
        traceback.print_exc()
        failure = e
    finally:
        try:
            driver.stop()
        finally:
            shutil.rmtree(data_root, ignore_errors=True)
    if failure is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"benchmarks/run.py: no result: {failure!r}", file=sys.stderr)
        return 1

    # the window has closed, the peak is read and the sessions are stopped:
    # now the plain reference answers, and every answer kept is held to it
    t = time.perf_counter()
    try:
        cell.compute_answers()
    except ValueError as e:
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"benchmarks/run.py: no result: {e}", file=sys.stderr)
        return 1
    CL.judge(cell, records)
    say("reference", {"reference_s": time.perf_counter() - t,
                      "answers_held_to_it": len(records)})

    window = Window(seconds=args.seconds, t_start=t0, setup_s=setup_s,
                    scan_rows=cell.scan_rows, records=records,
                    counters=counters, compiles=compiles)
    breakdown = None
    if tracer:
        try:
            trace = PR.load_trace(trace_dir, QUERY_ANNOTATION)
            offset = PR.clock_offset(
                trace["annotations"],
                [int(s * 1e9) for s in tracer.annotated_starts])
            if offset is not None:
                def on_trace_clock(t):
                    return int(t * 1e9) + offset
                span = (on_trace_clock(tracer.started_at),
                        on_trace_clock(tracer.stopped_at))
                queries = [(on_trace_clock(r.t_start), on_trace_clock(r.t_end))
                           for r in records]
                window.trace = PR.reduce_trace(trace, queries, span)
            say("trace", {
                "file_bytes": trace["file_bytes"],
                "annotations": len(trace["annotations"]),
                "stop_trace_s": tracer.stop_seconds,
                "traced_queries": sum(r.traced for r in records),
                "reduced": {k: v for k, v in (window.trace or {}).items()
                            if k != "breakdown"}})
            if window.trace:
                breakdown = window.trace["breakdown"]
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    failed = [r for r in records if not r.ok]
    for r in failed[:3]:
        say("failed_query", {"client": r.client, "binding": r.binding,
                             "error": r.error[-2000:]})
    fallbacks = sorted(set(driver.fallbacks))
    if fallbacks:
        say("fallbacks", fallbacks)
    say("window", {
        "latency_s": [r.latency_s for r in records],
        "clients": [r.client for r in records],
        "span_s": (max(r.t_end for r in records) - t0) if records else None,
        "compiles_in_window": compiles.compiles,
        "compile_s_in_window": compiles.seconds,
        "retries": counters.get("retryCount", 0)
        + counters.get("splitRetryCount", 0),
        "dispatchCount": counters.get("dispatchCount", 0),
        "driver": driver_stats, "drained": getattr(driver, "drained", None)})

    metrics = {}
    for m in entries:
        value = readers[m["name"]].read(window)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if window.trace:
        device["busy_s"] = window.trace["busy_s"]
        device["window_s"] = window.trace["window_s"]
    compared = CMP.compared_numbers(records, fallbacks)
    result = {
        "correct": CMP.is_correct(compared),
        "attempted": len(records), "failed": len(failed),
        "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = compared       # last in the line, as on stderr
    print(json.dumps(result), flush=True)
    CMP.print_compared(compared, result["correct"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
