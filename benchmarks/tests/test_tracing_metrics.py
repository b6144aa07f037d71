"""PR 26's additions: the manifest with the three host-interval metrics
stands; q1's cell and the star join's, rehearsed on the CPU under the
profiler, print the three metrics; and a program without the timers (the
parent commit) makes their readers report nothing instead of a zero.

``star_2m_batch`` is a cell of ``BENCHMARK.json`` since PR 28, which found
what the seed changed (the scan's packing of files into partitions) and
pinned the split of rows; ``entries_not_proved.json`` keeps the served cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import check_manifest as CM
from benchmarks.harness import cell as C
from benchmarks.harness.watch import CompileCounts
from benchmarks.harness.window import Window

NEW_METRICS = {"plan_host_s": ("planTime", "L6_plan"),
               "first_dispatch_s": ("firstDispatchTime", "scan"),
               "device_wait_host_s": ("deviceSyncTime", "L4_transfer")}


def manifest():
    with open(os.path.join(CM.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def not_proved():
    with open(os.path.join(CM.ROOT, "benchmarks",
                           "entries_not_proved.json")) as f:
        return json.load(f)


def test_manifest_stands_with_the_new_metrics():
    m = manifest()
    assert CM.check(m) == []
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name, (_timer, layer) in NEW_METRICS.items():
        p = by_name[name]
        assert (p["unit"], p["better"], p["source"], p["layer"],
                p["moves"]) == ("s/query", "lower", "program_span", layer,
                                "query_s")
        assert "workloads" not in p      # every cell reports them


def test_the_star_join_is_a_cell_of_the_manifest():
    """Configuration ``tpcds_star_2m``, traffic ``closed_direct_c1``, one
    chip; what waits beside the manifest no longer names it."""
    m = manifest()
    (cell,) = [w for w in m["workloads"] if w["name"] == "star_2m_batch"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpcds_star_2m", "closed_direct_c1", 1)
    kept = not_proved()
    assert "configs" not in kept
    assert [w["name"] for w in kept["workloads"]] == ["star_2m_served_c4"]
    assert [e["name"] for e in kept["end_to_end"]] == ["query_p95_s"]
    assert [p["name"] for p in kept["per_layer"]] == ["queue_wait_s",
                                                      "exec_share"]


@pytest.mark.parametrize("workload", ["q1_sf1_batch", "star_2m_batch"])
def test_traced_rehearsal_prints_the_three_metrics(workload):
    p = subprocess.run(
        manifest()["command"] + [
            "--workload", workload, "--seed", "2147483743", "--seconds", "3",
            "--trace", "1", "--scale-rows", "0.02"],
        cwd=C.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    for name in NEW_METRICS:
        m = result["metrics"][name]
        assert m["unit"] == "s/query" and m["value"] > 0, (name, m)
    # the first enqueue cannot come after the query's end
    assert result["metrics"]["first_dispatch_s"]["value"] < 60


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_reports_nothing_without_the_timer(name):
    """The parent commit has no such timer: its totals lack the key, and
    the reader leaves the metric out of the line (None), never a zero."""
    reader = C.load_module(os.path.join(CM.ROOT, "benchmarks", "metrics",
                                        name + ".py"), "metric_" + name)
    rec = C.QueryRecord(client=0, binding=0, t_start=0.0, t_end=1.0,
                        ok=True)
    base = dict(seconds=1.0, t_start=0.0, setup_s=1.0, scan_rows=1,
                records=[rec], compiles=CompileCounts(0, 0.0, 0, 0))
    assert reader.read(Window(counters={"dispatchCount": 3}, **base)) is None
    timer = NEW_METRICS[name][0]
    got = reader.read(Window(counters={timer: 2_500_000_000}, **base))
    assert got == pytest.approx(2.5)
