"""PR 26's additions: the manifest with the three host-interval metrics
stands; q1's cell and the star join's, rehearsed on the CPU under the
profiler, print the three metrics; and a program without the timers (the
parent commit) makes their readers report nothing instead of a zero.

``star_2m_batch`` is not in ``BENCHMARK.json``: the driver's two sets of six
runs spread its ``rows_per_s`` by 0.79% and 0.58% against half of a 1% bound
(the seed makes the data, and the page decode's time follows the data), so it
waits in ``entries_not_proved.json`` for a ``benchmark`` issue. It is
rehearsed here from those entries, laid over the manifest in a temporary
file."""

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

# run.py finds its manifest through harness.cell.MANIFEST and nowhere else
RUN_WITH_MANIFEST = (
    "import sys; sys.path.insert(0, {root!r}); "
    "from benchmarks.harness import cell as C; C.MANIFEST = {manifest!r}; "
    "from benchmarks import run; sys.exit(run.main(sys.argv[1:]))")


def manifest():
    with open(os.path.join(CM.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def not_proved():
    with open(os.path.join(CM.ROOT, "benchmarks",
                           "entries_not_proved.json")) as f:
        return json.load(f)


def with_the_star_join(m):
    """The manifest with ``star_2m_batch`` and its configuration appended,
    exactly as the not-proved file holds them."""
    kept = not_proved()
    m = dict(m)
    have = {w["name"] for w in m["workloads"]}
    if "star_2m_batch" not in have:
        m["configs"] = m["configs"] + kept["configs"][:1]
        m["workloads"] = m["workloads"] + kept["workloads"][:1]
    return m


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


def test_manifest_stands_with_the_star_join_added():
    """What a ``benchmark`` issue will add is whole: configuration
    ``tpcds_star_2m``, traffic ``closed_direct_c1``, one chip."""
    m = with_the_star_join(manifest())
    assert CM.check(m) == []
    (cell,) = [w for w in m["workloads"] if w["name"] == "star_2m_batch"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpcds_star_2m", "closed_direct_c1", 1)


@pytest.mark.parametrize("workload", ["q1_sf1_batch", "star_2m_batch"])
def test_traced_rehearsal_prints_the_three_metrics(workload, tmp_path):
    path = str(tmp_path / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(with_the_star_join(manifest()), f)
    p = subprocess.run(
        [sys.executable, "-c",
         RUN_WITH_MANIFEST.format(root=C.ROOT, manifest=path),
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
