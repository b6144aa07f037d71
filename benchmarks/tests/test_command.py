"""The command itself, rehearsed on the CPU at a tiny size: one last line
with exactly the contract's keys and, last among them, the numbers compared
beside their limits, naming the CPU as its device; the same numbers as the
last lines of standard error; a ``[bench] layout`` line before it."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import cell as C

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]


def run_command(*extra, env=None):
    with open(C.MANIFEST) as f:
        command = json.load(f)["command"]
    e = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    e.update(env or {})
    return subprocess.run(command + list(extra), cwd=C.ROOT, env=e,
                          capture_output=True, text=True, timeout=900)


def cells_of_the_manifest():
    with open(C.MANIFEST) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


# the star join needs 200,000 fact rows: at fewer a binding's answer can be
# empty, which the command refuses
@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", cells_of_the_manifest())
def test_last_line_is_the_contracts_result(workload, trace):
    scale = "0.01" if workload.startswith("q1") else "0.1"
    p = run_command("--workload", workload, "--seed", "2147483693",
                    "--seconds", "2", "--trace", str(trace),
                    "--scale-rows", scale)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(result) == RESULT_KEYS          # ``compared`` comes last
    for number in result["compared"].values():
        assert set(number) in ({"value", "limit"}, {"value", "at_least"})
    assert result["compared"]["answers_compared"]["value"] == result["attempted"]
    errors = p.stderr.strip().splitlines()
    assert errors[-1] == "[correct] True"
    assert [ln.split()[1] for ln in errors[-5:-1]] == list(result["compared"])
    layouts = [ln for ln in p.stdout.splitlines()
               if ln.startswith("[bench] layout: ")]
    assert len(layouts) == 1
    assert set(json.loads(layouts[0].split(": ", 1)[1])) == set(
        C.load_cell(workload, 1).config["tables"])
    assert result["device"]["platform"] == "cpu"     # a rehearsal says so
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    with open(C.MANIFEST) as f:
        manifest = json.load(f)
    group = manifest["per_layer" if trace else "end_to_end"]
    allowed = {m["name"] for m in group
               if "workloads" not in m or workload in m["workloads"]}
    assert set(result["metrics"]) <= allowed
    if not trace:
        assert set(result["metrics"]) == allowed
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], (int, float))
    assert not os.path.exists(os.path.join(C.ROOT, ".bench-data", "perf", workload))


def test_scale_rows_is_refused_outside_a_rehearsal():
    p = run_command("--workload", cells_of_the_manifest()[0], "--seed", "1",
                    "--seconds", "1", "--trace", "0", "--scale-rows", "0.01",
                    env={"JAX_PLATFORMS": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_unknown_workload_prints_no_result():
    p = run_command("--workload", "no_such_cell", "--seed", "1", "--seconds",
                    "1", "--trace", "0")
    assert p.returncode != 0 and "{" not in p.stdout
