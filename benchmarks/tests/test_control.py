"""``correct`` has to be able to come out false.

The control is each configuration's plain reference in the nearest lower
precision that can go wrong at its size (``control_answer`` in its
``reference.py``: float64 for q1, float32 for the star join), at the cell's
own size; a reference may hold more controls, each with one stated guarantee
broken (``<what>_control_answer``). The comparison has to refuse every one of
them on every seed. The faults are planted under a
whole rehearsed run of the command, where an answer is produced, and the
run's last line has to say ``correct: false`` and name the number that failed
beside its limit."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import cell as C
from benchmarks.harness.compare import (compared_numbers, first_difference,
                                        is_correct)

SEEDS = (2147483659, 2147483693, 3000000019)


def _reference(config: str):
    return C.load_module(os.path.join(C.BENCH_DIR, "configs", config,
                                      "reference.py"), f"{config}_control")


CONTROLS = [(config, name)
            for config in sorted(os.listdir(os.path.join(C.BENCH_DIR, "configs")))
            for name in sorted(vars(_reference(config)))
            if name.endswith("control_answer")]


def test_every_configuration_has_its_control():
    assert {c for c, _ in CONTROLS} == set(os.listdir(
        os.path.join(C.BENCH_DIR, "configs")))
    assert all((c, "control_answer") in CONTROLS for c, _ in CONTROLS)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config,control", CONTROLS)
def test_the_control_is_refused_at_the_cells_own_size(config, control, seed):
    """numpy alone, so the cell's full size fits a test: q1's float64 sums go
    wrong only past 2**53 and the star join's float32 sums past 2**24, which
    a rehearsal's few rows never reach."""
    cell = C.make_cell("control", 1, config, os.path.join(
        C.BENCH_DIR, "configs", config, "config.json"), "closed_direct_c1", seed)
    cell.generate()
    cell.compute_answers()
    broken = getattr(_reference(config), control)
    for binding, want in zip(cell.bindings, cell.answers):
        got = broken(cell.tables, binding)
        assert len(got) == len(want)          # the same statement, broken
        assert first_difference(want, got) != "equal"
        record = C.QueryRecord(client=0, binding=0, t_start=0.0, t_end=1.0,
                               ok=got == want, differs=got != want)
        compared = compared_numbers([record], [])
        assert compared["answers_wrong"] == {"value": 1, "limit": 0}
        assert not is_correct(compared)


def test_compared_numbers_and_their_limits():
    ok = C.QueryRecord(client=0, binding=0, t_start=0.0, t_end=1.0, ok=True)
    raised = C.QueryRecord(client=0, binding=0, t_start=1.0, t_end=2.0,
                           ok=False, error="Traceback")
    assert is_correct(compared_numbers([ok, ok], []))
    assert not is_correct(compared_numbers([], []))       # nothing compared
    assert not is_correct(compared_numbers([ok], ["Project: not on the TPU"]))
    missing = compared_numbers([ok, raised], [])
    assert missing["answers_missing"]["value"] == 1 and not is_correct(missing)


# run.py with one fault planted where an answer is produced, then its own main
FAULTY_RUN = """
import sys
sys.path.insert(0, {root!r})
from spark_rapids_tpu.sql import dataframe as D
honest = D.DataFrame.collect
calls = [0]
def faulty(self):
    rows = honest(self)
    calls[0] += 1
    if calls[0] > 1 and rows:            # the warm-up's answer stays honest
        {fault}
    return rows
D.DataFrame.collect = faulty
from benchmarks import run
sys.exit(run.main(sys.argv[1:]))
"""
FAULTS = {
    # an answer altered where it is produced: one value of the last row
    "altered": ("rows = rows[:-1] + [tuple(rows[-1][:-1]) + (0,)]",
                "answers_wrong"),
    # rows left out: the answer cut short
    "cut_short": ("rows = rows[:-1]", "answers_wrong"),
    # an answer that never comes
    "raised": ("raise RuntimeError('planted')", "answers_missing"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_under_the_timed_path_makes_the_run_not_correct(fault):
    with open(C.MANIFEST) as f:
        workload = json.load(f)["workloads"][0]["name"]
    statement, number = FAULTS[fault]
    p = subprocess.run(
        [sys.executable, "-c", FAULTY_RUN.format(root=C.ROOT, fault=statement),
         "--workload", workload, "--seed", "2147483743", "--seconds", "1",
         "--trace", "0", "--scale-rows", "0.01"],
        cwd=C.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] >= 1
    assert list(result)[-1] == "compared"
    assert result["compared"][number]["value"] >= 1
    assert result["compared"][number]["limit"] == 0
    last = p.stderr.strip().splitlines()[-5:]
    assert last[-1] == "[correct] False"
    assert any(line.startswith(f"[correct] {number} ") for line in last)
