"""The manifest check accepts the committed manifest and refuses the faults
that lost earlier PRs."""

import copy
import json
import os

import pytest

from benchmarks import check_manifest as CM


@pytest.fixture()
def manifest():
    with open(os.path.join(CM.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_committed_manifest_stands(manifest):
    assert CM.check(manifest) == []


def _with(manifest, group, index, **changes):
    m = copy.deepcopy(manifest)
    m[group][index].update(changes)
    return m


@pytest.mark.parametrize("group,changes,needle", [
    ("per_layer", {"layer": "L7 entry"}, "layer"),          # PR 22's fault
    ("per_layer", {"unit": "x" * 17}, "unit"),
    ("per_layer", {"unit": "rows per s"}, "unit"),
    ("per_layer", {"moves": "no_such_metric"}, "moves"),
    ("per_layer", {"why": "a key the contract does not know"}, "keys"),
    ("end_to_end", {"bound": 0.3}, "bound"),
    ("end_to_end", {"bound": 0.001}, "bound"),
    ("end_to_end", {"source": "program_counter"}, "source"),
    ("end_to_end", {"name": "query s"}, "name"),
    ("workloads", {"chips": 2}, "chips"),
    ("workloads", {"why": "x" * 201}, "why"),
    ("workloads", {"traffic": "no_such_mix"}, "traffic"),
    ("configs", {"reduced": ["head_dim"]}, "width"),
    ("configs", {"file": "bench.py"}, "under paths"),
])
def test_fault_is_refused(manifest, group, changes, needle):
    index = 1 if group == "end_to_end" else 0
    faults = CM.check(_with(manifest, group, index, **changes))
    assert faults and any(needle in f for f in faults), faults


def test_too_many_four_chip_cells(manifest):
    m = copy.deepcopy(manifest)
    one = m["workloads"][0]
    m["workloads"] = [dict(one, name=f"cell{i}", chips=4) for i in range(3)]
    assert any("4 chips" in f for f in CM.check(m))


def test_run_seconds_limit(manifest):
    m = copy.deepcopy(manifest)
    m["run_seconds"] = 52
    assert any("run_seconds" in f for f in CM.check(m))


def test_unknown_top_level_key(manifest):
    m = copy.deepcopy(manifest)
    m["notes"] = "x"
    assert CM.check(m)
