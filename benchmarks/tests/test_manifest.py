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


def test_the_two_cell_manifest(manifest):
    """q1 and the star join as one-chip batch cells under one traffic mix,
    three end-to-end metrics whose bounds follow PR 28's rule (at most 0.04;
    the set-up time 0.25), nine per-layer metrics that every cell reports."""
    assert [c["name"] for c in manifest["configs"]] == ["tpch_q1_sf1",
                                                        "tpcds_star_2m"]
    assert [(w["name"], w["config"], w["traffic"], w["chips"])
            for w in manifest["workloads"]] == [
        ("q1_sf1_batch", "tpch_q1_sf1", "closed_direct_c1", 1),
        ("star_2m_batch", "tpcds_star_2m", "closed_direct_c1", 1)]
    assert manifest["run_seconds"] == 48
    bounds = {m["name"]: m["bound"] for m in manifest["end_to_end"]}
    assert set(bounds) == {"setup_s", "query_s", "rows_per_s"}
    assert bounds["setup_s"] == 0.25
    assert 0.01 <= bounds["query_s"] <= 0.04
    assert 0.01 <= bounds["rows_per_s"] <= 0.04
    assert len(manifest["per_layer"]) == 9
    assert all("workloads" not in m and m["moves"] == "query_s"
               for m in manifest["per_layer"])


def test_every_fact_table_pins_its_split_of_rows(manifest):
    """The split of a partitioned table's rows over its files is written
    down, the same for every seed, and sums to the table's rows."""
    for c in manifest["configs"]:
        with open(os.path.join(CM.ROOT, c["file"])) as f:
            config = json.load(f)
        assert any("partition_rows" in note for note in config["assumed"])
        for table, spec in config["tables"].items():
            if spec["partitions"] > 1:
                pinned = spec["layout"]["partition_rows"]
                assert len(pinned) == spec["partitions"], table
                assert sum(pinned) == spec["rows"], table


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
