"""Each plain reference equals the CPU engine (``spark.rapids.sql.enabled=
false``) at a small size, for two seeds and every binding, and imports
nothing of the engine."""

import os
import subprocess
import sys

import pytest

from benchmarks.harness import cell as C
from benchmarks.harness.compare import first_difference, normal_rows

SEEDS = (20260730, 3000000019)   # the second does not fit 31 bits
# (configuration, traffic mix, share of the fact rows): built from the files,
# so that a configuration is held to the engine whether or not a cell of the
# manifest uses it yet
CELLS = {"q1": ("tpch_q1_sf1", "closed_direct_c1", 0.005),     # 30,006 rows
         "star": ("tpcds_star_2m", "closed_served_c4", 0.1)}   # 200,000 rows, 8 bindings


def make(which: str, seed: int, scale=None):
    config, traffic, share = CELLS[which]
    return C.make_cell(which, 1, config, os.path.join(
        C.BENCH_DIR, "configs", config, "config.json"), traffic, seed,
        share if scale is None else scale)


@pytest.fixture(scope="module")
def cpu_engine():
    from spark_rapids_tpu.sql.session import TpuSparkSession
    spark = TpuSparkSession({"spark.rapids.sql.enabled": "false"})
    yield spark
    spark.stop()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_reference_equals_cpu_engine(cell_name, seed, cpu_engine, tmp_path):
    cell = make(cell_name, seed)
    cell.generate()
    cell.write(str(tmp_path))
    cell.compute_answers()
    for table, path in cell.paths.items():
        cpu_engine.read.parquet(path).createOrReplaceTempView(table)
    assert len(cell.bindings) == int(cell.traffic["bindings"]) or \
        not cell.config["binding_domains"]
    for i in range(len(cell.bindings)):
        got = normal_rows(tuple(r) for r in cpu_engine.sql(cell.sql(i)).collect())
        assert got == cell.answers[i], (
            cell.bindings[i], first_difference(cell.answers[i], got))
        assert got, "an empty answer proves nothing"


def test_same_seed_same_data_and_bindings():
    a, b, c = make("star", 5, 0.01), make("star", 5, 0.01), make("star", 6, 0.01)
    a.generate(), b.generate(), c.generate()
    assert a.bindings == b.bindings and a.bindings != c.bindings
    for table in a.tables:
        for col in a.tables[table]:
            assert (a.tables[table][col] == b.tables[table][col]).all()
    assert (a.tables["store_sales"]["ss_item_sk"]
            != c.tables["store_sales"]["ss_item_sk"]).any()


def test_schedule_covers_every_binding_in_warm_up():
    cell = make("star", 9, 0.01)
    clients = 4
    steps = cell.warmup_steps(clients)
    seen = set()
    for client in range(clients):
        walk = cell.schedule(client, clients)
        seen |= {next(walk) for _ in range(steps)}
    assert seen == set(range(len(cell.bindings)))


@pytest.mark.parametrize("config", ["tpch_q1_sf1", "tpcds_star_2m"])
def test_reference_and_generator_import_nothing_of_the_engine(config):
    """In a fresh interpreter, so that this process's imports do not count."""
    d = os.path.join(C.BENCH_DIR, "configs", config)
    code = (
        "import importlib.util, sys\n"
        "for name in ('generator', 'reference'):\n"
        f"    spec = importlib.util.spec_from_file_location(name, r'{d}/' + name + '.py')\n"
        "    m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "bad = [m for m in sys.modules if m.startswith(('spark_rapids_tpu', 'jax', 'benchmarks'))]\n"
        "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_served_driver_runs_the_star_join_for_four_clients(tmp_path):
    """The served mix end to end in this process, at a small size: every
    binding warmed once, every answer of the window equal to the reference,
    the server drained."""
    import time

    from benchmarks.harness.drivers.closed_served import Driver
    cell = make("star", 2147483693)
    cell.generate()
    cell.write(str(tmp_path))
    cell.compute_answers()
    driver = Driver(cell)
    try:
        driver.start()
        warm = driver.warm_up()
        assert sorted(r.binding for r in warm) == list(range(8))
        records = driver.run_window(2.0, None, time.perf_counter())
    finally:
        driver.stop()
    from benchmarks.harness.closed_loop import judge
    judge(cell, warm + records)
    assert all(r.rows for r in warm + records)
    assert records and all(r.ok for r in warm + records), \
        [r.error for r in warm + records if not r.ok][:1]
    assert {r.client for r in records} == {0, 1, 2, 3}
    assert all(r.exec_ms is not None and r.queue_wait_ms is not None
               for r in records)
    assert driver.drained is True
