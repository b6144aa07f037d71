"""Stable program names and the profiler-side readers (docs/observability.md
"Program names", "Finding why the device was idle").

Every device program is built through ``jit_cache.named_jit`` under a name
``srt_<family>[_<tag>]`` that is a function of its structural key alone, so
XLA's modules, profiler traces and compile spans name the operator, and
JAX's persistent compilation cache (whose key holds the module's name) hits
from process to process. q1 and the star join — the benchmark's own
configurations, generators and statements, at a tiny size — run here on the
CPU under one profiler session; the xplane says which modules ran. (The
star join's cell waits in ``benchmarks/entries_not_proved.json``: the
driver found it too noisy for its bounds; its files are all there.)

The arithmetic of ``tools trace <profile dir>`` (device busy/idle, idle gaps
put down to host spans, device time by program and scope) takes plain lists
and is checked on hand-made ones: no chip needed.
"""

from __future__ import annotations

import glob
import os
import re
import shutil

import pytest

from spark_rapids_tpu import jit_cache as JC
from spark_rapids_tpu import tools as TL

CELLS = ("q1_sf1_batch", "star_2m_batch")
LEGACY = re.compile(r"^(jit_)?_?(fn|_fn|_lambda_|build|_sort|_extract|sm)\b")


# q1 and the star join fold their filters and projections into the
# aggregate's prelude; a chain with no aggregate above it is a fused
# stage of its own
STAGE_SQL = ("SELECT l_quantity * 2 AS q2, l_extendedprice FROM lineitem "
             "WHERE l_quantity > 10 AND l_discount < 5")


def _load_cell(name: str, seed: int, scale: float):
    """The cell of that name, from ``BENCHMARK.json`` or, while it is not
    there, from the entries that wait beside it for a ``benchmark`` issue."""
    from benchmarks.harness import cell as C
    try:
        return C.load_cell(name, seed, scale)
    except KeyError:
        kept = C.load_json(os.path.join(C.BENCH_DIR,
                                        "entries_not_proved.json"))
    w = C.by_name(kept["workloads"], name, "workload")
    c = C.by_name(kept["configs"], w["config"], "config")
    return C.make_cell(name, w["chips"], w["config"],
                       os.path.join(C.ROOT, c["file"]), w["traffic"], seed,
                       scale)


def _run_cell(name: str, seed: int, scale: float, root: str, binding=None,
              extra_sql=None):
    """One statement of the cell through ``sql(text).collect()``, as the
    benchmark's closed_direct driver sends it (then ``extra_sql``)."""
    from spark_rapids_tpu.sql.session import TpuSparkSession
    cell = _load_cell(name, seed, scale)
    cell.generate()
    cell.write(os.path.join(root, f"{name}-{seed}"))
    spark = TpuSparkSession(dict(cell.config["conf"]))
    try:
        for table, path in cell.paths.items():
            spark.read.parquet(path).createOrReplaceTempView(table)
        b = dict(cell.bindings[0])
        b.update(binding or {})
        rows = spark.sql(cell.statement.format(**b)).collect()
        assert not spark.last_rewrite_report.fallbacks
        if extra_sql:
            spark.sql(extra_sql).collect()
            assert not spark.last_rewrite_report.fallbacks
        return rows
    finally:
        spark.stop()


def _names_in_caches() -> set:
    out = set()
    for cache in list(JC._CACHES.values()):
        with cache._lock:
            values = list(cache._data.values())
        for v in values:
            n = JC.program_in(v)
            if n is not None:
                out.add(n)
    return out


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Both cells once under a profiler session: ``pjit`` = the names of
    the jitted functions that were called, ``modules`` = the HLO modules
    whose operations ran (CPU client lines carry ``hlo_module``)."""
    import jax
    from jax.profiler import ProfileData
    root = str(tmp_path_factory.mktemp("names"))
    prof = os.path.join(root, "prof")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(prof, profiler_options=options)
    try:
        _run_cell(CELLS[0], 11, 0.004, root, extra_sql=STAGE_SQL)
        # enough fact rows that the star join's result is not empty:
        # an empty one never reaches the TopN program
        _run_cell(CELLS[1], 11, 0.05, root)
    finally:
        jax.profiler.stop_trace()
    pjit, modules = set(), set()
    for f in glob.glob(os.path.join(prof, "plugins", "profile", "*",
                                    "*.xplane.pb")):
        for plane in ProfileData.from_file(f).planes:
            for line in plane.lines:
                for e in line.events:
                    m = re.match(r"PjitFunction\((.*)\)$", e.name)
                    if m:
                        pjit.add(m.group(1))
                    mod = dict(e.stats).get("hlo_module")
                    if mod:
                        modules.add(str(mod))
    shutil.rmtree(prof, ignore_errors=True)
    return {"pjit": pjit, "modules": modules, "root": root,
            "cached": _names_in_caches()}


# one case per program family that q1 and the star join build: each must
# have RUN (a PjitFunction event) under its srt_ name and lowered to a
# module called jit_<that name>
FAMILIES = {
    "decode": "srt_decode",            # Parquet page decode (scan)
    "stage": "srt_stage_",             # fused filter/project chain
    "agg_partial": "srt_agg_partial",
    "agg_merge": "srt_agg_merge",
    "agg_final": "srt_agg_final",
    "sort": "srt_sort",
    "project": "srt_project",
    "join_build": "srt_join_build",
    "join_probe": "srt_join_probe",
    "join_gather": "srt_join_gather",
    "fetch_pack": "srt_fetch_pack",
    "concat": "srt_concat",
    "shrink": "srt_shrink",
    "exchange": "srt_exchange_",
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_program_family_runs_under_its_srt_name(traced_run, family):
    prefix = FAMILIES[family]
    called = sorted(n for n in traced_run["pjit"] if n.startswith(prefix))
    assert called, (family, sorted(traced_run["pjit"]))
    for n in called:
        assert len(n) <= JC.PROGRAM_NAME_MAX and re.fullmatch(
            r"srt_[A-Za-z0-9_]+", n), n
    # the module XLA compiled is named for the function: jit_<name>
    lowered = [m for m in traced_run["modules"]
               if m.startswith("jit_" + prefix)]
    assert lowered or not traced_run["modules"], (
        family, sorted(traced_run["modules"]))


def test_no_program_keeps_a_closure_name(traced_run):
    """No jitted function of the engine is called ``fn``/``_fn``/a lambda
    any more (PR 24's ledger line read ``program:jit_fn_33604...``)."""
    bad = sorted(n for n in traced_run["pjit"] | traced_run["modules"]
                 if LEGACY.match(n))
    assert not bad, bad
    # and every program in a JitCache reads back an srt_ name
    for cache in list(JC._CACHES.values()):
        with cache._lock:
            values = list(cache._data.values())
        for v in values:
            fns = [x for x in (v if isinstance(v, tuple) else (v,))
                   if hasattr(x, "lower")]
            assert all(JC.program_of(f) for f in fns), (cache.name, fns)


def test_names_are_stable_across_literals_seeds_and_row_counts(traced_run):
    """Another seed, another row count and other literals build the SAME
    set of names: a name is a function of the structural key alone (a
    literal, capacity, hash() or counter in it would miss JAX's persistent
    cache in every process)."""
    JC_names_first = set(traced_run["cached"])
    for cache in list(JC._CACHES.values()):
        cache.clear()
    _run_cell("q1_sf1_batch", 2147483659, 0.007, traced_run["root"],
              extra_sql=STAGE_SQL.replace("> 10", "> 17"))
    _run_cell("star_2m_batch", 2147483659, 0.08, traced_run["root"],
              binding={"manufact_id": 436, "moy": 12})
    second = _names_in_caches()
    # programs of earlier test files may sit in the first snapshot; what
    # the two runs of the same statements built must agree
    assert second <= JC_names_first, sorted(second - JC_names_first)
    for n in second:
        assert not re.search(r"\d{3,}", n), f"a number in {n!r}"


@pytest.mark.parametrize("name,ok", [
    ("srt_stage_Filter_Project", True),
    ("srt_agg_partial", True),
    ("stage_Filter", False),               # no srt_ prefix
    ("srt_stage-Filter", False),           # outside [A-Za-z0-9_]
    ("srt_" + "x" * 60, False),            # over 48 characters
])
def test_named_jit_checks_the_name(name, ok):
    if ok:
        fn = JC.named_jit(name, lambda x: x + 1)
        assert JC.program_of(fn) == name
        import jax.numpy as jnp
        assert f"@jit_{name}" in fn.lower(jnp.arange(3)).as_text()
    else:
        with pytest.raises(ValueError):
            JC.named_jit(name, lambda x: x + 1)


def test_program_name_is_structural_and_bounded():
    assert JC.program_name("stage", "Filter", "Project") \
        == "srt_stage_Filter_Project"
    assert JC.program_name("join_gather", "left outer") \
        == "srt_join_gather_left_outer"
    assert JC.program_name("agg", "partial", None) == "srt_agg_partial"
    long = JC.program_name("stage", *(["Filter", "Project"] * 10))
    assert len(long) <= JC.PROGRAM_NAME_MAX and not long.endswith("_")


def test_lint_holds_named_jit_to_the_jit_cache_path(tmp_path):
    """named_jit is jax.jit to the jit-direct rule: outside a JitCache
    builder it needs a reasoned suppression."""
    from spark_rapids_tpu.lint import LintConfig, run_lint
    x = tmp_path / "spark_rapids_tpu" / "exec" / "x.py"
    x.parent.mkdir(parents=True)
    (tmp_path / "spark_rapids_tpu" / "__init__.py").write_text("")
    (x.parent / "__init__.py").write_text("")
    x.write_text(
        "from spark_rapids_tpu.jit_cache import JitCache, named_jit\n"
        "_C = JitCache('x')\n"
        "def good(key):\n"
        "    return _C.put(key, named_jit('srt_x', lambda a: a))\n"
        "def bad():\n"
        "    return named_jit('srt_y', lambda a: a)\n")
    r = run_lint(str(tmp_path), LintConfig(check_docs=False))
    assert [(f.rule, f.line) for f in r.findings] == [("jit-direct", 6)]


# ---------------------------------------------------------------------------
# tools trace <profile dir>: arithmetic on plain lists
# ---------------------------------------------------------------------------

def _span(name, t0, t1, tid=1, q=1, **args):
    return {"name": name, "t0": float(t0), "t1": float(t1), "tid": tid,
            "args": dict(args, q=q)}


GAP_CASES = {
    # a gap wholly inside one scanPrefetch span (and under the root)
    "inside_one_span": (
        [(10_000, 18_000)],
        [_span("srt.query", 0, 100_000),
         _span("scanPrefetch", 9_000, 19_000, tid=2)],
        {"scanPrefetch": 8_000.0}, "scanPrefetch"),
    # a gap under no span at all
    "under_no_span": (
        [(200_000, 205_000)],
        [_span("srt.query", 0, 100_000)],
        {"no_span": 5_000.0}, "no_span"),
    # a gap two spans share: split at the boundary, labelled by the larger
    "split_between_two": (
        [(20_000, 30_000)],
        [_span("srt.query", 0, 100_000),
         _span("plan", 15_000, 23_000),
         _span("deviceSync", 23_000, 40_000)],
        {"plan": 3_000.0, "deviceSync": 7_000.0}, "deviceSync"),
    # only the root covers it: the root is the deepest there is
    "root_only": (
        [(50_000, 52_000)],
        [_span("srt.query", 0, 100_000)],
        {"srt.query": 2_000.0}, "srt.query"),
    # shorter than a millisecond: counted, not attributed
    "too_short": (
        [(60_000, 60_400)],
        [_span("srt.query", 0, 100_000)],
        {}, None),
}


@pytest.mark.parametrize("case", sorted(GAP_CASES))
def test_idle_gap_attribution(case):
    gaps, spans, by_kind, label = GAP_CASES[case]
    att = TL.attribute_gaps(gaps, spans)
    assert att["byKind"] == by_kind
    if label is None:
        assert att["gaps"] == [] and att["shortGaps_us"] == 400.0
    else:
        assert [g["kind"] for g in att["gaps"]] == [label]
        assert sum(att["byQuery"].values()) == sum(by_kind.values())


def test_device_occupancy_and_device_time_on_lists():
    ops = {"/device:TPU:0": [
        ["%fusion.1", 0.0, 1_000.0, "Filter"],
        ["%sort.3", 1_000.0, 9_000.0, "groupby_sort"],
        ["%sort.4", 8_000.0, 12_000.0, "groupby_sort"],   # overlaps
        ["%while.5", 20_000.0, 50_000.0, "compact"]]}
    occ = TL.device_occupancy(ops, (0.0, 60_000.0))["/device:TPU:0"]
    assert occ["busy_us"] == 42_000.0 and occ["idle_us"] == 18_000.0
    assert occ["gaps"] == [(12_000.0, 20_000.0), (50_000.0, 60_000.0)]
    assert abs(occ["occupancy"] - 0.7) < 1e-9
    modules = [["jit_srt_agg_partial(3360405398469866636)", 0.0, 12_000.0],
               ["jit_srt_agg_partial(3360405398469866636)", 20_000.0,
                50_000.0]]
    dt = TL.device_time(ops["/device:TPU:0"], modules)
    assert dt["byProgram"] == {"jit_srt_agg_partial": 42_000.0}
    assert dt["byScope"]["jit_srt_agg_partial"] == {
        "Filter": 1_000.0, "groupby_sort": 12_000.0, "compact": 30_000.0}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(srt_agg_partial)/jit(main)/groupby_sort/sort", "groupby_sort"),
    ("jit(srt_stage_Filter_Project)/jit(main)/Filter/mul", "Filter"),
    ("jit(srt_agg_partial)/jit(main)/Project/agg_inputs/add",
     "Project/agg_inputs"),
    ("jit(srt_concat)/jit(main)/concatenate", "(no scope)"),
    ("", "(no scope)"),
])
def test_scope_of_op(op_name, scope):
    assert TL.scope_of_op(op_name) == scope


def test_enqueue_occupancy_keeps_the_host_view_under_its_own_name():
    """What the host spans give is when programs were ENQUEUED per chip;
    "occupancy" proper comes from the device planes."""
    spans = [dict(_span("TpuFusedStageExec.dispatch", 0, 10), args={
        "chip": 0, "q": 1}), _span("plan", 0, 100)]
    occ = TL.enqueue_occupancy(spans)
    assert list(occ) == [0] and occ[0]["dispatches"] == 1
    assert not hasattr(TL, "chip_occupancy")


def _pb(field: int, value) -> bytes:
    """Encode one protobuf field: int -> varint, bytes/str ->
    length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            b = n & 0x7F
            n >>= 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_xplane_op_names_reads_event_metadata(tmp_path):
    """The device's op events keep their HLO op_name (the named-scope
    path) in their EVENT METADATA's ``tf_op`` stat, which ProfileData
    does not hand out: a hand-encoded xplane with one device plane and
    one host plane reads back as {plane: {event name: op_name}}."""
    def stat_meta(sid, name):        # map entry of XPlane.stat_metadata
        return _pb(5, _pb(1, sid) + _pb(2, _pb(1, sid) + _pb(2, name)))

    def event_meta(eid, name, stats):  # map entry of event_metadata
        return _pb(4, _pb(1, eid) + _pb(2, _pb(1, eid) + _pb(2, name)
                                        + b"".join(_pb(5, s)
                                                   for s in stats)))

    long_name = "%while.23 = (u32[]) while(" + "x" * 300 + ")"
    device = (_pb(1, 7) + _pb(2, "/device:TPU:0")
              + stat_meta(26, "tf_op") + stat_meta(9, "flops")
              + stat_meta(40, "jit(srt_decode)/gather:")
              + event_meta(1, "%sort.3 = sort(...)", [
                  _pb(1, 9) + _pb(3, 123),
                  _pb(1, 26) + _pb(5, "jit(srt_agg_partial)/"
                                      "groupby_sort/sort:")])
              + event_meta(2, long_name, [_pb(1, 26) + _pb(7, 40)])
              + event_meta(3, "%copy.1 = copy(...)", []))
    host = _pb(2, "/host:CPU") + stat_meta(26, "tf_op") + event_meta(
        1, "plan", [_pb(1, 26) + _pb(5, "not a device plane")])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, device) + _pb(1, host))
    got = TL.xplane_op_names(str(path))
    assert got == {"/device:TPU:0": {
        "%sort.3 = sort(...)": "jit(srt_agg_partial)/groupby_sort/sort:",
        long_name: "jit(srt_decode)/gather:"}}
    assert TL.scope_of_op(got["/device:TPU:0"]["%sort.3 = sort(...)"]) \
        == "groupby_sort"
    assert TL.profile_files(str(path)) == [str(path)]
