"""tpu-lint corpus (docs/linting.md): fixture-driven good/bad pairs
for every rule family, suppression + baseline semantics, JSON output
schema, the CLI exit-code contract, and the zero-findings gate over
the real package (which makes tier-1 the lint CI gate)."""

import json
import os
import subprocess
import sys
import textwrap

from spark_rapids_tpu.lint import (LintConfig, load_config, render_json,
                                   run_lint)
from spark_rapids_tpu.lint.engine import default_root, write_baseline


def _tree(tmp_path, files):
    root = tmp_path / "fixture"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src).lstrip("\n"))
    for d in ("spark_rapids_tpu", "spark_rapids_tpu/exec",
              "spark_rapids_tpu/serve"):
        if (root / d).is_dir():
            init = root / d / "__init__.py"
            if not init.exists():
                init.write_text("")
    return str(root)


def _lint(root, **over):
    cfg = LintConfig(check_docs=False, **over)
    return run_lint(root, cfg)


def _rules(result):
    return sorted({f.rule for f in result.findings})


# ---------------------------------------------------------------------------
# family 1: retry coverage
# ---------------------------------------------------------------------------

def test_retry_coverage_bad_and_good(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        from spark_rapids_tpu import retry as R

        def bad(staged, device):
            return finish_upload(staged, device)

        def good(staged, device, conf):
            return R.with_retry(lambda: finish_upload(staged, device),
                                conf)
    """})
    r = _lint(root)
    assert _rules(r) == ["retry-coverage"]
    assert len(r.findings) == 1
    assert r.findings[0].line == 4  # only the unwrapped site


def test_retry_coverage_transitive_local_closure(tmp_path):
    # with_retry re-runs the whole closure: a local def passed BY NAME
    # to the combinator covers everything it calls in-module
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        from spark_rapids_tpu import retry as R

        def outer(src, conf):
            def upload_host(hb):
                return inner(hb)
            return R.with_split_retry(src, upload_host, conf)

        def inner(hb):
            return upload_batch(hb, 8)
    """})
    assert _lint(root).clean


def test_retry_coverage_allowlist_and_scope(tmp_path):
    files = {"spark_rapids_tpu/exec/x.py": """
        def proto(staged, device):
            return finish_upload(staged, device)
    """,
             # out of retry scope: same code, no finding
             "spark_rapids_tpu/sql/y.py": """
        def elsewhere(staged, device):
            return finish_upload(staged, device)
    """}
    root = _tree(tmp_path, files)
    assert _rules(_lint(root)) == ["retry-coverage"]
    allow = {"spark_rapids_tpu/exec/x.py::proto":
             "fixture protocol layer"}
    assert _lint(root, retry_allowlist=allow).clean


# ---------------------------------------------------------------------------
# family 2: compile discipline
# ---------------------------------------------------------------------------

def test_jit_direct_bad_and_routed_good(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax
        from spark_rapids_tpu.jit_cache import JitCache

        _C = JitCache("fixture")

        def bad(fn):
            return jax.jit(fn)

        def good(key, fn):
            got = _C.get(key)
            if got is None:
                got = _C.put(key, jax.jit(fn))
            return got

        def also_good(key):
            fn, _ = _C.get_or_build(key, lambda: _builder())
            return fn

        def _builder():
            return jax.jit(lambda x: x)
    """})
    r = _lint(root)
    assert _rules(r) == ["jit-direct"]
    assert [f.line for f in r.findings] == [7]


def test_jit_builder_resolves_across_modules(tmp_path):
    # _STAGE_CACHE.put(key, X.build_fn(...)) in one module makes the
    # jax.jit inside other_module.build_fn compliant
    root = _tree(tmp_path, {
        "spark_rapids_tpu/exec/a.py": """
            from spark_rapids_tpu.jit_cache import JitCache
            from spark_rapids_tpu.exec import b as B

            _C = JitCache("x")

            def use(key, steps):
                return _C.put(key, B.build_fn(steps))
        """,
        "spark_rapids_tpu/exec/b.py": """
            import jax

            def build_fn(steps):
                return jax.jit(lambda c: c)
        """})
    assert _lint(root).clean


def test_pallas_call_treated_like_jit(tmp_path):
    # pl.pallas_call is compile-discipline traffic exactly like
    # jax.jit: sanctioned inside the kernels/ registry package or a
    # JitCache builder closure, a finding anywhere else
    root = _tree(tmp_path, {
        "spark_rapids_tpu/exec/x.py": """
            import jax
            from jax.experimental import pallas as pl
            from spark_rapids_tpu.jit_cache import JitCache

            _C = JitCache("fixture")

            def bad(x):
                return pl.pallas_call(_k, out_shape=x)(x)

            def good(key):
                fn, _ = _C.get_or_build(key, lambda: _builder())
                return fn

            def _builder():
                return jax.jit(lambda x: pl.pallas_call(
                    _k, out_shape=None)(x))
        """,
        "spark_rapids_tpu/kernels/__init__.py": "",
        "spark_rapids_tpu/kernels/k.py": """
            from jax.experimental import pallas as pl

            def build_kernel(shape):
                # registry home: pallas_call sanctioned here
                return pl.pallas_call(_kern, out_shape=shape)
        """})
    r = _lint(root)
    assert _rules(r) == ["jit-direct"]
    assert [f.line for f in r.findings] == [8]
    assert "pl.pallas_call" in r.findings[0].message


def test_pallas_call_suppressible_with_reason(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        from jax.experimental import pallas as pl

        def probe(shape):
            return pl.pallas_call(_k, out_shape=shape)  # tpu-lint: disable=jit-direct(one-shot capability probe)
    """})
    assert _lint(root).clean


def test_no_module_of_the_package_imports_pallas_or_a_kernel_tier():
    # every operator has one device path, an XLA composition (PR 32):
    # outside lint/, whose rule keeps naming pallas_call, nothing
    # imports Pallas or a spark_rapids_tpu.kernels package
    import ast
    pkg = os.path.join(default_root(), "spark_rapids_tpu")
    assert not os.path.exists(os.path.join(pkg, "kernels"))
    bad = []
    for dirpath, _dirs, files in os.walk(pkg):
        if os.path.relpath(dirpath, pkg).split(os.sep)[0] == "lint":
            continue
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            with open(path) as fh:
                tree = ast.parse(fh.read(), path)
            for n in ast.walk(tree):
                if isinstance(n, ast.Import):
                    names = [a.name for a in n.names]
                elif isinstance(n, ast.ImportFrom):
                    names = [f"{n.module or ''}.{a.name}"
                             for a in n.names]
                else:
                    continue
                bad += [(os.path.relpath(path, pkg), n.lineno, nm)
                        for nm in names
                        if "pallas" in nm
                        or nm.startswith("spark_rapids_tpu.kernels")
                        or nm.split(".")[-1] == "kernels"]
    assert not bad, bad


def test_jit_module_cache_flags_raw_dicts(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        from collections import OrderedDict
        from spark_rapids_tpu.jit_cache import JitCache

        _BAD_CACHE = {}
        _ALSO_BAD_CACHE = OrderedDict()
        _GOOD_CACHE = JitCache("good")
        _PLAIN_TABLE = {}
    """})
    r = _lint(root)
    assert _rules(r) == ["jit-module-cache"]
    assert [f.line for f in r.findings] == [4, 5]


# ---------------------------------------------------------------------------
# family 3: concurrency
# ---------------------------------------------------------------------------

_LOCKY = """
    import threading
    import time

    class DeviceStore:
        def __init__(self):
            self._lock = threading.Lock()
            self._a = threading.Lock()
            self._b = threading.Lock()
            self._d = {}
"""


def test_lock_order_cycle_flagged(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/memory.py": _LOCKY + """
        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._b:
                with self._a:
                    pass
    """})
    r = _lint(root)
    assert _rules(r) == ["lock-order"]
    assert "DeviceStore._a" in r.findings[0].message


def test_lock_order_consistent_is_clean(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/memory.py": _LOCKY + """
        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._a:
                with self._b:
                    pass
    """})
    assert _lint(root).clean


def test_lock_order_interprocedural_edge(tmp_path):
    # with A held, calling a method that takes B adds the A->B edge
    root = _tree(tmp_path, {"spark_rapids_tpu/memory.py": _LOCKY + """
        def one(self):
            with self._a:
                self.takes_b()

        def takes_b(self):
            with self._b:
                pass

        def two(self):
            with self._b:
                with self._a:
                    pass
    """})
    assert _rules(_lint(root)) == ["lock-order"]


def test_blocking_call_under_critical_lock(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/memory.py": _LOCKY + """
        def bad_sleep(self):
            with self._lock:
                time.sleep(0.1)

        def bad_dispatch(self, staged):
            with self._lock:
                return finish_upload(staged)

        def good(self):
            with self._lock:
                n = 1
            time.sleep(0.1)
            return n
    """})
    r = _lint(root)
    assert _rules(r) == ["lock-blocking-call"]
    assert len(r.findings) == 2


def test_wait_on_different_lock_flagged(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/memory.py": """
        import threading

        class DeviceStore:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition()

            def bad(self):
                with self._lock:
                    self._cv.wait()

            def fine(self):
                with self._cv:
                    self._cv.wait()
    """})
    r = _lint(root)
    assert _rules(r) == ["lock-blocking-call"]
    assert len(r.findings) == 1
    assert "different lock" in r.findings[0].message


def test_check_then_act_bad_and_guarded(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/serve/s.py": """
        import threading

        class Sessions:
            def __init__(self):
                self._lock = threading.Lock()
                self._by_tenant = {}

            def racy(self, k):
                if k not in self._by_tenant:
                    self._by_tenant[k] = object()
                return self._by_tenant[k]

            def guarded(self, k):
                with self._lock:
                    if k not in self._by_tenant:
                        self._by_tenant[k] = object()
                    return self._by_tenant[k]
    """})
    r = _lint(root)
    assert _rules(r) == ["check-then-act"]
    assert len(r.findings) == 1
    assert "_by_tenant" in r.findings[0].message


# ---------------------------------------------------------------------------
# family 4: drift
# ---------------------------------------------------------------------------

def test_metric_key_rule(tmp_path):
    root = _tree(tmp_path, {
        "spark_rapids_tpu/metrics.py": """
            OP_TIME = "opTime"
            ROGUE = "notDescribedConstant"
            METRIC_DESCRIPTIONS = {
                OP_TIME: "operator wall",
                "goodKey": "described",
            }
            METRIC_PREFIX_DESCRIPTIONS = {"perChip.": "per chip <N>"}
        """,
        "spark_rapids_tpu/exec/x.py": """
            from spark_rapids_tpu import metrics as M

            def use(metrics):
                metrics.create("goodKey").add(1)
                metrics.create(M.OP_TIME).add(1)
                metrics.create("perChip.3").add(1)
                metrics.create("rogueLiteral").add(1)
                metrics.create(dynamic_key()).add(1)  # invisible: ok
        """})
    r = _lint(root)
    assert _rules(r) == ["metric-key"]
    msgs = " ".join(f.message for f in r.findings)
    assert "notDescribedConstant" in msgs  # constant direction
    assert "rogueLiteral" in msgs          # call-site direction
    assert len(r.findings) == 2


def test_conf_key_rule(tmp_path):
    root = _tree(tmp_path, {
        "spark_rapids_tpu/conf.py": """
            def conf(key):
                return key

            conf("spark.rapids.sql.fixture.enabled")
        """,
        "spark_rapids_tpu/exec/x.py": """
            GOOD = "spark.rapids.sql.fixture.enabled"
            BAD = "spark.rapids.sql.fixture.typo"
            PREFIX = "spark.rapids.sql.fixture."  # namespace match: ok
        """})
    r = _lint(root)
    assert _rules(r) == ["conf-key"]
    assert len(r.findings) == 1
    assert "typo" in r.findings[0].message


def test_span_scope_rule(tmp_path):
    root = _tree(tmp_path, {
        "spark_rapids_tpu/trace.py": "def span(*a, **k): pass\n",
        "spark_rapids_tpu/exec/x.py": """
            from spark_rapids_tpu import trace as _trace

            def use():
                _trace.span("leaky")
                with _trace.span("fine"):
                    pass
        """})
    r = _lint(root)
    assert _rules(r) == ["span-scope"]
    assert [f.line for f in r.findings] == [4]


def test_generated_doc_content_carries_drift_tables():
    """The content direction of the retired runtime drift tests:
    docs-drift proves docs == generator output byte-for-byte; this
    proves the GENERATOR still emits the metric description table and
    the conf/profile sections (otherwise regenerating stale docs could
    silently drop them both)."""
    import spark_rapids_tpu.profile  # noqa: F401 — registers confs
    import spark_rapids_tpu.trace  # noqa: F401 — registers confs
    from spark_rapids_tpu import metrics as M
    from spark_rapids_tpu.tools import generate_observability_docs
    doc = generate_observability_docs()
    for name in M.METRIC_DESCRIPTIONS:
        assert name in doc, name
    for key in ("spark.rapids.sql.profile.enabled",
                "spark.rapids.sql.profile.dir",
                "spark.rapids.sql.explain",
                "spark.rapids.sql.trace.enabled"):
        assert key in doc, key
    assert "Reading a query profile" in doc
    assert "Explain / fallback reasons" in doc


# ---------------------------------------------------------------------------
# family 5: cancellation discipline
# ---------------------------------------------------------------------------

def test_cancel_checkpoint_bad_and_good(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/serve/w.py": """
        import threading
        import time

        _CV = threading.Condition()

        def bad_wait():
            with _CV:
                _CV.wait()

        def bad_sleep():
            time.sleep(0.5)

        def bad_queue_get(q):
            return q.get()

        def bad_explicit_blocking_get(q):
            return q.get(block=True)

        def good_bounded_wait():
            with _CV:
                _CV.wait(timeout=0.05)

        def good_positional_wait(ev):
            ev.wait(0.05)

        def good_queue_get(q):
            return q.get(timeout=0.1)

        def good_nonblocking_get(q):
            return q.get(block=False)

        def fine_dict_get(d, k):
            return d.get(k)
    """})
    r = _lint(root)
    assert _rules(r) == ["cancel-checkpoint"]
    assert len(r.findings) == 4
    msgs = " | ".join(f.message for f in r.findings)
    assert "time.sleep" in msgs
    assert "unbounded .wait()" in msgs
    assert "blocking queue .get()" in msgs


def test_cancel_checkpoint_none_timeout_and_scope(tmp_path):
    files = {
        # timeout=None is NOT bounded
        "spark_rapids_tpu/jit_cache.py": """
        def bad(ev):
            ev.wait(timeout=None)
    """,
        # same primitives OUTSIDE the lifecycle-critical scope: clean
        "spark_rapids_tpu/exec/y.py": """
        import time

        def elsewhere(ev, q):
            time.sleep(0.5)
            ev.wait()
            return q.get()
    """}
    root = _tree(tmp_path, files)
    r = _lint(root)
    assert _rules(r) == ["cancel-checkpoint"]
    assert len(r.findings) == 1
    assert r.findings[0].path == "spark_rapids_tpu/jit_cache.py"


# ---------------------------------------------------------------------------
# family 6: interprocedural data-flow (tpu-lint v2)
# ---------------------------------------------------------------------------

def _of(result, rule):
    return [f for f in result.findings if f.rule == rule]


def test_donation_safety_direct_bad_and_good(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        _F = jax.jit(lambda a: a, donate_argnums=(0,))

        def bad(x):
            y = _F(x)
            return x.shape  # read after donate

        def good(x):
            n = x.shape  # staged BEFORE the donating dispatch
            y = _F(x)
            return y, n

        def rebound(x):
            y = _F(x)
            x = y
            return x.shape  # rebinding kills the flag

        def canonical(x):
            x = _F(x)  # rebound IN the donating statement
            return x.shape  # reads the program's output: clean

        def canonical_loop(batches, acc):
            for b in batches:
                use(acc)
                acc = _F(acc)  # same-statement rebind: clean
    """})
    r = _lint(root)
    bad = _of(r, "donation-safety")
    assert [f.line for f in bad] == [7]
    assert "`x` is read after being donated" in bad[0].message


def test_donation_safety_through_helper_one_level(tmp_path):
    # the helper donates ITS positional parameter; the caller's read
    # after the helper call is the finding (one call level deep)
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        _F = jax.jit(lambda a: a, donate_argnums=(0,))

        def helper(buf):
            return _F(buf)

        def caller(x):
            out = helper(x)
            return x.shape  # flagged: x was donated one call down
    """})
    r = _lint(root)
    bad = _of(r, "donation-safety")
    assert [f.line for f in bad] == [10]
    assert "helper" in bad[0].message


def test_donation_safety_resolves_jitcache_builder(tmp_path):
    # the real package's shape: fn, miss = CACHE.get_or_build(key,
    # lambda: build(...)) where build returns a MAY-donating jit
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax
        from spark_rapids_tpu.jit_cache import JitCache

        _C = JitCache("fixture")

        def build(donate):
            def fn(a, b):
                return a
            return jax.jit(fn, donate_argnums=(0, 1) if donate else ())

        def run(b, lits):
            fn, miss = _C.get_or_build("k", lambda: build(True))
            cols, act = fn(b.columns, b.active)
            return b.rows  # read after the donating dispatch

        def run_ok(b, lits):
            fn, miss = _C.get_or_build("k", lambda: build(True))
            rows = b.rows  # staged before
            cols, act = fn(b.columns, b.active)
            return rows
    """})
    r = _lint(root)
    bad = _of(r, "donation-safety")
    assert [f.line for f in bad] == [14]


def test_donation_safety_loop_back_edge(tmp_path):
    # the read PRECEDES the call in source but follows it on the loop's
    # back edge; the for target rebinds, so only the un-rebound name
    # (the accumulator) is flagged
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        _F = jax.jit(lambda a: a, donate_argnums=(0,))

        def bad(batches, acc):
            for b in batches:
                use(acc)  # next iteration reads the donated acc
                _F(acc)

        def good(batches):
            for b in batches:
                use(b)
                _F(b)  # b rebinds at the loop head: clean
    """})
    r = _lint(root)
    bad = _of(r, "donation-safety")
    assert [f.line for f in bad] == [7]
    assert "`acc`" in bad[0].message


def test_hidden_sync_tainted_flagged_host_value_not(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import numpy as np
        import jax.numpy as jnp

        def bad(col):
            s = jnp.sum(col)
            return s.item()  # device scalar forced on the hot path

        def bad2(col):
            s = jnp.sum(col)
            return float(np.asarray(s))  # one finding: the asarray

        def fine(host_list):
            a = np.asarray(host_list)  # NOT a device value
            return int(a[0])

        def kwargs_only(rows):
            return np.array(object=rows)  # no positional arg: no crash

        def outer(col):
            s = jnp.sum(col)

            def cb(s):
                return float(s)  # SHADOWED host param: not the device s
            return cb
    """})
    r = _lint(root)
    bad = _of(r, "hidden-sync")
    assert [f.line for f in bad] == [6, 10]
    assert ".item()" in bad[0].message


def test_hidden_sync_scope_and_allowlist(tmp_path):
    files = {"spark_rapids_tpu/exec/x.py": """
        import jax.numpy as jnp

        def drain(col):
            s = jnp.sum(col)
            return int(s)
    """,
             # identical code OUTSIDE the hot-path scopes: clean
             "spark_rapids_tpu/sql/y.py": """
        import jax.numpy as jnp

        def elsewhere(col):
            s = jnp.sum(col)
            return int(s)
    """}
    root = _tree(tmp_path, files)
    r = _lint(root)
    assert [(f.path, f.line) for f in _of(r, "hidden-sync")] == \
        [("spark_rapids_tpu/exec/x.py", 5)]
    allow = {"spark_rapids_tpu/exec/x.py::drain":
             "fixture sanctioned drain point"}
    assert not _of(_lint(root, sync_allowlist=allow), "hidden-sync")


def test_handle_leak_bad_and_escapes(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        def leak(staged, device):
            tok = start_upload(staged, device)  # never finished
            return None

        def dropped(staged, device):
            start_upload(staged, device)  # result dropped

        def tracked(store, b, out):
            h = store.register(b)
            out.append(h)  # escapes to the tracked container: fine

        def closed(store, b):
            h = store.register(b)
            try:
                return h.get()
            finally:
                h.close()

        def returned(store, b):
            return store.register(b)

        def except_only(store, b):
            h = store.register(b)
            try:
                return compute(h.get())
            except Exception:
                h.close()  # success path still leaks
                raise
    """})
    r = _lint(root)
    bad = _of(r, "handle-leak")
    assert [f.line for f in bad] == [2, 6, 23]
    assert "never closed" in bad[0].message
    assert "result dropped" in bad[1].message
    assert "exception path" in bad[2].message


def test_trace_purity_two_calls_deep(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import time

        import jax

        _REG = {}

        def build():
            return jax.jit(_traced)

        def _traced(x):
            return _helper(x)

        def _helper(x):
            t = time.time()  # host clock two calls below the builder
            _REG["k"] = t    # module-state mutation
            return x
    """})
    r = _lint(root)
    bad = _of(r, "trace-purity")
    assert [f.line for f in bad] == [14, 15]
    assert "host clock" in bad[0].message
    assert "mutates free state" in bad[1].message


def test_trace_purity_conf_read_and_pure_twin(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        def build(conf):
            limit = conf.get("k")  # snapshotted OUTSIDE the trace: ok
            return jax.jit(lambda x: _traced(x, limit))

        def _traced(x, limit):
            return x + limit

        def build_bad(conf):
            def fn(x):
                return x + conf.get("k")  # read AT TRACE TIME
            return jax.jit(fn)
    """})
    r = _lint(root)
    bad = _of(r, "trace-purity")
    assert [f.line for f in bad] == [12]
    assert "dynamic conf read" in bad[0].message


def test_trace_purity_cross_module_from_import(tmp_path):
    # `from mod import helper` flows must resolve across files: the
    # impurity sits one from-imported call below the traced root
    root = _tree(tmp_path, {
        "spark_rapids_tpu/exec/a.py": """
            import jax
            from spark_rapids_tpu.exec.b import helper

            def build():
                return jax.jit(_traced)

            def _traced(x):
                return helper(x)
        """,
        "spark_rapids_tpu/exec/b.py": """
            import time

            def helper(x):
                return x + time.time()
        """})
    bad = _of(_lint(root), "trace-purity")
    assert [(f.path, f.line) for f in bad] == \
        [("spark_rapids_tpu/exec/b.py", 4)]


def test_donation_attribute_receiver_no_name_collision(tmp_path):
    # `obj.dispatch(...)` must NOT resolve to an unrelated same-file
    # donating `def dispatch` — only self/cls receivers match in-file
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        _F = jax.jit(lambda a: a, donate_argnums=(0,))

        def dispatch(buf):
            return _F(buf)

        def unrelated(obj, y):
            obj.dispatch(y)
            return y.shape  # obj.dispatch is NOT the donating helper

        class C:
            def dispatch(self, buf):
                return _F(buf)

            def caller(self, z):
                self.dispatch(z)
                return z.shape  # self.dispatch IS: flagged
    """})
    bad = _of(_lint(root), "donation-safety")
    assert [f.line for f in bad] == [18]


def test_trace_purity_closure_accumulator_is_pure(tmp_path):
    # per-trace bookkeeping (the decode programs' lazy byte memo, the
    # lane planners' append) binds in an ENCLOSING function — that is
    # deterministic trace-local state, not cross-trace impurity
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        def build():
            def fn(x):
                lanes = []
                memo = None

                def add(v):
                    nonlocal memo
                    lanes.append(v)
                    memo = v
                    return memo
                return add(x)
            return jax.jit(fn)
    """})
    assert not _of(_lint(root), "trace-purity")


# ---------------------------------------------------------------------------
# engine: suppressions, baseline, JSON schema
# ---------------------------------------------------------------------------

def test_suppression_requires_reason(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        def a(fn):
            return jax.jit(fn)  # tpu-lint: disable=jit-direct(fixture program, bounded)

        def b(fn):
            return jax.jit(fn)  # tpu-lint: disable=jit-direct
    """})
    r = _lint(root)
    # the reasoned suppression holds; the reasonless one does NOT
    # suppress and is itself a finding
    assert r.suppressed == 1
    assert _rules(r) == ["bad-suppression", "jit-direct"]
    bad = [f for f in r.findings if f.rule == "jit-direct"]
    assert [f.line for f in bad] == [7]


def test_malformed_suppression_lists_fail_closed(tmp_path):
    # parens inside a reason / prose after the list must fail the
    # WHOLE comment (nothing suppressed, one bad-suppression), never
    # register fragments of free text as rules
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        def a(fn):
            return jax.jit(fn)  # tpu-lint: disable=jit-direct(probe (one-shot) cap)

        def b(fn):
            return jax.jit(fn)  # tpu-lint: disable=jit-direct(why) see docs/linting.md

        def c(fn):
            return jax.jit(fn)  # tpu-lint: disable=jit-direct(ok reason), span-scope(also fine)
    """})
    r = _lint(root)
    assert r.suppressed == 1  # only c's well-formed multi-item list
    rules = sorted(f.rule for f in r.findings)
    assert rules.count("jit-direct") == 2  # a and b stay findings
    assert rules.count("bad-suppression") == 2


def test_standalone_suppression_covers_next_line(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        # tpu-lint: disable=jit-direct(fixture program, bounded)
        _FN = jax.jit(lambda x: x)
    """})
    r = _lint(root)
    assert r.clean and r.suppressed == 1


def test_baseline_semantics_and_fix_baseline(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        def a(fn):
            return jax.jit(fn)
    """})
    cfg = LintConfig(check_docs=False)
    r = run_lint(root, cfg)
    assert len(r.findings) == 1 and r.baselined == 0
    # --fix-baseline captures current findings as accepted debt
    path = write_baseline(root, cfg, r.findings, r.pctx)
    data = json.load(open(path))
    assert data["version"] == 1 and len(data["findings"]) == 1
    assert data["findings"][0]["rule"] == "jit-direct"
    r2 = run_lint(root, cfg)
    assert r2.clean and r2.baselined == 1
    # baseline is line-TEXT keyed: edits above the site don't churn it
    p = os.path.join(root, "spark_rapids_tpu/exec/x.py")
    src = open(p).read()
    open(p, "w").write("import os  # shift lines\n" + src)
    r3 = run_lint(root, cfg)
    assert r3.clean and r3.baselined == 1
    # re-capturing with a NEW finding present must keep the still-live
    # old debt (what run_cli --fix-baseline writes), not drop it
    # (distinct line text: identical lines share a fingerprint by
    # design, like any text-keyed baseline)
    open(p, "a").write(
        "\n\ndef c(fn):\n    return jax.jit(fn, static_argnums=0)\n")
    r4 = run_lint(root, cfg)
    assert len(r4.findings) == 1 and r4.baselined == 1
    write_baseline(root, cfg, r4.findings + r4.baselined_findings,
                   r4.pctx)
    data = json.load(open(path))
    assert len(data["findings"]) == 2
    r5 = run_lint(root, cfg)
    assert r5.clean and r5.baselined == 2


def test_json_output_schema(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        def a(fn):
            return jax.jit(fn)
    """})
    r = run_lint(root, LintConfig(check_docs=False))
    out = json.loads(render_json(r, r.pctx))
    assert out["version"] == 1
    assert out["clean"] is False
    assert set(out["counts"]) == {"findings", "suppressed", "baselined",
                                  "files"}
    f = out["findings"][0]
    assert set(f) == {"rule", "path", "line", "col", "message",
                      "fingerprint"}
    assert f["rule"] == "jit-direct"
    assert "jit-direct" in out["rules"]
    assert out["internalErrors"] == []


def test_config_file_overrides(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        def proto(staged, device):
            return finish_upload(staged, device)
    """})
    (tmp_path / "fixture" / "tpu-lint.json").write_text(json.dumps({
        "check_docs": False,
        "retry_allowlist": {
            "spark_rapids_tpu/exec/x.py::proto": "fixture exemption"},
    }))
    cfg = load_config(root)
    assert cfg.check_docs is False
    assert run_lint(root, cfg).clean


# ---------------------------------------------------------------------------
# engine v2: timings + budget, github format, changed-only, stale
# baseline pruning
# ---------------------------------------------------------------------------

_BAD_JIT = """
    import jax

    def a(fn):
        return jax.jit(fn)
"""


def test_json_timings_and_budget_exit(tmp_path, capsys):
    from spark_rapids_tpu.lint import run_cli
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": _BAD_JIT})
    (tmp_path / "fixture" / "tpu-lint.json").write_text(
        json.dumps({"check_docs": False}))
    assert run_cli(root=root, as_json=True) == 1
    out = json.loads(capsys.readouterr().out)
    t = out["timings"]
    assert t["totalSeconds"] >= 0 and t["budgetSeconds"] == 60.0
    assert set(t["perRule"]) == set(out["rules"])
    assert all(v >= 0 for v in t["perRule"].values())
    # a --time-budget override must show up in the JSON it judges by
    assert run_cli(root=root, as_json=True, time_budget=45.0) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["timings"]["budgetSeconds"] == 45.0
    # an unaffordable run fails the gate even when findings-free:
    # exit 2, not a quietly slower tier-1
    clean = _tree(tmp_path / "c",
                  {"spark_rapids_tpu/exec/x.py": "X = 1\n"})
    ((tmp_path / "c") / "fixture" / "tpu-lint.json").write_text(
        json.dumps({"check_docs": False}))
    assert run_cli(root=clean) == 0
    capsys.readouterr()
    assert run_cli(root=clean, time_budget=1e-9) == 2
    # the breach goes to STDERR so --json stdout stays parseable
    captured = capsys.readouterr()
    assert "exceeded" in captured.err and "exceeded" not in captured.out


def test_time_budget_config_override(tmp_path, capsys):
    from spark_rapids_tpu.lint import run_cli
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": "X = 1\n"})
    (tmp_path / "fixture" / "tpu-lint.json").write_text(
        json.dumps({"check_docs": False, "time_budget_s": 1e-9}))
    assert run_cli(root=root) == 2
    assert "exceeded" in capsys.readouterr().err


def test_github_format_annotations(tmp_path, capsys):
    from spark_rapids_tpu.lint import run_cli
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": _BAD_JIT})
    (tmp_path / "fixture" / "tpu-lint.json").write_text(
        json.dumps({"check_docs": False}))
    assert run_cli(root=root, fmt="github") == 1
    out = capsys.readouterr().out
    assert ("::error file=spark_rapids_tpu/exec/x.py,line=4,col=12,"
            "title=tpu-lint jit-direct::") in out
    # the whole annotation (message included) stays on ONE line — a
    # raw newline would truncate the workflow command
    err_lines = [ln for ln in out.splitlines()
                 if ln.startswith("::error")]
    assert len(err_lines) == 1 and "jit-direct" in err_lines[0]


def test_changed_only_filters_to_git_diff(tmp_path, capsys):
    from spark_rapids_tpu.lint import run_cli
    root = _tree(tmp_path, {
        "spark_rapids_tpu/exec/old.py": _BAD_JIT,
        "spark_rapids_tpu/exec/new.py": _BAD_JIT,
    })
    (tmp_path / "fixture" / "tpu-lint.json").write_text(
        json.dumps({"check_docs": False}))
    git = ["git", "-C", root, "-c", "user.email=t@t",
           "-c", "user.name=t"]
    subprocess.run(git + ["init", "-q"], check=True)
    subprocess.run(git + ["add", "spark_rapids_tpu/exec/old.py"],
                   check=True)
    subprocess.run(git + ["commit", "-qm", "seed"], check=True)
    # full run sees both files' findings; --changed-only only the
    # untracked one (old.py is committed and unchanged vs HEAD)
    assert run_cli(root=root) == 1
    full = capsys.readouterr().out
    assert "old.py" in full and "new.py" in full
    assert run_cli(root=root, changed_only="HEAD") == 1
    changed = capsys.readouterr().out
    assert "new.py" in changed and "old.py:" not in changed
    # a bad base ref must not silently lint nothing
    assert run_cli(root=root, changed_only="no-such-ref") == 2


def test_changed_only_nested_root(tmp_path, capsys):
    # git toplevel ABOVE the lint root: `git diff` emits toplevel-
    # relative paths ("fixture/...") that must re-base onto the root,
    # or the incremental mode silently passes bad code
    from spark_rapids_tpu.lint import run_cli
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/old.py": _BAD_JIT})
    (tmp_path / "fixture" / "tpu-lint.json").write_text(
        json.dumps({"check_docs": False}))
    git = ["git", "-C", str(tmp_path), "-c", "user.email=t@t",
           "-c", "user.name=t"]
    subprocess.run(git + ["init", "-q"], check=True)
    subprocess.run(git + ["add", "-A"], check=True)
    subprocess.run(git + ["commit", "-qm", "seed"], check=True)
    p = os.path.join(root, "spark_rapids_tpu/exec/old.py")
    open(p, "a").write(
        "\n\ndef b(fn):\n    return jax.jit(fn, static_argnums=0)\n")
    assert run_cli(root=root, changed_only="HEAD") == 1
    assert "old.py" in capsys.readouterr().out


def test_stale_baseline_reported_and_pruned(tmp_path, capsys):
    from spark_rapids_tpu.lint import run_cli
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": _BAD_JIT})
    cfg = LintConfig(check_docs=False)
    r = run_lint(root, cfg)
    write_baseline(root, cfg, r.findings, r.pctx)
    # fix the violation: the baseline entry goes stale but the run
    # stays CLEAN (informational note, exit 0)
    p = os.path.join(root, "spark_rapids_tpu/exec/x.py")
    open(p, "w").write("def a(fn):\n    return fn\n")
    r2 = run_lint(root, cfg)
    assert r2.clean and r2.baselined == 0
    assert [e["rule"] for e in r2.stale_baseline] == ["jit-direct"]
    out = json.loads(render_json(r2, r2.pctx))
    assert out["clean"] is True
    assert out["staleBaseline"][0]["rule"] == "jit-direct"
    # --fix-baseline prunes the dead entry and says so
    (tmp_path / "fixture" / "tpu-lint.json").write_text(
        json.dumps({"check_docs": False}))
    assert run_cli(root=root, fix_baseline=True) == 0
    assert "1 stale entry pruned" in capsys.readouterr().out
    path = os.path.join(root, cfg.baseline)
    assert json.load(open(path))["findings"] == []
    assert run_lint(root, cfg).clean


def test_fix_baseline_no_churn_when_unchanged(tmp_path):
    # same accepted-debt SET (text-keyed fingerprints) -> the file is
    # left byte-identical even though line numbers shifted
    root = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": _BAD_JIT})
    cfg = LintConfig(check_docs=False)
    r = run_lint(root, cfg)
    path = write_baseline(root, cfg, r.findings, r.pctx)
    before = open(path).read()
    p = os.path.join(root, "spark_rapids_tpu/exec/x.py")
    src = open(p).read()
    open(p, "w").write("import os  # shift\n" + src)
    r2 = run_lint(root, cfg)
    assert r2.clean and r2.baselined == 1 and not r2.stale_baseline
    write_baseline(root, cfg,
                   r2.findings + r2.baselined_findings, r2.pctx)
    assert open(path).read() == before


# ---------------------------------------------------------------------------
# the real package is the ultimate fixture: zero findings, every
# suppression reasoned — this test IS the tier-1 lint gate
# ---------------------------------------------------------------------------

def test_real_package_is_lint_clean():
    root = default_root()
    cfg = load_config(root)
    assert cfg.check_docs  # docs-drift runs against the real docs/
    r = run_lint(root, cfg)
    assert r.internal_errors == []
    assert r.findings == [], "\n".join(
        f"{f.path}:{f.line} [{f.rule}] {f.message}" for f in r.findings)
    # the hand-audited invariants are live: suppressions exist and each
    # carried a reason (reasonless ones would be findings above)
    assert r.suppressed > 0
    assert r.files > 50


def test_cli_exit_contract(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # 0: clean repo (shells the real CLI — the CI gate invocation)
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "lint",
         "--json"],
        capture_output=True, text=True, env=env,
        cwd=default_root(), timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["clean"] is True

    # 1: findings
    bad = _tree(tmp_path, {"spark_rapids_tpu/exec/x.py": """
        import jax

        def a(fn):
            return jax.jit(fn)
    """})
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "lint",
         "--root", bad], capture_output=True, text=True, env=env,
        cwd=default_root(), timeout=300)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "jit-direct" in out.stdout

    # --fix-baseline flips it back to 0
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "lint",
         "--root", bad, "--fix-baseline"],
        capture_output=True, text=True, env=env,
        cwd=default_root(), timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "lint",
         "--root", bad], capture_output=True, text=True, env=env,
        cwd=default_root(), timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr

    # 2: internal error (unparseable source)
    broken = _tree(tmp_path / "b",
                   {"spark_rapids_tpu/x.py": "def broken(:\n"})
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "lint",
         "--root", broken], capture_output=True, text=True, env=env,
        cwd=default_root(), timeout=300)
    assert out.returncode == 2, out.stdout + out.stderr

    # 2: zero files collected (a wrong --root must not pass the gate)
    empty = str(tmp_path / "empty")
    os.makedirs(empty, exist_ok=True)
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "lint",
         "--root", empty], capture_output=True, text=True, env=env,
        cwd=default_root(), timeout=300)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "no files found" in out.stdout
