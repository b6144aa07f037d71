"""Rule family 2 — compile discipline (docs/fusion.md, PR 2/7).

``jit-direct``: every ``jax.jit(...)`` — and every
``jit_cache.named_jit(name, fn, ...)``, the same jit under a stable
``srt_`` program name, which is how the package builds its programs —
outside ``jit_cache.py`` must be
routed through a bounded single-flight ``JitCache`` — either lexically
inside the value argument of ``<cache>.put(key, ...)``, or inside a
builder reachable from a ``get_or_build`` / ``.put`` call (closed
transitively over the package call graph, across modules via imports:
``_STAGE_CACHE.put(key, X.build_stage_fn(...))`` makes
``ops/exprs.py::build_stage_fn`` a builder).

``pl.pallas_call`` is treated exactly like ``jax.jit`` (a Pallas
kernel pins a compiled program the same way): it must be built inside
the kernels/ registry package (``kernels_home``) — whose builders are
only ever invoked from JitCache-routed programs — or inside a
``JitCache`` builder closure, with reasoned suppressions for anything
else (the capability probes).

``jit-module-cache``: a module-level dict used as a compile cache
(``_FOO_CACHE = {}``) bypasses the LRU bound and the single-flight
build path — compiled programs pin XLA executables, so unbounded dicts
are a leak. Use ``JitCache`` instead.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from spark_rapids_tpu.lint import astutil as A
from spark_rapids_tpu.lint.engine import Finding, rule


def _is_jax_jit(fctx: A.FileCtx, call: ast.Call) -> bool:
    return A.jit_traced_arg(fctx, call)[0]


def _is_pallas_call(fctx: A.FileCtx, call: ast.Call) -> bool:
    p = A.resolve_path(fctx, call.func)
    return p is not None and (p == "pallas_call"
                              or p.endswith(".pallas_call"))


def _jitcache_names(fctx: A.FileCtx) -> Set[str]:
    """Names in this module bound to a JitCache(...) instance."""
    out: Set[str] = set()
    for node in ast.walk(fctx.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       ast.Call):
            if A.call_tail(node.value) == "JitCache":
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
    return out


def _resolve_callable(fctx: A.FileCtx, func: ast.AST
                      ) -> Tuple[str, str]:
    """(rel_path, func_name) a call target resolves to, best effort.
    Local names resolve to this file; ``X.fn`` resolves through the
    import alias map to the target module's path."""
    if isinstance(func, ast.Name):
        return fctx.rel, func.id
    if isinstance(func, ast.Attribute):
        if isinstance(func.value, ast.Name) \
                and func.value.id in fctx.imports:
            return A.module_rel(fctx.imports[func.value.id]), func.attr
        # self.method / other receivers: match by name in this file
        return fctx.rel, func.attr
    return "", ""


def _builder_closure(pctx) -> Dict[str, Set[int]]:
    """Per-file set of function/lambda node ids whose bodies are
    builder code for some JitCache (get_or_build builders, .put value
    expressions, and everything they call, package-wide)."""
    builder_nodes: Dict[str, Set[int]] = {f.rel: set()
                                          for f in pctx.files}
    # (rel, name) pairs still to mark
    work: List[Tuple[str, str]] = []
    seen: Set[Tuple[str, str]] = set()

    def seed_calls_in(fctx: A.FileCtx, node: ast.AST) -> None:
        for c in A.walk_calls(node):
            rel, name = _resolve_callable(fctx, c.func)
            if not name:
                continue
            key = (rel or fctx.rel, name)
            if key not in seen:
                seen.add(key)
                work.append(key)

    for fctx in pctx.files:
        caches = _jitcache_names(fctx)
        for call in A.file_calls(fctx):
            tail = A.call_tail(call)
            if tail == "put" and isinstance(call.func, ast.Attribute) \
                    and isinstance(call.func.value, ast.Name) \
                    and call.func.value.id in caches \
                    and len(call.args) >= 2:
                val = call.args[1]
                for sub in ast.walk(val):
                    if isinstance(sub, (ast.Lambda,)):
                        builder_nodes[fctx.rel].add(id(sub))
                # jits + builder calls inside the put value expression
                builder_nodes[fctx.rel].add(id(val))
                seed_calls_in(fctx, val)
            elif tail == "get_or_build" and len(call.args) >= 2:
                arg = call.args[1]
                if isinstance(arg, ast.Lambda):
                    builder_nodes[fctx.rel].add(id(arg))
                    seed_calls_in(fctx, arg)
                elif isinstance(arg, ast.Name):
                    key = (fctx.rel, arg.id)
                    if key not in seen:
                        seen.add(key)
                        work.append(key)

    defs_cache: Dict[str, Dict[str, List[ast.AST]]] = {
        f.rel: A.defs_by_name(f.tree) for f in pctx.files}
    while work:
        rel, name = work.pop()
        fctx = pctx.by_rel.get(rel)
        if fctx is None:
            continue
        for node in defs_cache[rel].get(name, ()):
            if id(node) in builder_nodes[rel]:
                continue
            builder_nodes[rel].add(id(node))
            seed_calls_in(fctx, node)
    return builder_nodes


@rule("jit-direct",
      "jax.jit / pl.pallas_call must be routed through the bounded "
      "single-flight JitCache (jit_cache.py) or, for pallas, built "
      "inside the kernels/ registry package")
def check_jit_direct(pctx):
    cfg = pctx.config
    kernels_home = getattr(cfg, "kernels_home",
                           "spark_rapids_tpu/kernels")
    builders = _builder_closure(pctx)
    for fctx in pctx.files:
        if fctx.rel == cfg.jit_home:
            continue
        in_kernels = fctx.rel.startswith(kernels_home.rstrip("/") + "/")
        file_builders = builders.get(fctx.rel, set())
        for call in A.file_calls(fctx):
            is_jit = _is_jax_jit(fctx, call)
            is_pallas = not is_jit and _is_pallas_call(fctx, call)
            if not (is_jit or is_pallas):
                continue
            if is_pallas and in_kernels:
                # the kernels/ registry IS the sanctioned home: its
                # builders only run inside JitCache-routed programs
                continue
            # inside a builder function/lambda or a .put value expr?
            ok = any(id(a) in file_builders
                     for a in [call] + list(A.ancestors(call)))
            if ok:
                continue
            what = "pl.pallas_call" if is_pallas else "jax.jit/named_jit"
            yield Finding(
                "jit-direct", fctx.rel, call.lineno,
                call.col_offset + 1,
                f"direct {what} outside the JitCache path — compile "
                "via a bounded JitCache (get_or_build or "
                "cache.put(key, named_jit(name, fn)))"
                + (", or move the kernel into the kernels/ registry "
                   "package" if is_pallas else "")
                + ", or suppress with a reason if the program is "
                "fixed and bounded by construction")


_DICTISH = ("dict", "OrderedDict", "defaultdict")


@rule("jit-module-cache",
      "module-level dict caches of compiled programs bypass the "
      "JitCache LRU bound")
def check_module_cache(pctx):
    cfg = pctx.config
    for fctx in pctx.files:
        if fctx.rel == cfg.jit_home:
            continue
        for stmt in fctx.tree.body:
            targets: List[ast.AST] = []
            value = None
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value \
                    is not None:
                targets, value = [stmt.target], stmt.value
            if value is None:
                continue
            is_dict = isinstance(value, ast.Dict) or (
                isinstance(value, ast.Call)
                and A.call_tail(value) in _DICTISH)
            if not is_dict:
                continue
            for t in targets:
                if isinstance(t, ast.Name) and "cache" in t.id.lower():
                    yield Finding(
                        "jit-module-cache", fctx.rel, stmt.lineno, 1,
                        f"module-level dict cache `{t.id}` — compiled "
                        f"programs must live in a bounded JitCache "
                        f"(LRU + single-flight + stats); suppress "
                        f"with a reason if it does not hold compiled "
                        f"functions")
