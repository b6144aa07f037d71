"""Rule family 6 — interprocedural data-flow invariants
(docs/linting.md "Family 6"; the tpu-lint v2 tier).

Four rules over ``lint/dataflow.py``'s call graph + reaching-defs
substrate, each machine-checking an invariant a previous PR audited by
hand:

``donation-safety`` — a name handed to a donating compiled program
(``jax.jit(..., donate_argnums=...)``, a ``pallas_call`` with
``input_output_aliases``, or anything resolving to one through a
JitCache route or a local helper one call deep) must not be READ on any
forward path after the donating call: the dispatch reuses the buffer's
HBM storage for the outputs, so a later read sees freed/aliased memory
(the PR 11 "kernel path never donates / stage everything before the
donating dispatch" invariant).

``hidden-sync`` — inside the hot-path scopes (``exec/``, ``ops/``,
``parallel/``, ``columnar/``), a device->host forcing
operation (``np.asarray``/``np.array``, ``float``/``int``/``bool``,
``.item()``, ``jax.device_get``, ``.block_until_ready()``) applied to
a value that reaches from a device-producing call stalls the async
dispatch pipeline for a flat D2H roundtrip. Sanctioned drain points
(the prefetched-scalar reads q1's pipeline is built around) live in
``sync_allowlist`` with a written reason, same grammar as the retry
allowlist.

``handle-leak`` — the value returned by a spillable registration
(``register_spillable``, ``start_upload``, ``<store>.register``) must
reach a ``close``/``release_*``/``finish_*`` call, a context-manager
scope, or escape into a tracked container/return on SOME path — and
not only on the exception path. A handle whose only release is GC's
weakref finalizer holds HBM until the collector happens to run (the
PR 13 ``release_plan_handles`` class).

``trace-purity`` — function bodies reachable from a ``jax.jit``/
``pl.pallas_call`` builder execute at TRACE time: a ``time.*`` or
``random.*``/``np.random.*`` call, a dynamic ``conf.get`` read, or a
mutation of nonlocal state inside them is baked into the compiled
program once and replayed never — a silent bit-identity break the
moment the impure value would have changed.

Every-path checking is approximated on the syntactic CFG: source order
plus loop back edges for donation reads, exception-path-only release
detection for handles. Dynamic dispatch is invisible, so these rules
under-approximate; anything they DO flag is real enough to need a fix,
an allowlist entry, or a reasoned suppression.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from spark_rapids_tpu.lint import astutil as A
from spark_rapids_tpu.lint import dataflow as DF
from spark_rapids_tpu.lint.engine import Finding, rule


def _callgraph(pctx) -> DF.CallGraph:
    cg = getattr(pctx, "_df_callgraph", None)
    if cg is None:
        cg = DF.CallGraph(pctx)
        pctx._df_callgraph = cg
    return cg


def _allowlisted(fctx: A.FileCtx, node: ast.AST,
                 allowlist: Dict[str, str]) -> bool:
    """True when any enclosing function of ``node`` is an allowlist
    entry (``<rel>::<qualname>`` -> reason)."""
    if not allowlist:
        return False
    for fn in A.enclosing_functions(node):
        if isinstance(fn, ast.Lambda):
            continue
        if f"{fctx.rel}::{A.qualname(fn)}" in allowlist:
            return True
    return False


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

@rule("donation-safety",
      "a buffer handed to a donating jax.jit / pallas_call program "
      "must not be read on any forward path after the donating call")
def check_donation_safety(pctx):
    cg = _callgraph(pctx)
    seen: Set[Tuple[str, int, int, str]] = set()
    for site in DF.donation_sites(pctx, cg):
        fctx = site.fctx
        scope = DF.enclosing_function(site.call) or fctx.tree
        for _pos, root in site.donated_roots():
            if root is None or root == "self":
                continue
            for read in DF.reads_after_call(scope, site.call, root):
                key = (fctx.rel, read.lineno, read.col_offset, root)
                if key in seen:
                    continue
                seen.add(key)
                yield Finding(
                    "donation-safety", fctx.rel, read.lineno,
                    read.col_offset + 1,
                    f"`{root}` is read after being donated at line "
                    f"{site.call.lineno} via {site.via} — the dispatch "
                    f"reuses donated HBM storage for its outputs, so "
                    f"this read sees freed/aliased memory; stage every "
                    f"post-call use (row counts, placement, tracing) "
                    f"BEFORE the donating dispatch, or drop the "
                    f"donation")


# ---------------------------------------------------------------------------
# hidden-sync
# ---------------------------------------------------------------------------

_FORCING_BUILTINS = ("float", "int", "bool")


def _owning_def(node: ast.AST):
    """Innermost enclosing FunctionDef/AsyncFunctionDef, looking
    through lambdas (a lambda belongs to the def that wrote it)."""
    for a in A.enclosing_functions(node):
        if not isinstance(a, ast.Lambda):
            return a
    return None


def _forcing_kind(fctx: A.FileCtx, call: ast.Call) -> Optional[str]:
    """The device->host forcing shape of a call, if any: 'asarray',
    'builtin', 'item', 'device_get', 'block'."""
    p = A.resolve_path(fctx, call.func)
    if p in ("numpy.asarray", "numpy.array") and call.args:
        return "asarray"
    if p == "jax.device_get":
        return "device_get"
    tail = A.call_tail(call)
    if tail == "block_until_ready" and isinstance(call.func,
                                                 ast.Attribute):
        return "block"
    if tail == "item" and isinstance(call.func, ast.Attribute) \
            and not call.args:
        return "item"
    if isinstance(call.func, ast.Name) \
            and call.func.id in _FORCING_BUILTINS \
            and len(call.args) == 1 and not call.keywords:
        return "builtin"
    return None


@rule("hidden-sync",
      "device->host forcing ops on values reaching from a "
      "device-producing call are findings in the hot-path scopes "
      "unless allowlisted with a reason")
def check_hidden_sync(pctx):
    cfg = pctx.config
    hot = getattr(cfg, "hot_scope", ())
    allow = getattr(cfg, "sync_allowlist", {})
    for fctx in pctx.files:
        if not pctx.in_scope(fctx.rel, hot):
            continue
        flagged: Set[int] = set()
        for fn in ast.walk(fctx.tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            tainted, programs = DF.device_taint(fctx, fn)

            def expr_is_device(e: ast.AST) -> bool:
                for n in ast.walk(e):
                    if isinstance(n, ast.Call) \
                            and DF._is_device_producing_call(
                                fctx, n, programs):
                        return True
                    if isinstance(n, ast.Name) \
                            and isinstance(n.ctx, ast.Load) \
                            and n.id in tainted:
                        return True
                return False

            for call in A.walk_calls(fn):
                if id(call) in flagged:
                    continue
                # a nested def is analyzed as its own unit with its own
                # taint (its parameters are NOT tainted): checking its
                # calls against the OUTER scope's taint would flag a
                # callback whose parameter shadows an outer device
                # name. Lambdas stay with the def that owns them.
                if _owning_def(call) is not fn:
                    continue
                kind = _forcing_kind(fctx, call)
                if kind is None:
                    continue
                if kind in ("asarray", "builtin"):
                    arg = call.args[0]
                    # int(np.asarray(c)): the inner asarray IS the
                    # sync; report once at the inner site
                    if isinstance(arg, ast.Call) \
                            and _forcing_kind(fctx, arg) is not None:
                        continue
                    if not expr_is_device(arg):
                        continue
                    what = ("np.asarray" if kind == "asarray"
                            else f"{call.func.id}()")
                elif kind == "item":
                    if not expr_is_device(call.func.value):
                        continue
                    what = ".item()"
                elif kind == "device_get":
                    what = "jax.device_get"
                else:
                    what = ".block_until_ready()"
                if _allowlisted(fctx, call, allow):
                    continue
                flagged.add(id(call))
                yield Finding(
                    "hidden-sync", fctx.rel, call.lineno,
                    call.col_offset + 1,
                    f"{what} forces a device->host sync on a hot-path "
                    f"value — the async dispatch pipeline stalls for a "
                    f"flat D2H roundtrip here; prefetch the scalar "
                    f"(_prefetch_host) and drain it at a sanctioned "
                    f"point, or add this function to sync_allowlist "
                    f"with a reason (docs/linting.md)")


# ---------------------------------------------------------------------------
# handle-leak
# ---------------------------------------------------------------------------

_RELEASE_TAILS = ("close",)
_RELEASE_PREFIXES = ("release", "finish")
_CONTAINERS = (ast.Tuple, ast.List, ast.Set, ast.Dict, ast.Starred,
               ast.IfExp)


def _is_release_name(tail: Optional[str]) -> bool:
    return tail is not None and (
        tail in _RELEASE_TAILS
        or any(tail.startswith(p + "_") or tail == p
               for p in _RELEASE_PREFIXES))


def _is_handle_source(fctx: A.FileCtx, call: ast.Call,
                      sources: Tuple[str, ...]) -> bool:
    tail = A.call_tail(call)
    if tail in sources:
        return True
    if tail == "register" and isinstance(call.func, ast.Attribute):
        recv = A.attr_path(call.func.value)
        return recv is not None and "store" in recv.lower()
    return False


def _source_binding(call: ast.Call) -> Tuple[str, Optional[str]]:
    """Classify where a registration call's value goes: ('name', n) to
    track, ('ok', None) when it escapes/releases at the source
    (returned, passed on, context-managed, stored), ('dropped', None)
    for a bare expression statement."""
    node: ast.AST = call
    par = A.parent(node)
    while isinstance(par, _CONTAINERS):
        node, par = par, A.parent(par)
    if isinstance(par, ast.Assign):
        # h = src(...)  (also `h = src(...) if c else None`); any
        # tuple/attr/subscript target or wrapped container escapes
        if node is par.value and len(par.targets) == 1 \
                and isinstance(par.targets[0], ast.Name):
            return "name", par.targets[0].id
        return "ok", None
    if isinstance(par, (ast.Return, ast.Yield, ast.Call, ast.withitem)):
        return "ok", None
    if isinstance(par, ast.Expr):
        return "dropped", None
    return "ok", None


def _handle_uses(fn: ast.AST, name: str, source: ast.Call
                 ) -> Tuple[List[ast.AST], List[ast.AST]]:
    """(releases, escapes) — Load uses of ``name`` that release the
    handle (`.close()`, `release_*`/`finish_*` calls, `with h`) or
    move its ownership (returned/yielded, passed to a call, stored
    into an attribute/subscript/alias, put in a container that is
    itself consumed). Plain reads (`h.get()`, `h.rows`, `h is None`)
    are neither."""
    releases: List[ast.AST] = []
    escapes: List[ast.AST] = []
    in_source = {id(n) for n in ast.walk(source)}
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Name) and node.id == name
                and isinstance(node.ctx, ast.Load)):
            continue
        if id(node) in in_source:
            continue
        cur: ast.AST = node
        par = A.parent(cur)
        while isinstance(par, _CONTAINERS):
            cur, par = par, A.parent(par)
        if isinstance(par, ast.Attribute) and par.value is cur:
            gp = A.parent(par)
            if isinstance(gp, ast.Call) and gp.func is par:
                if _is_release_name(par.attr):
                    releases.append(node)
            continue  # attribute read: not a sink
        if isinstance(par, ast.Call):
            if _is_release_name(A.call_tail(par)):
                releases.append(node)
            else:
                escapes.append(node)
        elif isinstance(par, (ast.Return, ast.Yield)):
            escapes.append(node)
        elif isinstance(par, ast.Assign) and par.value is cur:
            escapes.append(node)  # alias / stored: ownership moved
        elif isinstance(par, ast.withitem) and par.context_expr is cur:
            releases.append(node)  # context manager closes it
    return releases, escapes


def _under_except(node: ast.AST) -> bool:
    return any(isinstance(a, ast.ExceptHandler)
               for a in A.ancestors(node))


@rule("handle-leak",
      "a spillable registration's handle must reach a close/release/"
      "finish call or escape to a tracked container — not be freed "
      "only by GC, and not only on the exception path")
def check_handle_leak(pctx):
    cfg = pctx.config
    sources = getattr(cfg, "handle_sources",
                      ("register_spillable", "start_upload"))
    for fctx in pctx.files:
        seen: Set[int] = set()
        for fn in ast.walk(fctx.tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            for call in A.walk_calls(fn):
                if id(call) in seen:
                    continue
                if not _is_handle_source(fctx, call, sources):
                    continue
                if DF.enclosing_function(call) is not fn:
                    continue  # analyzed with its own def
                seen.add(id(call))
                tail = A.call_tail(call)
                role, name = _source_binding(call)
                if role == "ok":
                    continue
                if role == "dropped":
                    yield Finding(
                        "handle-leak", fctx.rel, call.lineno,
                        call.col_offset + 1,
                        f"`{tail}(...)` result dropped — the spillable "
                        f"handle/token it returns can only be freed by "
                        f"GC's weakref finalizer; bind it and close/"
                        f"finish it deterministically "
                        f"(docs/robustness.md)")
                    continue
                releases, escapes = _handle_uses(fn, name, call)
                if not releases and not escapes:
                    yield Finding(
                        "handle-leak", fctx.rel, call.lineno,
                        call.col_offset + 1,
                        f"`{name}` (from `{tail}`) is never closed, "
                        f"finished, released, or handed off — the "
                        f"handle leaks until GC; close it in a "
                        f"finally, or let it escape to the tracked "
                        f"container that owns it")
                elif all(_under_except(s) for s in releases + escapes):
                    yield Finding(
                        "handle-leak", fctx.rel, call.lineno,
                        call.col_offset + 1,
                        f"`{name}` (from `{tail}`) is only released on "
                        f"the exception path — the success path leaks "
                        f"it to GC; close it in normal flow or a "
                        f"finally")


# ---------------------------------------------------------------------------
# trace-purity
# ---------------------------------------------------------------------------

_MUTATORS = frozenset({"append", "extend", "add", "update", "insert",
                       "remove", "discard", "clear", "pop", "popitem",
                       "setdefault", "appendleft", "extendleft"})
_IMPURE_HEADS = ("time.", "random.", "numpy.random.")


def _purity_violations(fctx: A.FileCtx, fn: ast.AST):
    """(node, what) impurities lexically inside ``fn``. Names bound in
    a lexically ENCLOSING function count as local: a closure
    accumulator created fresh per trace (the decode programs' lazy
    ``bytes_all`` memo, the kernel lane planners' ``lanes.append``) is
    deterministic per-trace bookkeeping, not cross-trace state — only
    module/global mutation survives between traces and breaks
    bit-identity."""
    locals_ = DF.local_names(fn)
    for enc in A.enclosing_functions(fn):
        locals_ |= DF.local_names(enc)
    for node in ast.walk(fn):
        if isinstance(node, ast.Global):
            yield node, (f"`global {', '.join(node.names)}` "
                         f"(module-state mutation)")
        elif isinstance(node, ast.Call):
            p = A.resolve_path(fctx, node.func)
            if p is not None and any(p.startswith(h) or p == h[:-1]
                                     for h in _IMPURE_HEADS):
                yield node, f"`{p}(...)` (host clock/RNG)"
                continue
            tail = A.call_tail(node)
            if tail == "get" and isinstance(node.func, ast.Attribute):
                recv = A.attr_path(node.func.value)
                if recv is not None \
                        and "conf" in recv.split(".")[-1].lower():
                    yield node, (f"`{recv}.get(...)` (dynamic conf "
                                 f"read)")
                    continue
            if tail in _MUTATORS and isinstance(node.func,
                                                ast.Attribute):
                root = DF.root_name(node.func.value)
                if root is not None and root not in locals_:
                    yield node, (f"`{root}.{tail}(...)` (mutates "
                                 f"free state)")
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                if isinstance(t, (ast.Attribute, ast.Subscript)):
                    root = DF.root_name(t)
                    if root is not None and root not in locals_ \
                            and root != "self":
                        yield t, (f"assignment into `{root}` (mutates "
                                  f"free state)")


@rule("trace-purity",
      "function bodies reachable from a jax.jit / pallas_call builder "
      "must not read clocks/RNG/conf or mutate nonlocal state — "
      "impurity is baked in at trace time")
def check_trace_purity(pctx):
    cfg = pctx.config
    allow = getattr(cfg, "purity_allowlist", {})
    cg = _callgraph(pctx)
    roots: List[Tuple[A.FileCtx, ast.AST]] = []
    lambda_roots: List[Tuple[A.FileCtx, ast.AST, str]] = []
    for fctx, node, what in DF.traced_roots(pctx, cg):
        if isinstance(node, ast.Lambda):
            lambda_roots.append((fctx, node, what))
        roots.append((fctx, node))
    reached = cg.reachable(roots)
    seen: Set[Tuple[str, int, int]] = set()

    def emit(fctx, fn_label, node, what):
        key = (fctx.rel, node.lineno, node.col_offset)
        if key in seen:
            return None
        seen.add(key)
        return Finding(
            "trace-purity", fctx.rel, node.lineno,
            node.col_offset + 1,
            f"{what} inside `{fn_label}`, which is traced into a "
            f"compiled program — the impure value is baked in at "
            f"trace time and silently breaks bit-identity; hoist it "
            f"out of the traced body (snapshot before the builder)")

    for info in reached.values():
        if f"{info.rel}::{info.qualname}" in allow:
            continue
        for node, what in _purity_violations(info.fctx, info.node):
            f = emit(info.fctx, info.qualname, node, what)
            if f is not None:
                yield f
    for fctx, lam, _src in lambda_roots:
        if _allowlisted(fctx, lam, allow):
            continue
        for node, what in _purity_violations(fctx, lam):
            f = emit(fctx, "<traced lambda>", node, what)
            if f is not None:
                yield f
