"""Interprocedural data-flow plumbing for tpu-lint's v2 rule tier.

The lexical rules (families 1-5) check properties a single AST node and
its ancestors can prove. The bug classes PR 11 and PR 13 fixed by hand
— a donated buffer read after the donating dispatch, a hidden
device->host sync on the hot path, a spillable handle freed only by GC,
host impurity baked into a traced program — are DATA-FLOW properties:
they need to know where a value came from, where it goes, and what runs
after what. This module provides that substrate, stdlib-`ast` only:

* ``CallGraph`` — whole-package, cross-module call graph with targets
  resolved through import aliases exactly like the jit-rule builder
  closure (``X.fn`` follows the alias to the target module's defs;
  bare names and ``self.method`` match in-file), plus transitive
  reachability for the trace-purity closure.
* Donating-program resolution — which call sites invoke a compiled
  program that donates input buffers: direct
  ``jax.jit(..., donate_argnums=...)(args)`` invocations, names bound
  to donating jits, names bound through ``cache.get_or_build(key,
  builder)`` / ``cache.put(key, builder(...))`` where the builder
  returns a donating jit (the ``build_stage_fn`` shape), and local
  helpers that forward a parameter into a donating call one level deep.
  A conditional ``donate_argnums=(0, 1) if donate else ()`` reads as
  MAY-donate: the safety property must hold on every instantiation.
* Reaching-definitions helpers — ``reads_after_call`` finds loads of a
  name on any forward path from a call (source order after the call,
  plus the back edge of an enclosing loop), with straight-line
  rebindings killing the flag.
* Device-value taint — ``device_taint`` runs a per-function
  fixed point seeding from device-producing calls (``jax.*`` /
  ``jax.numpy.*`` and invocations of names bound from a JitCache
  ``get``/``put``/``get_or_build``) and propagating through
  assignments, so the hidden-sync rule only fires on values that
  actually reach from the device.

Everything here is best-effort static resolution: dynamic dispatch,
attribute tables and cross-instance aliasing are invisible, so the
rules built on top UNDER-approximate (missed findings are possible;
false positives should be rare and carry an allowlist/suppression
path).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from spark_rapids_tpu.lint import astutil as A


# ---------------------------------------------------------------------------
# Whole-package call graph
# ---------------------------------------------------------------------------

class FuncInfo:
    """One function/method definition somewhere in the package."""

    __slots__ = ("fctx", "rel", "node", "qualname")

    def __init__(self, fctx: A.FileCtx, node: ast.AST):
        self.fctx = fctx
        self.rel = fctx.rel
        self.node = node
        self.qualname = A.qualname(node)


class CallGraph:
    """Best-effort package call graph. Defs are indexed per file by
    bare name; a call target resolves to this file's defs (``foo(...)``,
    ``self.method(...)``) or, for ``X.fn(...)`` with ``X`` an import
    alias, to the aliased module's defs."""

    def __init__(self, pctx):
        self.pctx = pctx
        self.defs: Dict[Tuple[str, str], List[FuncInfo]] = {}
        self.infos: Dict[int, FuncInfo] = {}
        for fctx in pctx.files:
            for node in ast.walk(fctx.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    info = FuncInfo(fctx, node)
                    self.defs.setdefault((fctx.rel, node.name),
                                         []).append(info)
                    self.infos[id(node)] = info

    def resolve_name(self, fctx: A.FileCtx,
                     name: str) -> List[FuncInfo]:
        """A bare name: a def in this file, or a from-import
        (``from pkg.mod import fn`` maps ``fn`` ->
        ``pkg.mod.fn`` in the alias table) followed to its home."""
        got = self.defs.get((fctx.rel, name))
        if got:
            return got
        dotted = fctx.imports.get(name)
        if dotted and "." in dotted:
            mod, _, attr = dotted.rpartition(".")
            return self.defs.get((A.module_rel(mod), attr), [])
        return []

    def resolve_call(self, fctx: A.FileCtx,
                     call: ast.Call) -> List[FuncInfo]:
        f = call.func
        if isinstance(f, ast.Name):
            return self.resolve_name(fctx, f.id)
        if isinstance(f, ast.Attribute):
            if isinstance(f.value, ast.Name) \
                    and f.value.id in fctx.imports:
                rel = A.module_rel(fctx.imports[f.value.id])
                got = self.defs.get((rel, f.attr))
                if got:
                    return got
            # in-file method resolution ONLY for self/cls receivers: a
            # bare-name match on any `obj.foo()` would collide with
            # unrelated same-named defs and manufacture false donation
            # sites / purity reachability
            if isinstance(f.value, ast.Name) \
                    and f.value.id in ("self", "cls"):
                return self.defs.get((fctx.rel, f.attr), [])
        return []

    def reachable(self, roots: Iterable[Tuple[A.FileCtx, ast.AST]]
                  ) -> Dict[int, FuncInfo]:
        """Transitive closure from ``(fctx, fn-node)`` roots. Lambda
        roots seed their calls but only named defs are returned (a
        lambda's body is lexically part of whatever walks it)."""
        out: Dict[int, FuncInfo] = {}
        seen: Set[int] = set()
        work = list(roots)
        while work:
            fctx, node = work.pop()
            if id(node) in seen:
                continue
            seen.add(id(node))
            info = self.infos.get(id(node))
            if info is not None:
                out[id(node)] = info
            for call in A.walk_calls(node):
                for tgt in self.resolve_call(fctx, call):
                    if id(tgt.node) not in seen:
                        work.append((tgt.fctx, tgt.node))
        return out


# ---------------------------------------------------------------------------
# Position / scope helpers
# ---------------------------------------------------------------------------

def pos_of(node: ast.AST) -> Tuple[int, int]:
    return (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))


def root_name(expr: ast.AST) -> Optional[str]:
    """Base Name of a Name/Attribute/Subscript/Starred chain:
    ``b.columns`` -> ``b``; None for anything rootless (a literal, a
    call result used inline)."""
    cur = expr
    while isinstance(cur, (ast.Attribute, ast.Subscript, ast.Starred)):
        cur = cur.value
    return cur.id if isinstance(cur, ast.Name) else None


def local_names(fn: ast.AST) -> Set[str]:
    """Names BOUND inside a function/lambda: parameters, every Store
    target (assignments, loop/with/except/comprehension targets,
    walrus), nested defs, local imports. A Load of anything outside
    this set reads free state (closure or module)."""
    out: Set[str] = set()
    args = getattr(fn, "args", None)
    if args is not None:
        for a in (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs)
                  + ([args.vararg] if args.vararg else [])
                  + ([args.kwarg] if args.kwarg else [])):
            out.add(a.arg)
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)) and node is not fn:
            if not isinstance(node, ast.Lambda):
                out.add(node.name)
            # a nested def's parameters are bound within fn's subtree
            # too (a Pallas kernel's output refs are the inner kern's
            # params — writes to them are not free-state mutation)
            out |= local_names(node)
        elif isinstance(node, ast.Name) \
                and isinstance(node.ctx, ast.Store):
            out.add(node.id)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                out.add((a.asname or a.name).split(".")[0])
        elif isinstance(node, ast.ExceptHandler) and node.name:
            out.add(node.name)
    return out


def positional_params(fn: ast.AST) -> List[str]:
    args = getattr(fn, "args", None)
    if args is None:
        return []
    return [a.arg for a in list(args.posonlyargs) + list(args.args)]


def enclosing_function(node: ast.AST) -> Optional[ast.AST]:
    fns = A.enclosing_functions(node)
    return fns[0] if fns else None


def _outermost_loop_within(node: ast.AST,
                           stop: ast.AST) -> Optional[ast.AST]:
    loop = None
    for a in A.ancestors(node):
        if a is stop:
            break
        if isinstance(a, (ast.For, ast.AsyncFor, ast.While)):
            loop = a
    return loop


def _stores_of(scope: ast.AST, name: str) -> List[Tuple[int, int]]:
    return sorted(pos_of(n) for n in ast.walk(scope)
                  if isinstance(n, ast.Name)
                  and isinstance(n.ctx, ast.Store) and n.id == name)


def reads_after_call(fn: ast.AST, call: ast.Call,
                     name: str) -> List[ast.Name]:
    """Loads of ``name`` inside ``fn`` that sit on a forward path from
    ``call``: after it in source order, or anywhere in the call's
    outermost enclosing loop (the back edge runs the read AFTER the
    call on the next iteration). A rebinding of the name between the
    call and the read kills the flag — including the loop's own
    iteration target, which rebinds at the top of every pass."""
    cpos = pos_of(call)
    # the canonical donation idiom rebinds the name to the program's
    # output IN the donating statement (`x = _F(x)`): every later read
    # sees the new value, so nothing downstream can touch the donated
    # storage — the site is clean by construction
    for a in A.ancestors(call):
        if isinstance(a, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == name
                for t in a.targets):
            return []
        if isinstance(a, ast.stmt):
            break
    kills = _stores_of(fn, name)
    loop = _outermost_loop_within(call, fn)
    loop_ids = {id(n) for n in ast.walk(loop)} if loop is not None \
        else set()
    loop_kills = _stores_of(loop, name) if loop is not None else []
    in_call = {id(n) for n in ast.walk(call)}
    out: List[ast.Name] = []
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Name) and node.id == name
                and isinstance(node.ctx, ast.Load)):
            continue
        if id(node) in in_call:
            continue
        rpos = pos_of(node)
        if rpos > cpos:
            if not any(cpos < k <= rpos for k in kills):
                out.append(node)
        elif id(node) in loop_ids:
            # loop-carried path call -> loop end -> loop head -> read:
            # dead iff the name rebinds after the call (same iteration)
            # or before the read (next iteration)
            if not any(k > cpos for k in loop_kills) \
                    and not any(k < rpos for k in loop_kills):
                out.append(node)
    return sorted(out, key=pos_of)


# ---------------------------------------------------------------------------
# Donating-program resolution
# ---------------------------------------------------------------------------

def donated_positions(fctx: A.FileCtx,
                      call: ast.Call) -> Optional[Set[int]]:
    """Donated argument positions of a ``jax.jit``/``pl.pallas_call``
    EXPRESSION, or None when it does not donate. Every int literal in
    the ``donate_argnums`` expression counts (a conditional
    ``(0, 1) if donate else ()`` MAY donate — the rule must hold for
    the donating instantiation); pallas donation is the keys of an
    ``input_output_aliases`` dict."""
    p = A.resolve_path(fctx, call.func)
    if A.jit_traced_arg(fctx, call)[0]:
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                ints = {n.value for n in ast.walk(kw.value)
                        if isinstance(n, ast.Constant)
                        and type(n.value) is int}
                return ints or None
    elif p is not None and (p == "pallas_call"
                            or p.endswith(".pallas_call")):
        for kw in call.keywords:
            if kw.arg == "input_output_aliases" \
                    and isinstance(kw.value, ast.Dict):
                ints = {k.value for k in kw.value.keys
                        if isinstance(k, ast.Constant)
                        and type(k.value) is int}
                return ints or None
    return None


def donating_builders(pctx, cg: CallGraph) -> Dict[int, Set[int]]:
    """Function-node id -> donated positions for every def that RETURNS
    a donating jit (directly, or via a local name bound to one) — the
    ``build_stage_fn(steps, donate)`` shape."""
    out: Dict[int, Set[int]] = {}
    for fctx in pctx.files:
        for node in ast.walk(fctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            bound: Dict[str, Set[int]] = {}
            don_calls: Dict[int, Set[int]] = {}
            for c in A.walk_calls(node):
                ps = donated_positions(fctx, c)
                if ps:
                    don_calls[id(c)] = ps
                    par = A.parent(c)
                    if isinstance(par, ast.Assign):
                        for t in par.targets:
                            if isinstance(t, ast.Name):
                                bound[t.id] = ps
            for r in ast.walk(node):
                if not (isinstance(r, ast.Return) and r.value is not None):
                    continue
                if enclosing_function(r) is not node:
                    continue  # a nested def's return, not this one's
                v = r.value
                if id(v) in don_calls:
                    out.setdefault(id(node), set()).update(
                        don_calls[id(v)])
                elif isinstance(v, ast.Name) and v.id in bound:
                    out.setdefault(id(node), set()).update(bound[v.id])
    return out


def _donating_value(fctx: A.FileCtx, cg: CallGraph,
                    builders: Dict[int, Set[int]],
                    value: ast.AST) -> Optional[Set[int]]:
    """Donated positions of the program a VALUE expression evaluates
    to: a donating jit expression, a call to a donating builder, or a
    JitCache route (``cache.get_or_build(key, builder)`` /
    ``cache.put(key, builder(...))``) whose builder donates."""
    if isinstance(value, ast.Call):
        ps = donated_positions(fctx, value)
        if ps:
            return ps
        tail = A.call_tail(value)
        if tail in ("get_or_build", "put") and len(value.args) >= 2:
            return _donating_value(fctx, cg, builders, value.args[1])
        for tgt in cg.resolve_call(fctx, value):
            if id(tgt.node) in builders:
                return set(builders[id(tgt.node)])
        return None
    if isinstance(value, ast.Lambda):
        return _donating_value(fctx, cg, builders, value.body)
    if isinstance(value, ast.Name):
        for info in cg.resolve_name(fctx, value.id):
            if id(info.node) in builders:
                return set(builders[id(info.node)])
    return None


class DonationSite:
    """One call site that hands buffers to a donating program."""

    __slots__ = ("fctx", "call", "positions", "via")

    def __init__(self, fctx: A.FileCtx, call: ast.Call,
                 positions: Set[int], via: str):
        self.fctx = fctx
        self.call = call
        self.positions = positions
        self.via = via  # what resolved as donating, for the message

    def donated_roots(self) -> List[Tuple[int, Optional[str]]]:
        out = []
        for p in sorted(self.positions):
            if p < len(self.call.args):
                out.append((p, root_name(self.call.args[p])))
        return out


def donation_sites(pctx, cg: CallGraph) -> List[DonationSite]:
    """Every resolvable donating call site in the package, including
    one level of local-helper forwarding: a helper whose body donates
    one of its own positional parameters donates that position at ITS
    call sites too."""
    builders = donating_builders(pctx, cg)
    sites: List[DonationSite] = []
    for fctx in pctx.files:
        # scope-id -> name -> donated positions, for names bound to
        # donating programs (module scope and each function scope)
        bindings: Dict[int, Dict[str, Set[int]]] = {}
        for node in ast.walk(fctx.tree):
            if not isinstance(node, ast.Assign):
                continue
            ps = _donating_value(fctx, cg, builders, node.value)
            if not ps:
                continue
            scope = enclosing_function(node)
            scope_id = id(scope) if scope is not None else id(fctx.tree)
            for t in node.targets:
                name = None
                if isinstance(t, ast.Name):
                    name = t.id
                elif isinstance(t, ast.Tuple) and t.elts \
                        and isinstance(t.elts[0], ast.Name):
                    # fn, was_miss = cache.get_or_build(...)
                    name = t.elts[0].id
                if name:
                    bindings.setdefault(scope_id, {})[name] = ps

        def _lookup(call: ast.Call, fname: str) -> Optional[Set[int]]:
            for scope in A.enclosing_functions(call):
                got = bindings.get(id(scope), {}).get(fname)
                if got:
                    return got
            return bindings.get(id(fctx.tree), {}).get(fname)

        for call in A.file_calls(fctx):
            f = call.func
            if isinstance(f, ast.Call):
                ps = donated_positions(fctx, f)
                if ps:
                    sites.append(DonationSite(fctx, call, ps,
                                              "an inline donating jit"))
            elif isinstance(f, ast.Name):
                ps = _lookup(call, f.id)
                if ps:
                    sites.append(DonationSite(
                        fctx, call, ps,
                        f"`{f.id}` (bound to a donating program)"))
    # one level of helper forwarding: a function that donates its own
    # positional parameter k makes every call site of that function a
    # donation site at position k
    helper_donates: Dict[int, Set[int]] = {}
    for s in sites:
        fn = enclosing_function(s.call)
        if fn is None or id(fn) not in cg.infos:
            continue
        params = positional_params(fn)
        for _p, root in s.donated_roots():
            if root in params:
                helper_donates.setdefault(id(fn), set()).add(
                    params.index(root))
    if helper_donates:
        for fctx in pctx.files:
            for call in A.file_calls(fctx):
                for tgt in cg.resolve_call(fctx, call):
                    ps = helper_donates.get(id(tgt.node))
                    if not ps:
                        continue
                    # bound-method call: `self.helper(x)` binds self
                    # implicitly, so the helper's param index k maps
                    # to call.args[k-1]
                    shift = 1 if (
                        isinstance(call.func, ast.Attribute)
                        and positional_params(tgt.node)[:1]
                        in (["self"], ["cls"])) else 0
                    adj = {p - shift for p in ps if p - shift >= 0}
                    if adj:
                        sites.append(DonationSite(
                            fctx, call, adj,
                            f"helper `{tgt.node.name}` (donates its "
                            f"parameter one call down)"))
    return sites


# ---------------------------------------------------------------------------
# Device-value taint (hidden-sync substrate)
# ---------------------------------------------------------------------------

_JIT_ROUTE_TAILS = ("get", "put", "get_or_build")

# jax calls that return host metadata (topology, backend names), not
# device-resident data — using their results on the host is not a sync
_NON_DATA_JAX = frozenset({
    "jax.device_get", "jax.devices", "jax.local_devices",
    "jax.device_count", "jax.local_device_count",
    "jax.default_backend", "jax.process_index", "jax.process_count"})

# calls that FORCE a device value to the host: their result is a host
# value, so assigning it SANITIZES the target (the sync itself is the
# hidden-sync rule's finding; everything downstream is host-side)
_FORCING_PATHS = frozenset({"numpy.asarray", "numpy.array",
                            "jax.device_get"})


def _jitcache_instance_names(fctx: A.FileCtx) -> Set[str]:
    # memoized per file: device_taint calls this once per FUNCTION,
    # and the whole-tree walk dominated the hidden-sync rule's wall
    # (it is a pure function of the parsed tree)
    cached = getattr(fctx, "_jitcache_names", None)
    if cached is not None:
        return cached
    out: Set[str] = set()
    for node in ast.walk(fctx.tree):
        if isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and A.call_tail(node.value) == "JitCache":
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    fctx._jitcache_names = out
    return out


def _is_device_producing_call(fctx: A.FileCtx, call: ast.Call,
                              program_names: Set[str]) -> bool:
    p = A.resolve_path(fctx, call.func)
    if p is not None:
        head = p.split(".")[0]
        if head == "jax" and p not in _NON_DATA_JAX:
            return True
    f = call.func
    return isinstance(f, ast.Name) and f.id in program_names


def _is_forcing_call(fctx: A.FileCtx, call: ast.Call) -> bool:
    """A call whose RESULT is a host value pulled off the device:
    np.asarray/np.array/jax.device_get, the float/int/bool builtins,
    and ``x.item()``."""
    p = A.resolve_path(fctx, call.func)
    if p in _FORCING_PATHS:
        return True
    f = call.func
    if isinstance(f, ast.Name) and f.id in ("float", "int", "bool") \
            and len(call.args) == 1:
        return True
    return isinstance(f, ast.Attribute) and f.attr == "item" \
        and not call.args


def device_taint(fctx: A.FileCtx,
                 fn: ast.AST) -> Tuple[Set[str], Set[str]]:
    """Per-function fixed point: ``(tainted, program_names)``.
    ``tainted`` holds names whose value reaches from a device-producing
    call — a ``jax.*``/``jax.numpy.*`` call, or an invocation of a name
    bound from a JitCache ``get``/``put``/``get_or_build`` (a compiled
    program's output lives on the device); ``program_names`` are those
    compiled-program bindings themselves. Taint propagates through
    assignments and tuple unpacking; parameters are NOT tainted
    (callers own that knowledge)."""
    caches = _jitcache_instance_names(fctx)
    program_names: Set[str] = set()
    tainted: Set[str] = set()
    # names assigned from a forcing call are HOST values from then on
    # (flow-insensitively, sanitization wins — prefer a missed finding
    # over flagging host-side arithmetic after the one real sync)
    sanitized: Set[str] = set()

    def expr_tainted(e: ast.AST) -> bool:
        for n in ast.walk(e):
            if isinstance(n, ast.Call) \
                    and _is_device_producing_call(fctx, n,
                                                  program_names):
                return True
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                    and n.id in tainted:
                return True
        return False

    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            # program bindings: fn = CACHE.get(...) / .put(...) /
            # fn, miss = CACHE.get_or_build(...)
            if isinstance(v, ast.Call) \
                    and A.call_tail(v) in _JIT_ROUTE_TAILS \
                    and isinstance(v.func, ast.Attribute) \
                    and isinstance(v.func.value, ast.Name) \
                    and (v.func.value.id in caches
                         or "CACHE" in v.func.value.id.upper()):
                for t in node.targets:
                    name = None
                    if isinstance(t, ast.Name):
                        name = t.id
                    elif isinstance(t, ast.Tuple) and t.elts \
                            and isinstance(t.elts[0], ast.Name):
                        name = t.elts[0].id
                    if name and name not in program_names:
                        program_names.add(name)
                        changed = True
                continue
            if isinstance(v, ast.Call) and _is_forcing_call(fctx, v):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name) \
                                and isinstance(n.ctx, ast.Store) \
                                and n.id not in sanitized:
                            sanitized.add(n.id)
                            tainted.discard(n.id)
                            changed = True
                continue
            if not expr_tainted(v):
                continue
            for t in node.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name) \
                            and isinstance(n.ctx, ast.Store) \
                            and n.id not in tainted \
                            and n.id not in sanitized:
                        tainted.add(n.id)
                        changed = True
    return tainted, program_names


# ---------------------------------------------------------------------------
# Traced-root collection (trace-purity substrate)
# ---------------------------------------------------------------------------

def traced_roots(pctx, cg: CallGraph
                 ) -> Iterator[Tuple[A.FileCtx, ast.AST, str]]:
    """(fctx, fn-or-lambda node, description) for every function a
    ``jax.jit``/``pl.pallas_call`` builder traces: the first argument,
    resolved through local names — including one ``shard_map(f, ...)``
    wrapper hop — and import aliases."""
    for fctx in pctx.files:
        # name -> value expr for every single-target assignment, so
        # `sm = shard_map(per_shard, ...)` then `jax.jit(sm)` resolves
        assigns: Dict[str, ast.AST] = {}
        for node in ast.walk(fctx.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                assigns[node.targets[0].id] = node.value
        for call in A.file_calls(fctx):
            p = A.resolve_path(fctx, call.func)
            is_jit, traced = A.jit_traced_arg(fctx, call)
            is_pallas = p is not None and (p == "pallas_call"
                                           or p.endswith(".pallas_call"))
            if is_pallas and call.args:
                traced = call.args[0]
            if not (is_jit or is_pallas) or traced is None:
                continue
            what = "pl.pallas_call" if is_pallas else "jax.jit"
            for fctx2, node in _resolve_traced_arg(fctx, cg, assigns,
                                                   traced, 0):
                yield fctx2, node, what


def _resolve_traced_arg(fctx: A.FileCtx, cg: CallGraph,
                        assigns: Dict[str, ast.AST], arg: ast.AST,
                        depth: int) -> List[Tuple[A.FileCtx, ast.AST]]:
    if depth > 2:
        return []
    if isinstance(arg, ast.Lambda):
        return [(fctx, arg)]
    if isinstance(arg, ast.Name):
        infos = cg.resolve_name(fctx, arg.id)
        if infos:
            return [(i.fctx, i.node) for i in infos]
        v = assigns.get(arg.id)
        if isinstance(v, ast.Call):
            # one wrapper hop: shard_map(f, ...) / functools.partial(f)
            out = []
            for sub in v.args[:1]:
                out.extend(_resolve_traced_arg(fctx, cg, assigns, sub,
                                               depth + 1))
            return out
        return []
    if isinstance(arg, ast.Call):
        # jax.jit(shard_map(per_shard, ...)) inline
        out = []
        for sub in arg.args[:1]:
            out.extend(_resolve_traced_arg(fctx, cg, assigns, sub,
                                           depth + 1))
        return out
    if isinstance(arg, ast.Attribute):
        infos = cg.resolve_call(
            fctx, ast.Call(func=arg, args=[], keywords=[]))
        return [(i.fctx, i.node) for i in infos]
    return []
