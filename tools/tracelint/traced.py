"""Traced-context discovery: which functions run under a JAX trace.

A function body is *traced* when XLA records it instead of executing it —
host-side calls inside it either burn time once per (re)trace or crash on
tracers.  Rules R1 (host ops) and R4 (tracer branches) only fire inside
traced contexts, so this module computes that set once per run:

Seeds
  * functions decorated with ``jax.jit`` / ``jit`` / ``pmap`` (including
    ``@partial(jax.jit, ...)``),
  * function-valued arguments of ``jax.jit(...)`` / ``jax.vmap(...)`` /
    ``shard_map``-style wrapper calls — including through
    ``partial(f, ...)`` and lambdas,
  * bodies passed to ``lax.scan`` / ``lax.while_loop`` / ``lax.cond`` /
    ``lax.fori_loop`` / ... (the name must expand to ``jax.lax.*`` —
    ``jax.tree.map`` is a host call and must NOT match),
  * Pallas kernel bodies: any function named ``*_kernel`` defined under a
    ``kernels/`` package,
  * nested defs of the configured ``trace_roots`` builders (default:
    ``make_plan_fn`` / ``make_rollout_fn``) AND of any function whose call
    *result* is handed to a tracing wrapper (``jax.jit(make_step(cfg))``)
    — their returned closures are jitted by the caller.

Propagation
  The traced set is closed under calls: a function called (by resolvable
  name) from a traced context is traced too, and so are its own nested
  defs.  This is the *fn-reachability walk* — it is what lets R1 flag a
  ``np.percentile`` buried three helpers below a jitted entry point.

Taint
  Not every parameter of a traced function is a tracer.  Three precision
  mechanisms keep R1's cast checks and R4 honest:

  * ``static_argnames`` / ``static_argnums`` (decorator or call site) and
    keyword/positional bindings through ``functools.partial`` mark those
    parameters *static* — branching on them is how jit specialization is
    supposed to work.
  * Pallas ``*_kernel`` bodies taint only ``*_ref`` parameters; the rest
    are partial-bound Python config by house convention.
  * Functions traced only by *propagation* taint exactly the parameters
    that receive a tainted argument at some traced call site — so
    ``helper(x.shape[0], cfg)`` called from a jitted fn marks neither
    parameter, and ``if cfg.foo:`` inside the helper stays legal.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.tracelint.core import (FuncInfo, ModuleInfo, ProjectIndex, call_name, dotted_name, walk_skipping_funcs)

#: dotted suffixes whose call marks function-valued arguments as traced.
_TRACING_WRAPPERS = ("jit", "pmap", "vmap", "pallas_call", "shard_map",
                     "checkpoint", "remat", "grad",
                     "value_and_grad", "custom_vjp", "custom_jvp")
#: lax control-flow primitives whose callable args are traced bodies.
_LAX_BODIES = ("scan", "while_loop", "fori_loop", "cond", "switch", "map",
               "associative_scan")


def _is_tracing_call(mod: ModuleInfo, name: str) -> bool:
    leaf = name.split(".")[-1]
    if leaf in _TRACING_WRAPPERS:
        return True
    if leaf in _LAX_BODIES:
        expanded = mod.expanded(name)
        return expanded.startswith("jax.lax.") or name.startswith("lax.")
    return False


def _static_argnames_of(call: ast.Call, params: List[str]) -> Set[str]:
    """Parameter names pinned static by ``static_argnames`` /
    ``static_argnums`` keywords of a jit-style call."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for node in ast.walk(kw.value):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str):
                    out.add(node.value)
        elif kw.arg == "static_argnums":
            for node in ast.walk(kw.value):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, int) \
                        and 0 <= node.value < len(params):
                    out.add(params[node.value])
    return out


class TracedSet:
    """The set of traced FuncInfos, with a ``why`` trail for messages and
    per-function taint metadata (see module docstring)."""

    def __init__(self) -> None:
        self._traced: Dict[tuple, FuncInfo] = {}
        self.why: Dict[tuple, str] = {}
        #: params known static (static_argnames, partial-bound, non-_ref).
        self.static_params: Dict[tuple, Set[str]] = {}
        #: None -> all params tainted (seeds); a set -> only these
        #: (functions traced by propagation).
        self.limited_taint: Dict[tuple, Optional[Set[str]]] = {}

    def add(self, fn: FuncInfo, why: str, *,
            static: Optional[Set[str]] = None,
            limited: Optional[Set[str]] = None) -> bool:
        k = fn.key()
        if k in self._traced:
            # a second, stronger sighting may widen the taint
            if limited is None:
                self.limited_taint[k] = None
            elif self.limited_taint.get(k) is not None:
                self.limited_taint[k].update(limited)
            if static:
                self.static_params.setdefault(k, set()).update(static)
            return False
        self._traced[k] = fn
        self.why[k] = why
        self.static_params[k] = set(static or ())
        self.limited_taint[k] = set(limited) if limited is not None \
            else None
        return True

    def __contains__(self, fn: FuncInfo) -> bool:
        return fn.key() in self._traced

    def __iter__(self):
        return iter(self._traced.values())

    def reason(self, fn: FuncInfo) -> str:
        return self.why.get(fn.key(), "")

    def base_taint(self, fn: FuncInfo) -> Set[str]:
        """The parameters of ``fn`` considered tracer-valued."""
        k = fn.key()
        limited = self.limited_taint.get(k)
        params = set(fn.params) if limited is None else set(limited)
        params -= self.static_params.get(k, set())
        params.discard("self")
        return params


_LAMBDA_CACHE: Dict[tuple, FuncInfo] = {}


def _lambda_info(node: ast.Lambda, caller: Optional[FuncInfo],
                 module: ModuleInfo) -> FuncInfo:
    key = (module.rel, "<lambda>", node.lineno, node.col_offset)
    if key not in _LAMBDA_CACHE:
        qual = (caller.qualname + ".<lambda>") if caller else "<lambda>"
        _LAMBDA_CACHE[key] = FuncInfo(node=node, module=module,
                                      qualname=qual, parent=caller)
    return _LAMBDA_CACHE[key]


def _resolve_name(name: str, caller: Optional[FuncInfo],
                  module: ModuleInfo, index: ProjectIndex,
                  mod_funcs: Dict[str, FuncInfo]) -> List[FuncInfo]:
    if caller is not None:
        return index.resolve_call(name, caller)
    fn = mod_funcs.get(name)
    if fn is not None:
        return [fn]
    # module level: from-imported builders still resolve project-wide
    origin = module.from_imports.get(name)
    if origin is not None:
        return [f for f in index.functions.get(origin[1], ())
                if f.parent is None]
    return []


def _seed_arg(expr: ast.AST, caller: Optional[FuncInfo],
              mod: ModuleInfo, index: ProjectIndex,
              mod_funcs: Dict[str, FuncInfo], traced: TracedSet,
              why: str, extra_static: Set[str]) -> None:
    """Mark the traced functions referenced by one argument of a tracing
    wrapper call: direct names, lambdas, ``partial(f, ...)`` bindings, and
    — for call *results* like ``jax.jit(make_step(cfg))`` — the callee's
    nested closures."""
    if isinstance(expr, ast.Lambda):
        traced.add(_lambda_info(expr, caller, mod), why,
                   static=extra_static)
        # the lambda body runs traced; its calls are closed over later
        return
    if isinstance(expr, ast.Name):
        for fn in _resolve_name(expr.id, caller, mod, index, mod_funcs):
            traced.add(fn, why, static=extra_static)
        return
    if isinstance(expr, ast.Call):
        cname = call_name(expr) or ""
        leaf = cname.split(".")[-1]
        if leaf == "partial" and expr.args:
            bound: Set[str] = {kw.arg for kw in expr.keywords
                               if kw.arg is not None}
            targets = []
            inner = expr.args[0]
            if isinstance(inner, ast.Name):
                targets = _resolve_name(inner.id, caller, mod, index,
                                        mod_funcs)
            elif isinstance(inner, ast.Lambda):
                targets = [_lambda_info(inner, caller, mod)]
            n_pos = len(expr.args) - 1
            for fn in targets:
                static = set(bound) | set(fn.params[:n_pos]) | extra_static
                traced.add(fn, why, static=static)
            # nested partial(partial(f, ...), ...): recurse
            if isinstance(inner, ast.Call):
                _seed_arg(inner, caller, mod, index, mod_funcs, traced,
                          why, bound | extra_static)
            return
        # result of a builder call handed to the wrapper: the returned
        # closures (the callee's nested defs) are what gets traced
        if isinstance(expr.func, ast.Name):
            for callee in _resolve_name(expr.func.id, caller, mod, index,
                                        mod_funcs):
                for inner_fn in callee.nested:
                    traced.add(inner_fn,
                               f"closure of {callee.name}() whose result "
                               f"is {why}")
        for sub in list(expr.args) + [kw.value for kw in expr.keywords]:
            _seed_arg(sub, caller, mod, index, mod_funcs, traced, why,
                      extra_static)


def discover(index: ProjectIndex, trace_roots: Tuple[str, ...]
             ) -> TracedSet:
    traced = TracedSet()

    for mod in index.modules:
        mod_funcs = {f.name: f
                     for fns in index.functions.values() for f in fns
                     if f.module is mod and f.parent is None}
        in_kernels = "/kernels/" in f"/{mod.rel}"
        # seed 1: decorators + kernel naming + trace roots
        for fns in index.functions.values():
            for fn in fns:
                if fn.module is not mod or isinstance(fn.node, ast.Lambda):
                    continue
                for deco in fn.node.decorator_list:
                    names = [dotted_name(n) for n in ast.walk(deco)
                             if isinstance(n, (ast.Name, ast.Attribute))]
                    if any(n and n.split(".")[-1] in ("jit", "pmap")
                           for n in names):
                        static = _static_argnames_of(deco, fn.params) \
                            if isinstance(deco, ast.Call) else set()
                        traced.add(fn,
                                   f"@{fn.name} is jit/pmap-decorated",
                                   static=static)
                if in_kernels and fn.name.endswith("_kernel"):
                    non_refs = {p for p in fn.params
                                if not p.endswith("_ref")}
                    traced.add(fn, "Pallas kernel body (kernels/*, "
                                   "*_kernel)", static=non_refs)
                if fn.name in trace_roots:
                    for inner in fn.nested:
                        traced.add(
                            inner,
                            f"closure of trace root {fn.name}() — jitted "
                            f"by every caller")
        # seed 2: call sites handing functions to tracing wrappers
        for caller in _callers_of(index, mod):
            body = caller.node if caller is not None else mod.tree
            for node in walk_skipping_funcs(body):
                if not isinstance(node, ast.Call):
                    continue
                cname = call_name(node)
                if cname is None or not _is_tracing_call(mod, cname):
                    continue
                why = f"passed to {cname}() at {mod.rel}:{node.lineno}"
                site_static_params = node.keywords  # parsed per target
                for arg in list(node.args) + [kw.value
                                              for kw in node.keywords
                                              if kw.arg not in
                                              ("static_argnames",
                                               "static_argnums")]:
                    _seed_arg(arg, caller, mod, index, mod_funcs, traced,
                              why, _site_static(node, arg))
                del site_static_params

    _propagate(index, traced)
    return traced


def _site_static(call: ast.Call, arg: ast.AST) -> Set[str]:
    """static_argnames strings at a jit call site (argnums are resolved
    per target function inside ``_seed_arg`` callers; names suffice for
    the house style)."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for node in ast.walk(kw.value):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str):
                    out.add(node.value)
    return out


def _propagate(index: ProjectIndex, traced: TracedSet) -> None:
    """Close the traced set under calls (the fn-reachability walk),
    carrying positional/keyword taint into each callee."""
    work = list(traced)
    while work:
        fn = work.pop()
        # nested defs of a traced fn execute under the same trace
        for inner in fn.nested:
            if traced.add(inner, f"nested in traced {fn.qualname}"):
                work.append(inner)
        tainted = tainted_locals(fn, traced)
        if isinstance(fn.node, ast.Lambda):
            nodes = ast.walk(fn.node.body)
        else:
            nodes = walk_skipping_funcs(fn.node)
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            cname = call_name(node)
            if cname is None:
                continue
            for callee in index.resolve_call(cname, fn):
                limited = _callsite_taint(node, callee, tainted)
                fresh = traced.add(
                    callee,
                    f"called from traced {fn.qualname} "
                    f"({fn.module.rel}:{node.lineno})",
                    limited=limited)
                if fresh:
                    work.append(callee)


def _callsite_taint(call: ast.Call, callee: FuncInfo,
                    caller_tainted: Set[str]) -> Set[str]:
    """Callee parameters that receive a tainted argument at this site."""
    params = [p for p in callee.params if p != "self"]
    out: Set[str] = set()

    def is_tainted(expr: ast.AST) -> bool:
        return _mentions(expr, caller_tainted) \
            and not only_static_uses(expr, caller_tainted)

    for i, arg in enumerate(call.args):
        if isinstance(arg, ast.Starred):
            if is_tainted(arg.value):
                out.update(params[i:])
            break
        if is_tainted(arg) and i < len(params):
            out.add(params[i])
    for kw in call.keywords:
        if is_tainted(kw.value):
            if kw.arg is None:          # **kwargs: anything could match
                out.update(params)
            elif kw.arg in params:
                out.add(kw.arg)
    return out


def _callers_of(index: ProjectIndex, mod: ModuleInfo):
    """Every function in ``mod`` plus the module top level (None)."""
    out: List[Optional[FuncInfo]] = [None]
    for fns in index.functions.values():
        for fn in fns:
            if fn.module is mod and not isinstance(fn.node, ast.Lambda):
                out.append(fn)
    return out


# ---------------------------------------------------------------------------
# Taint: values derived from a traced function's arguments
# ---------------------------------------------------------------------------


def tainted_locals(fn: FuncInfo, traced: Optional[TracedSet] = None
                   ) -> Set[str]:
    """Names inside ``fn`` that (syntactically) derive from its
    tracer-valued parameters: the base taint from ``traced`` (all params
    for seeds, call-site-derived for propagated fns, minus
    static_argnames/partial-bound/non-``_ref`` statics) plus locals
    assigned from expressions mentioning a tainted name, to a fixpoint.
    Assignments that use tainted names only through static metadata
    (``m = x.shape[0]``) do NOT propagate.

    Closure variables are deliberately never tainted — in the house
    builder pattern (``make_plan_fn``) they are static configuration
    baked into the trace, and branching on them is exactly what SHOULD
    happen."""
    if traced is not None:
        base = traced.base_taint(fn)
    elif isinstance(fn.node, ast.Lambda):
        a = fn.node.args
        base = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
    else:
        base = set(fn.params) - {"self"}
    if isinstance(fn.node, ast.Lambda):
        return base
    tainted = set(base)
    changed = True
    while changed:
        changed = False
        for node in walk_skipping_funcs(fn.node):
            targets: List[ast.AST] = []
            value: Optional[ast.AST] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)) \
                    and node.value is not None:
                targets, value = [node.target], node.value
            elif isinstance(node, ast.For):
                targets, value = [node.target], node.iter
            if value is None:
                continue
            if not _mentions(value, tainted) \
                    or only_static_uses(value, tainted):
                continue
            for t in targets:
                for leaf in ast.walk(t):
                    if isinstance(leaf, ast.Name) \
                            and leaf.id not in tainted:
                        tainted.add(leaf.id)
                        changed = True
    return tainted


def _mentions(expr: ast.AST, names: Set[str]) -> bool:
    return any(isinstance(n, ast.Name) and n.id in names
               for n in ast.walk(expr))


#: attribute reads that yield STATIC metadata even on a tracer.
STATIC_ATTRS = ("shape", "ndim", "size", "dtype", "sharding")


def only_static_uses(test: ast.AST, tainted: Set[str]) -> bool:
    """True when every tainted name in ``test`` is only used through
    static metadata (``x.shape``, ``x.ndim``, ``isinstance(x, ...)``,
    ``x is None``, ``"k" in x`` pytree-structure checks) — such an
    expression is resolved at trace time and safe."""
    for node in ast.walk(test):
        if isinstance(node, ast.Name) and node.id in tainted:
            if not _static_context(node, test):
                return False
    return True


def _static_context(name: ast.Name, root: ast.AST) -> bool:
    """Is this occurrence of ``name`` inside a static-metadata context?"""
    path = _path_to(root, name)
    if path is None:
        return False
    for node in reversed(path):
        if isinstance(node, ast.Attribute) and node.attr in STATIC_ATTRS:
            return True
        if isinstance(node, ast.Call):
            cname = call_name(node)
            if cname in ("isinstance", "len", "callable", "type"):
                return True
        if isinstance(node, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                for op in node.ops):
            # identity and container-membership tests are structural:
            # `x is None`, `"err" in state` (a pytree dict)
            return True
    return False


def _path_to(root: ast.AST, target: ast.AST) -> Optional[List[ast.AST]]:
    if root is target:
        return [root]
    for child in ast.iter_child_nodes(root):
        sub = _path_to(child, target)
        if sub is not None:
            return [root] + sub
    return None
