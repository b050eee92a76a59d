"""graftguard — compile-safety lint (GL3xx, pass 5) over trace-eligible
closures.

A train step that is ONE donated XLA program
(``parallel.DataParallelTrainer``), a ``CachedOp`` forward or a fused
optimizer bucket has a hazard class the other passes cannot see:

* host round-trips hiding inside traced regions (a ``.asnumpy()`` in a
  loss function turns "one program" into "one program per step plus a
  device sync"),
* Python control flow on traced values (works eagerly, explodes or
  silently specializes under ``jax.jit``),
* values baked as compile-time constants that were supposed to vary
  (the lr/wd/rescale bug class),
* reads of donated buffers after dispatch (XLA aliased the memory; the
  value is gone on real hardware, and only *sometimes* gone on CPU —
  the worst kind of latent bug).

The pass is static (AST, no execution) and is run by ``graftlint --all``:

GL301    host materialization inside trace-eligible code: ``.asnumpy()``
         / ``.item()`` / ``.tolist()`` / ``float()/int()/bool()`` /
         ``np.*`` applied to a traced value
GL302    Python ``if``/``while``/ternary/``assert`` branching on a
         traced array value (shape/dtype/ndim reads stay static and are
         exempt)
GL303    nondeterminism inside a traced closure: ``os.environ`` /
         ``os.getenv`` / ``time.*`` / ``random.*`` / ``np.random.*`` /
         ``datetime``/``uuid``/``secrets`` reads get frozen at trace
         time (or fork per retrace) — hoist them out of the trace
GL304    mutation of captured Python state under trace (append/store to
         a closed-over list/dict, ``global``/``nonlocal`` writes): runs
         once at trace time, never again on the compiled path
GL305    hyperparameter-looking scalar (lr/wd/rescale/momentum/beta/
         eps/clip) closed over as a trace-time CONSTANT instead of
         riding as a traced operand — changing it later silently
         doesn't take effect (or forces a retrace)
GL306    a donated buffer referenced AFTER the donating dispatch in the
         same block: XLA aliased that memory for an output
GL308    a traced function parameter used ONLY for its shape/dtype —
         shape-polymorphic input with no value use: make it a static
         argument, or every new shape retraces a program that didn't
         need the data at all

CLI: ``python -m incubator_mxnet_tpu.analysis.compile_safety --selftest``
forces every rule through its fixture and its clean twin, and the
package and registry walks over the tree (lint tier 11).
"""

from __future__ import annotations

import ast
import builtins
import os
import re
import sys

from .contracts import Diagnostic, _fcompute_tree, suppressions_for
from .concurrency import _line_suppressions, package_root

__all__ = [
    "RULES", "lint_source", "lint_file", "lint_package", "lint_registry",
    "lint_callable", "selftest", "main",
]

RULES = {
    "GL301": "host materialization (.asnumpy/.item/float()/np.*) on a "
             "traced value inside trace-eligible code",
    "GL302": "Python if/while branching on a traced array value",
    "GL303": "env/config/clock/RNG nondeterminism inside a traced "
             "closure (frozen at trace time)",
    "GL304": "mutation of captured Python state under trace (runs once, "
             "at trace time)",
    "GL305": "hyperparameter scalar closed over as a trace-time "
             "constant instead of riding as a traced operand",
    "GL306": "donated buffer referenced after the donating dispatch",
    "GL308": "traced parameter used only for shape/dtype (shape-"
             "polymorphic input with no value use)",
}

# ---------------------------------------------------------------------------
# static pass: shared AST helpers
# ---------------------------------------------------------------------------

# attribute reads that stay STATIC under jit (reading them off a tracer
# yields concrete Python values, so taint does not flow through them)
_STATIC_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "context",
                           "ctx", "name", "grad_req", "_version"})
# calls whose results are static regardless of argument taint
_STATIC_CALLS = frozenset({"len", "isinstance", "type", "getattr",
                           "hasattr", "id", "callable"})
_MATERIALIZE_ATTRS = frozenset({"asnumpy", "item", "tolist", "asscalar"})
_CAST_BUILTINS = frozenset({"float", "int", "bool", "complex"})
_MUTATOR_METHODS = frozenset({"append", "extend", "insert", "add",
                              "update", "setdefault", "pop", "popitem",
                              "remove", "discard", "clear", "write"})
_NONDET_PREFIXES = (("os", "environ"), ("os", "getenv"), ("time",),
                    ("random",), ("numpy", "random"), ("datetime",),
                    ("uuid",), ("secrets",))
_HYPER_RE = re.compile(
    r"(?:^|_)(lr|learning_rate|wd|weight_decay|rescale(?:_grad)?|"
    r"momentum|beta1|beta2|eps|epsilon|clip(?:_gradient)?)(?:_|$)")
_BUILTIN_NAMES = frozenset(dir(builtins))

# calls whose function-typed arguments get traced by jax
_TRACE_ENTRYPOINTS = frozenset({
    "jit", "pjit", "pmap", "vjp", "jvp", "grad", "value_and_grad",
    "eval_shape", "make_jaxpr", "linearize", "checkpoint_policy",
    "functionalize", "serving_fn"})
_TRACE_KWARGS = frozenset({"fun", "f", "fn"})


def _dotted(node):
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _call_name(call):
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _fn_params(args_node, skip_self=True):
    names = []
    for a in (getattr(args_node, "posonlyargs", []) + args_node.args):
        names.append(a.arg)
    if args_node.vararg is not None:
        names.append(args_node.vararg.arg)
    for a in args_node.kwonlyargs:
        names.append(a.arg)
    if args_node.kwarg is not None:
        names.append(args_node.kwarg.arg)
    if skip_self and names and names[0] in ("self", "cls"):
        names = names[1:]
    return names


def _body_list(fn_node):
    body = fn_node.body
    return body if isinstance(body, list) else [body]


def _walk_skip_defs(root_nodes, skip_lambdas=False):
    """Walk statements/expressions, NOT descending into nested
    FunctionDefs (they are traced — and checked — separately if
    reachable); Lambdas share the enclosing namespace and ARE entered
    unless ``skip_lambdas``."""
    stack = list(root_nodes)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if skip_lambdas and isinstance(node, ast.Lambda):
            continue
        stack.extend(ast.iter_child_nodes(node))


class _TaintEnv(object):
    """Per-function taint: which local names carry traced array values.

    Coarse by design (nested lambdas share the namespace; tuple targets
    taint every element) — the rules it feeds are advisory lint, and
    over-taint is bounded by the _STATIC_ATTRS / _STATIC_CALLS breaks."""

    def __init__(self, fn_node, seeds, import_names):
        self.fn = fn_node
        self.imports = import_names
        self.locals = set(_fn_params(fn_node.args, skip_self=False))
        self.tainted = set(seeds)
        for node in _walk_skip_defs(_body_list(fn_node)):
            if isinstance(node, ast.Name) and isinstance(
                    node.ctx, (ast.Store, ast.Del)):
                self.locals.add(node.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.locals.add(node.name)
            elif isinstance(node, ast.Lambda):
                self.locals.update(_fn_params(node.args, skip_self=False))
        self._fixpoint()

    def is_free(self, name):
        return (name not in self.locals and name not in self.imports
                and name not in _BUILTIN_NAMES)

    def expr_tainted(self, node):
        """True if evaluating ``node`` can yield a traced value."""
        stack = [node]
        while stack:
            n = stack.pop()
            if isinstance(n, ast.Name):
                if isinstance(n.ctx, ast.Load) and n.id in self.tainted:
                    return True
                continue
            if isinstance(n, ast.Attribute):
                if n.attr in _STATIC_ATTRS:
                    continue            # x.shape is static under jit
                stack.append(n.value)
                continue
            if isinstance(n, ast.Call):
                cn = _call_name(n)
                if isinstance(n.func, ast.Name) and cn in _STATIC_CALLS:
                    continue            # len(x)/isinstance(x, T) static
                stack.extend(ast.iter_child_nodes(n))
                continue
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.extend(ast.iter_child_nodes(n))
        return False

    def _targets(self, t, out):
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, ast.Starred):
            self._targets(t.value, out)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                self._targets(e, out)
        elif isinstance(t, ast.Subscript):
            # storing a traced value INTO a container taints the
            # container name (shadows[n] = NDArray(v))
            root = t.value
            while isinstance(root, (ast.Subscript, ast.Attribute)):
                root = root.value
            if isinstance(root, ast.Name):
                out.add(root.id)

    def _fixpoint(self):
        for _ in range(4):
            grew = False
            for node in _walk_skip_defs(_body_list(self.fn)):
                tgt, val = None, None
                if isinstance(node, ast.Assign):
                    tgt, val = node.targets, node.value
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    tgt, val = [node.target], node.value
                elif isinstance(node, ast.NamedExpr):
                    tgt, val = [node.target], node.value
                elif isinstance(node, ast.For):
                    tgt, val = [node.target], node.iter
                elif isinstance(node, ast.comprehension):
                    tgt, val = [node.target], node.iter
                if val is None or tgt is None:
                    continue
                if not self.expr_tainted(val):
                    continue
                # `for k, v in D.items()` — dict keys are host values
                # (param-name strings), only the VALUES carry taint;
                # `.keys()` carries none
                if (isinstance(node, (ast.For, ast.comprehension))
                        and isinstance(val, ast.Call)
                        and isinstance(val.func, ast.Attribute)
                        and val.func.attr in ("items", "keys")):
                    if val.func.attr == "keys":
                        continue
                    t0 = tgt[0]
                    if isinstance(t0, (ast.Tuple, ast.List)) \
                            and len(t0.elts) == 2:
                        names = set()
                        self._targets(t0.elts[1], names)
                        new = names - self.tainted
                        if new:
                            self.tainted |= new
                            grew = True
                        continue
                names = set()
                for t in tgt:
                    self._targets(t, names)
                new = names - self.tainted
                if new:
                    self.tainted |= new
                    grew = True
            if not grew:
                return


# ---------------------------------------------------------------------------
# static pass: per-module scan
# ---------------------------------------------------------------------------

class _ModuleScan(object):
    def __init__(self, source, filename, module):
        self.source = source
        self.filename = filename
        self.module = module
        self.tree = ast.parse(source)
        self.suppress = _line_suppressions(source)
        self._scope_sup = {}
        self.diags = []
        self.parents = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[id(child)] = parent
        self.defs = []            # {"node","scope","cls","qual"}
        self.by_name = {}
        self.methods = {}         # (cls, name) -> def info
        self._collect_defs(self.tree, (), None)
        self.imports = self._import_aliases()
        self.assigned_funcs = {}  # name -> factory Call node
        self._collect_assignments()
        self.donated_names = {}   # callable name -> donated positions
        self.donated_keys = {}    # entry["..."] key -> donated positions
        self._collect_donations()
        self.traced = {}          # id(def node) -> (info, seed set)

    # -- structure ---------------------------------------------------------
    def _collect_defs(self, node, scope, cls, direct=False):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info = {"node": child, "scope": scope, "cls": cls,
                        "qual": ".".join(scope + (child.name,))}
                self.defs.append(info)
                self.by_name.setdefault(child.name, []).append(info)
                if cls is not None and direct:
                    self.methods[(cls, child.name)] = info
                # nested closures keep the enclosing class: their
                # ``self.X(...)`` calls must still resolve to methods
                self._collect_defs(child, scope + (child.name,), cls)
            elif isinstance(child, ast.ClassDef):
                self._collect_defs(child, scope, child.name, direct=True)
            else:
                self._collect_defs(child, scope, cls, direct)

    def _import_aliases(self):
        out = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    out[a.asname or a.name.split(".")[0]] = \
                        tuple(a.name.split("."))
            elif isinstance(node, ast.ImportFrom) and node.module:
                base = tuple(node.module.split("."))
                for a in node.names:
                    out[a.asname or a.name] = base + (a.name,)
        # common scientific alias even when imported indirectly
        out.setdefault("np", ("numpy",))
        out.setdefault("jnp", ("jax", "numpy"))
        return out

    def canonical(self, dotted):
        if not dotted:
            return dotted
        head = self.imports.get(dotted[0])
        if head:
            return head + dotted[1:]
        return dotted

    def _collect_assignments(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t, v = node.targets[0], node.value
            if not isinstance(t, ast.Name) or not isinstance(v, ast.Call):
                continue
            self.assigned_funcs.setdefault(t.id, v)

    # -- donation map ------------------------------------------------------
    def _donate_positions(self, kw_value, jit_call):
        node = kw_value
        if isinstance(node, ast.Name):
            # resolve `donate = (0, 1) if cond else ()` in the enclosing
            # function
            fn = self.parents.get(id(jit_call))
            while fn is not None and not isinstance(
                    fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = self.parents.get(id(fn))
            if fn is not None:
                for n in ast.walk(fn):
                    if (isinstance(n, ast.Assign)
                            and len(n.targets) == 1
                            and isinstance(n.targets[0], ast.Name)
                            and n.targets[0].id == node.id):
                        node = n.value
                        break
        cands = [node]
        if isinstance(node, ast.IfExp):
            cands = [node.body, node.orelse]
        out = set()
        for c in cands:
            if isinstance(c, (ast.Tuple, ast.List)):
                for e in c.elts:
                    if isinstance(e, ast.Constant) and isinstance(
                            e.value, int):
                        out.add(e.value)
            elif isinstance(c, ast.Constant) and isinstance(c.value, int):
                out.add(c.value)
        return out or None

    def _collect_donations(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call) or _call_name(node) not in (
                    "jit", "pjit"):
                continue
            pos = None
            for kw in node.keywords:
                if kw.arg == "donate_argnums":
                    pos = self._donate_positions(kw.value, node)
            if not pos:
                continue
            parent = self.parents.get(id(node))
            if not isinstance(parent, ast.Assign) or len(
                    parent.targets) != 1:
                continue
            t = parent.targets[0]
            if isinstance(t, ast.Name):
                self.donated_names[t.id] = pos
            elif (isinstance(t, ast.Subscript)
                  and isinstance(t.slice, ast.Constant)
                  and isinstance(t.slice.value, str)):
                self.donated_keys[t.slice.value] = pos

    def donated_positions_of_call(self, call):
        f = call.func
        if isinstance(f, ast.Name):
            return self.donated_names.get(f.id)
        if (isinstance(f, ast.Subscript)
                and isinstance(f.slice, ast.Constant)
                and isinstance(f.slice.value, str)):
            return self.donated_keys.get(f.slice.value)
        if isinstance(f, ast.Call) and _call_name(f) in ("jit", "pjit"):
            for kw in f.keywords:
                if kw.arg == "donate_argnums":
                    return self._donate_positions(kw.value, f)
        return None

    # -- traced-set discovery ----------------------------------------------
    def _lookup_def(self, name, scope):
        best = None
        for info in self.by_name.get(name, ()):
            s = info["scope"]
            if scope[:len(s)] == s and (
                    best is None or len(s) > len(best["scope"])):
                best = info
        return best

    def _returned_defs(self, factory_info):
        """Nested FunctionDefs (or lambdas) a factory returns."""
        out = []
        fscope = factory_info["scope"] + (factory_info["node"].name,)
        for node in _walk_skip_defs(_body_list(factory_info["node"])):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            v = node.value
            if isinstance(v, ast.Lambda):
                out.append({"node": v, "scope": fscope, "cls": None,
                            "qual": factory_info["qual"] + ".<lambda>"})
            elif isinstance(v, ast.Name):
                info = self._lookup_def(v.id, fscope)
                if info is not None:
                    out.append(info)
        return out

    def _resolve_callable_arg(self, arg, scope, cls):
        """Defs a function-typed argument resolves to."""
        if isinstance(arg, ast.Lambda):
            return [{"node": arg, "scope": scope, "cls": None,
                     "qual": ".".join(scope) + ".<lambda>"}]
        if isinstance(arg, ast.Name):
            info = self._lookup_def(arg.id, scope)
            fac = self.assigned_funcs.get(arg.id)
            # a local `step = self._make_step(...)` assignment SHADOWS a
            # same-named method/outer def: prefer the factory result
            # unless the def is at least as deeply nested as the call
            if info is not None and (fac is None
                                     or len(info["scope"]) >= len(scope)):
                return [info]
            if fac is not None:
                facs = self._resolve_callee(fac, scope, cls)
                out = [d for f in facs for d in self._returned_defs(f)]
                if out:
                    return out
            return [info] if info is not None else []
        if isinstance(arg, ast.Call):
            facs = self._resolve_callee(arg, scope, cls)
            return [d for f in facs for d in self._returned_defs(f)]
        return []

    def _resolve_callee(self, call, scope, cls):
        f = call.func
        if isinstance(f, ast.Name):
            info = self._lookup_def(f.id, scope)
            return [info] if info is not None else []
        if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id == "self" and cls is not None):
            info = self.methods.get((cls, f.attr))
            return [info] if info is not None else []
        return []

    def _enclosing(self, node):
        """(scope, cls) of the def/class region containing ``node``."""
        scope, cls, cur = [], None, self.parents.get(id(node))
        chain = []
        while cur is not None:
            chain.append(cur)
            cur = self.parents.get(id(cur))
        for n in reversed(chain):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope.append(n.name)
                cls = None
            elif isinstance(n, ast.ClassDef):
                cls = n.name
        # method bodies: cls is the class of the nearest enclosing def
        cur, mcls = self.parents.get(id(node)), None
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                p = self.parents.get(id(cur))
                if isinstance(p, ast.ClassDef):
                    mcls = p.name
                break
            cur = self.parents.get(id(cur))
        return tuple(scope), (mcls or cls)

    def _mark_traced(self, info, seeds):
        key = id(info["node"])
        entry = self.traced.get(key)
        if entry is None:
            self.traced[key] = (info, set(seeds))
            return True
        before = len(entry[1])
        entry[1].update(seeds)
        return len(entry[1]) != before

    def discover(self):
        work = []
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node) not in _TRACE_ENTRYPOINTS:
                continue
            scope, cls = self._enclosing(node)
            cands = list(node.args)
            cands += [kw.value for kw in node.keywords
                      if kw.arg in _TRACE_KWARGS]
            for arg in cands:
                for info in self._resolve_callable_arg(arg, scope, cls):
                    seeds = _fn_params(info["node"].args)
                    if self._mark_traced(info, seeds):
                        work.append(info)
        # propagate through direct calls, mapping argument taint onto
        # callee parameters (a literal flag like flat_mode=True must NOT
        # taint — branching on it is static specialization, not a bug)
        guard = 0
        while work and guard < 400:
            guard += 1
            info = work.pop()
            env = self._env_for(info)
            fscope = info["scope"] + (
                getattr(info["node"], "name", "<lambda>"),)
            for node in ast.walk(info["node"]):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if isinstance(f, ast.Name):
                    # same shadowing rules as argument resolution: a
                    # local `step = factory(...)` beats an outer def
                    callees = self._resolve_callable_arg(
                        f, fscope, info["cls"])
                else:
                    callees = self._resolve_callee(node, fscope,
                                                   info["cls"])
                if not callees:
                    continue
                for callee in callees:
                    params = _fn_params(callee["node"].args)
                    seeds = set()
                    for pos, a in enumerate(node.args):
                        if pos < len(params) and env.expr_tainted(a):
                            seeds.add(params[pos])
                    for kw in node.keywords:
                        if kw.arg in params and env.expr_tainted(kw.value):
                            seeds.add(kw.arg)
                    if self._mark_traced(callee, seeds):
                        work.append(callee)

    def _env_for(self, info):
        seeds = set(self.traced.get(id(info["node"]), (None, set()))[1])
        # params of nested traced lambdas share the namespace
        for node in _walk_skip_defs(_body_list(info["node"])):
            if isinstance(node, ast.Lambda) and id(node) in self.traced:
                seeds.update(self.traced[id(node)][1])
        return _TaintEnv(info["node"], seeds, self.imports)

    # -- emission ----------------------------------------------------------
    def emit(self, code, site, line, message):
        sup, why = False, None
        for ln in (line, line - 1):
            codes = self.suppress.get(ln) or {}
            if code in codes:
                sup, why = True, codes[code]
                break
        if not sup and code in self._scope_sup:
            # a directive on (or right above) the enclosing ``def`` line
            # suppresses for the whole closure — the deliberate-bake
            # idiom (optimizer formula appliers) without a comment per
            # flagged line
            sup, why = True, self._scope_sup[code]
        self.diags.append(Diagnostic(
            code, site, message, file=self.filename, line=line,
            suppressed=sup, justification=why))

    # -- rule checks -------------------------------------------------------
    def check_traced(self, info, seeds, rules=None):
        fn = info["node"]
        site = "%s.%s" % (self.module, info["qual"] or "<lambda>")
        self._scope_sup = {}
        for ln in (fn.lineno, fn.lineno - 1):
            self._scope_sup.update(self.suppress.get(ln) or {})
        env = _TaintEnv(fn, seeds, self.imports)
        on = (lambda c: rules is None or c in rules)
        body = _body_list(fn)
        if on("GL301"):
            self._gl301(env, body, site)
        if on("GL302"):
            self._gl302(env, body, site)
        if on("GL303"):
            self._gl303(body, site)
        if on("GL304"):
            self._gl304(env, body, site)
        if on("GL305"):
            self._gl305(env, body, site)
        if on("GL308") and isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._gl308(fn, seeds, site)
        self._scope_sup = {}

    def _gl301(self, env, body, site):
        for node in _walk_skip_defs(body):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (isinstance(f, ast.Attribute)
                    and f.attr in _MATERIALIZE_ATTRS
                    and env.expr_tainted(f.value)):
                self.emit("GL301", site, node.lineno,
                          ".%s() on a traced value forces a host "
                          "round-trip inside the trace — keep it a jax "
                          "value (or hoist the read out of the compiled "
                          "region)" % f.attr)
                continue
            if (isinstance(f, ast.Name) and f.id in _CAST_BUILTINS
                    and not env.is_free(f.id) is False and node.args
                    and f.id not in env.locals
                    and any(env.expr_tainted(a) for a in node.args)):
                self.emit("GL301", site, node.lineno,
                          "%s() on a traced value materializes it on "
                          "the host at trace time" % f.id)
                continue
            dotted = env_canonical = _dotted(f)
            if dotted:
                env_canonical = self.canonical(dotted)
            if (env_canonical and env_canonical[0] == "numpy"
                    and len(env_canonical) > 1
                    and any(env.expr_tainted(a) for a in node.args)):
                self.emit("GL301", site, node.lineno,
                          "%s on a traced value runs on the host (use "
                          "the jnp twin so it stays in the program)"
                          % ".".join(dotted))
            elif (env_canonical == ("jax", "device_get")
                    and any(env.expr_tainted(a) for a in node.args)):
                self.emit("GL301", site, node.lineno,
                          "jax.device_get inside a traced region "
                          "synchronizes the device mid-trace")

    def _static_test(self, env, test):
        """True when every tainted leaf of ``test`` is consumed by a
        host-static predicate: identity (`x is None`), or key/element
        membership with an untainted probe (`name in params`).  Such
        tests branch on Python-level structure, not traced VALUES, and
        are safe under trace."""
        if not env.expr_tainted(test):
            return True
        if isinstance(test, ast.BoolOp):
            return all(self._static_test(env, v) for v in test.values)
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._static_test(env, test.operand)
        if isinstance(test, ast.Compare):
            if all(isinstance(o, (ast.Is, ast.IsNot)) for o in test.ops):
                return True
            if (all(isinstance(o, (ast.In, ast.NotIn)) for o in test.ops)
                    and not env.expr_tainted(test.left)):
                return True
        return False

    def _gl302(self, env, body, site):
        for node in _walk_skip_defs(body):
            if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                test = node.test
            elif isinstance(node, ast.Assert):
                test = node.test
            else:
                continue
            if self._static_test(env, test):
                continue
            if env.expr_tainted(test):
                self.emit("GL302", site, node.lineno,
                          "Python control flow on a traced array value: "
                          "under jit this either fails or silently "
                          "specializes on the trace-time value (use "
                          "jnp.where / lax.cond)")

    def _gl303(self, body, site):
        for node in _walk_skip_defs(body):
            target = None
            if isinstance(node, ast.Call):
                target = _dotted(node.func)
            elif (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)):
                target = _dotted(node.value)
            if not target:
                continue
            canon = self.canonical(target)
            for pre in _NONDET_PREFIXES:
                if canon[:len(pre)] == pre:
                    self.emit("GL303", site, node.lineno,
                              "%s inside a traced closure is read ONCE "
                              "at trace time (and re-read only on "
                              "retrace) — hoist it out of the compiled "
                              "region" % ".".join(target))
                    break

    def _gl304(self, env, body, site):
        for node in _walk_skip_defs(body):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                self.emit("GL304", site, node.lineno,
                          "%s write under trace runs at trace time "
                          "only — the compiled program never repeats "
                          "it" % type(node).__name__.lower())
                continue
            root = None
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, (ast.Subscript, ast.Attribute)):
                        r = t
                        while isinstance(r, (ast.Subscript,
                                             ast.Attribute)):
                            r = r.value
                        if isinstance(r, ast.Name) and env.is_free(r.id):
                            root = r.id
            elif (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _MUTATOR_METHODS
                    and isinstance(node.func.value, ast.Name)
                    and env.is_free(node.func.value.id)):
                root = node.func.value.id
            if root is not None:
                self.emit("GL304", site, node.lineno,
                          "mutation of captured %r under trace happens "
                          "at trace time, not per step — the compiled "
                          "program will not repeat it" % root)

    def _gl305(self, env, body, site):
        for node in _walk_skip_defs(body):
            name, line = None, None
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and env.is_free(node.id)
                    and _HYPER_RE.search(node.id)):
                name, line = node.id, node.lineno
            elif (isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Load)
                    and _HYPER_RE.search(node.attr)
                    and not isinstance(self.parents.get(id(node)),
                                       ast.Call)
                    and not (isinstance(node.value, ast.Name)
                             and node.value.id in env.imports)):
                parent = self.parents.get(id(node))
                if not (isinstance(parent, ast.Call)
                        and parent.func is node):
                    name, line = node.attr, node.lineno
            if name is not None:
                self.emit("GL305", site, line,
                          "hyperparameter %r is closed over as a trace-"
                          "time CONSTANT — changing it later silently "
                          "has no effect on the compiled program (pass "
                          "it as a traced operand, the lr/wd/rescale "
                          "convention)" % name)

    def _gl308(self, fn, seeds, site):
        params = [p for p in _fn_params(fn.args)
                  if p in seeds and not p.startswith("_")]
        loads = {p: [] for p in params}
        for node in _walk_skip_defs(_body_list(fn)):
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in loads):
                loads[node.id].append(node)
        for p, uses in loads.items():
            if not uses:
                continue
            shape_only = True
            for u in uses:
                parent = self.parents.get(id(u))
                if (isinstance(parent, ast.Attribute)
                        and parent.attr in ("shape", "dtype", "ndim",
                                            "size")):
                    continue
                if (isinstance(parent, ast.Call)
                        and isinstance(parent.func, ast.Name)
                        and parent.func.id == "len"):
                    continue
                shape_only = False
                break
            if shape_only:
                self.emit("GL308", site, fn.lineno,
                          "traced parameter %r is used only for its "
                          "shape/dtype — a shape-polymorphic input with "
                          "no value use retraces per shape for data it "
                          "never reads (make it a static argument)"
                          % p)

    # -- module-wide rule (GL306) ------------------------------------------
    def _stmt_blocks(self, fn):
        """Every statement list in ``fn`` + stmt -> (block, idx) map."""
        blocks, pos = [], {}
        stack = [fn]
        while stack:
            node = stack.pop()
            for field in ("body", "orelse", "finalbody"):
                sub = getattr(node, field, None)
                if (isinstance(sub, list) and sub
                        and isinstance(sub[0], ast.stmt)):
                    blocks.append((sub, node))
                    for i, s in enumerate(sub):
                        pos[id(s)] = (sub, i, node)
                    stack.extend(
                        s for s in sub
                        if not isinstance(s, (ast.FunctionDef,
                                              ast.AsyncFunctionDef)))
            for h in getattr(node, "handlers", ()) or ():
                stack.append(h)
        return blocks, pos

    def _gl306(self):
        if not (self.donated_names or self.donated_keys):
            return
        for info in self.defs:
            fn = info["node"]
            site = "%s.%s" % (self.module, info["qual"])
            _blocks, pos = self._stmt_blocks(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                dpos = self.donated_positions_of_call(node)
                if not dpos:
                    continue
                dnames = {a.id for p, a in enumerate(node.args)
                          if p in dpos and isinstance(a, ast.Name)}
                if not dnames:
                    continue
                # the statement holding the call, then every LATER
                # statement of its block and of each ancestor block
                stmt = node
                while id(stmt) not in pos and id(stmt) in self.parents:
                    stmt = self.parents[id(stmt)]
                while id(stmt) in pos:
                    block, idx, owner = pos[id(stmt)]
                    for later in block[idx + 1:]:
                        for n in ast.walk(later):
                            if (isinstance(n, ast.Name)
                                    and isinstance(n.ctx, ast.Load)
                                    and n.id in dnames):
                                self.emit(
                                    "GL306", site, n.lineno,
                                    "%r was DONATED at line %d — XLA "
                                    "aliased its buffer for an output; "
                                    "this read sees freed memory on "
                                    "real hardware" % (n.id,
                                                       node.lineno))
                    stmt = owner
                    if isinstance(stmt, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        break

    # -- driver ------------------------------------------------------------
    def run(self, skip_registered=True):
        self.discover()
        for _key, (info, seeds) in sorted(
                self.traced.items(),
                key=lambda kv: kv[1][0]["node"].lineno):
            if skip_registered and self._is_registered(info["node"]):
                continue          # fcomputes are linted by lint_registry
            self.check_traced(info, seeds)
        self._gl306()
        return self._dedup(self.diags)

    def _is_registered(self, fn_node):
        for dec in getattr(fn_node, "decorator_list", ()) or ():
            d = dec.func if isinstance(dec, ast.Call) else dec
            name = _call_name(d) if isinstance(d, ast.Call) else (
                d.attr if isinstance(d, ast.Attribute)
                else getattr(d, "id", None))
            if name and "register" in name:
                return True
        return False

    @staticmethod
    def _dedup(diags):
        seen, out = set(), []
        for d in diags:
            key = (d.code, d.file, d.line, d.op_name)
            if key in seen:
                continue
            seen.add(key)
            out.append(d)
        return out


# ---------------------------------------------------------------------------
# static pass: public entry points
# ---------------------------------------------------------------------------

def lint_source(source, filename="<memory>", module=None):
    """Lint one source string (fixture tests, editor integration)."""
    module = module or os.path.splitext(os.path.basename(filename))[0]
    try:
        scan = _ModuleScan(source, filename, module)
    except SyntaxError:
        return []
    return scan.run()


def lint_file(path):
    with open(path) as f:
        return lint_source(f.read(), filename=path)


def lint_package(root=None):
    """GL3xx over every .py file in the package (serving/, armor/,
    parallel/ and everything else os.walk finds — the same walk the
    GL2xx pass uses, nothing opts out)."""
    root = root or package_root()
    diags = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path) as f:
                    source = f.read()
            except OSError:
                continue
            rel = os.path.relpath(path, os.path.dirname(root))
            diags.extend(lint_source(source, filename=path,
                                     module=rel[:-3].replace(os.sep,
                                                             ".")))
    return diags


# fcomputes already answer to GL108 for np.random/time/os.environ
# impurity, so registry mode runs only the rules GL1xx cannot express:
# materialization and control flow on the op's TRACED inputs
_REGISTRY_RULES = frozenset({"GL301", "GL302"})


def _array_param_seeds(args_node):
    """Taint seeds for an unnamed variadic fcompute: required params and
    None-default optionals are the arrays (``bias=None``); params with a
    bool/number/tuple default are host-side config (``axis=0``,
    ``no_bias=False``) and must NOT be seeded."""
    pos = list(getattr(args_node, "posonlyargs", ())) + list(args_node.args)
    defaults = list(args_node.defaults)
    first_def = len(pos) - len(defaults)
    seeds = set()
    for i, a in enumerate(pos):
        if i < first_def:
            seeds.add(a.arg)
        else:
            d = defaults[i - first_def]
            if isinstance(d, ast.Constant) and d.value is None:
                seeds.add(a.arg)
    for a, d in zip(args_node.kwonlyargs, args_node.kw_defaults):
        if d is None or (isinstance(d, ast.Constant) and d.value is None):
            seeds.add(a.arg)
    if args_node.vararg is not None:
        seeds.add(args_node.vararg.arg)
    return seeds


def lint_registry(names=None):
    """GL3xx over the live op registry: taint is seeded from the first
    ``num_inputs`` positional parameters (the traced arrays), so host
    kwargs like ``axis``/``is_train`` never false-positive."""
    from ..ops.registry import _REGISTRY
    diags, seen = [], set()
    for name in sorted(_REGISTRY):
        if names is not None and name not in names:
            continue
        op = _REGISTRY[name]
        if id(op) in seen:
            continue
        seen.add(id(op))
        fcompute = getattr(op, "fcompute", None)
        if fcompute is None:
            continue
        fn_node = _fcompute_tree(fcompute)
        if fn_node is None:
            continue
        params = _fn_params(fn_node.args)
        n = op.num_inputs if isinstance(op.num_inputs, int) else None
        if n is not None:
            seeds = set(params[:n])
        else:
            inames = getattr(op, "input_names", None)
            if inames:
                seeds = set(inames) & set(params)
            else:
                seeds = _array_param_seeds(fn_node.args)
        code = getattr(fcompute, "__code__", None)
        fname = code.co_filename if code else None
        line = code.co_firstlineno if code else None
        sup = suppressions_for(fcompute)
        scan = _ModuleScan("", fname or "<builtin>", "ops")
        scan.parents = {id(c): p for p in ast.walk(fn_node)
                        for c in ast.iter_child_nodes(p)}
        info = {"node": fn_node, "scope": (), "cls": None,
                "qual": fn_node.name}
        scan.check_traced(info, seeds, rules=_REGISTRY_RULES)
        for d in scan.diags:
            why = sup.get(d.code)
            diags.append(Diagnostic(
                d.code, name,
                "%s (line +%d)" % (d.message, d.line - fn_node.lineno),
                file=fname, line=line,
                suppressed=d.code in sup, justification=why))
    return _ModuleScan._dedup(diags)


def lint_callable(fn, taint_params=None, rules=None):
    """Lint one live function the way the package pass would lint a
    traced closure (a user's loss or step function, before it is
    handed to ``jax.jit``)."""
    import inspect
    import textwrap
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return []
    try:
        tree = ast.parse(src)
    except (SyntaxError, IndentationError):
        return []
    fn_node = None
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_node = node
            break
    if fn_node is None:
        return []
    code = getattr(fn, "__code__", None)
    scan = _ModuleScan(src, code.co_filename if code else "<callable>",
                       getattr(fn, "__module__", None) or "<callable>")
    seeds = set(taint_params if taint_params is not None
                else _fn_params(fn_node.args))
    info = {"node": fn_node, "scope": (), "cls": None,
            "qual": fn_node.name}
    scan.check_traced(info, seeds, rules=rules)
    return scan._dedup(scan.diags)


# ---------------------------------------------------------------------------
# selftest: every GL301-GL308 through its fixture, and the tree itself
# ---------------------------------------------------------------------------

_GL_FIXTURES = {
    # code -> (bad source, clean source)
    "GL301": (
        "import jax\n"
        "def step(f):\n"
        "    def loss(x):\n"
        "        return float(x.sum()) + x.asnumpy().mean()\n"
        "    return jax.jit(loss)\n",
        "import jax\n"
        "def step(f):\n"
        "    def loss(x):\n"
        "        return x.sum() * 2\n"
        "    return jax.jit(loss)\n"),
    "GL302": (
        "import jax\n"
        "def build():\n"
        "    def f(x):\n"
        "        if x.sum() > 0:\n"
        "            return x\n"
        "        return -x\n"
        "    return jax.jit(f)\n",
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def build():\n"
        "    def f(x):\n"
        "        if x.ndim > 1:\n"
        "            return x\n"
        "        return jnp.where(x > 0, x, -x)\n"
        "    return jax.jit(f)\n"),
    "GL303": (
        "import jax\n"
        "import os\n"
        "def build():\n"
        "    def f(x):\n"
        "        scale = 2.0 if os.environ.get('FAST') else 1.0\n"
        "        return x * scale\n"
        "    return jax.jit(f)\n",
        "import jax\n"
        "import os\n"
        "def build():\n"
        "    scale = 2.0 if os.environ.get('FAST') else 1.0\n"
        "    def f(x):\n"
        "        return x * scale\n"
        "    return jax.jit(f)\n"),
    "GL304": (
        "import jax\n"
        "def build():\n"
        "    seen = []\n"
        "    def f(x):\n"
        "        seen.append(1)\n"
        "        return x * 2\n"
        "    return jax.jit(f)\n",
        "import jax\n"
        "def build():\n"
        "    def f(x):\n"
        "        seen = []\n"
        "        seen.append(1)\n"
        "        return x * 2\n"
        "    return jax.jit(f)\n"),
    "GL305": (
        "import jax\n"
        "def build(lr):\n"
        "    def update(w, g):\n"
        "        return w - lr * g\n"
        "    return jax.jit(update)\n",
        "import jax\n"
        "def build():\n"
        "    def update(w, g, lr):\n"
        "        return w - lr * g\n"
        "    return jax.jit(update)\n"),
    "GL306": (
        "import jax\n"
        "def run(f, w, s, x):\n"
        "    prog = jax.jit(f, donate_argnums=(0, 1))\n"
        "    out = prog(w, s, x)\n"
        "    stale = w.sum()\n"
        "    return out, stale\n",
        "import jax\n"
        "def run(f, w, s, x):\n"
        "    prog = jax.jit(f, donate_argnums=(0, 1))\n"
        "    out = prog(w, s, x)\n"
        "    return out, x.sum()\n"),
    "GL308": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def build():\n"
        "    def f(x, template):\n"
        "        return x.reshape(template.shape[0], -1)\n"
        "    return jax.jit(f)\n",
        "import jax\n"
        "def build():\n"
        "    def f(x, template):\n"
        "        return x.reshape(template.shape[0], -1) + template\n"
        "    return jax.jit(f)\n"),
}


def _codes(diags, active_only=True):
    return sorted({d.code for d in diags
                   if not (active_only and d.suppressed)})


def selftest(verbose=False):
    """Returns a list of problems — empty means pass."""
    problems = []

    # ---- static: every rule's bad fixture fires, its clean twin doesn't
    for code, (bad, good) in sorted(_GL_FIXTURES.items()):
        got = _codes(lint_source(bad, filename="fixture_%s.py" % code))
        if code not in got:
            problems.append("%s: bad fixture produced %s (expected %s)"
                            % (code, got or "nothing", code))
        got_clean = _codes(lint_source(good,
                                       filename="fixture_%s_ok.py"
                                       % code))
        if code in got_clean:
            problems.append("%s: clean fixture still fires (%s)"
                            % (code, got_clean))
        if verbose:
            print("static %s: bad=%s clean=%s" % (code, got, got_clean))

    # ---- static: suppression honored
    sup_src = _GL_FIXTURES["GL304"][0].replace(
        "seen.append(1)",
        "seen.append(1)  # graftlint: disable=GL304 -- trace-time memo")
    sup = lint_source(sup_src, filename="fixture_sup.py")
    if any(d.code == "GL304" and not d.suppressed for d in sup):
        problems.append("suppression comment was not honored")
    if not any(d.code == "GL304" and d.suppressed
               and d.justification for d in sup):
        problems.append("suppressed finding lost its justification")

    # ---- static: the repo itself is clean (package walk + registry)
    import incubator_mxnet_tpu  # noqa: F401  (registers the op registry)
    pkg = [d for d in lint_package() if not d.suppressed]
    if pkg:
        problems.append("package pass not clean: %s"
                        % "; ".join(repr(d) for d in pkg[:8]))
    reg = [d for d in lint_registry() if not d.suppressed]
    if reg:
        problems.append("registry pass not clean: %s"
                        % "; ".join(repr(d) for d in reg[:8]))

    return problems


def main(argv=None):
    import argparse
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser(
        prog="python -m incubator_mxnet_tpu.analysis.compile_safety",
        description="graftguard compile-safety lint selftest")
    ap.add_argument("--selftest", action="store_true",
                    help="force every GL3xx diagnostic through its "
                         "fixture, then lint the tree (CI tier)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    if not args.selftest:
        ap.print_help()
        return 2
    problems = selftest(verbose=args.verbose)
    if problems:
        for p in problems:
            print("graftguard selftest FAIL: %s" % p, file=sys.stderr)
        return 1
    print("graftguard selftest OK (GL301-GL308 fixtures + clean twins, "
          "suppression flow, repo package+registry clean)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
