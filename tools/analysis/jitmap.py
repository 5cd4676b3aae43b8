"""Jit-boundary inference + taint propagation from traced arguments.

:class:`JitMap` answers "which functions execute under a JAX trace?" for the
whole project:

* **directly traced** — decorated with ``jax.jit`` / ``pjit`` / ``shard_map``
  (bare, factory-call, or through ``functools.partial``), or passed to a
  wrapper call form (``jax.jit(fn)``, ``shard_map(fn, ...)``) or a
  control-flow combinator (``lax.scan/cond/while_loop/fori_loop``,
  ``vmap``/``grad``/``remat``). ``static_argnums``/``static_argnames`` are
  parsed so static parameters are excluded from taint seeding.
* **nested** — a ``def`` inside a traced function body runs at trace time.
* **reachable** — a project function called from a traced region is traced
  too, transitively (the call-edge propagation the ISSUE asks for). Calls
  routed through ``jax.pure_callback``/``io_callback``/``debug.callback``
  are host escapes and do NOT propagate.

:class:`TaintWalker` is the shared dataflow pass: starting from tainted
parameter names it walks one function body in statement order (loop bodies
twice, for loop-carried taint) and reports *sink* events — Python casts,
``.item()``, ``np.asarray``, data-dependent ``if``/``while`` — through a
callback, plus the per-call-site argument taint the trace-safety analyzer
uses for its interprocedural fixpoint.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

from .core import FunctionInfo, Project, SourceFile, dotted_name

# canonical-name tests -------------------------------------------------------

_JIT_EXACT = {"jit", "pjit", "shard_map"}
_JIT_SUFFIX = (".jit", ".pjit", ".shard_map")

#: wrapper call -> positional indices of the function arguments it traces
_COMBINATOR_ARGS = {
    ".scan": (0,), ".cond": (1, 2), ".while_loop": (0, 1),
    ".fori_loop": (2,), ".vmap": (0,), ".grad": (0,),
    ".value_and_grad": (0,), ".remat": (0,), ".checkpoint": (0,),
    ".custom_vjp": (0,), ".custom_jvp": (0,), ".pmap": (0,),
}

_PARTIAL = {"functools.partial", "partial"}

#: a call through these is a deliberate host escape — do not propagate trace
_HOST_ESCAPES = ("pure_callback", "io_callback", "debug.callback",
                 "debug.print", "host_callback")

#: jax entry points that return host Python values (metadata / environment
#: queries), not traced arrays — exempt from the "jax calls yield tracers
#: under omnistaging" rule below
_JAX_HOST_FUNCS = {
    "jax.numpy.issubdtype", "jax.numpy.result_type", "jax.numpy.iinfo",
    "jax.numpy.finfo", "jax.numpy.ndim", "jax.numpy.shape",
    "jax.dtypes.issubdtype", "jax.dtypes.result_type",
    "jax.dtypes.canonicalize_dtype", "jax.default_backend",
    "jax.device_count", "jax.local_device_count", "jax.devices",
    "jax.local_devices", "jax.process_index", "jax.process_count",
    "jax.eval_shape", "jax.ShapeDtypeStruct", "jax.tree_util.tree_structure",
}

#: attributes of a traced value that are static (trace-time Python values)
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "aval", "sharding",
                 "weak_type"}

#: methods on a traced value that force a host sync / concretization
SYNC_METHODS = {"item", "tolist", "block_until_ready", "__bool__",
                "__int__", "__float__"}

#: numpy entry points that concretize a traced argument
NUMPY_SINKS = {"numpy.asarray", "numpy.array", "numpy.ascontiguousarray",
               "numpy.asfortranarray", "numpy.copy", "numpy.float32",
               "numpy.float64", "numpy.int32", "numpy.int64", "numpy.bool_",
               "numpy.save", "numpy.savez"}


def is_jit_like(canonical: Optional[str]) -> bool:
    if not canonical:
        return False
    return canonical in _JIT_EXACT or canonical.endswith(_JIT_SUFFIX)


def combinator_fn_args(canonical: Optional[str]) -> Optional[Tuple[int, ...]]:
    """Positional fn-arg indices if ``canonical`` is a tracing combinator."""
    if not canonical:
        return None
    # builtin map()/filter() must not match ".map"-style suffixes
    if "." not in canonical:
        return None
    for suffix, idxs in _COMBINATOR_ARGS.items():
        if canonical.endswith(suffix):
            return idxs
    return None


def is_host_escape(canonical: Optional[str]) -> bool:
    return bool(canonical) and any(h in canonical for h in _HOST_ESCAPES)


@dataclass
class TracedInfo:
    """Why one function is considered traced."""
    func: FunctionInfo
    reason: str                      # human-readable chain
    direct: bool                     # carries its own jit boundary
    static_params: Set[str] = field(default_factory=set)


def _param_names(node: ast.AST) -> List[str]:
    a = node.args
    return [p.arg for p in (a.posonlyargs + a.args)]


def _static_params_from_kwargs(keywords, params: List[str]) -> Set[str]:
    out: Set[str] = set()
    for kw in keywords or ():
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    out.add(n.value)
        elif kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    if 0 <= n.value < len(params):
                        out.add(params[n.value])
    return out


class JitMap:
    """Traced-function map for a whole project."""

    def __init__(self, project: Project,
                 roots: Optional[List[SourceFile]] = None):
        self.project = project
        self.traced: Dict[str, TracedInfo] = {}
        self.escaped: Set[str] = self._find_escaped()
        scope = roots if roots is not None else project.files
        for sf in scope:
            self._mark_decorated(sf)
            self._mark_call_forms(sf)
        self._mark_nested()
        self._propagate(scope)

    # -- host-escape inference --------------------------------------------
    def _find_escaped(self) -> Set[str]:
        """Functions that run OUTSIDE any ambient trace.

        ``jax.ensure_compile_time_eval()`` escapes the surrounding trace, so
        (a) a function whose body contains that with-block is an *escape
        provider*, and (b) a function decorated with an escape provider
        (a decorator whose wrapper enters the context manager) runs its
        body eagerly. Neither should be
        marked traced, and call edges must not propagate through them.
        """
        providers: Set[str] = set()
        for sf in self.project.files:
            for qual, info in sf.symbols.functions.items():
                for n in ast.walk(info.node):
                    if isinstance(n, ast.Call):
                        name = dotted_name(n.func)
                        if name and name.endswith("ensure_compile_time_eval"):
                            providers.add(info.full_name)
                            break
                else:
                    continue
                break
        escaped = set(providers)
        for sf in self.project.files:
            for info in sf.symbols.functions.values():
                for dec in getattr(info.node, "decorator_list", ()):
                    if isinstance(dec, ast.Call):
                        dec = dec.func
                    canon = self.project.canonical(sf, dotted_name(dec))
                    if canon in providers:
                        escaped.add(info.full_name)
        return escaped

    # -- direct boundaries ------------------------------------------------
    def _mark(self, info: FunctionInfo, reason: str, direct: bool,
              static_params: Optional[Set[str]] = None) -> None:
        if info.full_name in self.escaped:
            return
        cur = self.traced.get(info.full_name)
        if cur is not None and (cur.direct or not direct):
            return
        self.traced[info.full_name] = TracedInfo(
            func=info, reason=reason, direct=direct,
            static_params=set(static_params or ()))

    def _mark_decorated(self, sf: SourceFile) -> None:
        for info in sf.symbols.functions.values():
            node = info.node
            for dec in getattr(node, "decorator_list", ()):
                params = _param_names(node)
                if isinstance(dec, ast.Call):
                    fn_canon = self.project.canonical(sf, dotted_name(
                        dec.func))
                    if fn_canon in _PARTIAL and dec.args:
                        inner = self.project.canonical(
                            sf, dotted_name(dec.args[0]))
                        if is_jit_like(inner):
                            self._mark(info, f"@partial({inner}, ...)", True,
                                       _static_params_from_kwargs(
                                           dec.keywords, params))
                    elif is_jit_like(fn_canon):
                        self._mark(info, f"@{fn_canon}(...)", True,
                                   _static_params_from_kwargs(dec.keywords,
                                                              params))
                else:
                    canon = self.project.canonical(sf, dotted_name(dec))
                    if is_jit_like(canon) or combinator_fn_args(canon):
                        self._mark(info, f"@{canon}", True)

    def _local_functions_named(self, sf: SourceFile,
                               name: str) -> List[FunctionInfo]:
        return [i for q, i in sf.symbols.functions.items()
                if q.split(".")[-1] == name]

    def _mark_call_forms(self, sf: SourceFile) -> None:
        for call in ast.walk(sf.tree):
            if not isinstance(call, ast.Call):
                continue
            canon = self.project.canonical(sf, dotted_name(call.func))
            fn_idxs: Tuple[int, ...] = ()
            static: Set[str] = set()
            if is_jit_like(canon):
                fn_idxs = (0,)
            else:
                idxs = combinator_fn_args(canon)
                if idxs:
                    fn_idxs = idxs
            for i in fn_idxs:
                if i >= len(call.args):
                    continue
                arg = call.args[i]
                if isinstance(arg, ast.Name):
                    for info in self._local_functions_named(sf, arg.id):
                        sp = (_static_params_from_kwargs(
                            call.keywords, _param_names(info.node))
                            if is_jit_like(canon) else set())
                        self._mark(info, f"{canon}({arg.id}, ...)", True, sp)

    def _mark_nested(self) -> None:
        # a def inside a traced function body runs at trace time
        for sf in self.project.files:
            prefixes = [q for q, i in sf.symbols.functions.items()
                        if i.full_name in self.traced]
            for qual, info in sf.symbols.functions.items():
                if info.full_name in self.traced:
                    continue
                for p in prefixes:
                    if qual.startswith(p + "."):
                        self._mark(info, f"defined inside traced {p}", False)
                        break

    # -- call-edge propagation --------------------------------------------
    def resolve_callee(self, sf: SourceFile, info: Optional[FunctionInfo],
                       call: ast.Call) -> Optional[FunctionInfo]:
        """Project-internal FunctionInfo a call refers to, or None."""
        name = dotted_name(call.func)
        if name is None:
            return None
        # lexically-scoped lookup: a bare name called inside a (possibly
        # nested) function resolves innermost-first within this module
        if "." not in name:
            parts = info.qualname.split(".") if info is not None else []
            for cut in range(len(parts), -1, -1):
                target = sf.symbols.functions.get(
                    ".".join(parts[:cut] + [name]))
                if target is not None:
                    return target
        # self.method() / cls.method() within the same class
        head, _, rest = name.partition(".")
        if (info is not None and info.class_name and rest and "." not in rest
                and head in ("self", "cls")):
            target = sf.symbols.functions.get(f"{info.class_name}.{rest}")
            if target is not None:
                return target
        canon = self.project.canonical(sf, name)
        if not canon:
            return None
        # longest module prefix wins: "pkg.mod.Class.method" etc.
        parts = canon.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            mod = ".".join(parts[:cut])
            target_sf = self.project.by_module.get(mod)
            if target_sf is None:
                continue
            qual = ".".join(parts[cut:])
            target = target_sf.symbols.functions.get(qual)
            if target is None and "." not in qual:
                # constructor call or bare function defined deeper
                cands = self._local_functions_named(target_sf, qual)
                target = cands[0] if len(cands) == 1 else None
            return target
        return None

    def _calls_in_body(self, info: FunctionInfo) -> List[ast.Call]:
        """Calls lexically in this function, excluding nested defs (those
        are separate functions, marked by _mark_nested)."""
        out: List[ast.Call] = []
        nested: List[ast.AST] = []

        def visit(node, top=False):
            if not top and isinstance(node, (ast.FunctionDef,
                                             ast.AsyncFunctionDef)):
                nested.append(node)
                return
            if isinstance(node, ast.Call):
                out.append(node)
            for child in ast.iter_child_nodes(node):
                visit(child)

        visit(info.node, top=True)
        return out

    def _propagate(self, scope: List[SourceFile]) -> None:
        by_full: Dict[str, Tuple[SourceFile, FunctionInfo]] = {}
        for sf in self.project.files:
            for info in sf.symbols.functions.values():
                by_full[info.full_name] = (sf, info)
        work = list(self.traced)
        while work:
            full = work.pop()
            entry = by_full.get(full)
            if entry is None:
                continue
            sf, info = entry
            for call in self._calls_in_body(info):
                canon = self.project.canonical(sf, dotted_name(call.func))
                if is_host_escape(canon):
                    continue
                callee = self.resolve_callee(sf, info, call)
                if callee is None or callee.full_name in self.traced \
                        or callee.full_name in self.escaped:
                    continue
                chain = self.traced[full].reason
                # keep the ROOT boundary, not the whole hop chain
                root = (chain if chain.startswith("called from traced via ")
                        else f"called from traced via {full} ({chain})")
                self._mark(callee, root, False)
                work.append(callee.full_name)

    def is_traced(self, full_name: str) -> bool:
        return full_name in self.traced


# -- taint dataflow -----------------------------------------------------------

#: sink kinds reported to the callback
SINK_CAST = "cast"          # bool()/int()/float() on a traced value
SINK_METHOD = "method"      # .item()/.tolist()/... on a traced value
SINK_NUMPY = "numpy"        # np.asarray/np.array/... on a traced value
SINK_BRANCH = "branch"      # if/while/assert on a traced value

_CAST_FUNCS = {"bool", "int", "float", "complex"}


class TaintWalker:
    """Single-function forward taint pass.

    ``on_sink(kind, node, detail)`` fires for each hazard site; call-site
    argument taints for project-internal callees are accumulated in
    ``self.callee_arg_taint`` ({callee full_name: set of tainted param
    names}) for the interprocedural fixpoint.
    """

    def __init__(self, project: Project, sf: SourceFile, info: FunctionInfo,
                 seeds: Set[str], jitmap: JitMap,
                 on_sink: Optional[Callable] = None,
                 fn_return_taint: Optional[Dict[str, object]] = None):
        self.project = project
        self.sf = sf
        self.info = info
        self.jitmap = jitmap
        self.on_sink = on_sink or (lambda *a: None)
        self.env: Set[str] = set(seeds)
        self.callee_arg_taint: Dict[str, Set[str]] = {}
        #: {callee full_name: bool or per-tuple-element [bool]} — computed
        #: return taints from earlier fixpoint rounds (interprocedural
        #: precision: `a, b, static = f(x)` taints only the traced elements)
        self.fn_return_taint = fn_return_taint or {}
        #: this function's own return taint after run(): None/bool/[bool]
        self.returns: object = None
        self._reported: Set[Tuple[str, int, int]] = set()

    # -- public --
    def run(self) -> None:
        body = list(getattr(self.info.node, "body", ()))
        # two passes: loop-carried assignments reach taint fixpoint for the
        # patterns that matter (x = f(x) inside for/while)
        for _ in range(2):
            for stmt in body:
                self._stmt(stmt)

    # -- helpers --
    def _sink(self, kind: str, node: ast.AST, detail: str) -> None:
        key = (kind, node.lineno, node.col_offset)
        if key in self._reported:
            return
        self._reported.add(key)
        self.on_sink(kind, node, detail)

    def _canon(self, node: ast.AST) -> Optional[str]:
        return self.project.canonical(self.sf, dotted_name(node))

    def _bind(self, target: ast.AST, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            (self.env.add if tainted else self.env.discard)(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt.value if isinstance(elt, ast.Starred)
                           else elt, tainted)
        # attribute/subscript stores don't track

    # -- statements --
    def _stmt(self, node: ast.AST) -> None:
        meth = getattr(self, "_stmt_" + type(node).__name__, None)
        if meth is not None:
            meth(node)
        else:
            # default: evaluate embedded expressions for sinks
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.expr):
                    self._taint(child)

    def _stmt_Assign(self, node: ast.Assign) -> None:
        vec = self._call_return_vec(node.value)
        t = self._taint(node.value)
        if (vec is not None and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Tuple)
                and len(node.targets[0].elts) == len(vec)
                and not any(isinstance(e, ast.Starred)
                            for e in node.targets[0].elts)):
            for elt, tv in zip(node.targets[0].elts, vec):
                self._bind(elt, tv)
            return
        for target in node.targets:
            self._bind(target, t)

    def _call_return_vec(self, node: ast.AST) -> Optional[List[bool]]:
        """Per-element return taint when ``node`` is a call to a function
        whose returns are a tuple with known element taints."""
        if not isinstance(node, ast.Call):
            return None
        callee = self.jitmap.resolve_callee(self.sf, self.info, node)
        if callee is None:
            return None
        rt = self.fn_return_taint.get(callee.full_name)
        return rt if isinstance(rt, list) else None

    def _stmt_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._bind(node.target, self._taint(node.value))

    def _stmt_AugAssign(self, node: ast.AugAssign) -> None:
        t = self._taint(node.value)
        if isinstance(node.target, ast.Name):
            if t:
                self.env.add(node.target.id)

    def _stmt_Expr(self, node: ast.Expr) -> None:
        self._taint(node.value)

    def _stmt_Return(self, node: ast.Return) -> None:
        if node.value is None:
            return
        if isinstance(node.value, ast.Tuple) and not any(
                isinstance(e, ast.Starred) for e in node.value.elts):
            got: object = [self._taint(e) for e in node.value.elts]
        else:
            got = self._taint(node.value)
        self._merge_return(got)

    def _merge_return(self, got: object) -> None:
        cur = self.returns
        if cur is None:
            self.returns = got
        elif (isinstance(cur, list) and isinstance(got, list)
                and len(cur) == len(got)):
            self.returns = [a or b for a, b in zip(cur, got)]
        else:
            def _any(v):
                return any(v) if isinstance(v, list) else bool(v)
            self.returns = _any(cur) or _any(got)

    def _stmt_If(self, node: ast.If) -> None:
        if self._taint(node.test):
            self._sink(SINK_BRANCH, node.test,
                       "Python `if` on a value derived from traced "
                       "arguments")
        for stmt in node.body + node.orelse:
            self._stmt(stmt)

    def _stmt_While(self, node: ast.While) -> None:
        if self._taint(node.test):
            self._sink(SINK_BRANCH, node.test,
                       "Python `while` on a value derived from traced "
                       "arguments")
        for stmt in node.body + node.orelse:
            self._stmt(stmt)

    def _stmt_Assert(self, node: ast.Assert) -> None:
        if self._taint(node.test):
            self._sink(SINK_BRANCH, node.test,
                       "`assert` on a value derived from traced arguments")

    def _stmt_For(self, node: ast.For) -> None:
        self._bind(node.target, self._taint(node.iter))
        for stmt in node.body + node.orelse:
            self._stmt(stmt)

    def _stmt_With(self, node: ast.With) -> None:
        for item in node.items:
            t = self._taint(item.context_expr)
            if item.optional_vars is not None:
                self._bind(item.optional_vars, t)
        for stmt in node.body:
            self._stmt(stmt)

    def _stmt_Try(self, node: ast.Try) -> None:
        for stmt in node.body + node.orelse + node.finalbody:
            self._stmt(stmt)
        for h in node.handlers:
            for stmt in h.body:
                self._stmt(stmt)

    def _stmt_Delete(self, node: ast.Delete) -> None:
        for t in node.targets:
            if isinstance(t, ast.Name):
                self.env.discard(t.id)

    def _stmt_FunctionDef(self, node) -> None:
        pass          # nested defs are analyzed as their own functions
    _stmt_AsyncFunctionDef = _stmt_ClassDef = _stmt_FunctionDef

    # -- expressions (returns: is the value traced?) --
    def _taint(self, node: Optional[ast.AST]) -> bool:
        if node is None:
            return False
        meth = getattr(self, "_taint_" + type(node).__name__, None)
        if meth is not None:
            return meth(node)
        # conservative default: tainted if any child expression is
        out = False
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                out |= self._taint(child)
        return out

    def _taint_Name(self, node: ast.Name) -> bool:
        return node.id in self.env

    def _taint_Constant(self, node: ast.Constant) -> bool:
        return False

    def _taint_JoinedStr(self, node: ast.JoinedStr) -> bool:
        for v in node.values:
            self._taint(v)       # f-string of a tracer: visit for sinks
        return False

    def _taint_Lambda(self, node: ast.Lambda) -> bool:
        return False

    def _taint_Attribute(self, node: ast.Attribute) -> bool:
        base = self._taint(node.value)
        if node.attr in _STATIC_ATTRS:
            return False         # x.shape / x.dtype are trace-time static
        return base

    def _taint_Subscript(self, node: ast.Subscript) -> bool:
        return self._taint(node.value) or self._taint(node.slice)

    def _taint_Compare(self, node: ast.Compare) -> bool:
        operands = self._taint(node.left)
        for c in node.comparators:
            operands |= self._taint(c)
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return False         # identity tests yield host bools
        return operands

    def _taint_BoolOp(self, node: ast.BoolOp) -> bool:
        return any([self._taint(v) for v in node.values])

    def _taint_IfExp(self, node: ast.IfExp) -> bool:
        if self._taint(node.test):
            self._sink(SINK_BRANCH, node.test,
                       "conditional expression on a value derived from "
                       "traced arguments")
        return self._taint(node.body) | self._taint(node.orelse)

    def _taint_Call(self, node: ast.Call) -> bool:
        arg_taints = [self._taint(a) for a in node.args]
        kw_taints = [self._taint(kw.value) for kw in node.keywords]
        any_tainted = any(arg_taints) or any(kw_taints)
        canon = self._canon(node.func)

        # sinks -----------------------------------------------------------
        if canon in _CAST_FUNCS and any_tainted:
            self._sink(SINK_CAST, node,
                       f"`{canon}()` on a value derived from traced "
                       "arguments forces a host sync (ConcretizationError "
                       "under jit)")
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in SYNC_METHODS \
                and self._taint(node.func.value):
            self._sink(SINK_METHOD, node,
                       f"`.{node.func.attr}()` on a value derived from "
                       "traced arguments forces a host sync")
        if canon in NUMPY_SINKS and any_tainted:
            self._sink(SINK_NUMPY, node,
                       f"`{canon.replace('numpy', 'np')}()` on a value "
                       "derived from traced arguments (TracerArray"
                       "ConversionError under jit)")

        # call-site argument taint for interprocedural propagation ---------
        callee = self.jitmap.resolve_callee(self.sf, self.info, node)
        if callee is not None:
            params = _param_names(callee.node)
            if params and params[0] in ("self", "cls") \
                    and isinstance(node.func, ast.Attribute):
                params = params[1:]
            tainted_params = self.callee_arg_taint.setdefault(
                callee.full_name, set())
            for i, t in enumerate(arg_taints):
                if t and i < len(params):
                    tainted_params.add(params[i])
            for kw, t in zip(node.keywords, kw_taints):
                if t and kw.arg:
                    tainted_params.add(kw.arg)

        # result taint ------------------------------------------------------
        if callee is not None and callee.full_name in self.fn_return_taint:
            rt = self.fn_return_taint[callee.full_name]
            return any(rt) if isinstance(rt, list) else bool(rt)
        if callee is not None \
                and callee.full_name in self.jitmap.escaped:
            return False         # runs under ensure_compile_time_eval
        if canon:
            if canon in _JAX_HOST_FUNCS or canon.startswith("jax._src."):
                return False     # metadata / backend plumbing: host values
            if canon.startswith(("jax.", "jax")) and not is_host_escape(
                    canon):
                # under omnistaging EVERY jnp/lax op inside a trace stages
                # into it, even on fresh concrete operands (the docstring of
                # ops/hist_kernel._eager_selftest records the observed
                # failure)
                return True
            if canon in {"len", "isinstance", "hasattr", "id", "type",
                         "repr", "str", "print", "range", "enumerate"}:
                return False
            if canon in _CAST_FUNCS:
                return False     # flagged above; result is a host scalar
        if isinstance(node.func, ast.Attribute) and node.func.attr in (
                "shape", "keys", "values", "items"):
            return any_tainted
        # method call on a tainted object, or any tainted argument
        if isinstance(node.func, ast.Attribute) \
                and self._taint(node.func.value):
            return True
        return any_tainted
