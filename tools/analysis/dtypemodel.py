"""Interprocedural dtype-flow fact base for the numerics analyzers.

The mixed-precision surface (the int8/bf16/f32 histogram wire ladder, bf16
flash-attention blocks, donated f32 accumulators) is invisible to the other
fact bases: jitmap knows *where* values are traced, axismap knows *which
axis* they reduce over, but nothing knows what **dtype** a value carries
when it reaches a reduction, a quantized collective, or a checkpoint
boundary. This module closes that gap with a conservative abstract
interpretation over each function body:

* a **dtype lattice** (bool < ints < bf16/f16 < f32 < f64, plus
  ``unknown`` on top) with JAX promotion semantics — weak Python scalars do
  not widen strong array dtypes, bf16+f16 promote to f32, int+float keeps
  the float — under the repo's x64-disabled default (Python floats are weak
  f32, ints weak int32);
* per-expression :class:`DtypeInfo` facts (dtype, weak flag, "was any input
  ever f32", lossy-downcast provenance, finite-guard provenance) memoized
  for every expression node, so analyzers just look up the operand of the
  call they care about;
* **interprocedural summaries** over ``jitmap.resolve_callee`` call edges:
  three fixpoint passes join observed argument dtypes into parameter seeds
  and merge return dtypes (with per-tuple-element summaries and
  "returns the dtype of param *i*" passthrough, the ``_maybe_psum`` shape);
* pytree-leaf flow piggybacks on the same machinery: ``tree_map``-style
  combinators preserve their operand dtype, matching how the existing
  TaintWalker treats leaves as one abstract value.

Everything here is *recall-bounded*: when inference cannot prove a dtype it
says ``unknown``, and the analyzers built on top never flag unknown —
precision over recall, same contract as the SPMD/concurrency fact bases.
"""

from __future__ import annotations

import ast
import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

from .core import Project, SourceFile, dotted_name
from .jitmap import JitMap, _param_names

UNKNOWN_DT = "unknown"

#: canonical lattice element for every dtype spelling we understand
_DTYPE_NAMES = {
    "bool": "bool", "bool_": "bool",
    "int8": "int8", "int16": "int16", "int32": "int32", "int64": "int64",
    "uint8": "uint8", "uint16": "uint16", "uint32": "uint32",
    "uint64": "uint64",
    "int": "int32", "int_": "int32", "intc": "int32",
    "bfloat16": "bf16", "bf16": "bf16",
    "float16": "f16", "half": "f16", "f16": "f16",
    "float32": "f32", "single": "f32", "f32": "f32",
    # x64 is disabled repo-wide: a bare "float" canonicalizes to f32 inside
    # jax; numpy-side float64 data is tracked as f64 (still "ever f32+")
    "float": "f32", "float_": "f32",
    "float64": "f64", "double": "f64", "f64": "f64",
}

_FLOATS = {"bf16": 1, "f16": 1, "f32": 2, "f64": 3}
_INTS = {"int8": 1, "uint8": 1, "int16": 2, "uint16": 2,
         "int32": 3, "uint32": 3, "int64": 4, "uint64": 4}
#: the narrow wire dtypes the quantized-collective contract is about
NARROW_FLOATS = ("bf16", "f16")
WIDE_FLOATS = ("f32", "f64")
#: int16 headroom for the EQuARX grid-exactness contract: an exact integer
#: grid sum of n block-quantized values (each |q| <= qmax) needs
#: n * qmax <= INT16_LIMIT before int16 accumulation is lossless
INT16_LIMIT = 32767


@dataclass(frozen=True)
class DtypeInfo:
    """Abstract dtype fact for one value."""
    dtype: str = UNKNOWN_DT
    weak: bool = False            # Python-scalar weak type (does not widen)
    ever_f32: bool = False        # an f32/f64 value flowed into this
    downcast: bool = False        # explicitly cast down to bf16/f16
    cast_line: int = 0            # line of that lossy downcast (0 = none)
    guarded: bool = False         # bounded by clip/maximum/abs/eps idioms
    literal_cast: bool = False    # dtype came from a literal dtype spelling
    bound_derived: bool = False   # dtype picked by a compare-bounded IfExp
    guard_lhs: Optional[int] = None   # folded n*qmax behind that compare
    param: Optional[int] = None   # still carries the dtype of param #i

    def but(self, **kw) -> "DtypeInfo":
        return dataclasses.replace(self, **kw)

    @property
    def is_float(self) -> bool:
        return self.dtype in _FLOATS

    @property
    def is_int(self) -> bool:
        return self.dtype in _INTS


UNKNOWN = DtypeInfo()


def _mk(dtype: str, **kw) -> DtypeInfo:
    kw.setdefault("ever_f32", dtype in WIDE_FLOATS)
    return DtypeInfo(dtype=dtype, **kw)


def promote(a: DtypeInfo, b: DtypeInfo) -> DtypeInfo:
    """JAX-style binary promotion of two facts."""
    ever = a.ever_f32 or b.ever_f32
    down = a.downcast or b.downcast
    cast = a.cast_line or b.cast_line
    guarded = a.guarded and b.guarded
    param = a.param if a.param is not None else b.param
    carry = dict(ever_f32=ever, downcast=down, cast_line=cast,
                 guarded=guarded)
    if a.dtype == UNKNOWN_DT or b.dtype == UNKNOWN_DT:
        # weak scalar against unknown keeps the unknown side's identity so
        # passthrough survives `x * 0.5`
        keep = b if a.dtype == UNKNOWN_DT else a
        if (a.weak and a.dtype != UNKNOWN_DT) or \
                (b.weak and b.dtype != UNKNOWN_DT):
            return keep.but(**carry)
        return DtypeInfo(param=param, **carry)
    if a.weak and not b.weak:
        return _weak_into(a, b).but(**carry)
    if b.weak and not a.weak:
        return _weak_into(b, a).but(**carry)
    out = _strong_promote(a.dtype, b.dtype)
    carry["ever_f32"] = ever or out in WIDE_FLOATS
    return DtypeInfo(dtype=out, weak=a.weak and b.weak, param=param, **carry)


def _weak_into(weak: DtypeInfo, strong: DtypeInfo) -> DtypeInfo:
    # a weak Python scalar never widens a strong array dtype; a weak float
    # against an int array produces the default float
    if weak.dtype in _FLOATS and strong.dtype in _INTS:
        return _mk("f32")
    if weak.dtype in _FLOATS or strong.dtype != "bool":
        return strong.but(weak=False)
    return weak.but(weak=False)


def _strong_promote(a: str, b: str) -> str:
    if a == b:
        return a
    if a == "bool":
        return b
    if b == "bool":
        return a
    if a in _FLOATS and b in _FLOATS:
        if _FLOATS[a] == _FLOATS[b] == 1:
            return "f32"                     # bf16 + f16 -> f32 (jax table)
        return a if _FLOATS[a] >= _FLOATS[b] else b
    if a in _FLOATS:
        return a                             # int + float keeps the float
    if b in _FLOATS:
        return b
    if a in _INTS and b in _INTS:
        wide = a if _INTS[a] >= _INTS[b] else b
        # mixed signedness widens to the signed int of that width
        if a.startswith("u") != b.startswith("u"):
            return wide.lstrip("u") if wide.startswith("u") else wide
        return wide
    return UNKNOWN_DT


# --- dtype spellings ---------------------------------------------------------

_CAST_CALLS = {"jax.lax.convert_element_type", "jax.numpy.astype",
               "numpy.astype"}
_RESULT_TYPE = {"jax.numpy.result_type", "numpy.result_type",
                "jax.numpy.promote_types", "numpy.promote_types"}


class FunctionFacts:
    """Per-function dtype facts: an info for every expression node."""

    def __init__(self) -> None:
        self.expr: Dict[int, DtypeInfo] = {}
        self.env: Dict[str, DtypeInfo] = {}
        self.returns: DtypeInfo = UNKNOWN
        self.return_parts: Optional[List[DtypeInfo]] = None

    def info(self, node: Optional[ast.AST]) -> DtypeInfo:
        if node is None:
            return UNKNOWN
        return self.expr.get(id(node), UNKNOWN)


@dataclass
class Summary:
    """Context-insensitive call summary for one project function."""
    returns: DtypeInfo = UNKNOWN
    parts: Optional[List[DtypeInfo]] = None


class DtypeModel:
    """Whole-project dtype-flow facts over the package files."""

    PASSES = 3

    def __init__(self, project: Project, jitmap: Optional[JitMap] = None):
        self.project = project
        self.jitmap = jitmap if jitmap is not None else JitMap(project)
        self.files = [sf for sf in project.files
                      if sf.rel.startswith("synapseml_tpu/")]
        self._consts: Dict[str, Dict[str, object]] = {}
        self.summaries: Dict[str, Summary] = {}
        self._seeds: Dict[str, Dict[int, DtypeInfo]] = {}
        self.facts: Dict[str, FunctionFacts] = {}
        self._build()

    # -- module-level constant folding ------------------------------------
    def module_consts(self, sf: SourceFile) -> Dict[str, object]:
        cached = self._consts.get(sf.rel)
        if cached is None:
            # registered before it is filled: parse_dtype_name looks names
            # up here, and a top-level alias (``shard_map = jax.shard_map``)
            # would otherwise recurse forever
            cached = self._consts[sf.rel] = {}
            for node in getattr(sf.tree, "body", []):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    continue
                v = node.value
                if isinstance(v, ast.Constant) and isinstance(
                        v.value, (int, float)) and not isinstance(
                        v.value, bool):
                    cached[node.targets[0].id] = v.value
                else:
                    dt = self.parse_dtype_name(sf, v)
                    if dt is not None:
                        cached[node.targets[0].id] = dt
        return cached

    def fold_int(self, sf: SourceFile, node: ast.AST) -> Optional[int]:
        """Statically fold an integer expression over literals and
        module-level integer constants; None when unresolvable."""
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and not isinstance(node.value, bool):
            return node.value
        if isinstance(node, ast.Name):
            v = self.module_consts(sf).get(node.id)
            return v if isinstance(v, int) else None
        if isinstance(node, ast.BinOp):
            le = self.fold_int(sf, node.left)
            ri = self.fold_int(sf, node.right)
            if le is None or ri is None:
                return None
            try:
                if isinstance(node.op, ast.Add):
                    return le + ri
                if isinstance(node.op, ast.Sub):
                    return le - ri
                if isinstance(node.op, ast.Mult):
                    return le * ri
                if isinstance(node.op, ast.FloorDiv) and ri:
                    return le // ri
                if isinstance(node.op, ast.Pow) and 0 <= ri < 64:
                    return le ** ri
            except (OverflowError, ValueError):
                return None
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            v = self.fold_int(sf, node.operand)
            return -v if v is not None else None
        return None

    # -- dtype spelling resolution ----------------------------------------
    def parse_dtype_name(self, sf: SourceFile,
                         node: Optional[ast.AST]) -> Optional[str]:
        """Lattice element named by a *literal* dtype expression
        (``jnp.bfloat16``, ``"float32"``, ``np.dtype("int8")``,
        ``jnp.result_type(a, b)`` over literal spellings), else None."""
        if node is None:
            return None
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return _DTYPE_NAMES.get(node.value)
        name = dotted_name(node)
        if name is not None:
            leaf = name.split(".")[-1]
            if leaf in _DTYPE_NAMES:
                canon = self.project.canonical(sf, name) or name
                root = canon.split(".")[0]
                if root in ("jax", "numpy", "builtins", "jnp", "np",
                            "ml_dtypes") or "." not in name:
                    return _DTYPE_NAMES[leaf]
            v = self.module_consts(sf).get(name)
            if isinstance(v, str) and v in set(_DTYPE_NAMES.values()):
                return v
            return None
        if isinstance(node, ast.Call):
            canon = self.project.canonical(sf, dotted_name(node.func)) or ""
            if canon in ("numpy.dtype", "jax.numpy.dtype") and node.args:
                return self.parse_dtype_name(sf, node.args[0])
            if canon in _RESULT_TYPE:
                parts = [self.parse_dtype_name(sf, a) for a in node.args]
                if parts and all(p is not None for p in parts):
                    out = parts[0]
                    for p in parts[1:]:
                        out = _strong_promote(out, p)
                    return out
        return None

    # -- build --------------------------------------------------------------
    def _iter_functions(self):
        for sf in self.files:
            for qual, info in sf.symbols.functions.items():
                if isinstance(info.node, (ast.FunctionDef,
                                          ast.AsyncFunctionDef, ast.Lambda)):
                    yield sf, info

    def _build(self) -> None:
        for _ in range(self.PASSES):
            sums: Dict[str, Summary] = {}
            seeds: Dict[str, Dict[int, DtypeInfo]] = {}
            facts: Dict[str, FunctionFacts] = {}
            for sf, info in self._iter_functions():
                fa = _FnAnalysis(self, sf, info, seeds)
                out = fa.run()
                facts[info.full_name] = out
                sums[info.full_name] = Summary(out.returns, out.return_parts)
            stable = (self._same_summaries(sums)
                      and self._same_seeds(seeds))
            self.summaries = sums
            self._seeds = seeds
            self.facts = facts
            if stable:
                break

    def _same_summaries(self, new: Dict[str, Summary]) -> bool:
        if set(new) != set(self.summaries):
            return False
        return all(new[k].returns == self.summaries[k].returns
                   and new[k].parts == self.summaries[k].parts for k in new)

    def _same_seeds(self, new: Dict[str, Dict[int, DtypeInfo]]) -> bool:
        return new == self._seeds

    def facts_for(self, info) -> FunctionFacts:
        return self.facts.get(info.full_name, FunctionFacts())


# --- function-level abstract interpretation ----------------------------------

#: calls whose result carries the first argument's dtype unchanged
_PRESERVE = {
    "jax.lax.psum", "jax.lax.pmean", "jax.lax.pmax", "jax.lax.pmin",
    "jax.lax.psum_scatter", "jax.lax.all_gather", "jax.lax.ppermute",
    "jax.lax.all_to_all", "jax.lax.stop_gradient", "jax.lax.slice",
    "jax.lax.dynamic_slice", "jax.lax.dynamic_update_slice",
    "jax.numpy.reshape", "jax.numpy.transpose", "jax.numpy.moveaxis",
    "jax.numpy.swapaxes", "jax.numpy.squeeze", "jax.numpy.expand_dims",
    "jax.numpy.broadcast_to", "jax.numpy.flip", "jax.numpy.roll",
    "jax.numpy.ravel", "jax.numpy.negative", "jax.numpy.cumsum",
    "jax.numpy.sort", "jax.numpy.take", "jax.numpy.take_along_axis",
    "jax.numpy.pad", "jax.numpy.tile", "jax.numpy.repeat",
    "jax.numpy.round", "jax.numpy.sum", "jax.numpy.prod",
    "jax.numpy.nansum", "jax.numpy.max", "jax.numpy.min",
    "jax.numpy.amax", "jax.numpy.amin", "jax.numpy.cumprod",
    "jax.device_put", "jax.numpy.copy",
    "numpy.reshape", "numpy.transpose", "numpy.ascontiguousarray",
    "numpy.sum", "numpy.cumsum", "numpy.sort", "numpy.squeeze",
}
#: guards that bound a value away from log/div/sqrt domain errors
_GUARDS = {
    "jax.numpy.clip", "jax.numpy.maximum", "jax.numpy.abs",
    "jax.numpy.absolute", "jax.numpy.exp", "jax.numpy.square",
    "jax.numpy.nan_to_num", "jax.nn.softplus", "jax.nn.sigmoid",
    "jax.nn.softmax", "jax.nn.log_sigmoid", "jax.numpy.logaddexp",
    "numpy.clip", "numpy.maximum", "numpy.abs", "numpy.exp",
    "numpy.square", "numpy.nan_to_num", "max", "abs",
}
#: float-valued elementwise transforms: float in -> same float out,
#: int in -> default float out
_FLOAT_UNARY = {
    "jax.numpy.exp", "jax.numpy.expm1", "jax.numpy.log", "jax.numpy.log1p",
    "jax.numpy.log2", "jax.numpy.log10", "jax.numpy.sqrt", "jax.numpy.sin",
    "jax.numpy.cos", "jax.numpy.tanh", "jax.numpy.sigmoid",
    "jax.lax.rsqrt", "jax.lax.log", "jax.lax.exp", "jax.lax.sqrt",
    "jax.nn.softplus", "jax.nn.sigmoid", "jax.nn.relu", "jax.nn.gelu",
    "jax.nn.softmax", "jax.nn.log_softmax", "jax.scipy.special.logsumexp",
    "numpy.exp", "numpy.log", "numpy.sqrt",
}
#: n-ary promotion over the positional args
_PROMOTE_N = {
    "jax.numpy.maximum", "jax.numpy.minimum", "jax.numpy.add",
    "jax.numpy.subtract", "jax.numpy.multiply", "jax.numpy.dot",
    "jax.numpy.matmul", "jax.numpy.logaddexp", "jax.lax.add",
    "jax.lax.mul", "jax.lax.max", "jax.lax.min", "jax.numpy.power",
    "numpy.maximum", "numpy.minimum", "numpy.dot", "numpy.matmul",
}
_CONCAT = {"jax.numpy.concatenate", "jax.numpy.stack", "jax.numpy.hstack",
           "jax.numpy.vstack", "numpy.concatenate", "numpy.stack"}
#: dtype kwarg (or default-float) constructors; numpy defaults to f64,
#: jnp to f32
_CTOR_F = {
    "jax.numpy.zeros": "f32", "jax.numpy.ones": "f32",
    "jax.numpy.full": "f32", "jax.numpy.empty": "f32",
    "jax.numpy.linspace": "f32", "jax.numpy.eye": "f32",
    "jax.random.normal": "f32", "jax.random.uniform": "f32",
    "numpy.zeros": "f64", "numpy.ones": "f64", "numpy.full": "f64",
    "numpy.empty": "f64", "numpy.linspace": "f64", "numpy.eye": "f64",
}
_LIKE = {"jax.numpy.zeros_like", "jax.numpy.ones_like",
         "jax.numpy.full_like", "jax.numpy.empty_like",
         "numpy.zeros_like", "numpy.ones_like"}
_ASARRAY = {"jax.numpy.asarray", "jax.numpy.array", "numpy.asarray",
            "numpy.array", "jax.numpy.atleast_1d", "jax.numpy.atleast_2d"}
_PRESERVE_METHODS = {
    "sum", "prod", "max", "min", "cumsum", "cumprod", "reshape",
    "transpose", "copy", "flatten", "ravel", "squeeze", "clip", "round",
    "block_until_ready", "T", "real", "sort", "take",
}


class _FnAnalysis:
    """One pass of abstract interpretation over a single function body."""

    def __init__(self, model: DtypeModel, sf: SourceFile, info,
                 seed_sink: Dict[str, Dict[int, DtypeInfo]]):
        self.m = model
        self.sf = sf
        self.info = info
        self.seed_sink = seed_sink
        self.out = FunctionFacts()
        self.env: Dict[str, DtypeInfo] = {}
        self.returns: List[DtypeInfo] = []
        self.return_parts: List[Optional[List[DtypeInfo]]] = []

    # -- entry ------------------------------------------------------------
    def run(self) -> FunctionFacts:
        node = self.info.node
        params = (_param_names(node)
                  if not isinstance(node, ast.Lambda)
                  else [a.arg for a in node.args.args])
        seeds = self.m._seeds.get(self.info.full_name, {})
        for i, p in enumerate(params):
            seeded = seeds.get(i)
            if seeded is not None and seeded.dtype != UNKNOWN_DT:
                self.env[p] = seeded.but(param=i)
            else:
                base = seeds.get(i, UNKNOWN)
                self.env[p] = base.but(param=i)
        if isinstance(node, ast.Lambda):
            self.returns.append(self.eval(node.body))
            self.return_parts.append(self._tuple_parts(node.body))
        else:
            self._block(node.body)
        self.out.env = self.env
        self.out.returns = self._merge(self.returns)
        parts_list = [p for p in self.return_parts if p is not None]
        if parts_list and len(self.return_parts) == len(parts_list) and \
                len({len(p) for p in parts_list}) == 1:
            n = len(parts_list[0])
            self.out.return_parts = [
                self._merge([p[i] for p in parts_list]) for i in range(n)]
        return self.out

    @staticmethod
    def _merge(infos: Sequence[DtypeInfo]) -> DtypeInfo:
        if not infos:
            return UNKNOWN
        out = infos[0]
        for i in infos[1:]:
            out = promote(out, i)
        return out

    def _tuple_parts(self, node: ast.AST) -> Optional[List[DtypeInfo]]:
        if isinstance(node, (ast.Tuple, ast.List)):
            return [self.eval(e) for e in node.elts]
        return None

    # -- statements -------------------------------------------------------
    def _block(self, body: Sequence[ast.stmt]) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, node: ast.stmt) -> None:
        if isinstance(node, ast.Assign):
            val = self.eval(node.value)
            parts = self._call_parts(node.value) or \
                self._tuple_parts(node.value)
            for t in node.targets:
                self._bind(t, val, parts)
        elif isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._bind(node.target, self.eval(node.value), None)
        elif isinstance(node, ast.AugAssign):
            name = dotted_name(node.target)
            cur = self.env.get(name, UNKNOWN) if name else UNKNOWN
            new = promote(cur, self.eval(node.value))
            if isinstance(node.op, ast.Div):
                new = self._float_result(new)
            self.out.expr[id(node)] = new
            if name:
                self.env[name] = new
        elif isinstance(node, ast.Return):
            if node.value is not None:
                self.returns.append(self.eval(node.value))
                self.return_parts.append(self._tuple_parts(node.value))
        elif isinstance(node, ast.Expr):
            self.eval(node.value)
        elif isinstance(node, ast.If):
            self.eval(node.test)
            before = dict(self.env)
            self._block(node.body)
            after_body = self.env
            self.env = dict(before)
            self._block(node.orelse)
            self._join(after_body)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            it = self.eval(node.iter)
            # iterating an array yields elements of the same dtype
            self._bind(node.target, it.but(weak=False), None)
            self._block(node.body)
            self._block(node.body)      # second pass: loop-carried joins
            self._block(node.orelse)
        elif isinstance(node, ast.While):
            self.eval(node.test)
            self._block(node.body)
            self._block(node.body)
            self._block(node.orelse)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                self.eval(item.context_expr)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars, UNKNOWN, None)
            self._block(node.body)
        elif isinstance(node, ast.Try):
            self._block(node.body)
            for h in node.handlers:
                self._block(h.body)
            self._block(node.orelse)
            self._block(node.finalbody)
        elif isinstance(node, ast.Assert):
            self.eval(node.test)
        elif isinstance(node, ast.Raise):
            if node.exc is not None:
                self.eval(node.exc)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            pass                        # nested defs analyzed on their own
        # Pass/Break/Continue/Import/Global/Delete: nothing to track

    def _join(self, other: Dict[str, DtypeInfo]) -> None:
        for k in set(self.env) | set(other):
            a, b = self.env.get(k), other.get(k)
            if a is None or b is None:
                keep = a if a is not None else b
                self.env[k] = keep.but(param=None) if keep else UNKNOWN
            else:
                self.env[k] = promote(a, b)

    def _bind(self, target: ast.AST, val: DtypeInfo,
              parts: Optional[List[DtypeInfo]]) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for i, t in enumerate(target.elts):
                self._bind(t, parts[i] if parts and i < len(parts)
                           else UNKNOWN, None)
            return
        name = dotted_name(target)
        if name:
            self.env[name] = val

    # -- expressions ------------------------------------------------------
    def eval(self, node: Optional[ast.AST]) -> DtypeInfo:
        if node is None:
            return UNKNOWN
        key = id(node)
        cached = self.out.expr.get(key)
        info = self._eval(node)
        # keep the LAST program-point fact (loops re-evaluate bodies)
        if cached is None or cached != info:
            self.out.expr[key] = info
        return info

    def _eval(self, node: ast.AST) -> DtypeInfo:   # noqa: C901
        if isinstance(node, ast.Constant):
            v = node.value
            if isinstance(v, bool):
                return DtypeInfo("bool", weak=True, guarded=True)
            if isinstance(v, int):
                return DtypeInfo("int32", weak=True, guarded=True)
            if isinstance(v, float):
                return DtypeInfo("f32", weak=True, guarded=True)
            return UNKNOWN
        if isinstance(node, ast.Name):
            return self._lookup(node.id)
        if isinstance(node, ast.Attribute):
            name = dotted_name(node)
            if name is not None and name in self.env:
                return self.env[name]
            if node.attr in _PRESERVE_METHODS:
                return self.eval(node.value)
            return UNKNOWN
        if isinstance(node, ast.Subscript):
            self.eval(node.slice)
            return self.eval(node.value)
        if isinstance(node, ast.UnaryOp):
            inner = self.eval(node.operand)
            if isinstance(node.op, ast.Not):
                return DtypeInfo("bool")
            return inner.but(guarded=False)
        if isinstance(node, ast.BinOp):
            le, ri = self.eval(node.left), self.eval(node.right)
            if isinstance(node.op, ast.Pow):
                exp = node.right
                even = (isinstance(exp, ast.Constant)
                        and isinstance(exp.value, (int, float))
                        and float(exp.value) % 2 == 0)
                out = promote(le, ri)
                return out.but(guarded=out.guarded or even)
            out = promote(le, ri)
            if isinstance(node.op, ast.Div):
                out = self._float_result(out)
            if isinstance(node.op, ast.Add):
                # x + positive-literal: the additive-epsilon guard idiom
                for side in (node.left, node.right):
                    if isinstance(side, ast.Constant) and isinstance(
                            side.value, (int, float)) and side.value > 0:
                        out = out.but(guarded=True)
            elif isinstance(node.op, (ast.Sub, ast.Mod, ast.FloorDiv)):
                out = out.but(guarded=le.guarded and ri.guarded)
            return out
        if isinstance(node, ast.BoolOp):
            for v in node.values:
                self.eval(v)
            return DtypeInfo("bool")
        if isinstance(node, ast.Compare):
            self.eval(node.left)
            for c in node.comparators:
                self.eval(c)
            return DtypeInfo("bool", guarded=True)
        if isinstance(node, ast.IfExp):
            self.eval(node.test)
            return promote(self.eval(node.body), self.eval(node.orelse))
        if isinstance(node, ast.Call):
            return self._eval_call(node)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            for e in node.elts:
                self.eval(e)
            return UNKNOWN
        if isinstance(node, ast.Dict):
            for v in node.values:
                self.eval(v)
            return UNKNOWN
        if isinstance(node, ast.Starred):
            return self.eval(node.value)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            for gen in node.generators:
                self.eval(gen.iter)
            return UNKNOWN
        if isinstance(node, ast.NamedExpr):
            val = self.eval(node.value)
            self._bind(node.target, val, None)
            return val
        return UNKNOWN

    def _lookup(self, name: str) -> DtypeInfo:
        got = self.env.get(name)
        if got is not None:
            return got
        const = self.m.module_consts(self.sf).get(name)
        if isinstance(const, float):
            return DtypeInfo("f32", weak=True, guarded=True)
        if isinstance(const, int):
            return DtypeInfo("int32", weak=True, guarded=True)
        return UNKNOWN

    @staticmethod
    def _float_result(out: DtypeInfo) -> DtypeInfo:
        if out.dtype in _INTS or out.dtype == "bool":
            return out.but(dtype="f32", weak=False)
        return out

    # -- calls ------------------------------------------------------------
    def _kw(self, call: ast.Call, name: str) -> Optional[ast.AST]:
        for kw in call.keywords:
            if kw.arg == name:
                return kw.value
        return None

    def _cast_target(self, dtype_arg: ast.AST, src: DtypeInfo) -> DtypeInfo:
        """Fact after casting ``src`` to the dtype named by ``dtype_arg``."""
        # x.astype(y.dtype): carries y's (possibly symbolic) dtype
        if isinstance(dtype_arg, ast.Attribute) and dtype_arg.attr == "dtype":
            ref = self.eval(dtype_arg.value)
            return ref.but(ever_f32=src.ever_f32 or ref.ever_f32,
                           guarded=src.guarded, weak=False)
        bound = False
        guard_lhs: Optional[int] = None
        dt: Optional[str] = None
        if isinstance(dtype_arg, ast.IfExp) and isinstance(
                dtype_arg.test, ast.Compare):
            # the _acc_dtype idiom: dtype picked by a static-bound compare
            bound = True
            test = dtype_arg.test
            lhs = self.m.fold_int(self.sf, test.left)
            rhs = (self.m.fold_int(self.sf, test.comparators[0])
                   if len(test.comparators) == 1 else None)
            if lhs is not None and rhs is not None and len(test.ops) == 1:
                op = test.ops[0]
                taken = (lhs <= rhs if isinstance(op, ast.LtE) else
                         lhs < rhs if isinstance(op, ast.Lt) else
                         lhs >= rhs if isinstance(op, ast.GtE) else
                         lhs > rhs if isinstance(op, ast.Gt) else None)
                if taken is not None:
                    branch = dtype_arg.body if taken else dtype_arg.orelse
                    dt = self.m.parse_dtype_name(self.sf, branch)
                    guard_lhs = lhs
        if dt is None and not bound:
            dt = self.m.parse_dtype_name(self.sf, dtype_arg)
        if dt is None:
            return DtypeInfo(bound_derived=bound, guard_lhs=guard_lhs,
                             ever_f32=src.ever_f32, guarded=src.guarded)
        lossy = (dt in NARROW_FLOATS
                 and src.dtype not in NARROW_FLOATS + ("bool",)
                 and not src.weak)
        line = getattr(dtype_arg, "lineno", 0)
        return DtypeInfo(
            dtype=dt, literal_cast=not bound, bound_derived=bound,
            guard_lhs=guard_lhs, guarded=src.guarded,
            ever_f32=(src.ever_f32 or src.dtype in WIDE_FLOATS
                      or dt in WIDE_FLOATS),
            downcast=src.downcast or lossy,
            cast_line=line if lossy else src.cast_line)

    def _eval_call(self, call: ast.Call) -> DtypeInfo:   # noqa: C901
        for kw in call.keywords:
            self.eval(kw.value)
        arg_infos = [self.eval(a) for a in call.args]
        func = call.func
        # .astype(dt) / .view(dt) method casts
        if isinstance(func, ast.Attribute) and func.attr in ("astype",
                                                             "view"):
            src = self.eval(func.value)
            if call.args:
                return self._cast_target(call.args[0], src)
            return src
        canon = self.m.project.canonical(self.sf, dotted_name(func))
        if canon in _CAST_CALLS and len(call.args) >= 2:
            return self._cast_target(call.args[1], arg_infos[0])
        dt_kw = self._kw(call, "dtype")
        pet = self._kw(call, "preferred_element_type")
        if pet is not None:
            got = self.m.parse_dtype_name(self.sf, pet)
            if got is not None:
                return _mk(got, literal_cast=True)
        if canon in _ASARRAY:
            if dt_kw is not None and call.args:
                return self._cast_target(dt_kw, arg_infos[0])
            if len(call.args) >= 2:
                return self._cast_target(call.args[1], arg_infos[0])
            return (arg_infos[0].but(weak=False) if arg_infos else UNKNOWN)
        if canon in _CTOR_F:
            if dt_kw is not None:
                return self._cast_target(dt_kw, UNKNOWN)
            if len(call.args) >= 2:
                got = self.m.parse_dtype_name(self.sf, call.args[1])
                if got is not None:
                    return _mk(got, literal_cast=True)
            return _mk(_CTOR_F[canon])
        if canon in _LIKE:
            if dt_kw is not None:
                return self._cast_target(dt_kw, UNKNOWN)
            return arg_infos[0] if arg_infos else UNKNOWN
        if canon in ("jax.numpy.arange", "numpy.arange"):
            if dt_kw is not None:
                return self._cast_target(dt_kw, UNKNOWN)
            floaty = any(isinstance(a, ast.Constant)
                         and isinstance(a.value, float) for a in call.args)
            return _mk("f32" if floaty else "int32")
        if canon in _PRESERVE and arg_infos:
            return arg_infos[0].but(weak=False)
        if canon in ("jax.numpy.mean", "jax.lax.pmean", "numpy.mean"):
            base = arg_infos[0] if arg_infos else UNKNOWN
            if dt_kw is not None:
                return self._cast_target(dt_kw, base)
            return self._float_result(base).but(weak=False)
        if canon in _FLOAT_UNARY and arg_infos:
            out = self._float_result(arg_infos[0]).but(weak=False)
            if canon in _GUARDS:
                out = out.but(guarded=True)
            return out
        if canon in _GUARDS:
            base = self._merge(arg_infos) if arg_infos else UNKNOWN
            return base.but(guarded=True, weak=False)
        if canon in _PROMOTE_N and arg_infos:
            return self._merge(arg_infos)
        if canon in ("jax.numpy.where", "jax.lax.select") and \
                len(arg_infos) >= 3:
            return promote(arg_infos[1], arg_infos[2])
        if canon in _CONCAT and call.args:
            seq = call.args[0]
            if isinstance(seq, (ast.List, ast.Tuple)):
                return self._merge([self.eval(e) for e in seq.elts])
            return self.eval(seq)
        if canon == "jax.lax.scan":
            init = call.args[1] if len(call.args) > 1 else \
                self._kw(call, "init")
            carry = self.eval(init) if init is not None else UNKNOWN
            self.out.expr[id(call)] = carry
            return carry
        if canon in ("jax.tree_util.tree_map", "jax.tree.map") and \
                len(arg_infos) >= 2:
            return arg_infos[1].but(weak=False)   # leaves keep their dtype
        # project-internal call: use/record the interprocedural summary
        callee = self.m.jitmap.resolve_callee(self.sf, self.info, call)
        if callee is not None and isinstance(
                callee.node, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            self._record_args(call, callee, arg_infos)
            summ = self.m.summaries.get(callee.full_name)
            if summ is not None:
                return self._apply_summary(call, callee, summ, arg_infos)
        # value-receiver array methods: e.sum(-1) / x.mean() keep the
        # receiver's provenance (notably `guarded` — an exp/maximum-derived
        # operand stays nonnegative through its reduction). Module receivers
        # (np.sum) resolved via canonical above; project methods named
        # `sum` etc. resolved via the callee summary above.
        if callee is None and isinstance(func, ast.Attribute):
            recv_name = dotted_name(func.value)
            if recv_name is None or self.m.project.canonical(
                    self.sf, recv_name) == recv_name:
                if func.attr in _PRESERVE_METHODS:
                    src = self.eval(func.value)
                    if dt_kw is not None:
                        return self._cast_target(dt_kw, src)
                    return src.but(weak=False)
                if func.attr in ("mean", "var", "std"):
                    src = self.eval(func.value)
                    if dt_kw is not None:
                        return self._cast_target(dt_kw, src)
                    return self._float_result(src).but(weak=False)
        return UNKNOWN

    def _callee_offset(self, call: ast.Call, callee) -> int:
        # self.method(x): positional args are shifted past `self`
        if callee.class_name and isinstance(call.func, ast.Attribute):
            head = dotted_name(call.func.value)
            if head in ("self", "cls") or head == callee.class_name:
                return 1
        return 0

    def _record_args(self, call: ast.Call, callee,
                     arg_infos: List[DtypeInfo]) -> None:
        try:
            params = _param_names(callee.node)
        except AttributeError:
            return
        off = self._callee_offset(call, callee)
        sink = self.seed_sink.setdefault(callee.full_name, {})

        def put(idx: int, got: DtypeInfo) -> None:
            got = got.but(param=None)
            cur = sink.get(idx)
            sink[idx] = got if cur is None else promote(cur, got)

        for i, got in enumerate(arg_infos):
            if i < len(call.args) and isinstance(call.args[i], ast.Starred):
                return                      # *args: positions unknowable
            if i + off < len(params):
                put(i + off, got)
        for kw in call.keywords:
            if kw.arg and kw.arg in params:
                put(params.index(kw.arg), self.eval(kw.value))

    def _apply_summary(self, call: ast.Call, callee, summ: Summary,
                       arg_infos: List[DtypeInfo]) -> DtypeInfo:
        def resolve(info: DtypeInfo) -> DtypeInfo:
            if info.param is None:
                return info
            off = self._callee_offset(call, callee)
            idx = info.param - off
            if 0 <= idx < len(arg_infos):
                base = arg_infos[idx]
                return base.but(
                    ever_f32=base.ever_f32 or info.ever_f32,
                    downcast=base.downcast or info.downcast,
                    cast_line=base.cast_line or info.cast_line)
            try:
                params = _param_names(callee.node)
                pname = params[info.param]
                for kw in call.keywords:
                    if kw.arg == pname:
                        return self.eval(kw.value)
            except (AttributeError, IndexError):
                pass
            return info.but(param=None)

        out = resolve(summ.returns)
        if summ.parts is not None:
            self.out.expr[id(call)] = out
            # expose per-element facts for tuple unpacking
            self._last_parts = [resolve(p) for p in summ.parts]
        return out

    def _call_parts(self, node: ast.AST) -> Optional[List[DtypeInfo]]:
        if not isinstance(node, ast.Call):
            return None
        self._last_parts: Optional[List[DtypeInfo]] = None
        self.eval(node)
        parts = getattr(self, "_last_parts", None)
        if parts is not None:
            return parts
        canon = self.m.project.canonical(self.sf, dotted_name(node.func))
        if canon == "jax.lax.scan":
            init = node.args[1] if len(node.args) > 1 else None
            return [self.eval(init) if init is not None else UNKNOWN,
                    UNKNOWN]
        return None
