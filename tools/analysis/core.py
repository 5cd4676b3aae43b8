"""Shared infrastructure for all analyzers.

One :class:`Project` parses every target file once and exposes:

* per-module **symbol tables** (:class:`SymbolTable`): every binding in the
  file (any scope), every import with its resolved absolute target, every
  function/class definition with its qualified name;
* **cross-module import resolution** (:meth:`Project.canonical`): a dotted
  name as written in one module (``shard_map``, ``partial``, ``jnp.where``)
  is followed through import aliases — including re-exports through other
  package modules — to a canonical fully-qualified name
  (``jax.experimental.shard_map.shard_map``, ``functools.partial``, ...);
* :class:`Finding` objects with stable **fingerprints** (analyzer + path +
  source-line text + occurrence index, so baselines survive unrelated line
  drift) and inline ``# lint-ok[: analyzer-id]`` suppression.

Analyzers receive the Project and return ``list[Finding]``; they never parse
files themselves.
"""

from __future__ import annotations

import ast
import builtins
import hashlib
import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PACKAGE = "synapseml_tpu"

DEFAULT_TARGETS = ["synapseml_tpu", "tools", "bench.py", "chip_smoke.py",
                   "__graft_entry__.py", "tests"]

#: ``# lint-ok`` suppresses every analyzer on that line;
#: ``# lint-ok: trace-safety, determinism`` suppresses the named ones.
#: Trailing justification prose after the ids is encouraged and ignored.
#: Matched against COMMENT tokens only (never string/docstring contents)
#: and anchored at the start of the comment.
_SUPPRESS_RE = re.compile(
    r"#\s*lint-ok\b"
    r"(?::\s*([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*))?")

BUILTINS = set(dir(builtins)) | {
    "__file__", "__name__", "__doc__", "__package__", "__spec__",
    "__loader__", "__builtins__", "__debug__", "__annotations__",
    "__dict__", "__class__", "__path__", "__version__", "__all__",
    "WindowsError",  # guarded platform-specific uses
}


@dataclass
class Finding:
    analyzer: str        # analyzer id, e.g. "trace-safety"
    path: str            # repo-relative, forward slashes
    line: int
    col: int
    message: str
    fingerprint: str = ""

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: " \
               f"[{self.analyzer}] {self.message}"


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclass
class FunctionInfo:
    """One function/method definition (nested defs get dotted qualnames)."""
    node: ast.AST                 # FunctionDef | AsyncFunctionDef | Lambda
    module: str                   # dotted module name
    qualname: str                 # module-relative, e.g. "Cls.method.inner"
    class_name: Optional[str]     # innermost enclosing class, if any
    lineno: int

    @property
    def full_name(self) -> str:
        return f"{self.module}.{self.qualname}"


class SymbolTable(ast.NodeVisitor):
    """Everything one file binds, imports and defines (any scope).

    The binding union is deliberately scope-blind (the lint.py design): it
    cannot model shadowing, but anything absent from it is a genuine unbound
    name — zero false positives for the undefined-name analyzer, and a safe
    over-approximation for taint seeding.
    """

    def __init__(self, module: str, is_pkg: bool):
        self.module = module
        self.is_pkg = is_pkg
        self.bound: Set[str] = set()
        #: local alias -> absolute dotted target ("partial" ->
        #: "functools.partial", "jnp" -> "jax.numpy", ...)
        self.import_targets: Dict[str, str] = {}
        self.import_linenos: Dict[str, int] = {}    # alias -> first lineno
        self.top_level_modules: Set[str] = set()    # import-time cycle edges
        self.functions: Dict[str, FunctionInfo] = {}   # qualname -> info
        self.classes: Dict[str, ast.ClassDef] = {}
        self._stack: List[str] = []       # qualname parts
        self._class_stack: List[str] = []
        self._func_depth = 0

    # -- imports --
    def _resolve_relative(self, mod: str, level: int) -> str:
        """``from ..core import x`` in this module -> absolute module."""
        base = self.module.split(".")
        if not self.is_pkg:
            base = base[:-1]
        if level > 1:
            base = base[:-(level - 1)]
        return ".".join(base + ([mod] if mod else [])).strip(".")

    def visit_Import(self, node: ast.Import):
        for a in node.names:
            alias = a.asname or a.name.split(".")[0]
            self.bound.add(alias)
            self.import_targets.setdefault(
                alias, a.name if a.asname else a.name.split(".")[0])
            self.import_linenos.setdefault(alias, node.lineno)
            if self._func_depth == 0:
                self.top_level_modules.add(a.name)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        mod = node.module or ""
        if node.level:
            mod = self._resolve_relative(mod, node.level)
        for a in node.names:
            if a.name == "*":
                continue
            alias = a.asname or a.name
            self.bound.add(alias)
            if (node.module or node.level) and mod != "__future__":
                self.import_targets.setdefault(alias, f"{mod}.{a.name}")
                self.import_linenos.setdefault(alias, node.lineno)
        if mod and mod != "__future__" and self._func_depth == 0:
            self.top_level_modules.add(mod)
        self.generic_visit(node)

    # -- bindings --
    def _bind_target(self, t: ast.AST):
        for n in ast.walk(t):
            if isinstance(n, ast.Name):
                self.bound.add(n.id)

    def visit_Assign(self, node: ast.Assign):
        for t in node.targets:
            self._bind_target(t)
        # module-level alias assignment (``shard_map = _shard_map``) behaves
        # like an import for cross-module resolution purposes
        if (self._func_depth == 0 and not self._class_stack
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            src = dotted_name(node.value)
            if src:
                self.import_targets.setdefault(node.targets[0].id, src)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign):
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_NamedExpr(self, node: ast.NamedExpr):
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_For(self, node):
        self._bind_target(node.target)
        self.generic_visit(node)
    visit_AsyncFor = visit_For

    def visit_withitem(self, node: ast.withitem):
        if node.optional_vars:
            self._bind_target(node.optional_vars)
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        if node.name:
            self.bound.add(node.name)
        self.generic_visit(node)

    def visit_comprehension(self, node: ast.comprehension):
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_Global(self, node: ast.Global):
        self.bound.update(node.names)

    def visit_Nonlocal(self, node: ast.Nonlocal):
        self.bound.update(node.names)

    # -- definitions --
    def _visit_func(self, node):
        self.bound.add(node.name)
        a = node.args
        for arg in (a.posonlyargs + a.args + a.kwonlyargs
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            self.bound.add(arg.arg)
        self._stack.append(node.name)
        qual = ".".join(self._stack)
        self.functions[qual] = FunctionInfo(
            node=node, module=self.module, qualname=qual,
            class_name=self._class_stack[-1] if self._class_stack else None,
            lineno=node.lineno)
        self._func_depth += 1
        self.generic_visit(node)
        self._func_depth -= 1
        self._stack.pop()
    visit_FunctionDef = visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node: ast.ClassDef):
        self.bound.add(node.name)
        self._stack.append(node.name)
        self._class_stack.append(node.name)
        self.classes[".".join(self._stack)] = node
        self.generic_visit(node)
        self._class_stack.pop()
        self._stack.pop()

    def visit_Lambda(self, node: ast.Lambda):
        a = node.args
        for arg in (a.posonlyargs + a.args + a.kwonlyargs
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            self.bound.add(arg.arg)
        self.generic_visit(node)


@dataclass
class SourceFile:
    path: str                       # absolute
    rel: str                        # repo-relative, forward slashes
    module: str                     # dotted module name ("tests.conftest")
    is_pkg: bool
    text: str
    lines: List[str]
    tree: ast.AST
    symbols: SymbolTable
    syntax_error: Optional[str] = None
    #: line -> suppressed analyzer ids ({"*"} = all)
    suppressions: Dict[int, Set[str]] = field(default_factory=dict)

    def suppressed(self, line: int, analyzer: str) -> bool:
        ids = self.suppressions.get(line)
        return bool(ids) and ("*" in ids or analyzer in ids)


def _module_name(path: str, repo: str) -> Tuple[str, bool]:
    rel = os.path.relpath(path, repo).replace(os.sep, ".")
    rel = rel[:-3] if rel.endswith(".py") else rel
    if rel.endswith(".__init__"):
        return rel[:-9], True
    return rel, False


def discover(targets: List[str], repo: str = REPO) -> List[str]:
    """Expand file/dir targets into a sorted list of .py files."""
    files: List[str] = []
    for t in targets:
        t = t if os.path.isabs(t) else os.path.join(repo, t)
        if os.path.isfile(t):
            files.append(t)
        else:
            for root, dirs, names in os.walk(t):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                files.extend(os.path.join(root, n) for n in names
                             if n.endswith(".py"))
    return sorted(set(files))


class Project:
    """Every target file parsed once, with symbol tables and resolution."""

    def __init__(self, files: List[str], repo: str = REPO):
        self.repo = repo
        self.files: List[SourceFile] = []
        self.by_module: Dict[str, SourceFile] = {}
        for path in files:
            rel = os.path.relpath(path, repo).replace(os.sep, "/")
            try:
                with open(path, "rb") as f:
                    text = f.read().decode("utf-8", "replace")
            except OSError:
                continue
            module, is_pkg = _module_name(path, repo)
            err = None
            try:
                tree = ast.parse(text, filename=path)
            except SyntaxError as e:
                err = f"syntax error: {e.msg}"
                tree = ast.Module(body=[], type_ignores=[])
            symbols = SymbolTable(module, is_pkg)
            symbols.visit(tree)
            sf = SourceFile(path=path, rel=rel, module=module, is_pkg=is_pkg,
                            text=text, lines=text.splitlines(), tree=tree,
                            symbols=symbols, syntax_error=err,
                            suppressions=_scan_suppressions(text))
            self.files.append(sf)
            self.by_module[module] = sf

    @classmethod
    def from_targets(cls, targets: Optional[List[str]] = None,
                     repo: str = REPO) -> "Project":
        return cls(discover(targets or DEFAULT_TARGETS, repo), repo)

    # -- resolution --
    def canonical(self, sf: SourceFile, dotted: Optional[str],
                  _depth: int = 0) -> Optional[str]:
        """Follow import aliases (incl. re-exports through package modules)
        to a fully-qualified dotted name. Best-effort: unknown names resolve
        to themselves-qualified-by-nothing (returned as written)."""
        if not dotted or _depth > 4:
            return dotted
        head, _, rest = dotted.partition(".")
        target = sf.symbols.import_targets.get(head)
        if target is None:
            # a local definition: qualify by this module
            if head in sf.symbols.functions or head in sf.symbols.classes:
                return f"{sf.module}.{dotted}"
            return dotted
        resolved = f"{target}.{rest}" if rest else target
        # follow re-exports through other in-project modules: e.g.
        # core.compat.shard_map is itself an import of the jax one
        for modlen in range(resolved.count(".") + 1, 0, -1):
            mod = ".".join(resolved.split(".")[:modlen])
            inner = self.by_module.get(mod)
            if inner is not None and inner is not sf:
                tail = resolved[len(mod) + 1:]
                if tail:
                    deeper = self.canonical(inner, tail, _depth + 1)
                    if deeper and deeper != tail:
                        return deeper
                break
        return resolved

    # -- finding post-processing --
    def finalize(self, findings: List[Finding],
                 ran: Optional[Iterable[str]] = None,
                 known: Optional[Iterable[str]] = None) -> List[Finding]:
        """Drop suppressed findings, attach fingerprints, sort.

        When ``ran`` (the analyzer ids that executed this run) is given,
        every ``# lint-ok`` comment is audited: a suppression naming an
        analyzer that *ran* yet matched no finding is itself reported (id
        ``unused-suppression``) — stale suppressions hide future
        regressions. A named analyzer that did not run is left unjudged; a
        bare ``# lint-ok`` is only judged when ``ran`` covers the whole
        registry (``known``). Ids absent from ``known`` are flagged as
        typos.
        """
        by_rel = {sf.rel: sf for sf in self.files}
        kept: List[Finding] = []
        #: (path, line) -> analyzer ids a suppression actually absorbed
        matched: Dict[Tuple[str, int], Set[str]] = {}
        for f in findings:
            sf = by_rel.get(f.path)
            if sf is not None and sf.suppressed(f.line, f.analyzer):
                matched.setdefault((f.path, f.line), set()).add(f.analyzer)
                continue
            kept.append(f)
        if ran is not None:
            kept.extend(self._audit_suppressions(set(ran),
                                                 set(known or ()), matched))
        occurrence: Dict[Tuple[str, str, str], int] = {}
        out: List[Finding] = []
        for f in sorted(kept,
                        key=lambda f: (f.path, f.line, f.col, f.analyzer)):
            sf = by_rel.get(f.path)
            line_text = ""
            if sf is not None and 0 < f.line <= len(sf.lines):
                line_text = sf.lines[f.line - 1].strip()
            key = (f.analyzer, f.path, line_text)
            idx = occurrence.get(key, 0)
            occurrence[key] = idx + 1
            raw = f"{f.analyzer}|{f.path}|{line_text}|{idx}"
            f.fingerprint = hashlib.sha1(raw.encode()).hexdigest()[:16]
            out.append(f)
        return out

    def _audit_suppressions(self, ran: Set[str], known: Set[str],
                            matched: Dict[Tuple[str, int], Set[str]]
                            ) -> List[Finding]:
        extra: List[Finding] = []
        full_run = bool(known) and ran >= known
        for sf in self.files:
            for line, ids in sorted(sf.suppressions.items()):
                hit = matched.get((sf.rel, line), set())
                if ids == {"*"}:
                    if full_run and not hit:
                        extra.append(Finding(
                            analyzer="unused-suppression", path=sf.rel,
                            line=line, col=0,
                            message=("bare `# lint-ok` suppressed nothing "
                                     "— remove it, or name the analyzer "
                                     "it is meant for")))
                    continue
                for aid in sorted(ids - hit):
                    if known and aid not in known:
                        extra.append(Finding(
                            analyzer="unused-suppression", path=sf.rel,
                            line=line, col=0,
                            message=(f"`# lint-ok: {aid}` names an unknown "
                                     "analyzer id (see --list) — the "
                                     "suppression can never match")))
                    elif aid in ran:
                        extra.append(Finding(
                            analyzer="unused-suppression", path=sf.rel,
                            line=line, col=0,
                            message=(f"`# lint-ok: {aid}` suppressed "
                                     f"nothing — `{aid}` ran and found no "
                                     "issue on this line; remove the stale "
                                     "suppression")))
        return extra


def _scan_suppressions(text: str) -> Dict[int, Set[str]]:
    """line -> suppressed analyzer ids, from real COMMENT tokens only.

    Tokenizing (instead of grepping lines) keeps ``lint-ok`` inside string
    literals, docstrings and test fixtures from registering as suppressions;
    anchoring at the comment start keeps prose *mentioning* the marker from
    matching. Falls back to a plain line scan when the file doesn't tokenize
    (the syntax-error path still parses what it can).
    """
    out: Dict[int, Set[str]] = {}
    if "lint-ok" not in text:
        return out
    try:
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type != tokenize.COMMENT or "lint-ok" not in tok.string:
                continue
            m = _SUPPRESS_RE.match(tok.string)
            if m:
                out[tok.start[0]] = _suppress_ids(m)
    except (tokenize.TokenError, IndentationError, SyntaxError):
        out.clear()
        for i, line in enumerate(text.splitlines(), 1):
            if "lint-ok" not in line:
                continue
            hash_at = line.find("#")
            m = _SUPPRESS_RE.match(line[hash_at:]) if hash_at >= 0 else None
            if m:
                out[i] = _suppress_ids(m)
    return out


def _suppress_ids(m: "re.Match") -> Set[str]:
    ids = m.group(1)
    return ({s.strip() for s in ids.split(",") if s.strip()} if ids
            else {"*"})


def walk_calls(root: ast.AST) -> Iterator[ast.Call]:
    for n in ast.walk(root):
        if isinstance(n, ast.Call):
            yield n
