"""Shared AST infrastructure: findings, module model, function roles.

``SourceModule`` parses one file and precomputes what every checker
needs:

  * an import-alias map so call names canonicalize (``pl.store`` →
    ``jax.experimental.pallas.store`` whatever the local alias);
  * parent links on every AST node;
  * function roles — *hot* (``@hot_path`` / ``config.HOT_PATHS``),
    *traced* (passed to ``jax.jit`` / ``shard_map`` / ``pmap``, or
    decorated with them), *kernel* (passed to ``pl.pallas_call``,
    directly or through ``functools.partial`` / an assigned alias).

Role discovery is intentionally *syntactic and intra-module*: the
checkers never import the code they scan, so a function is traced/kernel
only when this module can see it handed to the tracer.  That
conservatism is the right default for a contract checker — it can miss,
but what it flags is real.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis import config
from repro.analysis.pragmas import BAD_PRAGMA_RULE, parse_pragmas


@dataclass(frozen=True, order=True)
class Finding:
    """One checker hit, stable enough to baseline and diff."""

    file: str     # path as given to the CLI (repo-relative in CI)
    line: int     # 1-indexed
    rule: str     # checker id, e.g. "pallas-index"
    message: str

    def render(self) -> str:
        return f"{self.file}:{self.line}: {self.rule}: {self.message}"

    def key(self) -> str:
        """Baseline identity (includes the line: the meta-test pins the
        baseline to an exact fresh run, so drift is caught, not hidden)."""
        return f"{self.file}:{self.line}:{self.rule}:{self.message}"


# canonical roots treated as "the jax namespace" after alias resolution
_TRACER_NAMES = {
    "jax.jit",
    "jax.pmap",
    "jax.experimental.shard_map.shard_map",
    "jax.shard_map",
}
_PALLAS_CALL = {"jax.experimental.pallas.pallas_call"}
_PARTIAL_NAMES = {"functools.partial"}


def _module_name_for(path: str) -> Optional[str]:
    """Dotted module for files under a ``src/`` root (else None)."""
    norm = os.path.normpath(path).replace(os.sep, "/")
    if "/src/" in norm:
        rel = norm.split("/src/", 1)[1]
    elif norm.startswith("src/"):
        rel = norm[len("src/"):]
    else:
        return None
    if not rel.endswith(".py"):
        return None
    rel = rel[:-3]
    if rel.endswith("/__init__"):
        rel = rel[: -len("/__init__")]
    return rel.replace("/", ".")


class _Parenter(ast.NodeVisitor):
    def generic_visit(self, node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            child._repro_parent = node  # type: ignore[attr-defined]
        super().generic_visit(node)


def parent(node: ast.AST) -> Optional[ast.AST]:
    return getattr(node, "_repro_parent", None)


@dataclass
class FunctionInfo:
    """One (possibly nested) function definition and its roles."""

    node: ast.AST                 # FunctionDef / AsyncFunctionDef / Lambda
    qualname: str
    hot: bool = False
    traced: bool = False
    kernel: bool = False
    hot_line: Optional[int] = None


class SourceModule:
    """Parsed file + alias map + pragmas + function role table."""

    def __init__(self, path: str, source: Optional[str] = None):
        self.path = path
        if source is None:
            with open(path, "r", encoding="utf-8") as f:
                source = f.read()
        self.source = source
        self.tree = ast.parse(source, filename=path)
        _Parenter().visit(self.tree)
        self.module = _module_name_for(path)
        self.aliases = self._collect_aliases()
        self.suppress, self.bad_pragmas, self.pragmas = parse_pragmas(source)
        self.functions: Dict[ast.AST, FunctionInfo] = {}
        self._collect_functions()
        self._assign_roles()

    # -- imports ------------------------------------------------------------

    def _collect_aliases(self) -> Dict[str, str]:
        aliases: Dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for a in node.names:
                    if a.name == "*":
                        continue
                    aliases[a.asname or a.name] = f"{node.module}.{a.name}"
        # the two jax spellings everyone uses
        aliases.setdefault("jnp", "jax.numpy")
        return aliases

    def dotted(self, node: ast.AST) -> Optional[str]:
        """Canonical dotted name of a Name/Attribute chain (alias-resolved
        at the root), e.g. ``pl.store`` → ``jax.experimental.pallas.store``;
        None for anything that is not a plain chain."""
        parts: List[str] = []
        cur = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if not isinstance(cur, ast.Name):
            return None
        parts.append(cur.id)
        parts.reverse()
        parts[0] = self.aliases.get(parts[0], parts[0])
        return ".".join(parts)

    def call_name(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Call):
            return self.dotted(node.func)
        return None

    # -- functions + roles --------------------------------------------------

    def _collect_functions(self) -> None:
        stack: List[str] = []

        def visit(node: ast.AST) -> None:
            is_fn = isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            is_cls = isinstance(node, ast.ClassDef)
            if is_fn or is_cls:
                stack.append(node.name)
                if is_fn:
                    qual = ".".join(stack)
                    self.functions[node] = FunctionInfo(node, qual)
            elif isinstance(node, ast.Lambda):
                qual = ".".join(stack + ["<lambda>"])
                self.functions[node] = FunctionInfo(node, qual)
            for child in ast.iter_child_nodes(node):
                visit(child)
            if is_fn or is_cls:
                stack.pop()

        visit(self.tree)

    def _callable_target(self, node: ast.AST,
                         partial_alias: Dict[str, ast.AST]) -> Optional[
                             ast.AST]:
        """Resolve the function an expression hands to a tracer: a bare
        name, a ``functools.partial(f, ...)``, a lambda, or a local alias
        previously assigned from one of those."""
        if isinstance(node, ast.Lambda):
            return node
        if isinstance(node, ast.Name):
            if node.id in partial_alias:
                return partial_alias[node.id]
            return self._find_def(node)
        if isinstance(node, ast.Call) \
                and self.dotted(node.func) in _PARTIAL_NAMES and node.args:
            return self._callable_target(node.args[0], partial_alias)
        return None

    def _find_def(self, name_node: ast.Name) -> Optional[ast.AST]:
        """Nearest enclosing-scope FunctionDef whose name matches."""
        target = name_node.id
        scope: Optional[ast.AST] = name_node
        while scope is not None:
            for fn in self.functions:
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and fn.name == target \
                        and parent(fn) is not None \
                        and self._same_or_enclosing(parent(fn), scope):
                    return fn
            scope = parent(scope)
        return None

    @staticmethod
    def _same_or_enclosing(container: ast.AST, node: ast.AST) -> bool:
        cur: Optional[ast.AST] = node
        while cur is not None:
            if cur is container:
                return True
            cur = parent(cur)
        return False

    def _assign_roles(self) -> None:
        # local aliases: name = functools.partial(kernel_fn, ...)
        partial_alias: Dict[str, ast.AST] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and self.dotted(node.value.func) in _PARTIAL_NAMES \
                    and node.value.args:
                tgt = self._callable_target(node.value.args[0], {})
                if tgt is not None:
                    partial_alias[node.targets[0].id] = tgt

        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self.dotted(node.func)
            if name in _TRACER_NAMES and node.args:
                tgt = self._callable_target(node.args[0], partial_alias)
                if tgt is not None and tgt in self.functions:
                    self.functions[tgt].traced = True
            elif name is not None and name in _PALLAS_CALL and node.args:
                tgt = self._callable_target(node.args[0], partial_alias)
                if tgt is not None and tgt in self.functions:
                    self.functions[tgt].kernel = True

        for fn, info in self.functions.items():
            if isinstance(fn, ast.Lambda):
                continue
            for dec in fn.decorator_list:
                dname = (self.dotted(dec.func) if isinstance(dec, ast.Call)
                         else self.dotted(dec))
                if dname is None:
                    continue
                if dname.endswith(".hot_path") or dname == "hot_path":
                    info.hot = True
                    info.hot_line = dec.lineno
                if dname in _TRACER_NAMES:
                    info.traced = True
                if isinstance(dec, ast.Call) \
                        and self.dotted(dec.func) in _PARTIAL_NAMES \
                        and dec.args \
                        and self.dotted(dec.args[0]) in _TRACER_NAMES:
                    info.traced = True
            if self.module is not None:
                if f"{self.module}.{info.qualname}" in config.HOT_PATHS:
                    info.hot = True

    # -- convenience --------------------------------------------------------

    def enclosing_function(self, node: ast.AST) -> Optional[FunctionInfo]:
        cur = parent(node)
        while cur is not None:
            if cur in self.functions:
                return self.functions[cur]
            cur = parent(cur)
        return None

    def functions_of_role(self, role: str) -> List[FunctionInfo]:
        return [i for i in self.functions.values() if getattr(i, role)]


class Checker:
    """Base class: one rule id, one ``check(SourceModule)`` pass."""

    rule: str = ""

    def check(self, mod: SourceModule) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, mod: SourceModule, node: ast.AST,
                message: str) -> Finding:
        return Finding(file=mod.path, line=getattr(node, "lineno", 0),
                       rule=self.rule, message=message)


def collect_files(paths: Sequence[str]) -> List[str]:
    """Expand files/dirs to a sorted ``.py`` list, excluding fixture and
    cache directories (fixtures are known-bad corpora that must flag in
    tests, not in CI)."""
    out: Set[str] = set()
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                out.add(os.path.normpath(p))
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = sorted(d for d in dirs
                             if d not in config.EXCLUDED_DIR_NAMES)
            for f in sorted(files):
                if f.endswith(".py"):
                    out.add(os.path.normpath(os.path.join(root, f)))
    return sorted(out)


def run_module(mod: SourceModule, checkers: Iterable[Checker],
               ) -> Tuple[List[Finding], List[Finding]]:
    """Run ``checkers`` over one module → (kept, suppressed) findings.
    Malformed pragmas surface as ``bad-pragma`` findings (never
    suppressible)."""
    kept: List[Finding] = []
    suppressed: List[Finding] = []
    for checker in checkers:
        for f in checker.check(mod):
            if checker.rule in mod.suppress.get(f.line, ()):
                suppressed.append(f)
            else:
                kept.append(f)
    for line, problem in mod.bad_pragmas:
        kept.append(Finding(file=mod.path, line=line,
                            rule=BAD_PRAGMA_RULE, message=problem))
    return kept, suppressed


def run_paths(paths: Sequence[str],
              checkers: Optional[Iterable[Checker]] = None,
              ) -> Tuple[List[Finding], List[Finding], List[Finding]]:
    """Analyze ``paths`` → (findings, suppressed, errors).  ``errors``
    are files the parser rejected, reported as ``parse-error`` findings
    so a syntactically broken file fails the shard instead of silently
    dropping out of coverage."""
    if checkers is None:
        from repro.analysis.checkers import get_checkers
        checkers = get_checkers()
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    errors: List[Finding] = []
    for path in collect_files(paths):
        try:
            mod = SourceModule(path)
        except (SyntaxError, UnicodeDecodeError) as e:
            errors.append(Finding(file=path,
                                  line=getattr(e, "lineno", 0) or 0,
                                  rule="parse-error", message=str(e)))
            continue
        kept, supp = run_module(mod, checkers)
        findings.extend(kept)
        suppressed.extend(supp)
    findings.sort()
    suppressed.sort()
    return findings, suppressed, errors
