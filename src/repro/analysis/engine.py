"""tracecheck engine — AST visitor framework + per-module reachability.

The engine owns everything rule modules share:

* :class:`ModuleContext` — one parsed file: import-alias resolution
  (``jnp.asarray`` → ``jax.numpy.asarray``), a qualified-name function
  table, a per-module call/reference graph, and the **jit-reachability
  closure**.  Roots are functions decorated with (or wrapped by)
  ``jax.jit``-family transforms and closures handed to trace-taking
  callables (``lax.fori_loop``/``while_loop``/``scan``/``cond``/
  ``switch``/``map``, ``vmap``/``pmap``/``shard_map``, plus
  config-listed extras like ``adaptive_search``); reachability
  propagates along call/reference edges and into functions *defined
  inside* reachable functions (closure bodies trace with their parent).
* Suppressions — ``# tracecheck: ignore[TRC00x] -- reason`` on the
  finding's line or alone on the preceding line.  The justification is
  mandatory: a bare ``ignore[...]`` suppresses its target but raises
  TRC000.
* :class:`Finding`, the runner (:func:`run`), and JSON/human reports.

Host-orchestration code (``fit`` drivers, result assembly) is *not*
jit-reachable by construction, so host reads there never fire TRC001 —
the rules only police code that executes under a trace.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from .config import Config, path_in_scope

__all__ = [
    "Finding", "FuncInfo", "ModuleContext", "Report",
    "analyze_file", "run", "format_human", "report_to_json",
]

SUPPRESS_RE = re.compile(
    r"#\s*tracecheck:\s*ignore\[([A-Za-z0-9_,\s]+)\]\s*(?:--\s*(\S.*))?")

# Callables whose function-valued arguments execute under a trace.
TRACE_TAKERS = frozenset({
    "jax.jit", "jax.vmap", "jax.pmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.custom_jvp", "jax.custom_vjp",
    "jax.lax.while_loop", "jax.lax.fori_loop", "jax.lax.scan",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "jax.shard_map",
})

# Decorators that make the decorated function a trace root.
JIT_DECORATORS = frozenset({
    "jax.jit", "jax.vmap", "jax.pmap",
    "jax.shard_map",
})

_FUNC_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)


@dataclasses.dataclass
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str
    function: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    def human(self) -> str:
        where = f" [{self.function}]" if self.function else ""
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule}{where} {self.message}")


@dataclasses.dataclass
class FuncInfo:
    qualname: str
    node: ast.AST                     # FunctionDef / AsyncFunctionDef / Lambda
    parent: Optional[str] = None      # qualname of enclosing *function*
    cls: Optional[str] = None         # name of enclosing class, if a method
    reach_reason: str = ""            # why jit-reachable ("" = not reachable)


class _FuncCollector(ast.NodeVisitor):
    """Builds the function table with dotted qualified names."""

    def __init__(self) -> None:
        self.funcs: Dict[str, FuncInfo] = {}
        self.by_node: Dict[int, FuncInfo] = {}
        self._scope: List[str] = []          # qualname parts
        self._func_stack: List[str] = []     # enclosing function qualnames
        self._class_stack: List[str] = []

    def _qual(self, name: str) -> str:
        return ".".join(self._scope + [name])

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._scope.append(node.name)
        self._class_stack.append(node.name)
        self.generic_visit(node)
        self._class_stack.pop()
        self._scope.pop()

    def _visit_func(self, node) -> None:
        qual = self._qual(node.name)
        info = FuncInfo(
            qualname=qual,
            node=node,
            parent=self._func_stack[-1] if self._func_stack else None,
            cls=self._class_stack[-1] if self._class_stack else None,
        )
        # First definition wins for name collisions (rare; over-approx).
        self.funcs.setdefault(qual, info)
        self.by_node[id(node)] = info
        self._scope.append(node.name)
        self._func_stack.append(qual)
        self.generic_visit(node)
        self._func_stack.pop()
        self._scope.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func


class ModuleContext:
    """One parsed source file plus everything the rules need to see."""

    def __init__(self, path: str, source: str, config: Config) -> None:
        self.path = path.replace(os.sep, "/")
        self.source = source
        self.config = config
        self.tree = ast.parse(source, filename=path)
        self.lines = source.splitlines()
        self.suppressions, self.bare_suppressions = self._parse_suppressions()
        self.aliases = self._collect_aliases()
        collector = _FuncCollector()
        collector.visit(self.tree)
        self.functions: Dict[str, FuncInfo] = collector.funcs
        self._by_node = collector.by_node
        self._lambda_roots: List[FuncInfo] = []
        self._simple_names: Dict[str, List[str]] = {}
        for qual in self.functions:
            self._simple_names.setdefault(qual.rsplit(".", 1)[-1],
                                          []).append(qual)
        self._edges = self._call_graph()
        self._reachable = self._reachability_closure()

    # ---------------------------------------------------------- aliases

    def _collect_aliases(self) -> Dict[str, str]:
        amap: Dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        amap[a.asname] = a.name
                    else:
                        first = a.name.split(".", 1)[0]
                        amap[first] = first
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                mod = node.module or ""
                for a in node.names:
                    if a.name == "*":
                        continue
                    amap[a.asname or a.name] = (
                        f"{mod}.{a.name}" if mod else a.name)
        return amap

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Dotted path of a Name/Attribute chain with aliases applied."""
        if isinstance(node, ast.Name):
            return self.aliases.get(node.id, node.id)
        if isinstance(node, ast.Attribute):
            base = self.resolve(node.value)
            return None if base is None else f"{base}.{node.attr}"
        return None

    # ------------------------------------------------------ suppressions

    def _parse_suppressions(self) -> Tuple[Dict[int, Set[str]], List[int]]:
        sup: Dict[int, Set[str]] = {}
        bare: List[int] = []
        lines = self.source.splitlines()
        for i, line in enumerate(lines, 1):
            m = SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            code = line.split("#", 1)[0]
            if code.strip():
                target = i
            else:
                # Standalone comment: applies to the next code line, so a
                # multi-line justification block stays one suppression.
                target = i + 1
                for j in range(i, len(lines)):
                    stripped = lines[j].strip()
                    if stripped and not stripped.startswith("#"):
                        target = j + 1
                        break
            sup.setdefault(target, set()).update(rules)
            if not m.group(2):
                bare.append(i)
        return sup, bare

    def suppressed(self, rule: str, line: int) -> bool:
        return rule in self.suppressions.get(line, ())

    # -------------------------------------------------------- call graph

    def _local_targets(self, node: ast.AST) -> List[str]:
        """Local functions a Name/Attribute reference may point at."""
        if isinstance(node, ast.Name):
            if node.id in self.aliases and self.aliases[node.id] != node.id:
                return []  # shadowed by an import
            return list(self._simple_names.get(node.id, ()))
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in ("self", "cls")):
            return list(self._simple_names.get(node.attr, ()))
        return []

    def _call_graph(self) -> Dict[str, Set[str]]:
        edges: Dict[str, Set[str]] = {q: set() for q in self.functions}
        for info in self.functions.values():
            for node in self.walk_own(info.node):
                if isinstance(node, (ast.Name, ast.Attribute)):
                    for tgt in self._local_targets(node):
                        if tgt != info.qualname:
                            edges[info.qualname].add(tgt)
        return edges

    # ------------------------------------------------------ reachability

    def _is_banned(self, qual: str) -> bool:
        hb = self.config.host_boundary
        return any(qual == b or qual.endswith("." + b) for b in hb)

    def _decorator_roots(self) -> Iterator[Tuple[str, str]]:
        for info in self.functions.values():
            node = info.node
            if not isinstance(node, _FUNC_DEFS):
                continue
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                r = self.resolve(target)
                if r in JIT_DECORATORS:
                    yield info.qualname, f"decorated @{r}"
                elif r in ("functools.partial", "partial") and isinstance(
                        dec, ast.Call):
                    if dec.args and self.resolve(
                            dec.args[0]) in JIT_DECORATORS:
                        yield (info.qualname,
                               f"decorated @partial({self.resolve(dec.args[0])})")

    def _callsite_roots(self) -> Iterator[Tuple[str, str]]:
        extra = set(self.config.extra_trace_takers)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            r = self.resolve(node.func)
            simple = r.rsplit(".", 1)[-1] if r else None
            if r not in TRACE_TAKERS and simple not in extra:
                continue
            taker = r or simple
            args = list(node.args) + [kw.value for kw in node.keywords]
            for a in args:
                if isinstance(a, ast.Lambda):
                    info = FuncInfo(
                        qualname=f"<lambda:{a.lineno}>", node=a,
                        reach_reason=f"lambda passed to {taker}")
                    self._lambda_roots.append(info)
                    continue
                for tgt in self._local_targets(a):
                    yield tgt, f"passed to {taker}"
                if isinstance(a, ast.Call):
                    # functools.partial(fn, ...) handed to a trace taker
                    pr = self.resolve(a.func)
                    if pr in ("functools.partial", "partial"):
                        for pa in a.args:
                            for tgt in self._local_targets(pa):
                                yield tgt, f"partial passed to {taker}"

    def _assignment_roots(self) -> Iterator[Tuple[str, str]]:
        # X = jax.jit(fn, ...)  /  X = functools.partial(jax.jit, ...)(fn)
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            value = getattr(node, "value", None)
            if not isinstance(value, ast.Call):
                continue
            r = self.resolve(value.func)
            if r in JIT_DECORATORS:
                for a in value.args:
                    for tgt in self._local_targets(a):
                        yield tgt, f"wrapped by {r} assignment"

    def _reachability_closure(self) -> Dict[str, str]:
        reach: Dict[str, str] = {}

        def add(qual: str, reason: str) -> None:
            if qual in self.functions and qual not in reach:
                if not self._is_banned(qual):
                    reach[qual] = reason

        for qual, reason in self._decorator_roots():
            add(qual, reason)
        for qual, reason in self._assignment_roots():
            add(qual, reason)
        for qual, reason in self._callsite_roots():
            add(qual, reason)
        if path_in_scope(self.path, self.config.all_roots_paths):
            for qual, info in self.functions.items():
                if info.parent is None and info.cls is None:
                    add(qual, "kernel-module public surface")

        changed = True
        while changed:
            changed = False
            for qual in list(reach):
                for succ in self._edges.get(qual, ()):
                    if succ not in reach:
                        add(succ, f"called from {qual}")
                        changed = succ in reach or changed
            for qual, info in self.functions.items():
                if qual in reach or info.parent is None:
                    continue
                if info.parent in reach:
                    add(qual, f"defined inside {info.parent}")
                    changed = qual in reach or changed

        for info in self.functions.values():
            info.reach_reason = reach.get(info.qualname, "")
        return reach

    # ---------------------------------------------------------- walking

    @staticmethod
    def walk_own(func_node: ast.AST) -> Iterator[ast.AST]:
        """Walk a function body without descending into nested defs.

        Lambdas ARE descended into: a lambda inside a traced function
        traces with it, and lambdas have no table entry of their own
        unless passed straight to a trace taker.
        """
        body = getattr(func_node, "body", None)
        todo = list(body) if isinstance(body, list) else [body]
        while todo:
            n = todo.pop()
            if n is None or isinstance(n, _FUNC_DEFS):
                continue
            yield n
            todo.extend(ast.iter_child_nodes(n))

    def reachable_functions(self) -> Iterator[FuncInfo]:
        for info in self.functions.values():
            if info.reach_reason:
                yield info
        for info in self._lambda_roots:
            yield info

    def walk_scoped(self) -> Iterator[Tuple[ast.AST, str]]:
        """Yield every node with its enclosing function qualname ("" =
        module level)."""

        def rec(node: ast.AST, scope: str) -> Iterator[Tuple[ast.AST, str]]:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, _FUNC_DEFS):
                    info = self._by_node.get(id(child))
                    inner = info.qualname if info else child.name
                    yield child, scope
                    yield from rec(child, inner)
                else:
                    yield child, scope
                    yield from rec(child, scope)

        yield from rec(self.tree, "")

    def finding(self, rule: str, node: ast.AST, message: str,
                function: str = "") -> Finding:
        return Finding(rule=rule, path=self.path,
                       line=getattr(node, "lineno", 0),
                       col=getattr(node, "col_offset", 0),
                       message=message, function=function)


# ------------------------------------------------------------------ runner

@dataclasses.dataclass
class Report:
    findings: List[Finding]
    files_scanned: int
    suppressed: int

    @property
    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return dict(sorted(out.items()))


def _iter_py_files(paths: Iterable[str],
                   exclude: Tuple[str, ...]) -> Iterator[str]:
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = sorted(
                d for d in dirs
                if not path_in_scope(
                    os.path.join(root, d).replace(os.sep, "/") + "/",
                    exclude))
            for name in sorted(files):
                if name.endswith(".py"):
                    yield os.path.join(root, name)


def analyze_file(path: str, config: Config,
                 rules=None) -> Tuple[List[Finding], int]:
    """Run the rule pack on one file → (findings, n_suppressed)."""
    from . import rules as rulepack
    if rules is None:
        rules = rulepack.ALL_RULES
    with open(path, encoding="utf-8") as fh:
        source = fh.read()
    try:
        ctx = ModuleContext(path, source, config)
    except SyntaxError as exc:
        return [Finding("TRC-PARSE", path.replace(os.sep, "/"),
                        exc.lineno or 0, exc.offset or 0,
                        f"could not parse: {exc.msg}")], 0

    findings: List[Finding] = []
    suppressed = 0
    for rule in rules:
        scope = config.rule_scope(rule.rule_id)
        if scope and not path_in_scope(ctx.path, scope):
            continue
        for f in rule.check(ctx, config):
            if ctx.suppressed(f.rule, f.line):
                suppressed += 1
            else:
                findings.append(f)
    # TRC000: suppression comments without a `-- reason` justification.
    for line in ctx.bare_suppressions:
        findings.append(Finding(
            "TRC000", ctx.path, line, 0,
            "suppression without justification — use "
            "`# tracecheck: ignore[RULE] -- <why this is safe>`"))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, suppressed


def run(paths: Iterable[str], config: Config, rules=None) -> Report:
    findings: List[Finding] = []
    suppressed = 0
    n_files = 0
    for path in _iter_py_files(paths, config.exclude):
        n_files += 1
        fs, sup = analyze_file(path, config, rules=rules)
        findings.extend(fs)
        suppressed += sup
    return Report(findings=findings, files_scanned=n_files,
                  suppressed=suppressed)


# ----------------------------------------------------------------- output

def report_to_json(report: Report) -> dict:
    return {
        "tool": "tracecheck",
        "version": 1,
        "files_scanned": report.files_scanned,
        "suppressed": report.suppressed,
        "counts": report.counts,
        "findings": [f.to_json() for f in report.findings],
    }


def format_human(report: Report) -> str:
    lines = [f.human() for f in report.findings]
    tail = (f"{len(report.findings)} finding(s) in "
            f"{report.files_scanned} file(s), "
            f"{report.suppressed} suppressed")
    if report.findings:
        per_rule = ", ".join(f"{k}={v}" for k, v in report.counts.items())
        tail += f" [{per_rule}]"
    lines.append(tail)
    return "\n".join(lines)


def dump_json(report: Report, path: str) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(report_to_json(report), fh, indent=2, sort_keys=True)
        fh.write("\n")
