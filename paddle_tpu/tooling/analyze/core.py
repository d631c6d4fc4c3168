"""graft-lint infrastructure: source model, suppressions, ratchet baseline.

The analyzer is pure `ast` + `tokenize` over the working tree — no imports
of the analyzed code, no jax, so it runs in well under a second per
hundred files and can never be broken by a backend.  Each rule receives a
:class:`SourceFile` (parsed tree, comment/suppression map, import aliases,
scope index, traced-function set) and yields :class:`Finding`s.

Ratchet contract (the CI seat of the reference's L0 ``PADDLE_ENFORCE``
discipline): findings are fingerprinted WITHOUT line numbers — (rule,
file, enclosing symbol, message) — and the committed baseline stores a
multiset of fingerprints.  A run fails only when some fingerprint's count
EXCEEDS its baseline count, so pre-existing findings never block a PR,
moving code never churns the baseline, and any new instance of a flagged
class fails tier-1 the moment it is written.
"""

from __future__ import annotations

import ast
import hashlib
import io
import json
import os
import re
import tokenize
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Set, Tuple

__all__ = [
    "Finding", "Rule", "SourceFile", "iter_source_files",
    "analyze_paths", "baseline_counts", "load_baseline",
    "save_baseline", "new_findings", "DEFAULT_BASELINE_PATH",
]

# the committed ratchet baseline rides next to the analyzer itself
DEFAULT_BASELINE_PATH = os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "baseline.json")

_SUPPRESS_RE = re.compile(
    r"graft-lint:\s*disable=([A-Za-z0-9_,\s]+)")


@dataclass(frozen=True)
class Finding:
    """One rule hit.  ``message`` must not embed line numbers — the
    ratchet fingerprint hashes it, and line drift must not read as a new
    finding."""

    rule: str
    path: str            # repo-relative, forward slashes
    line: int
    col: int
    message: str
    symbol: str = ""     # enclosing function/class qualname ('' = module)

    def fingerprint(self) -> str:
        raw = "|".join((self.rule, self.path, self.symbol, self.message))
        return hashlib.sha1(raw.encode()).hexdigest()[:12]

    def format(self) -> str:
        where = f" [{self.symbol}]" if self.symbol else ""
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule}{where} {self.message}")

    def to_json(self) -> Dict[str, Any]:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "symbol": self.symbol,
                "message": self.message,
                "fingerprint": self.fingerprint()}


# --------------------------------------------------------------- rule base

class Rule:
    """Base of every graft-lint rule (lives here so the intra-file rule
    set in `rules.py` and the interprocedural set in `interproc.py`
    can both build on it without importing each other)."""

    id = "R000"
    name = "base"
    # test modules deliberately WRITE the bad patterns (jit graph-break
    # fixtures, donation probes), so the code rules skip `test_*` files;
    # R010 (the tier-1 budget rule) inverts this and runs ONLY on them.
    tests_only = False

    def wants(self, sf: "SourceFile") -> bool:
        is_test = sf.stem.startswith("test_")
        return is_test if self.tests_only else not is_test

    def run(self, sources: List["SourceFile"]) -> List["Finding"]:
        out: List[Finding] = []
        for sf in sources:
            if self.wants(sf):
                out.extend(self.check_file(sf))
        return out

    def check_file(self, sf: "SourceFile") -> List["Finding"]:  # pragma: no cover
        return []

    def finding(self, sf: "SourceFile", node: ast.AST, message: str,
                symbol: Optional[str] = None) -> "Finding":
        return Finding(rule=self.id, path=sf.rel,
                       line=getattr(node, "lineno", 1),
                       col=getattr(node, "col_offset", 0),
                       message=message,
                       symbol=symbol if symbol is not None
                       else sf.symbol_for(node))


# --------------------------------------------------------------- the model

# callables whose function-valued argument gets TRACED (jit capture):
# code inside runs at trace time, not dispatch time.
TRACE_WRAPPERS = {
    "jit", "pjit", "to_static", "vmap", "pmap", "grad", "value_and_grad",
    "scan", "cond", "while_loop", "fori_loop", "switch", "shard_map",
    "remat", "custom_jvp", "custom_vjp",
}
# suffix forms still recognized (e.g. a module-local `_shard_map` alias)
_TRACE_SUFFIXES = ("jit", "to_static", "shard_map")


def callee_segment(func: ast.AST) -> Optional[str]:
    """Last dotted segment of a call target (``jax.lax.scan`` -> scan)."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _is_trace_wrapper(seg: Optional[str]) -> bool:
    if seg is None:
        return False
    base = seg.lstrip("_")
    if base in TRACE_WRAPPERS:
        return True
    return any(base.endswith(s) for s in _TRACE_SUFFIXES)


def expr_text(node: ast.AST) -> Optional[str]:
    """Dotted text of a Name/Attribute chain (``self.tables``), or None
    for anything else (calls, subscripts...)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclass
class ProgramInfo:
    """A variable holding a compiled/captured program in some scope."""

    target: str                      # dotted text of the bound name
    line: int
    donate: Tuple[int, ...] = ()     # resolved donate_argnums (may be ())
    kind: str = "jit"                # jit | to_static


class SourceFile:
    """Parsed view of one file plus everything the rules share."""

    def __init__(self, path: str, root: str):
        self.path = path
        rel = os.path.relpath(path, root)
        self.rel = rel.replace(os.sep, "/")
        with open(path, "rb") as f:
            raw = f.read()
        self.text = raw.decode("utf-8", errors="replace")
        self.tree = ast.parse(self.text, filename=self.rel)
        self.stem = os.path.splitext(os.path.basename(path))[0]
        self.suppress: Dict[int, Set[str]] = {}
        self.comment_only: Set[int] = set()
        self._collect_comments(raw)
        # ONE full pass builds parent links, the nearest-enclosing-
        # function map, the flat node list and the function/class lists —
        # every later consumer iterates these instead of re-walking
        self.parents: Dict[ast.AST, ast.AST] = {}
        self._nearest_fn: Dict[ast.AST, Optional[ast.AST]] = {}
        self.all_nodes: List[ast.AST] = []
        self.functions: List[ast.AST] = []
        self.classes: List[ast.ClassDef] = []
        _FN = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        stack: List[Tuple[ast.AST, Optional[ast.AST]]] = [(self.tree, None)]
        while stack:
            parent, fn = stack.pop()
            child_fn = parent if isinstance(parent, _FN) else fn
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
                self._nearest_fn[child] = child_fn
                self.all_nodes.append(child)
                if isinstance(child, _FN):
                    self.functions.append(child)
                elif isinstance(child, ast.ClassDef):
                    self.classes.append(child)
                stack.append((child, child_fn))
        # per-scope node buckets (lambda buckets merge into the nearest
        # real function: lambdas share the enclosing scope's variables);
        # rules iterate scopes many times — one pass here pays for all
        self._scope_nodes: Dict[Optional[ast.AST], List[ast.AST]] = {}
        for node, fn in self._nearest_fn.items():
            owner = fn
            while isinstance(owner, ast.Lambda):
                owner = self._nearest_fn.get(owner)
            self._scope_nodes.setdefault(owner, []).append(node)
        self.np_aliases, self.jnp_aliases, self.jax_aliases, \
            self.module_aliases = self._collect_aliases()
        self.traced: Set[ast.AST] = self._compute_traced()
        self.programs: Dict[ast.AST, Dict[str, ProgramInfo]] = \
            self._collect_programs()

    # ------------------------------------------------------------ comments
    def _collect_comments(self, raw: bytes) -> None:
        if "graft-lint" not in self.text:
            return      # tokenizing every file costs more than parsing it
        try:
            tokens = list(tokenize.tokenize(io.BytesIO(raw).readline))
        except (tokenize.TokenError, SyntaxError):  # pragma: no cover
            return
        code_lines: Set[int] = set()
        for tok in tokens:
            if tok.type == tokenize.COMMENT:
                m = _SUPPRESS_RE.search(tok.string)
                if m:
                    rules = {r.strip().upper() for r in
                             m.group(1).split(",") if r.strip()}
                    self.suppress.setdefault(
                        tok.start[0], set()).update(rules)
            elif tok.type not in (tokenize.NL, tokenize.NEWLINE,
                                  tokenize.INDENT, tokenize.DEDENT,
                                  tokenize.ENCODING, tokenize.ENDMARKER):
                for ln in range(tok.start[0], tok.end[0] + 1):
                    code_lines.add(ln)
        for ln in self.suppress:
            if ln not in code_lines:
                self.comment_only.add(ln)

    def suppressed(self, rule: str, line: int) -> bool:
        """``# graft-lint: disable=RXXX`` on the finding's line, or on a
        standalone comment line directly above it."""
        rules = self.suppress.get(line)
        if rules and (rule in rules or "ALL" in rules):
            return True
        rules = self.suppress.get(line - 1)
        if rules and line - 1 in self.comment_only and \
                (rule in rules or "ALL" in rules):
            return True
        return False

    # ------------------------------------------------------------- aliases
    def _collect_aliases(self):
        np_a, jnp_a, jax_a = {"np", "numpy"}, {"jnp"}, {"jax"}
        mod_a: Dict[str, str] = {}
        for node in self.all_nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name.split(".")[0]
                    if a.name == "numpy":
                        np_a.add(name)
                    elif a.name == "jax.numpy":
                        jnp_a.add(name)
                    elif a.name == "jax":
                        jax_a.add(name)
                    mod_a[name] = a.name
            elif isinstance(node, ast.ImportFrom):
                for a in node.names:
                    name = a.asname or a.name
                    # `from .. import flags as _flags` -> module alias
                    mod_a.setdefault(name, (node.module or "") + "." +
                                     a.name if node.module else a.name)
        return np_a, jnp_a, jax_a, mod_a

    # ------------------------------------------------------ traced closure
    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        return self._nearest_fn.get(node)

    def _fn_ancestors(self, node: ast.AST) -> Set[Optional[ast.AST]]:
        """The lexical function chain of ``node`` (plus None = module)."""
        out: Set[Optional[ast.AST]] = {None}
        fn = self.enclosing_function(node)
        while fn is not None:
            out.add(fn)
            fn = self.enclosing_function(fn)
        return out

    def _visible(self, fn: ast.AST, site: ast.AST) -> bool:
        """May a bare-Name reference at ``site`` resolve to function
        ``fn``?  Methods (direct child of a ClassDef) are only reachable
        via attributes; other defs must live in an enclosing scope."""
        if isinstance(self.parents.get(fn), ast.ClassDef):
            return False
        return self.enclosing_function(fn) in self._fn_ancestors(site)

    def enclosing_class(self, node: ast.AST) -> Optional[ast.ClassDef]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur
            cur = self.parents.get(cur)
        return None

    def qualname(self, node: ast.AST) -> str:
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            elif isinstance(cur, ast.Lambda):
                parts.append("<lambda>")
            cur = self.parents.get(cur)
        return ".".join(reversed(parts))

    def symbol_for(self, node: ast.AST) -> str:
        fn = node if isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef, ast.Lambda)) \
            else self.enclosing_function(node)
        if fn is None:
            return ""
        return self.qualname(fn)

    def in_traced(self, node: ast.AST) -> Optional[ast.AST]:
        """Nearest enclosing traced function of ``node`` (or None)."""
        fn = self.enclosing_function(node)
        while fn is not None:
            if fn in self.traced:
                return fn
            fn = self.enclosing_function(fn)
        return None

    def _compute_traced(self) -> Set[ast.AST]:
        by_name, _methods = self._fn_tables()
        traced: Set[ast.AST] = set()
        # (a) decorators
        for fn in self.functions:
            for dec in getattr(fn, "decorator_list", []):
                target = dec.func if isinstance(dec, ast.Call) else dec
                if _is_trace_wrapper(callee_segment(target)):
                    traced.add(fn)
        # (b) function names / lambdas passed to a trace wrapper (bare
        # names resolve LEXICALLY — a method `step` is not the local
        # `step` handed to jax.jit three scopes away)
        for node in self.all_nodes:
            if not isinstance(node, ast.Call):
                continue
            if not _is_trace_wrapper(callee_segment(node.func)):
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Lambda):
                    traced.add(arg)
                elif isinstance(arg, ast.Name):
                    for fn in by_name.get(arg.id, []):
                        if self._visible(fn, node):
                            traced.add(fn)
        # (c) lexical nesting + (d) local calls from traced bodies, to a
        # fixpoint: a helper invoked at trace time runs at trace time.
        # The edge graph is the shared per-module call graph (also the
        # seat of the interprocedural rules R007-R010).
        edges = self.call_edges()
        queue = list(traced)
        while queue:
            t = queue.pop()
            for c, _site in edges.get(t, ()):
                if c not in traced:
                    traced.add(c)
                    queue.append(c)
        return traced

    # ----------------------------------------------- per-module call graph
    def resolve_call(self, call: ast.Call) -> List[ast.AST]:
        """Resolve a call site to functions DEFINED IN THIS FILE: bare
        names lexically (the same discipline `_compute_traced` uses — a
        method `step` is not the local `step`), ``self.<m>`` to the
        enclosing class's method.  Empty for anything unresolvable
        (imports, attributes of other objects)."""
        by_name, methods = self._fn_tables()
        if isinstance(call.func, ast.Name):
            return [f for f in by_name.get(call.func.id, [])
                    if self._visible(f, call)]
        if isinstance(call.func, ast.Attribute) and \
                isinstance(call.func.value, ast.Name) and \
                call.func.value.id == "self":
            cls = self.enclosing_class(call)
            if cls is not None:
                m = methods.get((cls.name, call.func.attr))
                if m is not None:
                    return [m]
        return []

    def _fn_tables(self):
        if getattr(self, "_fn_tables_cache", None) is None:
            by_name: Dict[str, List[ast.AST]] = {}
            methods: Dict[Tuple[str, str], ast.AST] = {}
            for fn in self.functions:
                if isinstance(fn, ast.Lambda):
                    continue
                by_name.setdefault(fn.name, []).append(fn)
                cls = self.enclosing_class(fn)
                if cls is not None:
                    methods[(cls.name, fn.name)] = fn
            self._fn_tables_cache = (by_name, methods)
        return self._fn_tables_cache

    def call_edges(self) -> Dict[ast.AST, List[Tuple[ast.AST,
                                                     Optional[ast.Call]]]]:
        """The per-module CALL GRAPH: fn -> [(callee fn, call site)].
        A lexically nested def rides as an edge with site None (it may
        run whenever the parent does).  Memoized — `_compute_traced`
        and every interprocedural rule share one build."""
        if getattr(self, "_call_edges_cache", None) is not None:
            return self._call_edges_cache
        edges: Dict[ast.AST, List[Tuple[ast.AST,
                                        Optional[ast.Call]]]] = {}
        for fn in self.functions:
            if isinstance(fn, ast.Lambda):
                continue
            outs: List[Tuple[ast.AST, Optional[ast.Call]]] = []
            for node in self.scope_walk(fn):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    if self.enclosing_function(node) is fn:
                        outs.append((node, None))   # lexical nesting
                    continue
                if not isinstance(node, ast.Call):
                    continue
                for callee in self.resolve_call(node):
                    outs.append((callee, node))
            edges[fn] = outs
        self._call_edges_cache = edges
        return edges

    # ------------------------------------------------- compiled programs
    def _unwrap_program(self, value: ast.AST):
        """Peel `wrap_first_call(jax.jit(f, donate_argnums=...), ...)`
        (and friends) down to the jit/to_static call, or None."""
        for _ in range(4):
            if not isinstance(value, ast.Call):
                return None
            seg = callee_segment(value.func)
            base = (seg or "").lstrip("_")
            if base == "jit" or base.endswith("jit"):
                return value, "jit"
            if base == "to_static" or base.endswith("to_static"):
                return value, "to_static"
            if value.args:
                value = value.args[0]
            else:
                return None
        return None

    def _resolve_donate(self, call: ast.Call,
                        scope: ast.AST) -> Tuple[int, ...]:
        expr = None
        for kw in call.keywords:
            if kw.arg in ("donate_argnums", "donate_argnames"):
                expr = kw.value
        if expr is None:
            return ()

        def literal(e) -> Optional[Tuple[int, ...]]:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                return (e.value,)
            if isinstance(e, ast.Tuple) and all(
                    isinstance(x, ast.Constant) and isinstance(x.value, int)
                    for x in e.elts):
                return tuple(x.value for x in e.elts)
            return None

        direct = literal(expr)
        if direct is not None:
            return direct
        if isinstance(expr, ast.IfExp):
            out: Set[int] = set()
            for branch in (expr.body, expr.orelse):
                lit = literal(branch)
                if lit:
                    out.update(lit)
            return tuple(sorted(out))
        if isinstance(expr, ast.Name):
            # a local `donate = (1,) if ... else ()` assignment
            for node in ast.walk(scope):
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == expr.id
                        for t in node.targets):
                    v = node.value
                    lit = literal(v)
                    if lit is not None:
                        return lit
                    if isinstance(v, ast.IfExp):
                        out = set()
                        for branch in (v.body, v.orelse):
                            lit = literal(branch)
                            if lit:
                                out.update(lit)
                        return tuple(sorted(out))
        return ()

    def _collect_programs(self) -> Dict[ast.AST, Dict[str, ProgramInfo]]:
        out: Dict[ast.AST, Dict[str, ProgramInfo]] = {}
        for node in self.all_nodes:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            target = expr_text(node.targets[0])
            if target is None:
                continue
            unwrapped = self._unwrap_program(node.value)
            if unwrapped is None:
                continue
            call, kind = unwrapped
            scope = self.enclosing_function(node) or self.tree
            donate = self._resolve_donate(call, scope) if kind == "jit" \
                else ()
            out.setdefault(scope, {})[target] = ProgramInfo(
                target=target, line=node.lineno, donate=donate, kind=kind)
        return out

    def programs_visible(self, scope: ast.AST) -> Dict[str, ProgramInfo]:
        """Programs bound in this scope or at module level."""
        merged = dict(self.programs.get(self.tree, {}))
        merged.update(self.programs.get(scope, {}))
        return merged

    def scopes(self) -> List[ast.AST]:
        """Every analysis scope: the module plus each non-lambda function."""
        return [self.tree] + [f for f in self.functions
                              if not isinstance(f, ast.Lambda)]

    def scope_walk(self, scope: ast.AST) -> List[ast.AST]:
        """Every node whose nearest enclosing function is ``scope``
        (module scope: nodes outside any function; lambda bodies merge
        into the enclosing function's scope)."""
        key = None if isinstance(scope, ast.Module) else scope
        return self._scope_nodes.get(key, [])


# ----------------------------------------------------------------- driver

_SKIP_DIRS = {"__pycache__", ".git", ".pytest_cache", ".jax_cache",
              "node_modules", ".claude"}


def iter_source_files(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if not os.path.exists(p):
            # a typoed/renamed path must not make the ratchet gate pass
            # vacuously on zero files
            raise FileNotFoundError(f"graft-lint: no such path: {p!r}")
        if os.path.isfile(p):
            if not p.endswith(".py"):
                raise ValueError(
                    f"graft-lint: not a Python source file: {p!r}")
            out.append(os.path.abspath(p))
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.abspath(os.path.join(dirpath, fn)))
    return sorted(set(out))


def analyze_paths(paths: Iterable[str], root: Optional[str] = None,
                  rules: Optional[Iterable[str]] = None,
                  collect_errors: Optional[List[str]] = None
                  ) -> List[Finding]:
    """Run the rule set over ``paths`` (files or directories).  Returns
    suppression-filtered findings sorted by (path, line, rule).  Files
    that fail to parse are skipped (recorded in ``collect_errors``) —
    the analyzer must never take tier-1 down with it."""
    from . import rules as _rules
    root = os.path.abspath(root or os.getcwd())
    active = _rules.get_rules(rules)
    sources: List[SourceFile] = []
    for path in iter_source_files(paths):
        try:
            sources.append(SourceFile(path, root))
        except (SyntaxError, ValueError, UnicodeDecodeError) as e:
            if collect_errors is not None:
                collect_errors.append(f"{path}: {e}")
    findings: List[Finding] = []
    for rule in active:
        findings.extend(rule.run(sources))
    by_rel = {s.rel: s for s in sources}
    findings = [f for f in findings
                if not by_rel[f.path].suppressed(f.rule, f.line)]
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


# ---------------------------------------------------------------- ratchet

def baseline_counts(findings: Iterable[Finding]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for f in findings:
        fp = f.fingerprint()
        counts[fp] = counts.get(fp, 0) + 1
    return counts


def save_baseline(path: str, findings: List[Finding]) -> None:
    payload = {
        "schema": "paddle_tpu.graft-lint/v1",
        "findings": [f.to_json() for f in findings],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")


def load_baseline(path: str) -> Dict[str, int]:
    """Baseline fingerprint multiset; missing file = empty baseline."""
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        payload = json.load(f)
    counts: Dict[str, int] = {}
    for rec in payload.get("findings", []):
        fp = rec["fingerprint"]
        counts[fp] = counts.get(fp, 0) + 1
    return counts


def new_findings(findings: List[Finding],
                 baseline: Dict[str, int]) -> List[Finding]:
    """Findings beyond the baseline's per-fingerprint budget — the set
    that fails the ratchet."""
    budget = dict(baseline)
    out: List[Finding] = []
    for f in findings:
        fp = f.fingerprint()
        if budget.get(fp, 0) > 0:
            budget[fp] -= 1
        else:
            out.append(f)
    return out
