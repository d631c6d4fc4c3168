"""graft-lint v2: the interprocedural pass layer + rules R007-R010.

PR 8's six rules are intra-file pattern matchers; the serving tier built
since (refcounted prefix/KV blocks, shard_map TP programs with a
bit-parity contract, per-shape program caches, a tier-1 time budget)
rests on invariants that span FUNCTIONS: a block acquired in one helper
is released by another on the error path; a shard_map body's contraction
happens two calls deep; a cached program's trace reads state its cache
key never saw.  This module adds the per-module call graph + def-use
chains over the existing :class:`core.SourceFile` index and the four
rules that consume them:

* **R007 unbalanced-block-lifecycle** — an ``_alloc_X``/``_ref_X``
  acquisition that can reach a ``return``/``raise``/dispatch-that-can-
  raise while still held, with no matching ``_release_X`` (direct, or
  transitively through a local helper) on that path.
* **R008 shard-map-partial-escape** — inside a ``shard_map`` body, a
  contraction over an operand whose sharded axis is the CONTRACTED one
  escapes the body without a ``psum``-family collective: the partial
  sum the TP bit-parity contract forbids.
* **R009 under-keyed-program-cache** — a memoized compiled-program
  builder whose build (or traced body) reads a flag or a mutable
  ``self.*`` attribute that is not part of the cache key: the stale-
  program class ``compile_tracker`` can only blame after the fact.
* **R010 unbudgeted-heavy-test** — a test function running subprocesses
  / long training loops / seconds-scale sleeps without
  ``@pytest.mark.slow``: the ROADMAP tier-1 budget rule, enforced.

Like R001-R006 these are deliberately HEURISTIC (fixture-pinned both
directions in `tests/test_static_analysis.py`); the analysis state is
kept UNDER-approximate at joins (intersection merges, escape-on-handoff)
so a finding is worth reading — the ratchet keeps the tree at zero.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .core import Finding, Rule, SourceFile, callee_segment, expr_text

__all__ = ["ModuleIPA", "UnbalancedBlockLifecycle",
           "ShardMapPartialEscape", "UnderKeyedProgramCache",
           "UnbudgetedHeavyTest", "RULES_V2"]


# ========================================== the interprocedural pass layer

class ModuleIPA:
    """Lazy per-module interprocedural index over one SourceFile: the
    call graph (shared with `_compute_traced` via
    :meth:`SourceFile.call_edges`), transitive call-segment summaries,
    per-scope def-use chains, and per-class attribute-store maps.
    Built once per file per run and cached on the SourceFile."""

    def __init__(self, sf: SourceFile):
        self.sf = sf
        self._seg_summary: Dict[ast.AST, Set[str]] = {}
        self._def_use: Dict[ast.AST, Tuple[Dict[str, List[ast.AST]],
                                           Dict[str, List[ast.AST]]]] = {}
        self._attr_stores: Dict[ast.ClassDef, Dict[str, Set[str]]] = {}

    @classmethod
    def of(cls, sf: SourceFile) -> "ModuleIPA":
        ipa = getattr(sf, "_ipa_cache", None)
        if ipa is None:
            ipa = sf._ipa_cache = cls(sf)
        return ipa

    # ------------------------------------------------- call summaries
    def transitive_segments(self, fn: ast.AST) -> Set[str]:
        """Every dotted-call LAST SEGMENT reachable from ``fn``: its own
        call sites plus (to a fixpoint over the per-module call graph)
        those of every local function it can invoke.  The summary a
        caller consults to learn "does this helper release blocks?"
        without re-walking the callee."""
        cached = self._seg_summary.get(fn)
        if cached is not None:
            return cached
        sf = self.sf
        edges = sf.call_edges()
        segs: Set[str] = set()
        seen: Set[ast.AST] = set()
        stack = [fn]
        while stack:
            cur = stack.pop()
            if cur in seen:
                continue
            seen.add(cur)
            for node in sf.scope_walk(cur):
                if isinstance(node, ast.Call):
                    seg = callee_segment(node.func)
                    if seg:
                        segs.add(seg)
            for callee, _site in edges.get(cur, ()):
                stack.append(callee)
        self._seg_summary[fn] = segs
        return segs

    # ---------------------------------------------------- def-use chains
    def def_use(self, scope: ast.AST) -> Tuple[Dict[str, List[ast.AST]],
                                               Dict[str, List[ast.AST]]]:
        """(defs, uses) for one scope: dotted-text -> binding nodes
        (Assign/AugAssign/AnnAssign/for-target/with-as) and -> Load
        sites.  The chains R008 resolves spec variables through and
        R009 resolves key aliases through."""
        cached = self._def_use.get(scope)
        if cached is not None:
            return cached
        defs: Dict[str, List[ast.AST]] = {}
        uses: Dict[str, List[ast.AST]] = {}

        def bind(target: ast.AST, node: ast.AST) -> None:
            if isinstance(target, (ast.Tuple, ast.List)):
                for el in target.elts:
                    bind(el, node)
                return
            text = expr_text(target)
            if text is not None:
                defs.setdefault(text, []).append(node)

        for node in self.sf.scope_walk(scope):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    bind(t, node)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                bind(node.target, node)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                bind(node.target, node)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if item.optional_vars is not None:
                        bind(item.optional_vars, node)
            elif isinstance(node, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(node, "ctx", None), ast.Load):
                text = expr_text(node)
                if text is not None:
                    uses.setdefault(text, []).append(node)
        self._def_use[scope] = (defs, uses)
        return defs, uses

    def resolve_name(self, scope: ast.AST, name: str,
                     depth: int = 2) -> Optional[ast.AST]:
        """Single-assignment resolution of ``name`` in ``scope`` (module
        scope included as the fallback): the VALUE expression if exactly
        one binding exists, chasing plain ``a = b`` aliases ``depth``
        hops.  None when ambiguous — the rules must stay quiet rather
        than guess."""
        for sc in (scope, self.sf.tree):
            defs, _ = self.def_use(sc)
            nodes = defs.get(name, [])
            if len(nodes) == 1 and isinstance(nodes[0], ast.Assign):
                value = nodes[0].value
                alias = expr_text(value)
                if alias is not None and alias != name and depth > 0:
                    deeper = self.resolve_name(scope, alias, depth - 1)
                    return deeper if deeper is not None else value
                return value
            if nodes:
                return None
        return None

    # ------------------------------------------------- class attr stores
    def attr_stores(self, cls: ast.ClassDef) -> Dict[str, Set[str]]:
        """self.<attr> ASSIGNMENT sites per attribute -> method names.
        Subscript stores (``self.tables[i] = ...``) do not rebind the
        attribute and are excluded; R009 uses this to split init-frozen
        attributes from live state."""
        cached = self._attr_stores.get(cls)
        if cached is not None:
            return cached
        sf = self.sf
        out: Dict[str, Set[str]] = {}
        for fn in sf.functions:
            if isinstance(fn, ast.Lambda) or sf.enclosing_class(fn) is not cls:
                continue
            owner = sf.enclosing_function(fn)
            name = fn.name if owner is None else \
                (owner.name if not isinstance(owner, ast.Lambda)
                 else fn.name)
            for node in sf.scope_walk(fn):
                targets: List[ast.AST] = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                for t in targets:
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self":
                        out.setdefault(t.attr, set()).add(name)
        self._attr_stores[cls] = out
        return out


# ============================================================== R007

_ACQ_VERBS = ("alloc", "acquire", "ref")
_REL_VERBS = ("release", "free", "deref")


def _lifecycle_family(seg: Optional[str]) -> Optional[Tuple[str, str]]:
    """``_alloc_block`` -> ("acq", "block"); ``_release_block`` ->
    ("rel", "block"); None for everything else.  Families pair an
    acquire verb with its release verb over the same resource noun."""
    s = (seg or "").lstrip("_")
    for v in _ACQ_VERBS:
        if s.startswith(v + "_") and len(s) > len(v) + 1:
            return ("acq", s[len(v) + 1:])
    for v in _REL_VERBS:
        if s.startswith(v + "_") and len(s) > len(v) + 1:
            return ("rel", s[len(v) + 1:])
    return None


class _LifeState:
    """Must-held acquisitions along the current path: name -> family.
    ``merge`` is INTERSECTION (held on every incoming path) so
    conditionally-acquired resources never false-flag downstream; the
    branch that acquires checks its own exits before the join."""

    __slots__ = ("held",)

    def __init__(self, held: Optional[Dict[str, str]] = None):
        self.held = dict(held or {})

    def copy(self) -> "_LifeState":
        return _LifeState(self.held)

    def merge(self, other: Optional["_LifeState"]) -> "_LifeState":
        if other is None:          # that path terminated (return/raise)
            return self
        keep = {n: f for n, f in self.held.items()
                if other.held.get(n) == f}
        return _LifeState(keep)

    def clear_family(self, fam: str) -> None:
        self.held = {n: f for n, f in self.held.items() if f != fam}


class UnbalancedBlockLifecycle(Rule):
    """A path-sensitive (branch-local) walk of every function that
    acquires a refcounted resource (``_alloc_X()``/``_ref_X(b)``):
    ownership must, on EVERY path, either be released (``_release_X``,
    directly or through a local helper whose transitive call summary
    releases — the interprocedural half), escape into owner state
    (stored into an attribute/subscript, passed to another function,
    returned), or the path is a leak.  Exception edges count: a
    dispatch-like call that can raise while a resource is held, outside
    any ``try`` whose handler releases, leaks on the unwind path — the
    exact shape of the serving admission/eviction/refund code this rule
    guards."""

    id = "R007"
    name = "unbalanced-block-lifecycle"

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        ipa = ModuleIPA.of(sf)
        for fn in sf.functions:
            if isinstance(fn, ast.Lambda):
                continue
            if _lifecycle_family(fn.name) is not None:
                continue      # the accessor definitions themselves
            if not self._has_direct_acquisition(sf, fn):
                continue
            out.extend(self._check_function(sf, ipa, fn))
        return out

    def _has_direct_acquisition(self, sf: SourceFile, fn) -> bool:
        for node in sf.scope_walk(fn):
            if isinstance(node, ast.Call):
                fam = _lifecycle_family(callee_segment(node.func))
                if fam and fam[0] == "acq":
                    return True
        return False

    # ------------------------------------------------------ summaries
    def _releases_families(self, sf: SourceFile, ipa: ModuleIPA,
                           call: ast.Call) -> Set[str]:
        """Families this call releases: a direct ``_release_X``, a local
        callee whose transitive summary contains one, or a call handed a
        release accessor as an ARGUMENT (callback handoff, e.g.
        ``prefix.evict(n, self._release_block, ...)``)."""
        fams: Set[str] = set()
        fam = _lifecycle_family(callee_segment(call.func))
        if fam and fam[0] == "rel":
            fams.add(fam[1])
        for callee in sf.resolve_call(call):
            for seg in ipa.transitive_segments(callee):
                f = _lifecycle_family(seg)
                if f and f[0] == "rel":
                    fams.add(f[1])
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            text = expr_text(arg)
            if text is not None:
                f = _lifecycle_family(text.split(".")[-1])
                if f and f[0] == "rel":
                    fams.add(f[1])
        return fams

    def _returns_acquisition(self, sf: SourceFile, fn) -> Optional[str]:
        """Does ``fn`` RETURN a value it acquired (a factory)?  Callers
        binding such a call re-acquire the resource."""
        bound: Dict[str, str] = {}
        for node in sf.scope_walk(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                fam = _lifecycle_family(callee_segment(node.value.func))
                if fam and fam[0] == "acq":
                    for t in node.targets:
                        text = expr_text(t)
                        if text:
                            bound[text] = fam[1]
        for node in sf.scope_walk(fn):
            if isinstance(node, ast.Return) and node.value is not None:
                if isinstance(node.value, ast.Call):
                    fam = _lifecycle_family(
                        callee_segment(node.value.func))
                    if fam and fam[0] == "acq":
                        return fam[1]
                text = expr_text(node.value)
                if text in bound:
                    return bound[text]
        return None

    # ------------------------------------------------------- the walk
    def _check_function(self, sf: SourceFile, ipa: ModuleIPA,
                        fn) -> List[Finding]:
        findings: List[Finding] = []
        self._sf, self._ipa, self._fn = sf, ipa, fn
        self._findings = findings
        self._aliases: Dict[str, str] = {}     # loop var -> held name
        end = self._walk(fn.body, _LifeState(), protected=frozenset())
        if end is not None and end.held:
            fam = next(iter(end.held.values()))
            findings.append(self.finding(
                sf, fn, f"`{fn.name}` can fall off its end still "
                f"holding an unreleased `{fam}` acquisition "
                f"(`{'`, `'.join(sorted(end.held))}`): every path must "
                "release it, hand it to owner state, or return it",
                symbol=sf.qualname(fn)))
        return findings

    def _leak(self, node: ast.AST, state: _LifeState, why: str) -> None:
        fam = next(iter(state.held.values()))
        self._findings.append(self.finding(
            self._sf, node,
            f"`{self._fn.name}` {why} while still holding an "
            f"unreleased `{fam}` acquisition "
            f"(`{'`, `'.join(sorted(state.held))}`): release it on this "
            "path (or hand it to owner state) — a leaked refcount is "
            "pool capacity gone for the process lifetime",
            symbol=self._sf.qualname(self._fn)))

    def _escape_names(self, state: _LifeState, expr: ast.AST) -> None:
        """Any held name appearing inside ``expr`` escapes (stored,
        passed, or returned — someone else owns it now)."""
        if not state.held:
            return
        for sub in ast.walk(expr):
            text = expr_text(sub) if isinstance(
                sub, (ast.Name, ast.Attribute)) else None
            if text is None:
                continue
            real = self._aliases.get(text, text)
            state.held.pop(text, None)
            state.held.pop(real, None)

    def _acquisitions(self, stmt: ast.AST):
        for sub in ast.walk(stmt):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, ast.Call):
                fam = _lifecycle_family(callee_segment(sub.func))
                if fam and fam[0] == "acq":
                    yield sub, fam[1]

    def _dispatchish(self, stmt: ast.AST) -> Optional[ast.Call]:
        """A call likely to raise at run time: a compiled-program
        dispatch (`prog(...)`, `self._x_program(L)(...)`) or a jnp/jax
        device call — the exception edges the serving admission paths
        guard with try/except."""
        progs = self._sf.programs_visible(
            self._sf.enclosing_function(stmt) or self._sf.tree)
        for sub in ast.walk(stmt):
            if not isinstance(sub, ast.Call):
                continue
            target = expr_text(sub.func)
            if target is not None and target in progs:
                return sub
            if isinstance(sub.func, ast.Call):
                seg = callee_segment(sub.func.func) or ""
                if seg.endswith("_program") or seg.endswith("jit"):
                    return sub
            if isinstance(sub.func, ast.Attribute) and \
                    isinstance(sub.func.value, ast.Name) and \
                    sub.func.value.id in self._sf.jnp_aliases and \
                    sub.func.attr in ("asarray", "array"):
                return sub
        return None

    def _walk(self, stmts: Sequence[ast.AST], state: _LifeState,
              protected: frozenset) -> Optional[_LifeState]:
        """Process a statement list; returns the fall-through state or
        None if every path terminates.  ``protected`` = families some
        enclosing try's handler releases (exception edges covered)."""
        sf, ipa = self._sf, self._ipa
        for stmt in stmts:
            if state is None:
                return None
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue     # a def does not run here
            if isinstance(stmt, ast.Return):
                if stmt.value is not None:
                    self._escape_names(state, stmt.value)
                if state.held:
                    self._leak(stmt, state, "returns early")
                return None
            if isinstance(stmt, ast.Raise):
                # families an enclosing try's handler releases are
                # covered on this unwind (same filter as the dispatch
                # exception edge)
                unprot = {n: f for n, f in state.held.items()
                          if f not in protected}
                if unprot:
                    self._leak(stmt, _LifeState(unprot), "raises")
                return None
            if isinstance(stmt, (ast.Break, ast.Continue)):
                return state      # loop-local; keep it simple
            if isinstance(stmt, ast.If):
                then = self._walk(stmt.body, state.copy(), protected)
                other = self._walk(stmt.orelse, state.copy(), protected)
                if then is None and other is None:
                    return None
                state = (then or other).merge(
                    other if then is not None else then)
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._note_loop_aliases(state, stmt)
                body = self._walk(stmt.body, state.copy(), protected)
                state = state.merge(body) if body is not None else state
                tail = self._walk(stmt.orelse, state.copy(), protected)
                state = state if tail is None else state.merge(tail)
                continue
            if isinstance(stmt, ast.While):
                body = self._walk(stmt.body, state.copy(), protected)
                state = state.merge(body) if body is not None else state
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                inner = self._walk(stmt.body, state, protected)
                if inner is None:
                    return None
                state = inner
                continue
            if isinstance(stmt, ast.Try):
                handler_fams: Set[str] = set()
                for h in stmt.handlers:
                    for sub in ast.walk(h):
                        if isinstance(sub, ast.Call):
                            handler_fams |= self._releases_families(
                                sf, ipa, sub)
                body = self._walk(
                    stmt.body, state.copy(),
                    protected | frozenset(handler_fams))
                # handlers run with whatever the body held when it blew
                # up — conservatively, the try-entry state minus what
                # the handler itself releases
                for h in stmt.handlers:
                    hstate = state.copy()
                    hs = self._walk(h.body, hstate, protected)
                    if hs is not None and body is not None:
                        body = body.merge(hs)
                    elif hs is not None:
                        body = hs
                state = body
                if stmt.finalbody:
                    state = self._walk(stmt.finalbody,
                                       state if state is not None
                                       else _LifeState(), protected)
                if state is None:
                    return None
                continue
            # ---- plain statement: releases, acquisitions, escapes
            state = self._flat_statement(stmt, state, protected)
        return state

    def _note_loop_aliases(self, state: _LifeState, stmt) -> None:
        """``for b in blocks:`` — escaping the loop var escapes the
        held collection it iterates."""
        it = stmt.iter
        if isinstance(it, ast.Call) and \
                callee_segment(it.func) == "enumerate" and it.args:
            it = it.args[0]
        base = expr_text(it)
        if isinstance(it, ast.Subscript):
            base = expr_text(it.value)
        if base is None or base not in state.held:
            return
        targets = stmt.target.elts if isinstance(
            stmt.target, (ast.Tuple, ast.List)) else [stmt.target]
        for t in targets:
            text = expr_text(t)
            if text:
                self._aliases[text] = base

    def _flat_statement(self, stmt: ast.AST, state: _LifeState,
                        protected: frozenset) -> _LifeState:
        sf, ipa = self._sf, self._ipa
        # (1) releases first (a release call obviously may mention the
        # held name without that being an escape)
        released = False
        for sub in ast.walk(stmt):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if not isinstance(sub, ast.Call):
                continue
            direct = _lifecycle_family(callee_segment(sub.func))
            if direct and direct[0] == "rel":
                released = True
                if len(sub.args) == 1:
                    text = expr_text(sub.args[0])
                    if text is not None and text in state.held:
                        state.held.pop(text)
                        continue
                state.clear_family(direct[1])
                continue
            fams = self._releases_families(sf, ipa, sub)
            if fams:
                released = True
                for fam in fams:
                    state.clear_family(fam)
        # (2) exception edge: a dispatch while holding an unprotected
        # acquisition leaks on the unwind path
        if state.held and not released:
            disp = self._dispatchish(stmt)
            if disp is not None:
                unprot = {n: f for n, f in state.held.items()
                          if f not in protected}
                if unprot:
                    self._leak(
                        disp, _LifeState(unprot),
                        "dispatches a program that can raise (no "
                        "try/except releasing the acquisition)")
                    for n in unprot:     # report once per acquisition
                        state.held.pop(n, None)
        # (3) escapes: held names stored into attributes/subscripts,
        # passed as arguments, or rebound
        if state.held:
            for sub in ast.walk(stmt):
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        if isinstance(t, (ast.Subscript, ast.Attribute)):
                            self._escape_names(state, sub.value)
                elif isinstance(sub, ast.Call):
                    fam = _lifecycle_family(callee_segment(sub.func))
                    if fam is not None:
                        continue
                    for arg in list(sub.args) + \
                            [kw.value for kw in sub.keywords]:
                        self._escape_names(state, arg)
        # (4) new acquisitions bind to their assignment target (or the
        # pinned argument for _ref_X); a call to a local FACTORY that
        # returns its acquisition binds too
        for sub in ast.walk(stmt):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if not isinstance(sub, ast.Call):
                continue
            fam = _lifecycle_family(callee_segment(sub.func))
            bound_fam: Optional[str] = None
            if fam and fam[0] == "acq":
                verb = (callee_segment(sub.func) or "").lstrip("_")
                if verb.startswith("ref") and sub.args:
                    text = expr_text(sub.args[0])
                    if text is not None:
                        state.held[text] = fam[1]
                        continue
                bound_fam = fam[1]
            else:
                for callee in sf.resolve_call(sub):
                    got = self._returns_acquisition(sf, callee)
                    if got is not None:
                        bound_fam = got
            if bound_fam is None:
                continue
            target = self._binding_target(stmt, sub)
            if target is not None:
                state.held[target] = bound_fam
            elif isinstance(stmt, ast.Expr) and stmt.value is sub:
                # bare `self._alloc_block()` discarding the id: an
                # immediate leak, nothing can ever release it
                state.held[f"<anonymous:{bound_fam}>"] = bound_fam
        return state

    def _binding_target(self, stmt: ast.AST,
                        call: ast.Call) -> Optional[str]:
        if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
            return None
        target = expr_text(stmt.targets[0])
        if target is None or "." in target:
            return None      # attribute store = owner state, not held
        for sub in ast.walk(stmt.value):
            if sub is call:
                return target
        return None


# ============================================================== R008

_CONTRACTIONS = {"matmul", "dot", "einsum", "tensordot", "sum", "mean"}
_CLEANSE = {"psum", "all_reduce", "psum_scatter", "all_gather",
            "reduce_scatter", "allreduce"}


class ShardMapPartialEscape(Rule):
    """Inside a ``shard_map`` body whose ``in_specs`` are statically
    readable, a contraction (`matmul`/`einsum`/`sum`/`@`) over an
    operand whose SHARDED axis is the CONTRACTED axis yields a partial
    sum; if that value can reach the body's return without a
    psum-family collective, every rank holds a different "replicated"
    result — the exact class the TP bit-parity contract forbids
    (`inference/tp.py`: no contraction dimension is ever split).
    Column-parallel contractions (sharded axis NOT contracted) pass.
    Bodies/specs the analyzer cannot resolve are skipped, not guessed;
    helpers called with sharded operands are followed one hop through
    the call graph."""

    id = "R008"
    name = "shard-map-partial-escape"

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        ipa = ModuleIPA.of(sf)
        for node in sf.all_nodes:
            if not isinstance(node, ast.Call):
                continue
            seg = callee_segment(node.func) or ""
            if not seg.lstrip("_").endswith("shard_map"):
                continue
            body = self._resolve_body(sf, node)
            if body is None:
                continue
            specs = self._in_specs(sf, ipa, node)
            if specs is None:
                continue
            out.extend(self._check_body(sf, ipa, body, specs, hops=1))
        return out

    def _resolve_body(self, sf: SourceFile, call: ast.Call):
        if not call.args:
            return None
        arg = call.args[0]
        if isinstance(arg, ast.Name):
            by_name, _ = sf._fn_tables()
            for f in by_name.get(arg.id, []):
                if sf._visible(f, call):
                    return f
        return None

    # -------------------------------------------------- spec parsing
    def _in_specs(self, sf: SourceFile, ipa: ModuleIPA,
                  call: ast.Call) -> Optional[List[Optional[Set[int]]]]:
        """Per-parameter sharded-axis sets: set() = replicated, a
        non-empty set = sharded on those dims, None = unresolvable
        (parameter skipped)."""
        expr = None
        for kw in call.keywords:
            if kw.arg == "in_specs":
                expr = kw.value
        if expr is None:
            return None
        scope = sf.enclosing_function(call) or sf.tree
        elts = self._tuple_elements(sf, ipa, scope, expr)
        if elts is None:
            elts = [expr]
        return [self._parse_spec(sf, ipa, scope, e) for e in elts]

    def _tuple_elements(self, sf, ipa, scope,
                        expr: ast.AST) -> Optional[List[ast.AST]]:
        """Flatten tuple literals including ``(a, b) + (c,) * 3``
        concatenation/repetition — the idiom the serving TP programs
        build their spec tuples with."""
        if isinstance(expr, ast.Tuple):
            return list(expr.elts)
        if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
            left = self._tuple_elements(sf, ipa, scope, expr.left)
            right = self._tuple_elements(sf, ipa, scope, expr.right)
            if left is not None and right is not None:
                return left + right
            return None
        if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Mult):
            base = self._tuple_elements(sf, ipa, scope, expr.left)
            if base is not None and \
                    isinstance(expr.right, ast.Constant) and \
                    isinstance(expr.right.value, int):
                return base * expr.right.value
            return None
        if isinstance(expr, ast.Name):
            resolved = ipa.resolve_name(scope, expr.id)
            if resolved is not None and resolved is not expr:
                return self._tuple_elements(sf, ipa, scope, resolved)
        return None

    def _parse_spec(self, sf, ipa, scope,
                    expr: ast.AST) -> Optional[Set[int]]:
        if isinstance(expr, ast.Name):
            resolved = ipa.resolve_name(scope, expr.id)
            if resolved is None:
                return None
            expr = resolved
        if not isinstance(expr, ast.Call):
            return None
        seg = (callee_segment(expr.func) or "").lstrip("_")
        if seg not in ("P", "PartitionSpec"):
            return None
        dims: Set[int] = set()
        for i, arg in enumerate(expr.args):
            if isinstance(arg, ast.Constant) and arg.value is None:
                continue
            if isinstance(arg, (ast.Constant, ast.Name, ast.Attribute)):
                dims.add(i)
            else:
                return None
        return dims

    # ------------------------------------------------- body analysis
    def _check_body(self, sf: SourceFile, ipa: ModuleIPA, body,
                    specs: List[Optional[Set[int]]],
                    hops: int) -> List[Finding]:
        params = [a.arg for a in body.args.args]
        sharded: Dict[str, Optional[Set[int]]] = {}
        known_any = False
        for i, p in enumerate(params):
            if i < len(specs) and specs[i] is not None and specs[i]:
                sharded[p] = set(specs[i])
                known_any = True
        if not known_any:
            return []
        partial: Dict[str, ast.AST] = {}    # name -> contraction site
        findings: List[Finding] = []
        nodes = [n for n in sf.scope_walk(body)]

        def operand_sharded_dims(e: ast.AST) -> Optional[Set[int]]:
            text = expr_text(e)
            if text is not None and text in sharded:
                return sharded[text]
            return None

        def is_partial_expr(e: ast.AST) -> Optional[ast.AST]:
            """The contraction node if ``e`` produces/contains a
            partial sum, else None."""
            for sub in ast.walk(e):
                site = contraction_partial(sub)
                if site is not None:
                    return site
                text = expr_text(sub) if isinstance(
                    sub, (ast.Name, ast.Attribute)) else None
                if text is not None and text in partial:
                    return partial[text]
            return None

        def contraction_partial(sub: ast.AST) -> Optional[ast.AST]:
            if isinstance(sub, ast.BinOp) and \
                    isinstance(sub.op, ast.MatMult):
                a, b = sub.left, sub.right
                da, db = operand_sharded_dims(a), operand_sharded_dims(b)
                # 2-D contraction: a's dim 1 meets b's dim 0
                if da and 1 in da:
                    return sub
                if db and 0 in db:
                    return sub
                return None
            if not isinstance(sub, ast.Call):
                return None
            seg = callee_segment(sub.func)
            if seg not in _CONTRACTIONS:
                return None
            if seg in ("matmul", "dot") and len(sub.args) >= 2:
                da = operand_sharded_dims(sub.args[0])
                db = operand_sharded_dims(sub.args[1])
                # contracting dims: a's LAST, b's FIRST (2-D case, the
                # shard_map body idiom); sharded elsewhere = column-
                # parallel = exact
                if db and 0 in db:
                    return sub
                if da is not None and da:
                    # a's last dim index is unknown statically; only a
                    # rank-2 P(..., axis) spec pins it — dim 1
                    if 1 in da:
                        return sub
                return None
            if seg == "einsum" and sub.args and \
                    isinstance(sub.args[0], ast.Constant) and \
                    isinstance(sub.args[0].value, str):
                spec = sub.args[0].value.replace(" ", "")
                if "->" not in spec:
                    return None
                ins, outp = spec.split("->", 1)
                in_subs = ins.split(",")
                for opnd, letters in zip(sub.args[1:], in_subs):
                    dims = operand_sharded_dims(opnd)
                    if not dims:
                        continue
                    for d in dims:
                        if d < len(letters) and \
                                letters[d] not in outp:
                            return sub
                return None
            if seg in ("sum", "mean"):
                opnd = sub.args[0] if sub.args else None
                if opnd is None and isinstance(sub.func, ast.Attribute):
                    opnd = sub.func.value
                if opnd is None:
                    return None
                dims = operand_sharded_dims(opnd)
                if not dims:
                    return None
                axis = None
                for kw in sub.keywords:
                    if kw.arg == "axis":
                        axis = kw.value
                if len(sub.args) >= 2:
                    axis = sub.args[1]
                if axis is None:
                    return sub          # full reduction: always partial
                if isinstance(axis, ast.Constant) and \
                        isinstance(axis.value, int) and \
                        axis.value in dims:
                    return sub
                return None
            return None

        for n in nodes:
            if isinstance(n, ast.Assign):
                site = is_partial_expr(n.value)
                cleansed = any(
                    isinstance(sub, ast.Call) and
                    callee_segment(sub.func) in _CLEANSE
                    for sub in ast.walk(n.value))
                for t in n.targets:
                    text = expr_text(t)
                    if text is None:
                        continue
                    if site is not None and not cleansed:
                        partial[text] = site
                    else:
                        partial.pop(text, None)
                        # a value derived from a sharded param stays
                        # sharded-derived only for direct aliases
                        alias = expr_text(n.value)
                        if alias in sharded:
                            sharded[text] = sharded[alias]
            elif isinstance(n, ast.Return) and n.value is not None:
                cleansed = any(
                    isinstance(sub, ast.Call) and
                    callee_segment(sub.func) in _CLEANSE
                    for sub in ast.walk(n.value))
                if cleansed:
                    continue
                site = is_partial_expr(n.value)
                if site is not None:
                    findings.append(self.finding(
                        sf, site, "partial contraction over a sharded "
                        "operand escapes the shard_map body "
                        f"`{body.name}` without a psum-family "
                        "collective: every rank returns a DIFFERENT "
                        "partial sum where the out_spec promises "
                        "replication — reduce it (`psum`) before it "
                        "leaves the body, or document the replication "
                        "with a suppression",
                        symbol=sf.qualname(body)))
            elif isinstance(n, ast.Call) and hops > 0:
                # one-hop interprocedural: a helper called with a
                # sharded operand in a known position
                for callee in sf.resolve_call(n):
                    sub_specs: List[Optional[Set[int]]] = []
                    any_sharded = False
                    for arg in n.args:
                        dims = operand_sharded_dims(arg)
                        sub_specs.append(set(dims) if dims else
                                         (set() if dims == set()
                                          else None))
                        if dims:
                            any_sharded = True
                    if any_sharded:
                        findings.extend(self._check_body(
                            sf, ipa, callee, sub_specs, hops - 1))
        return findings


# ============================================================== R009

class UnderKeyedProgramCache(Rule):
    """A memoized compiled-program builder — ``fn = cache.get(key)`` /
    ``cache[key] = wrap(jit(body))`` or the attribute-slot twin
    (``if self._fn is not None: return self._fn``) — whose build or
    traced body reads state the cache key does not cover: a
    ``get_flag``/``FLAGS_*`` read, or a ``self.<attr>`` that some OTHER
    method reassigns after construction.  The read is baked into the
    compiled program at trace time, so later state changes silently
    serve the stale program (or force a recompile the key cannot
    express) — the class `compile_tracker` can only blame after the
    fact.  Init-frozen attributes (assigned only in ``__init__``) are
    exactly what a per-instance cache key already covers and never
    flag."""

    id = "R009"
    name = "under-keyed-program-cache"

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        seen: Set[Tuple[int, int, str]] = set()
        ipa = ModuleIPA.of(sf)
        for fn in sf.functions:
            if isinstance(fn, ast.Lambda):
                continue
            cache = self._builder_cache(sf, fn)
            if cache is None:
                continue
            key_names, slot, factories = cache
            for f in self._check_builder(sf, ipa, fn, key_names, slot,
                                         factories):
                fp = (f.line, f.col, f.message)
                if fp not in seen:
                    seen.add(fp)
                    out.append(f)
        return out

    def _builder_cache(self, sf: SourceFile, fn):
        """(key name set, cache slot text, factory fns) when ``fn`` is
        a memoized program builder, else None.  A builder both PROBES a
        cache slot and STORES a compiled program into it; ``factories``
        are local functions the store expression routes through
        (``self._build_x(k)``-style) whose bodies trace."""
        store_sub = None      # cache[key] = <program>
        store_attr = None     # self._x = <program>
        factories: List[ast.AST] = []
        assigns = [n for n in sf.scope_walk(fn)
                   if isinstance(n, ast.Assign)]
        # pass 1: direct program stores identify the cache slot
        for node in assigns:
            if sf._unwrap_program(node.value) is None:
                continue
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    base = expr_text(t.value)
                    if base is not None:
                        store_sub = (base, t.slice)
                elif isinstance(t, ast.Attribute):
                    text = expr_text(t)
                    if text is not None and text.startswith("self."):
                        store_attr = text
        # pass 2: factory stores into the SAME slot (`fn =
        # self._cache[k] = self._build_x(k)` — the TP-path twin) route
        # the trace scope through the factory method
        for node in assigns:
            if sf._unwrap_program(node.value) is not None or \
                    not isinstance(node.value, ast.Call):
                continue
            for t in node.targets:
                hit = (isinstance(t, ast.Subscript) and
                       store_sub is not None and
                       expr_text(t.value) == store_sub[0]) or \
                      (isinstance(t, ast.Attribute) and
                       store_attr is not None and
                       expr_text(t) == store_attr)
                if hit:
                    factories.extend(sf.resolve_call(node.value))
        if store_sub is not None:
            base, slice_expr = store_sub
            probed = any(
                isinstance(n, ast.Call) and
                callee_segment(n.func) == "get" and
                isinstance(n.func, ast.Attribute) and
                expr_text(n.func.value) == base
                for n in sf.scope_walk(fn)) or any(
                isinstance(n, ast.Subscript) and
                isinstance(getattr(n, "ctx", None), ast.Load) and
                expr_text(n.value) == base
                for n in sf.scope_walk(fn))
            if not probed:
                return None
            key_names = {expr_text(s) for s in ast.walk(slice_expr)
                         if isinstance(s, (ast.Name, ast.Attribute))
                         and expr_text(s)}
            key_names |= {a.arg for a in fn.args.args}
            return key_names, base, factories
        if store_attr is not None:
            probed = any(
                isinstance(n, (ast.Name, ast.Attribute)) and
                isinstance(getattr(n, "ctx", None), ast.Load) and
                expr_text(n) == store_attr
                for n in sf.scope_walk(fn))
            if not probed:
                return None
            return ({a.arg for a in fn.args.args}, store_attr,
                    factories)
        return None

    def _mutable_attrs(self, sf: SourceFile, ipa: ModuleIPA, fn,
                       slot: str) -> Dict[str, Set[str]]:
        """Attributes reassigned after construction by methods that do
        NOT also invalidate the cache slot.  A mutator that resets the
        cache (``self._compiled = {}`` alongside ``self._loss = ...``)
        can never serve a stale program and is covered; so is the
        builder itself (it refreshes the attr on the call path)."""
        cls = sf.enclosing_class(fn)
        if cls is None:
            return {}
        stores = ipa.attr_stores(cls)
        slot_attr = slot.split(".", 1)[1] if slot.startswith("self.") \
            else slot
        invalidators = stores.get(slot_attr, set())
        exempt = {"__init__", fn.name} | invalidators
        return {attr: owners - exempt
                for attr, owners in stores.items()
                if owners - exempt}

    def _trace_scopes(self, sf: SourceFile, fn,
                      factories: Iterable[ast.AST]) -> List[ast.AST]:
        """The scopes whose reads BAKE into the compiled program: every
        function lexically nested in the builder (the traced body is
        one of them), the resolved factory methods and their nested
        functions, plus one hop into local helpers those bodies call at
        trace time.  The builder's own top-level scope is deliberately
        EXCLUDED — its reads happen at build/dispatch time and feed the
        program as inputs."""
        seeds: List[ast.AST] = []
        for g in sf.functions:
            if isinstance(g, ast.Lambda):
                continue
            if self._nested_in(sf, g, fn):
                seeds.append(g)
        for fac in factories:
            if fac is fn:
                continue
            seeds.append(fac)
            for g in sf.functions:
                if not isinstance(g, ast.Lambda) and \
                        self._nested_in(sf, g, fac):
                    seeds.append(g)
        edges = sf.call_edges()
        out = list(seeds)
        for s in seeds:
            for callee, site in edges.get(s, ()):
                if site is not None and callee not in out \
                        and callee is not fn:
                    out.append(callee)
        return out

    def _check_builder(self, sf: SourceFile, ipa: ModuleIPA, fn,
                       key_names: Set[str], slot: str,
                       factories) -> List[Finding]:
        findings: List[Finding] = []
        mutable = self._mutable_attrs(sf, ipa, fn, slot)
        slot_attr = slot.split(".", 1)[1] if slot.startswith("self.") \
            else slot
        for scope in self._trace_scopes(sf, fn, factories):
            scope_keys = key_names | {a.arg for a in scope.args.args}
            for node in sf.scope_walk(scope):
                if isinstance(node, ast.Call):
                    seg = callee_segment(node.func)
                    if seg in ("get_flag", "get_flags"):
                        findings.append(self.finding(
                            sf, node, f"`{seg}(...)` read at trace "
                            "time by the program cached in "
                            f"`{slot}`: the value bakes into the "
                            "compiled program but is not part of the "
                            "cache key — a later flag change silently "
                            "serves the stale program; read the flag "
                            "at dispatch and pass it in, or fold it "
                            "into the key",
                            symbol=sf.qualname(fn)))
                elif isinstance(node, ast.Name) and \
                        node.id.startswith("FLAGS_") and \
                        node.id not in scope_keys:
                    findings.append(self.finding(
                        sf, node, f"`{node.id}` read at trace time by "
                        f"the program cached in `{slot}`: baked into "
                        "the program, absent from the cache key — "
                        "stale-program risk; hoist to dispatch or key "
                        "on it",
                        symbol=sf.qualname(fn)))
                elif isinstance(node, ast.Attribute) and \
                        isinstance(getattr(node, "ctx", None),
                                   ast.Load) and \
                        isinstance(node.value, ast.Name) and \
                        node.value.id == "self" and \
                        node.attr in mutable and \
                        node.attr != slot_attr and \
                        f"self.{node.attr}" not in scope_keys:
                    owners = ", ".join(sorted(mutable[node.attr]))
                    findings.append(self.finding(
                        sf, node, "trace-time read of "
                        f"`self.{node.attr}`, which `{owners}` "
                        "reassigns after construction without "
                        f"invalidating `{slot}`: the cached program "
                        "freezes the build-time value — key on it, "
                        "pass it as a program input, or reset the "
                        "cache where it mutates",
                        symbol=sf.qualname(fn)))
        return findings

    def _nested_in(self, sf: SourceFile, inner, outer) -> bool:
        cur = sf.enclosing_function(inner)
        while cur is not None:
            if cur is outer:
                return True
            cur = sf.enclosing_function(cur)
        return False


# ============================================================== R010

_SUBPROCESS_CALLS = {"run", "Popen", "check_call", "check_output",
                     "call"}
_TRAIN_CALLS = {"backward", "step", "fit", "run", "train_batch",
                "minimize"}


class UnbudgetedHeavyTest(Rule):
    """Test modules only: a ``test_*`` function that shells out to a
    subprocess, spins a long training/decode loop (``range(N >= 24)``
    around ``backward``/``step``/``fit``/``run``), or sleeps for
    seconds, without ``@pytest.mark.slow`` — the ROADMAP tier-1 budget
    rule (the 870s selection must stay seconds-margined; PR 10 landed
    with ~33s).  Mark it ``slow``, shrink it, or justify with a
    suppression."""

    id = "R010"
    name = "unbudgeted-heavy-test"
    tests_only = True

    LOOP_THRESHOLD = 24
    SLEEP_THRESHOLD = 1.0

    def check_file(self, sf: SourceFile) -> List[Finding]:
        if not sf.stem.startswith("test_"):
            return []
        if self._module_marked_slow(sf):
            return []
        out: List[Finding] = []
        for fn in sf.functions:
            if isinstance(fn, ast.Lambda) or \
                    not fn.name.startswith("test_"):
                continue
            if sf.enclosing_function(fn) is not None:
                continue
            if self._marked_slow(fn) or self._class_marked_slow(sf, fn):
                continue
            reason = self._heavy_reason(sf, fn)
            if reason is not None:
                why, node = reason
                out.append(self.finding(
                    sf, node, f"test `{fn.name}` {why} without "
                    "`@pytest.mark.slow`: tier-1 runs `-m 'not slow'` "
                    "under a hard wall-clock budget — mark it slow, "
                    "shrink it, or justify with a suppression",
                    symbol=sf.qualname(fn)))
        return out

    @staticmethod
    def _decorators_slow(decs) -> bool:
        for dec in decs:
            target = dec.func if isinstance(dec, ast.Call) else dec
            text = expr_text(target) or ""
            if text.split(".")[-1] == "slow" or ".slow" in text:
                return True
        return False

    def _marked_slow(self, fn) -> bool:
        return self._decorators_slow(getattr(fn, "decorator_list", []))

    def _class_marked_slow(self, sf: SourceFile, fn) -> bool:
        cls = sf.enclosing_class(fn)
        if cls is None:
            return False
        if self._decorators_slow(cls.decorator_list):
            return True
        return any(
            isinstance(n, ast.Assign) and
            any(expr_text(t) == "pytestmark" for t in n.targets) and
            "slow" in ast.dump(n.value)
            for n in cls.body)

    def _module_marked_slow(self, sf: SourceFile) -> bool:
        for node in sf.tree.body:
            if isinstance(node, ast.Assign) and any(
                    expr_text(t) == "pytestmark" for t in node.targets):
                if "slow" in ast.dump(node.value):
                    return True
        return False

    def _heavy_reason(self, sf: SourceFile, fn):
        """(description, anchor node) for the first heavy marker in the
        test's body (nested helpers included — they run when it does),
        else None."""
        sub_aliases = {n for n, mod in sf.module_aliases.items()
                       if mod == "subprocess"} | {"subprocess"}
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and \
                    f.attr in _SUBPROCESS_CALLS and \
                    isinstance(f.value, ast.Name) and \
                    f.value.id in sub_aliases:
                return (f"runs a subprocess (`{f.value.id}.{f.attr}`)",
                        node)
            if isinstance(f, ast.Attribute) and f.attr == "sleep":
                arg = node.args[0] if node.args else None
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, (int, float)) and \
                        arg.value >= self.SLEEP_THRESHOLD:
                    return (f"sleeps {arg.value}s", node)
        for node in ast.walk(fn):
            if not isinstance(node, (ast.For, ast.AsyncFor)):
                continue
            it = node.iter
            if not (isinstance(it, ast.Call) and
                    callee_segment(it.func) == "range" and it.args):
                continue
            bound = it.args[-1] if len(it.args) <= 2 else it.args[1]
            if not (isinstance(bound, ast.Constant) and
                    isinstance(bound.value, int) and
                    bound.value >= self.LOOP_THRESHOLD):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    seg = callee_segment(sub.func)
                    if seg in _TRAIN_CALLS:
                        return (f"loops `range({bound.value})` around "
                                f"`.{seg}(...)`", node)
        return None


RULES_V2: List[Rule] = [
    UnbalancedBlockLifecycle(), ShardMapPartialEscape(),
    UnderKeyedProgramCache(), UnbudgetedHeavyTest(),
]
