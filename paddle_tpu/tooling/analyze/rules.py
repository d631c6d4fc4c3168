"""graft-lint rules R001-R006: the JAX/TPU footgun classes this repo has
paid for in production debugging time.

Each rule is deliberately HEURISTIC: a static analyzer cannot prove a
value is a tracer or that a program is in flight, so rules pattern-match
the shapes those bugs take in this codebase (and the fixture corpus in
`tests/test_static_analysis.py` pins both directions).  False positives
are handled by the ratchet baseline or an inline
``# graft-lint: disable=RXXX`` with a justification comment; the expensive
failure mode — a silent new instance of a class that once cost days — is
the one the tier-1 ratchet makes impossible.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .core import (Finding, ProgramInfo, Rule, SourceFile,
                   callee_segment, expr_text)

__all__ = ["RULES", "Rule", "get_rules"]


def _is_np_call(sf: SourceFile, node: ast.Call,
                names: Sequence[str]) -> bool:
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr in names
            and isinstance(f.value, ast.Name)
            and f.value.id in sf.np_aliases)


def _is_jnp_call(sf: SourceFile, node: ast.Call,
                 names: Sequence[str]) -> bool:
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr in names
            and isinstance(f.value, ast.Name)
            and f.value.id in sf.jnp_aliases)


# =========================================================== R001
class HostSyncInTracedCode(Rule):
    """Host materialization inside a traced function: `.item()`,
    `np.asarray`, `float()/int()/bool()` of a tracer.  At best it's a
    silent trace-time constant; at worst a ConcretizationTypeError at
    the first recompile.  The value must leave the program as an output
    and sync at dispatch instead."""

    id = "R001"
    name = "host-sync-in-traced-code"

    _SYNC_METHODS = {"item", "numpy", "tolist", "block_until_ready"}

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for node in sf.all_nodes:
            if not isinstance(node, ast.Call):
                continue
            tfn = sf.in_traced(node)
            if tfn is None:
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and \
                    f.attr in self._SYNC_METHODS and not node.args:
                out.append(self.finding(
                    sf, node, f"host sync `.{f.attr}()` inside traced "
                    f"function `{sf.qualname(tfn) or '<lambda>'}`: the "
                    "value freezes at trace time (or raises under jit); "
                    "return it as a program output and sync at dispatch"))
                continue
            if _is_np_call(sf, node, ("asarray", "array", "copy")) \
                    and node.args and not isinstance(node.args[0],
                                                     ast.Constant):
                out.append(self.finding(
                    sf, node, "numpy materialization "
                    f"`{ast.unparse(node.func)}(...)` inside traced "
                    f"function `{sf.qualname(tfn) or '<lambda>'}`: a "
                    "traced value cannot cross to host here; keep it in "
                    "jnp or move the conversion outside the program"))
                continue
            if isinstance(f, ast.Name) and f.id in ("float", "int",
                                                    "bool") and \
                    len(node.args) == 1 and not isinstance(
                        node.args[0], ast.Constant):
                out.append(self.finding(
                    sf, node, f"`{f.id}(...)` on a non-literal inside "
                    f"traced function `{sf.qualname(tfn) or '<lambda>'}`"
                    ": concretizes the operand at trace time (value "
                    "frozen into the program, or ConcretizationType"
                    "Error); use jnp ops or hoist the read"))
            if isinstance(f, ast.Attribute) and f.attr == "device_get":
                out.append(self.finding(
                    sf, node, "`device_get` inside traced function "
                    f"`{sf.qualname(tfn) or '<lambda>'}`: host transfer "
                    "cannot run under trace"))
        return out


# =========================================================== R002
class AliasUnsafeDeviceInput(Rule):
    """A host numpy buffer handed to the device (`jnp.asarray`,
    `device_put`, or a compiled-program call) and then mutated in place
    in the same scope.  jax may alias numpy memory ZERO-COPY and
    dispatch is async, so the in-flight program can read the mutated
    bytes — the PR 3 scheduler race.  Hand the device a private copy
    (`jnp.asarray(x.copy())`) or delay the mutation past the sync."""

    id = "R002"
    name = "alias-unsafe-device-input"

    _HANDOFF = {"asarray", "device_put",
                "make_array_from_single_device_arrays"}
    _INPLACE_METHODS = {"fill", "sort", "put", "itemset", "setfield",
                        "partition", "resize", "byteswap"}

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        flagged: Set[Tuple[str, int]] = set()
        for scope in sf.scopes():
            for f in self._check_scope(sf, scope, flagged):
                out.append(f)
        out.extend(self._check_cross_method(sf, flagged))
        return out

    def _handoffs(self, sf: SourceFile,
                  scope: ast.AST) -> List[Tuple[str, ast.Call, bool]]:
        """(buffer text, handoff call, was_view) triples.  A Subscript
        arg (``self.tables[s:s+1]``) is a VIEW of its base — zero-copy
        aliasing follows the base buffer, so the base is what must not
        mutate."""
        progs = sf.programs_visible(scope)
        res: List[Tuple[str, ast.Call, bool]] = []
        for node in sf.scope_walk(scope):
            if not isinstance(node, ast.Call):
                continue
            seg = callee_segment(node.func)
            is_handoff = False
            if seg in self._HANDOFF:
                # np.asarray is a host copy, not a device handoff
                if seg == "asarray" and _is_np_call(sf, node,
                                                    ("asarray",)):
                    is_handoff = False
                else:
                    is_handoff = True
            else:
                target = expr_text(node.func)
                if target is not None and target in progs:
                    is_handoff = True
                elif isinstance(node.func, ast.Call):
                    inner = callee_segment(node.func.func) or ""
                    if inner.endswith("_program") or inner.endswith("jit"):
                        is_handoff = True   # self._prefill_program(L)(...)
            if not is_handoff:
                continue
            for arg in node.args:
                text = expr_text(arg)
                if text is not None:
                    res.append((text, node, False))
                elif isinstance(arg, ast.Subscript):
                    base = expr_text(arg.value)
                    if base is not None:
                        res.append((base, node, True))
        return res

    def _check_scope(self, sf: SourceFile, scope: ast.AST,
                     flagged: Set[Tuple[str, int]]) -> List[Finding]:
        handoffs = self._handoffs(sf, scope)
        if not handoffs:
            return []
        out: List[Finding] = []
        nodes = sf.scope_walk(scope)
        for text, call, view in handoffs:
            handoff_line = call.lineno
            rebind_line = None
            for n in nodes:
                if isinstance(n, ast.Assign) and n.lineno > handoff_line:
                    for t in n.targets:
                        if expr_text(t) == text:
                            rebind_line = min(rebind_line or n.lineno,
                                              n.lineno)
            mutation = self._first_mutation(sf, nodes, text, handoff_line,
                                            rebind_line)
            if mutation is not None:
                what = f"a view of `{text}`" if view else f"`{text}`"
                flagged.add((text, call.lineno))
                out.append(self.finding(
                    sf, mutation, f"host buffer {what} is handed to "
                    "the device and the base buffer is then mutated in "
                    "place in the same scope; async dispatch + zero-copy "
                    "aliasing lets the in-flight program read the "
                    "mutation — pass a private copy (`.copy()`) at the "
                    "handoff",
                    symbol=sf.symbol_for(call)))
        return out

    def _check_cross_method(self, sf: SourceFile,
                            flagged: Set[Tuple[str, int]]) -> List[Finding]:
        """The PR 3 shape: a `self.<buf>` handed to the device in one
        method, mutated in place by a DIFFERENT method of the same class
        (scheduler bookkeeping between async ticks).  No line ordering
        exists across methods, so any such pair is reported — at the
        handoff, naming the mutating method."""
        out: List[Finding] = []
        for cls in [n for n in sf.classes
                    if isinstance(n, ast.ClassDef)]:
            methods = [f for f in sf.functions
                       if not isinstance(f, ast.Lambda)
                       and sf.enclosing_class(f) is cls
                       and sf.enclosing_function(f) is None]
            if len(methods) < 2:
                continue
            mutators: Dict[str, str] = {}   # self.X -> method name
            for m in methods:
                for n in sf.scope_walk(m):
                    t = self._selfattr_mutation_target(sf, n)
                    if t is not None:
                        mutators.setdefault(t, m.name)
            if not mutators:
                continue
            for m in methods:
                for text, call, view in self._handoffs(sf, m):
                    if not text.startswith("self."):
                        continue
                    if (text, call.lineno) in flagged:
                        continue
                    other = mutators.get(text)
                    if other is None or other == m.name:
                        continue
                    what = f"a view of `{text}`" if view else f"`{text}`"
                    out.append(self.finding(
                        sf, call, f"host buffer {what} is handed to the "
                        f"device here while method `{other}` mutates it "
                        "in place; if the program can still be in "
                        "flight when the mutation runs (async dispatch "
                        "+ zero-copy aliasing), it reads the mutated "
                        "bytes — hand the device a private copy",
                        symbol=sf.symbol_for(call)))
        return out

    def _selfattr_mutation_target(self, sf: SourceFile,
                                  n: ast.AST) -> Optional[str]:
        if isinstance(n, ast.Assign):
            for t in n.targets:
                if isinstance(t, ast.Subscript):
                    base = expr_text(t.value)
                    if base and base.startswith("self."):
                        return base
        elif isinstance(n, ast.AugAssign):
            t = n.target
            if isinstance(t, ast.Subscript):
                base = expr_text(t.value)
                if base and base.startswith("self."):
                    return base
        elif isinstance(n, ast.Call):
            f = n.func
            if isinstance(f, ast.Attribute) and \
                    f.attr in self._INPLACE_METHODS:
                base = expr_text(f.value)
                if base and base.startswith("self."):
                    return base
        return None

    def _first_mutation(self, sf: SourceFile, nodes, text: str,
                        after: int, before: Optional[int]):
        best = None
        for n in nodes:
            line = getattr(n, "lineno", 0)
            if line <= after or (before is not None and line >= before):
                continue
            hit = False
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Subscript) and \
                            expr_text(t.value) == text:
                        hit = True
            elif isinstance(n, ast.AugAssign):
                t = n.target
                if (isinstance(t, ast.Subscript) and
                        expr_text(t.value) == text) or \
                        expr_text(t) == text:
                    hit = True
            elif isinstance(n, ast.Call):
                f = n.func
                if isinstance(f, ast.Attribute) and \
                        f.attr in self._INPLACE_METHODS and \
                        expr_text(f.value) == text:
                    hit = True
                elif _is_np_call(sf, n, ("copyto",)) and n.args and \
                        expr_text(n.args[0]) == text:
                    hit = True
            if hit and (best is None or line < best.lineno):
                best = n
        return best


# =========================================================== R003
class UseAfterDonate(Rule):
    """A buffer passed at a donated argnum of a compiled program and
    referenced afterwards.  On TPU the donated buffer is DEAD the moment
    the call dispatches — reads return garbage or raise; on CPU (where
    donation is ignored) the bug is silent until the code meets real
    hardware.  Rebind from the program's outputs instead."""

    id = "R003"
    name = "use-after-donate"

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for scope in sf.scopes():
            progs = {t: p for t, p in sf.programs_visible(scope).items()
                     if p.donate}
            calls: List[Tuple[ProgramInfo, ast.Call]] = []
            nodes = list(sf.scope_walk(scope))
            for node in nodes:
                if isinstance(node, ast.Call):
                    target = expr_text(node.func)
                    if target in progs:
                        calls.append((progs[target], node))
                    else:
                        # inline `jax.jit(f, donate_argnums=...)(args)`
                        inline = self._inline_donated(sf, node, scope)
                        if inline is not None:
                            calls.append((inline, node))
            for info, call in calls:
                out.extend(self._check_call(sf, nodes, info, call))
        return out

    def _inline_donated(self, sf: SourceFile, node: ast.Call,
                        scope: ast.AST) -> Optional[ProgramInfo]:
        if not isinstance(node.func, ast.Call):
            return None
        unwrapped = sf._unwrap_program(node.func)
        if unwrapped is None:
            return None
        call, kind = unwrapped
        if kind != "jit":
            return None
        donate = sf._resolve_donate(call, scope if not isinstance(
            scope, ast.Module) else sf.tree)
        if not donate:
            return None
        return ProgramInfo(target="<inline>", line=node.lineno,
                           donate=donate)

    def _check_call(self, sf: SourceFile, nodes, info: ProgramInfo,
                    call: ast.Call) -> List[Finding]:
        out: List[Finding] = []
        # a multi-line donated call spans [lineno, end_lineno]: the
        # argument expression itself must not read as a post-call use
        call_end = getattr(call, "end_lineno", None) or call.lineno
        for idx in info.donate:
            if idx >= len(call.args):
                continue
            text = expr_text(call.args[idx])
            if text is None:
                continue
            rebind = None
            for n in nodes:
                if isinstance(n, (ast.Assign, ast.AugAssign)) and \
                        n.lineno > call_end:
                    targets = n.targets if isinstance(n, ast.Assign) \
                        else [n.target]
                    if any(expr_text(t) == text for t in targets):
                        rebind = min(rebind or n.lineno, n.lineno)
            use = None
            for n in nodes:
                if isinstance(n, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(n, "ctx", None), ast.Load) and \
                        expr_text(n) == text and n.lineno > call_end \
                        and (rebind is None or n.lineno < rebind):
                    if use is None or n.lineno < use.lineno:
                        use = n
            if use is not None:
                out.append(self.finding(
                    sf, use, f"`{text}` is donated (argnum {idx}) to "
                    "a compiled program and referenced afterwards; on "
                    "TPU the buffer is dead at dispatch — rebind from "
                    "the program's outputs before touching it",
                    symbol=sf.symbol_for(call)))
        return out


# =========================================================== R004
class TraceTimeFlagRead(Rule):
    """`get_flag`/`FLAGS_*` read inside a traced function body: the read
    happens ONCE at trace time and bakes the value into the compiled
    program, so later `set_flags` calls silently do nothing for already-
    compiled signatures.  Read the flag at dispatch (outside the
    program) and pass the result in, or accept trace-time freezing with
    an explicit suppression."""

    id = "R004"
    name = "trace-time-flag-read"

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for node in sf.all_nodes:
            tfn = None
            if isinstance(node, ast.Call):
                seg = callee_segment(node.func)
                if seg in ("get_flag", "get_flags"):
                    tfn = sf.in_traced(node)
                    if tfn is not None:
                        out.append(self.finding(
                            sf, node, f"`{seg}(...)` inside traced "
                            f"function `{sf.qualname(tfn) or '<lambda>'}`"
                            ": the flag value freezes at trace time "
                            "instead of being live at dispatch; read it "
                            "outside the program and pass it in"))
            elif isinstance(node, ast.Name) and \
                    node.id.startswith("FLAGS_"):
                tfn = sf.in_traced(node)
                if tfn is not None:
                    out.append(self.finding(
                        sf, node, f"`{node.id}` read inside traced "
                        f"function `{sf.qualname(tfn) or '<lambda>'}`: "
                        "frozen at trace time; hoist the read to "
                        "dispatch"))
        return out


# =========================================================== R005
class LockOrderInversion(Rule):
    """Cross-module `with <lock>` nesting cycles (the PR 7 AB-BA class).
    Edges come from literal nesting, from flag-MUTATION API calls under
    a held lock (`set_flags`/`flag_guard` serialize on the hook lock
    while running `on_change` hooks), and from locks taken inside
    `define_flag(on_change=...)` hooks (which run under that same hook
    lock).  Plain `get_flag` reads are NOT an edge: the registry value
    lock is a leaf — it is held only for the read and never while
    acquiring anything else — which is precisely why module code may
    read flags under its own lock.  Any cycle means two threads can
    deadlock; module-to-module nesting needs an explicit hierarchy."""

    id = "R005"
    name = "lock-order-inversion"

    HOOK_LOCK = "flags._hook_lock"
    _FLAG_SET_API = {"set_flags", "flag_guard"}
    _LOCK_CTORS = {"Lock", "RLock"}

    def run(self, sources: List[SourceFile]) -> List[Finding]:
        # edge -> list of (sf, node, description)
        edges: Dict[Tuple[str, str], List[Tuple[SourceFile, ast.AST,
                                                str]]] = {}
        for sf in sources:
            if self.wants(sf):
                self._collect_file(sf, edges)
        graph: Dict[str, Set[str]] = {}
        for (a, b) in edges:
            graph.setdefault(a, set()).add(b)
        out: List[Finding] = []
        for (a, b), sites in edges.items():
            if a == b:
                continue  # recursive RLock re-entry is not an inversion
            if self._reaches(graph, b, a):
                for sf, node, desc in sites:
                    out.append(self.finding(
                        sf, node, f"lock-order inversion: acquiring "
                        f"`{b}` while holding `{a}` ({desc}) completes "
                        f"a cycle with the reverse order seen elsewhere "
                        "— two threads can AB-BA deadlock; fix the "
                        "acquisition order (flags lock before module "
                        "locks) or drop the nested acquisition"))
        return out

    @staticmethod
    def _reaches(graph: Dict[str, Set[str]], src: str, dst: str) -> bool:
        seen: Set[str] = set()
        stack = [src]
        while stack:
            cur = stack.pop()
            if cur == dst:
                return True
            if cur in seen:
                continue
            seen.add(cur)
            stack.extend(graph.get(cur, ()))
        return False

    # ---------------------------------------------------------- per-file
    def _lock_ident(self, sf: SourceFile, expr: ast.AST,
                    local_locks: Set[str]) -> Optional[str]:
        text = expr_text(expr)
        if text is None:
            return None
        parts = text.split(".")
        last = parts[-1]
        lockish = "lock" in last.lower() or "mutex" in last.lower()
        if len(parts) == 1:
            if text in local_locks or lockish:
                return f"{sf.stem}.{text}"
            return None
        if parts[0] == "self":
            if lockish or ".".join(parts[1:]) in local_locks:
                cls = sf.enclosing_class(expr)
                cname = cls.name if cls is not None else "self"
                return f"{sf.stem}.{cname}.{'.'.join(parts[1:])}"
            return None
        # module-alias attribute: `_flags._lock`
        mod = sf.module_aliases.get(parts[0])
        if mod is not None and lockish:
            stem = mod.split(".")[-1]
            return f"{stem}.{'.'.join(parts[1:])}"
        if lockish:
            return f"{sf.stem}.{text}"
        return None

    def _collect_file(self, sf: SourceFile, edges) -> None:
        local_locks: Set[str] = set()
        for node in sf.all_nodes:
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    callee_segment(node.value.func) in self._LOCK_CTORS:
                for t in node.targets:
                    text = expr_text(t)
                    if text is not None:
                        local_locks.add(text.removeprefix("self."))

        # function name -> (direct lock idents, calls flag api?)
        fn_summary: Dict[str, Tuple[Set[str], bool, List[ast.AST]]] = {}
        for fn in sf.functions:
            if isinstance(fn, ast.Lambda):
                continue
            locks: Set[str] = set()
            flag_api = False
            sites: List[ast.AST] = []
            for node in sf.scope_walk(fn):
                if isinstance(node, ast.With):
                    for item in node.items:
                        ident = self._lock_ident(
                            sf, item.context_expr, local_locks)
                        if ident:
                            locks.add(ident)
                            sites.append(node)
                elif isinstance(node, ast.Call) and \
                        callee_segment(node.func) in self._FLAG_SET_API:
                    flag_api = True
                    sites.append(node)
            fn_summary[fn.name] = (locks, flag_api, sites)

        def walk_same_scope(node: ast.AST):
            """ast.walk that PRUNES nested function definitions: a
            callback merely DEFINED under a lock does not run under it
            (same reason scope_walk buckets per function)."""
            stack = [node]
            while stack:
                cur = stack.pop()
                yield cur
                for child in ast.iter_child_nodes(cur):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.Lambda)):
                        continue
                    stack.append(child)

        def inner_acquisitions(body_nodes: Iterable[ast.AST], depth=1):
            """(ident, node, desc) acquired inside a with-block body,
            including one hop through local function calls."""
            for node in body_nodes:
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                    continue   # a def under the lock does not RUN under it
                for sub in walk_same_scope(node):
                    if isinstance(sub, ast.With):
                        for item in sub.items:
                            ident = self._lock_ident(
                                sf, item.context_expr, local_locks)
                            if ident:
                                yield ident, sub, "nested `with`"
                    elif isinstance(sub, ast.Call):
                        seg = callee_segment(sub.func)
                        if seg in self._FLAG_SET_API:
                            yield (self.HOOK_LOCK, sub,
                                   f"`{seg}` runs on_change hooks "
                                   "under the flags hook lock")
                        elif depth > 0 and isinstance(sub.func, ast.Name) \
                                and sub.func.id in fn_summary:
                            locks, flag_api, _ = fn_summary[sub.func.id]
                            for ident in locks:
                                yield (ident, sub,
                                       f"via call to `{sub.func.id}`")
                            if flag_api:
                                yield (self.HOOK_LOCK, sub,
                                       f"via call to `{sub.func.id}` "
                                       "which sets flags")

        # (1) acquisitions under a held lock
        for node in sf.all_nodes:
            if not isinstance(node, ast.With):
                continue
            for item in node.items:
                outer = self._lock_ident(sf, item.context_expr,
                                         local_locks)
                if outer is None:
                    continue
                for ident, site, desc in inner_acquisitions(node.body):
                    edges.setdefault((outer, ident), []).append(
                        (sf, site, desc))

        # (2) on_change hooks run under the flags HOOK lock (set_flags
        # serializes hook execution on it)
        for node in sf.all_nodes:
            if not (isinstance(node, ast.Call) and
                    callee_segment(node.func) == "define_flag"):
                continue
            hook = None
            for kw in node.keywords:
                if kw.arg == "on_change" and isinstance(kw.value,
                                                        ast.Name):
                    hook = kw.value.id
            if hook is None or hook not in fn_summary:
                continue
            locks, _, _ = fn_summary[hook]
            hook_fn = next(f for f in sf.functions
                           if not isinstance(f, ast.Lambda)
                           and f.name == hook)
            for ident in locks:
                edges.setdefault((self.HOOK_LOCK, ident), []).append(
                    (sf, hook_fn,
                     f"on_change hook `{hook}` runs under the flags "
                     "hook lock"))
            # one hop: hook calls a local function that takes a lock
            # (scope_walk: defs nested in the hook are not hook code)
            for sub in sf.scope_walk(hook_fn):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Name) and \
                        sub.func.id in fn_summary:
                    for ident in fn_summary[sub.func.id][0]:
                        edges.setdefault(
                            (self.HOOK_LOCK, ident), []).append(
                            (sf, sub, f"on_change hook `{hook}` -> "
                             f"`{sub.func.id}`"))


# =========================================================== R006
class UnsyncedTiming(Rule):
    """A `perf_counter()` interval around a compiled-program dispatch
    with no host sync before the stop: jax dispatch is async, so the
    interval measures ENQUEUE, not compute — the classic silently-wrong
    benchmark.  Call `block_until_ready` (or materialize an output)
    before reading the clock."""

    id = "R006"
    name = "unsynced-timing"

    _CLOCKS = {"perf_counter", "monotonic"}

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for scope in sf.scopes():
            out.extend(self._check_scope(sf, scope))
        return out

    def _check_scope(self, sf: SourceFile, scope) -> List[Finding]:
        nodes = list(sf.scope_walk(scope))
        starts: Dict[str, int] = {}
        for n in nodes:
            if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                    isinstance(n.targets[0], ast.Name) and \
                    isinstance(n.value, ast.Call) and \
                    callee_segment(n.value.func) in self._CLOCKS:
                starts[n.targets[0].id] = n.lineno
        if not starts:
            return []
        progs = sf.programs_visible(scope)
        out: List[Finding] = []
        for n in nodes:
            if not (isinstance(n, ast.BinOp) and
                    isinstance(n.op, ast.Sub)):
                continue
            right = n.right
            if not (isinstance(right, ast.Name) and right.id in starts):
                continue
            left_ok = (isinstance(n.left, ast.Call) and
                       callee_segment(n.left.func) in self._CLOCKS) or \
                      (isinstance(n.left, ast.Name) and
                       n.left.id in starts and
                       starts[n.left.id] > starts[right.id])
            if not left_ok:
                continue
            lo, hi = starts[right.id], n.lineno
            dispatch = self._find_dispatch(sf, nodes, progs, lo, hi)
            if dispatch is None:
                continue
            if self._has_sync(sf, nodes, dispatch, hi):
                continue
            out.append(self.finding(
                sf, n, "timing interval closes over an async compiled-"
                "program dispatch with no host sync before the stop "
                "clock read: this measures dispatch, not compute — add "
                "`block_until_ready`/materialize an output first",
                symbol=sf.symbol_for(n)))
        return out

    def _find_dispatch(self, sf: SourceFile, nodes, progs,
                       lo: int, hi: int):
        for n in nodes:
            if not isinstance(n, ast.Call):
                continue
            if not (lo < n.lineno <= hi):
                continue
            target = expr_text(n.func)
            if target is not None and target in progs:
                return n
            if isinstance(n.func, ast.Call):
                inner_seg = callee_segment(n.func.func) or ""
                if inner_seg.endswith("_program") or \
                        inner_seg.endswith("jit"):
                    return n
        return None

    def _has_sync(self, sf: SourceFile, nodes, dispatch: ast.Call,
                  hi: int) -> bool:
        """A host sync counts only AFTER the dispatch statement — a
        conversion feeding the dispatch's INPUT on the same line runs
        before the program is even enqueued.  A sync call that wraps the
        dispatch itself (`np.asarray(prog(x))`) does count: it blocks on
        the output."""
        disp_end = getattr(dispatch, "end_lineno", None) or dispatch.lineno
        for n in nodes:
            if not isinstance(n, ast.Call):
                continue
            if n.lineno > hi:
                continue
            if n.lineno <= disp_end:
                # same-statement sync only if the dispatch is INSIDE it
                # (sync of the output, not of an input)
                if not any(sub is dispatch for sub in ast.walk(n)):
                    continue
            seg = callee_segment(n.func)
            if seg in ("block_until_ready", "device_get"):
                return True
            if seg == "item" and not n.args:
                return True
            if _is_np_call(sf, n, ("asarray", "array")):
                return True
            if isinstance(n.func, ast.Name) and n.func.id == "float" \
                    and len(n.args) == 1:
                return True
        return False


# =========================================================== R011
class UnpairedKVHandoff(Rule):
    """A KV handoff — a scope that both exports a prefix cache
    (`export_prefix_cache`) and imports one (`_import_prefix_cache`) —
    without the ownership-transfer pair: the export side must
    `release_exported_prefix` (the serialized blocks return to the
    source engine's free pool; otherwise the KV has TWO owners and the
    source pool leaks until eviction pressure) and the import side must
    be `blocksan_verify`-checked (the adopted blocks re-pinned through
    the destination's refcount ledger).  Export alone (drain) and
    import alone (warm construction) are fine — only the handoff shape,
    where ownership MOVES, needs the pairing.  See
    inference/fleet/handoff.py for the canonical site."""

    id = "R011"
    name = "unpaired-kv-handoff"

    _EXPORT = "export_prefix_cache"
    _IMPORT = "_import_prefix_cache"
    _RELEASE = "release_exported_prefix"
    _VERIFY = "blocksan_verify"

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for scope in sf.scopes():
            if not isinstance(scope, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                continue
            calls: Dict[str, ast.Call] = {}
            for n in sf.scope_walk(scope):
                if isinstance(n, ast.Call):
                    seg = callee_segment(n.func)
                    if seg in (self._EXPORT, self._IMPORT,
                               self._RELEASE, self._VERIFY):
                        calls.setdefault(seg, n)
            if self._EXPORT not in calls or self._IMPORT not in calls:
                continue
            missing = [m for m in (self._RELEASE, self._VERIFY)
                       if m not in calls]
            if missing:
                out.append(self.finding(
                    sf, calls[self._EXPORT],
                    f"KV handoff in `{sf.qualname(scope) or '<lambda>'}` "
                    f"(calls both `{self._EXPORT}` and `{self._IMPORT}`) "
                    f"without {' / '.join(f'`{m}`' for m in missing)}: "
                    "ownership must TRANSFER — release the exported "
                    "blocks on the source engine and blocksan-verify the "
                    "adopting side, or the KV ends up with two owners "
                    "(source pool leak) / an unchecked refcount ledger"))
        return out


# =========================================================== R012
class UnpropagatedTraceContext(Rule):
    """A scope that handles distributed trace context — it mentions the
    ``X-Graft-Trace`` header literal or constructs a serving `Request`
    — and then crosses a process/engine boundary (an HTTP
    ``conn.request(...)`` or a ``hand_off(...)``) WITHOUT threading any
    trace context into that boundary call.  A hop that drops the trace
    id splits the fleet timeline: `dump --fleet-trace` renders the
    downstream spans as an orphan trace, and the whole point of the
    telescope — one request, one timeline, every process — is lost.
    Boundary calls whose source text carries a trace argument (a
    ``trace_id=``/header kwarg, a ``_trace``-named variable, the
    TRACE_HEADER constant) pass.  Scopes with no boundary call, or no
    trace source, are fine — only the shape where context is IN HAND
    and then dropped at the hop is flagged.  See
    inference/fleet/handoff.py for the canonical compliant site."""

    id = "R012"
    name = "unpropagated-trace-context"

    _HEADER = "X-Graft-Trace"
    _REQUEST = "Request"
    _SINKS = ("request", "hand_off")

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for scope in sf.scopes():
            if not isinstance(scope, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                continue
            has_source = False
            sinks: List[ast.Call] = []
            for n in sf.scope_walk(scope):
                if isinstance(n, ast.Constant) and n.value == self._HEADER:
                    has_source = True
                elif isinstance(n, ast.Call):
                    seg = callee_segment(n.func)
                    if seg == self._REQUEST:
                        has_source = True
                    elif seg in self._SINKS:
                        sinks.append(n)
            if not has_source:
                continue
            for call in sinks:
                try:
                    text = ast.unparse(call)
                except Exception:  # pragma: no cover - malformed node
                    continue
                if "trace" in text.lower():
                    continue
                seg = callee_segment(call.func)
                out.append(self.finding(
                    sf, call,
                    f"`{sf.qualname(scope) or '<lambda>'}` holds trace "
                    f"context (the `{self._HEADER}` header or a serving "
                    f"`Request`) but its `{seg}(...)` boundary call "
                    "carries none of it: thread the trace id through "
                    "the hop (forward the header / pass `trace_id=`) or "
                    "the downstream spans render as an orphan trace in "
                    "`dump --fleet-trace`"))
                break
        return out


# =========================================================== R013
class InterpretModeKernelInHotPath(Rule):
    """A ``pallas_call(...)`` that HARDCODES ``interpret=True`` outside
    any backend/fallback guard.  Interpret mode is the CPU-parity
    executor — it copies every input buffer per grid step and runs the
    kernel as traced XLA, orders of magnitude off the Mosaic lowering —
    so a literal ``interpret=True`` in library code silently pins the
    hot path to the slow executor even on a real TPU (the exact
    regression the X-ray kernel-coverage audit exists to catch; its
    ``via`` column would still read "interpret" on a TPU build).
    Compliant shapes: thread a computed flag (``interpret=None``
    resolved by `ops/pallas_common.interpret_default()` — the one seat
    every kernel wrapper in `ops/` asks), a conditional expression, or put the literal inside an ``if`` whose test probes
    the backend (a CPU-fallback branch).  Tests may hardcode it freely
    (the rule skips ``test_*`` files like the rest of the code rules)."""

    id = "R013"
    name = "interpret-mode-kernel-in-hot-path"

    # an enclosing `if` whose test mentions any of these reads as a
    # deliberate backend/fallback branch, not a pinned executor
    _GUARD_MARKERS = ("tpu", "backend", "interpret", "cpu", "fallback",
                      "debug")

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for scope in sf.scopes():
            guards: List[tuple] = []
            calls: List[ast.Call] = []
            for n in sf.scope_walk(scope):
                if isinstance(n, ast.If):
                    try:
                        ttext = ast.unparse(n.test).lower()
                    except Exception:  # pragma: no cover - malformed node
                        ttext = ""
                    if any(m in ttext for m in self._GUARD_MARKERS):
                        guards.append((n.lineno,
                                       getattr(n, "end_lineno", n.lineno)))
                elif isinstance(n, ast.Call) and \
                        callee_segment(n.func) == "pallas_call":
                    kw = next((k for k in n.keywords
                               if k.arg == "interpret"), None)
                    if kw is not None and isinstance(kw.value, ast.Constant) \
                            and kw.value.value is True:
                        calls.append(n)
            for call in calls:
                if any(a <= call.lineno <= b for a, b in guards):
                    continue
                out.append(self.finding(
                    sf, call,
                    "`pallas_call(..., interpret=True)` hardcodes the "
                    "interpret-mode executor: on a TPU build this pins "
                    "the kernel to the slow traced-XLA path (per-grid-"
                    "step buffer copies, no Mosaic lowering) and the "
                    "X-ray audit keeps reporting via=interpret.  Ask the "
                    "one seat instead (`interpret=pallas_common."
                    "interpret_default()`) or guard the literal with a "
                    "backend check"))
        return out


# =========================================================== R014
class EagerCollectiveInStepLoop(Rule):
    """An EAGER collective (`all_gather`/`all_reduce`/`reduce_scatter`/
    `psum`/...) issued inside a loop in a training-step scope instead of
    being traced into the compiled step program.  A per-layer eager
    collective dispatches one program per call — per layer, per step:
    XLA's latency-hiding scheduler never sees gather N+1 next to compute
    N (the overlap the fused ZeRO-3 step exists for,
    `fleet/hybrid_step.py make_zero3_train_step`), and the program count
    grows with depth instead of staying constant after warmup.
    Compliant shape: move the loop under `jax.jit`/`shard_map` so the
    collectives trace into ONE program (calls lexically inside a traced
    function — directly or through helpers — are exempt)."""

    id = "R014"
    name = "eager-collective-in-step-loop"

    _COLLECTIVES = frozenset({
        "all_gather", "all_reduce", "reduce_scatter",
        "all_gather_into_tensor", "reduce_scatter_tensor",
        "alltoall", "alltoall_single", "all_to_all", "broadcast",
        "psum", "psum_scatter", "pmean", "ppermute",
    })
    # only scopes that read as a training-step loop body; a data loader
    # sharding its manifest with an eager all_gather is not the hot path
    _SCOPE_MARKERS = ("step", "train")

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for scope in sf.scopes():
            qn = (sf.qualname(scope) or "").lower()
            if not any(m in qn for m in self._SCOPE_MARKERS):
                continue
            loops: List[tuple] = []
            calls: List[ast.Call] = []
            for n in sf.scope_walk(scope):
                if isinstance(n, (ast.For, ast.While)):
                    loops.append((n.lineno,
                                  getattr(n, "end_lineno", n.lineno)))
                elif isinstance(n, ast.Call) and \
                        callee_segment(n.func) in self._COLLECTIVES:
                    calls.append(n)
            for call in calls:
                if sf.in_traced(call) is not None:
                    continue    # traces into the step program: the fix
                # strictly inside a loop BODY (the header line runs once)
                if not any(a < call.lineno <= b for a, b in loops):
                    continue
                seg = callee_segment(call.func)
                out.append(self.finding(
                    sf, call,
                    f"eager `{seg}(...)` inside a loop in "
                    f"`{sf.qualname(scope) or '<module>'}`: each "
                    "iteration dispatches its own collective program — "
                    "per layer, per step — so nothing overlaps with "
                    "compute and the program count grows with depth.  "
                    "Trace the loop into the compiled step "
                    "(`jax.jit`/`shard_map`, the fused ZeRO-3 shape of "
                    "`make_zero3_train_step`) so XLA schedules gather "
                    "N+1 behind compute N"))
        return out


# =========================================================== R015
class UntimedStoreWait(Rule):
    """A blocking rendezvous-store call (`store.wait(...)` /
    `store.get(...)` / `store.barrier(...)`) with no ``timeout=``,
    reachable from launcher / rendezvous / elastic-supervision code.
    GET and WAIT park on the server until the key EXISTS — if the peer
    that was supposed to publish it died, the caller wedges forever,
    which is exactly how a dead node used to hang every survivor (the
    failure class the ISSUE 20 heartbeat leases exist to catch; a
    lease expiry can only help a node that is still making progress).
    Scope: `distributed/launch/`, `distributed/fleet/elastic/` and
    `distributed/store.py` — control-plane code that must stay live
    through peer death.  Compliant shapes: pass ``timeout=`` (the
    elastic timeout for rendezvous keys, a short bound for polls), or
    gate the read behind ``store.check(key)`` AND still bound the get.
    A ``.get(key, default)`` two-positional-argument call reads as a
    mapping lookup, not a blocking store get."""

    id = "R015"
    name = "untimed-store-wait"

    _SCOPE_DIRS = ("distributed/launch/", "distributed/fleet/elastic/")
    _SCOPE_FILES = ("distributed/store.py",)
    _METHODS = frozenset({"wait", "get", "barrier"})

    def wants(self, sf: SourceFile) -> bool:
        if not super().wants(sf):
            return False
        return (any(d in sf.rel for d in self._SCOPE_DIRS)
                or sf.rel.endswith(self._SCOPE_FILES))

    def check_file(self, sf: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for n in sf.all_nodes:
            if not isinstance(n, ast.Call) \
                    or not isinstance(n.func, ast.Attribute):
                continue
            meth = n.func.attr
            if meth not in self._METHODS:
                continue
            recv = (expr_text(n.func.value) or "").lower()
            if "store" not in recv:
                continue
            if any(k.arg == "timeout" for k in n.keywords):
                continue
            if meth == "get" and len(n.args) >= 2:
                continue    # mapping .get(key, default) — or a
                # positional timeout, which is bounded either way
            out.append(self.finding(
                sf, n,
                f"untimed `{recv}.{meth}(...)` in launcher/rendezvous "
                "code: GET/WAIT park on the server until the key "
                "exists, so a dead peer (the node that was supposed to "
                "publish it) wedges this caller forever — the hang the "
                "heartbeat-lease protocol cannot save it from.  Pass "
                "`timeout=` (the elastic timeout for rendezvous keys, "
                "a short bound for watch-loop polls) so peer death "
                "surfaces as TimeoutError and feeds the restart path"))
        return out


RULES: List[Rule] = [
    HostSyncInTracedCode(), AliasUnsafeDeviceInput(), UseAfterDonate(),
    TraceTimeFlagRead(), LockOrderInversion(), UnsyncedTiming(),
    UnpairedKVHandoff(), UnpropagatedTraceContext(),
    InterpretModeKernelInHotPath(), EagerCollectiveInStepLoop(),
    UntimedStoreWait(),
]

# the interprocedural rule set (R007-R010) registers itself here; the
# import is at the bottom because interproc builds on Rule above
from .interproc import RULES_V2 as _RULES_V2  # noqa: E402

RULES.extend(_RULES_V2)


def get_rules(ids: Optional[Iterable[str]] = None) -> List[Rule]:
    if ids is None:
        return list(RULES)
    wanted = {i.strip().upper() for i in ids}
    unknown = wanted - {r.id for r in RULES}
    if unknown:
        raise ValueError(f"unknown rule ids: {sorted(unknown)}")
    return [r for r in RULES if r.id in wanted]
