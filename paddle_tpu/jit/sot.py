"""SOT-lite: guarded value-specializing capture.

Role of the reference's SOT stack (`python/paddle/jit/sot/translate.py:31`,
`jit/sot/opcode_translator/` opcode interpreter + guard system,
`paddle/fluid/pybind/eval_frame.c` PEP-523 frame hook), re-designed for the
JAX tracing model:

The reference interprets CPython bytecode to build a graph, burying the
*taken* path of value-dependent Python control flow into the captured
program and installing GUARDS — cheap predicates re-checked on every call;
a guard miss triggers recompilation of a new specialization, and
untranslatable code falls back to eager with a logged break reason.

Here the tracer is `jax.jit` itself, so no bytecode interpretation is
needed — what SOT adds over direct tracing is exactly the *value
specialization*: `bool(t)` / `int(t)` / `float(t)` / `t.item()` on a traced
Tensor (the things that otherwise raise ConcretizationTypeError and force a
whole-function eager fallback) are intercepted:

1. **Record** — the state-discovery pass runs the function eagerly (the
   one case in which it does not just trace it) with recording ON: every
   concretization's Python value is appended, in execution order, to the
   burn list.
2. **Replay** — during `jax.jit` tracing the same call sites pop the
   burned values (so Python takes the same branches) and emit the traced
   predicate as an extra program OUTPUT — the guard.
3. **Guard check** — every call runs the specialized program, then
   compares the guard outputs against the burned values BEFORE committing
   any state mutation (these programs never donate their inputs, so a
   discarded run is side-effect free).  A mismatch re-dispatches to the
   specialization whose burn list matches, or records + compiles a new one.

Python control flow between specializations stays ordinary Python — each
specialization is one straight-line XLA program, the exact analogue of the
reference's guarded SOT subgraphs.

`paddle.jit.status()` reports per-function signatures, specializations,
guard misses, and graph-break reasons (the observability the reference's
SOT logs provide).
"""

from __future__ import annotations

import weakref
from typing import Any, List, Optional, Tuple

import numpy as np

__all__ = ["status", "GuardMiss", "SotUnsupported", "MAX_SPECIALIZATIONS"]

# specializations per argument signature before declaring guard thrash
# (e.g. a float() burn that changes every step) and falling back to eager
MAX_SPECIALIZATIONS = 8


class GuardMiss(Exception):
    """A specialized program's guard outputs disagreed with its burn list.
    Carries the observed values; entries AFTER the first divergence ran
    under a wrong branch and are untrustworthy."""

    def __init__(self, observed: Tuple, diverged_at: int):
        super().__init__(f"guard miss at #{diverged_at}")
        self.observed = observed
        self.diverged_at = diverged_at


class SotUnsupported(Exception):
    """Raised when replay cannot proceed (control flow diverged between
    record and replay, or a concretization kind mismatch)."""


class _SotState:
    """Module-global capture state (tracing is single-threaded)."""

    mode: Optional[str] = None        # None | "record" | "replay"
    recorded: List[Tuple[str, Any]] = []
    idx: int = 0
    guards: List[Any] = []


_S = _SotState()


class _Recording:
    def __enter__(self):
        if _S.mode is not None:
            # nested capture (StaticFunction inside StaticFunction):
            # inner recording would corrupt the outer burn list
            raise SotUnsupported("nested SOT capture")
        _S.mode, _S.recorded = "record", []
        return self

    def __exit__(self, *exc):
        self.values = list(_S.recorded)
        _S.mode, _S.recorded = None, []
        return False


class _Replaying:
    def __init__(self, burned):
        self.burned = burned

    def __enter__(self):
        if _S.mode is not None:
            raise SotUnsupported("nested SOT capture")
        _S.mode, _S.recorded, _S.idx, _S.guards = (
            "replay", list(self.burned), 0, [])
        return self

    def __exit__(self, *exc):
        self.guards = list(_S.guards)
        self.consumed = _S.idx
        _S.mode, _S.recorded, _S.idx, _S.guards = None, [], 0, []
        return False


recording = _Recording
replaying = _Replaying


def recording_active() -> bool:
    """True while an eager SOT record runs the function (the record
    holds pre-step buffer references for its rollback)."""
    return _S.mode == "record"


def intercept(kind: str, tensor, concretize):
    """Concretization hook used by Tensor.__bool__/__int__/__float__/item.

    Eager (mode None): plain conversion.  Record: convert + burn the
    value.  Replay on a traced value: pop the burned value (Python then
    takes the recorded branch) and emit the traced scalar as a guard."""
    if _S.mode == "replay":
        if _S.idx >= len(_S.recorded):
            raise SotUnsupported(
                f"replay ran past the recorded burn list at a {kind}() — "
                "control flow diverged between record and trace")
        rkind, rval = _S.recorded[_S.idx]
        if rkind != kind:
            raise SotUnsupported(
                f"replay expected {rkind}() but hit {kind}() — control "
                "flow diverged between record and trace")
        _S.idx += 1
        if tensor._is_traced():
            _S.guards.append(tensor._value)
            return rval
        # non-traced (closure-constant) tensor: its value is baked into
        # the trace as a Python constant anyway — consume the burn entry
        # to stay in sync with the record pass, but emit NO guard (the
        # guard positions must line up with the traced burns only)
        _S.guards.append(None)
        return concretize()
    out = concretize()
    if _S.mode == "record":
        _S.recorded.append((kind, out))
    return out


def check_guards(burned, guard_vals):
    """Compare a run's guard outputs against the program's burn list;
    raise GuardMiss (with the observed prefix) on divergence.  Exact
    equality — a float specialization that never repeats will thrash up
    to MAX_SPECIALIZATIONS and then fall back to eager, which is the
    honest behavior for a value burned into the program."""
    if len(guard_vals) != len(burned):
        raise SotUnsupported(
            f"guard count {len(guard_vals)} != burn count {len(burned)} "
            "— record/replay desynchronized")
    observed = []
    for (kind, burn), g in zip(burned, guard_vals):
        if g is None:              # closure-constant burn: not guarded
            observed.append((kind, burn))
            continue
        v = np.asarray(g).item()
        v = type(burn)(v) if not isinstance(v, type(burn)) else v
        observed.append((kind, v))
    for i, (b, o) in enumerate(zip(burned, observed)):
        if b != o:
            raise GuardMiss(tuple(observed), i)


def match_prefix(specs, observed, diverged_at):
    """Pick the cached specialization consistent with the TRUSTWORTHY
    guard prefix (everything up to and including the first divergence —
    later values were computed under a wrong branch)."""
    prefix = observed[:diverged_at + 1]
    for burned in specs:
        if tuple(burned[:len(prefix)]) == tuple(prefix):
            return burned
    return None


# ------------------------------------------------------------- status()

_REGISTRY: "weakref.WeakSet" = weakref.WeakSet()


def register(static_fn):
    _REGISTRY.add(static_fn)


def status() -> dict:
    """Per-StaticFunction capture report: compiled signatures, SOT
    specializations, guard misses, and graph-break reasons.  The
    observability counterpart of the reference SOT's break-reason logs
    (`jit/sot/utils/exceptions.py` BreakGraphError classes)."""
    report = {}
    for sf in list(_REGISTRY):
        st = getattr(sf, "_stats", None)
        if st is None:
            continue
        name = getattr(sf, "__name__", "static_fn")
        entry = dict(st)
        entry["graph_breaks"] = list(st.get("graph_breaks", []))
        base = name
        n = 2
        while name in report:      # distinct functions sharing a __name__
            name = f"{base}#{n}"
            n += 1
        report[name] = entry
    return report
