"""Whole-graph capture: paddle_tpu.jit.to_static.

Role of the reference's dy2static stack (`python/paddle/jit/api.py:135`
to_static, SOT bytecode capture `jit/sot/translate.py:31`, AST transform
`jit/dy2static/program_translator.py`) re-designed for XLA:

the eager API is already traceable — every op bottoms out in jax primitives —
so capture is *direct tracing* of the user's Python (the role SOT plays is
done by jax.jit's tracer), with a state-discovery pass replacing ProgramDesc
variable scoping:

1. **Record** — run the function once eagerly with a dispatch hook that
   records every concrete leaf Tensor feeding an op (parameters, buffers,
   closure constants).  Mutations are rolled back afterwards.
2. **Functionalize** — lift the surviving recorded tensors (plus live
   optimizer accumulators / step counters / LR) into program inputs; run the
   function under `jax.jit`, swapping tensor storage for tracers. In-place
   mutations (param updates, BN running stats) surface as extra outputs.
3. **Execute** — cached executable per arg-signature; state buffers that
   mutate are donated so XLA updates them in place in HBM.

This captures full train steps (forward + loss + backward + optimizer.step)
into ONE XLA program — the analogue of the reference's whole-program
`PirInterpreter` execution with CINN fusion, but with XLA doing the fusion.

Limits (same spirit as the reference's graph-break list): dynamic-shape ops
(nonzero/unique/masked_select) and Python branching on tensor *values* need
an eager fallback — wrap those regions out of the jit or keep them host-side.
"""

from __future__ import annotations

import gc
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as _random
from ..framework.tensor import Tensor
from ..observability import compile_tracker as _compile_tracker
from ..observability import metrics as _metrics
from ..observability import span as _span
from ..ops import registry as _registry
from . import sot as _sot

_M_JIT_TRACES = _metrics.counter(
    "jit.traces", "to_static capture builds (record + trace passes)")
_M_JIT_COMPILE_S = _metrics.histogram(
    "jit.compile_seconds",
    "capture cost per program, by stage label, the seconds of the "
    "to_static:<stage> spans: stage=discover is the eager state-discovery "
    "run, trace_lower is jaxpr tracing + lowering, compile is the cache "
    "load or backend compile, first_run the first execution")
_M_SOT_GUARD = _metrics.counter(
    "jit.sot_guards", "SOT guarded-dispatch outcomes (kind=hit|miss)")
_M_GRAPH_BREAKS = _metrics.counter(
    "jit.graph_breaks", "signatures that fell back to eager execution")

__all__ = ["to_static", "StaticFunction", "not_to_static", "ignore_module"]


class _FirstCall:
    """The first call of a captured program, split into the spans
    ``to_static:trace_lower``, ``to_static:compile`` and
    ``to_static:first_run`` without changing how the step runs: `jax.jit`
    is lazy, so its first call traces, lowers, loads or compiles, and
    runs, and jax reports the end of each stage on its monitoring
    channel (`fun_name` = ``jit(<name>)``).  Each span ends where the
    next begins, so with ``to_static:discover`` they add up to the first
    call's wall time."""

    _MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"
    current = threading.local()     # .call: this thread's first call

    def __init__(self, fn: str):
        self.fn, self.module = fn, f"jit({fn})"
        self.cache_hit = False
        self.seconds = {}
        self._stage = "trace_lower"
        self._span = _span("to_static:trace_lower", fn=fn)

    def __enter__(self):
        # a captured function may call another one's first call
        self._outer = getattr(_FirstCall.current, "call", None)
        _FirstCall.current.call = self
        self._span.begin()
        return self

    def __exit__(self, *exc):
        _FirstCall.current.call = self._outer
        self._close()
        return False

    def _close(self):
        self.seconds[self._stage] = self._span.end()

    def _advance(self, stage):
        self._close()
        self._stage = stage
        self._span = _span("to_static:" + stage, fn=self.fn).begin()

    @classmethod
    def on_duration(cls, event, duration, fun_name=None, **kw):
        call = getattr(cls.current, "call", None)
        if call is None or fun_name != call.module:
            return
        if event == cls._MLIR and call._stage == "trace_lower":
            call._advance("compile")
        elif event == cls._BACKEND and call._stage == "compile":
            call._span.set(cache_hit=call.cache_hit)
            call._advance("first_run")

    @classmethod
    def on_event(cls, event, **kw):
        call = getattr(cls.current, "call", None)
        if call is not None and event == cls._CACHE_HIT:
            call.cache_hit = True


jax.monitoring.register_event_duration_secs_listener(_FirstCall.on_duration)
jax.monitoring.register_event_listener(_FirstCall.on_event)


def _is_tracer(v) -> bool:
    return isinstance(v, jax.core.Tracer)


def _blame_signature(sig):
    """Reshape an `_arg_key` signature tuple into named per-arg entries
    so the compile tracker's recompile diff reads "arg0.shape: (2, 3) ->
    (4, 3)" instead of a positional tuple dump."""
    if sig is None:
        return None
    out = []
    for i, entry in enumerate(sig):
        if isinstance(entry, tuple) and entry and entry[0] in ("T", "A"):
            d = {"kind": "tensor" if entry[0] == "T" else "array",
                 "shape": entry[1], "dtype": entry[2]}
            if entry[0] == "T" and len(entry) > 3:
                d["stop_gradient"] = entry[3]
            out.append((f"arg{i}", d))
        elif isinstance(entry, tuple) and entry and entry[0] == "S":
            out.append((f"arg{i}", {"static": repr(entry[1])[:80]}))
        else:
            out.append((f"arg{i}", repr(entry)[:80]))
    return tuple(out)


class _TensorSlot:
    """State slot backed by a Tensor's storage."""

    def __init__(self, tensor: Tensor):
        self.ref = weakref.ref(tensor)
        self.input_only = False

    def get(self):
        t = self.ref()
        return t._value if t is not None else None

    def set(self, v):
        t = self.ref()
        if t is not None:
            t._value = v


class _DictSlot:
    """State slot backed by an optimizer accumulator dict entry."""

    def __init__(self, store: dict, key):
        self.store = store
        self.key = key
        self.input_only = False

    def get(self):
        return self.store.get(self.key)

    def set(self, v):
        self.store[self.key] = v


class _AttrSlot:
    def __init__(self, obj, attr, cast=None):
        self.obj = obj
        self.attr = attr
        self.cast = cast
        self.input_only = False

    def get(self):
        v = getattr(self.obj, self.attr)
        return self.cast(v) if self.cast else v

    def set(self, v):
        setattr(self.obj, self.attr, v)


class _LRSlot:
    """Input-only slot: reads the current LR each call so LR schedules keep
    working after capture.  During trace, installs the tracer as an override
    that Optimizer.get_lr returns."""

    def __init__(self, opt):
        self.opt = opt
        self.input_only = True

    def get(self):
        return jnp.asarray(self.opt.get_lr(), jnp.float32)

    def set(self, v):
        self.opt._lr_override = v if _is_tracer(v) else None


class _Recorder:
    def __init__(self):
        self.first_seen: List[Tuple[Tensor, Any]] = []
        self._seen_ids = set()
        self._produced_ids = set()

    def on_inputs(self, leaves):
        for t in leaves:
            if t is None or id(t) in self._seen_ids or \
                    id(t) in self._produced_ids:
                continue
            if _is_tracer(t._value):
                continue
            self._seen_ids.add(id(t))
            self.first_seen.append((t, t._value, t._grad))

    def on_outputs(self, outs):
        for t in outs:
            self._produced_ids.add(id(t))


def _map_tensors(obj, fn):
    if isinstance(obj, Tensor):
        return fn(obj)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_map_tensors(o, fn) for o in obj)
    if isinstance(obj, dict):
        return {k: _map_tensors(v, fn) for k, v in obj.items()}
    return obj


class StaticFunction:
    """Callable wrapping a compiled-on-demand eager function.

    Reference: `jit/dy2static/program_translator.py` StaticFunction —
    per-signature program cache with rollback-safe capture."""

    def __init__(self, function: Callable, input_spec=None, build_strategy=None,
                 backend=None, full_graph=False, donate_state: bool = True):
        # dy2static pass: rewrite tensor-dependent if/while into
        # lax.cond/while_loop converters (no-op when nothing converts)
        from . import dy2static as _d2s
        self._fn = _d2s.convert_function(function)
        self._cache: Dict[Any, Any] = {}
        self._donate_state = donate_state
        self._full_graph = full_graph
        self._broken_keys: set = set()
        self.__name__ = getattr(function, "__name__", "static_fn")
        self._stats = {"signatures": 0, "sot_specializations": 0,
                       "guard_misses": 0, "eager_calls": 0,
                       "graph_breaks": []}
        _sot.register(self)

    # -------------------------------------------------------------- helpers
    def _arg_key(self, args, kwargs):
        leaves, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        sig = []
        for leaf in leaves:
            if isinstance(leaf, Tensor):
                sig.append(("T", tuple(leaf.shape), str(leaf.dtype),
                            leaf.stop_gradient))
            elif isinstance(leaf, (jax.Array, np.ndarray)):
                sig.append(("A", tuple(leaf.shape), str(leaf.dtype)))
            else:
                sig.append(("S", leaf))
        return (treedef, tuple(sig))

    def _discover_state(self, args, kwargs, sot_record=False):
        """Recording pass: eager run + rollback; returns
        (slots, changed, burned) — `burned` is the ordered concretization
        list when sot_record is on (see jit/sot.py), else None."""
        from ..optimizer.optimizer import _live_optimizers
        rec = _Recorder()
        # snapshot optimizer state for rollback
        opts = list(_live_optimizers())
        opt_snapshots = [(o, {n: dict(s) for n, s in o._accumulators.items()},
                          o._global_step) for o in opts]
        rng_state = _random.get_rng_state()
        # the registry fires these only from THIS thread — concurrent op
        # dispatch (the dataloader's device-prefetch producer fetching
        # the next batch) cannot leak into the recorded state
        _registry.set_trace_recorder(rec.on_inputs)
        _registry.set_trace_out_recorder(rec.on_outputs)
        burned = None
        try:
            if sot_record:
                with _sot.recording() as srec:
                    self._fn(*args, **kwargs)
                burned = srec.values
            else:
                self._fn(*args, **kwargs)
        finally:
            _registry.set_trace_recorder(None)
            _registry.set_trace_out_recorder(None)
        _random.set_rng_state(rng_state)

        slots: List[Any] = []
        changed: List[bool] = []
        arg_ids = set()
        _map_tensors((args, kwargs), lambda t: arg_ids.add(id(t)))
        recorded = []
        for t, v0, g0 in rec.first_seen:
            if id(t) in arg_ids:
                t._grad = g0
                continue
            was_changed = t._value is not v0
            # rollback
            t._value = v0
            t._grad = g0
            recorded.append((t, was_changed))
        # Optimizer rollback: keep entries created by the recorded step (the
        # trace needs them as inputs) but reset values — pre-existing entries
        # to their snapshot, fresh ones to zeros (their pre-step state).
        for o, accs, gstep in opt_snapshots:
            if o._global_step == gstep:
                continue  # this optimizer didn't step inside fn
            params_by_id = {id(p): p for p in o._parameter_list}
            for name, store in o._accumulators.items():
                for key in store:
                    old = accs.get(name, {}).get(key)
                    if old is not None:
                        store[key] = old
                    elif name == "master_weight":
                        # pre-step master state is the fp32 param, not zeros
                        p = params_by_id.get(key)
                        store[key] = p._value.astype(jnp.float32) \
                            if p is not None else store[key]
                    else:
                        arr = store[key]
                        z = jnp.zeros(arr.shape, arr.dtype)
                        # zeros_like on a non-default-memory array (e.g.
                        # pinned_host offloaded state) trips XLA's memory-
                        # space check; build zeros then copy the placement
                        # — of a state that WAS placed (mesh-sharded,
                        # offloaded).  State left at the default
                        # placement stays uncommitted like the parameters
                        # beside it: a device_put would commit it, the
                        # first step's outputs would then all come back
                        # committed, and the second call would compile
                        # the whole step a second time.
                        if getattr(arr, "committed", False):
                            z = jax.device_put(z, arr.sharding)
                        store[key] = z
                    slots.append(_DictSlot(store, key))
                    changed.append(True)
            o._global_step = gstep
            slots.append(_AttrSlot(o, "_global_step",
                                   cast=lambda v: jnp.asarray(v, jnp.int32)))
            changed.append(True)
            slots.append(_LRSlot(o))
            changed.append(False)
        # drop temporaries: only tensors still alive elsewhere are state
        refs = [(weakref.ref(t), ch) for t, ch in recorded]
        del recorded, rec
        gc.collect()
        for r, ch in refs:
            t = r()
            if t is None:
                continue
            slots.append(_TensorSlot(t))
            changed.append(ch)
        return slots, changed, burned

    def _build(self, args, kwargs, sot=False):
        with _span("to_static:discover", fn=self.__name__) as discover:
            slots, changed, burned = self._discover_state(args, kwargs,
                                                          sot_record=sot)
        mutable_idx = [i for i, c in enumerate(changed) if c]
        readonly_idx = [i for i, c in enumerate(changed) if not c]
        spec: Dict[str, Any] = {}
        fn = self._fn

        def functional(mutable_vals, readonly_vals, key, arg_vals):
            # install traced values into the real objects; rollback happens
            # at runtime in __call__ (trace-time constants are tracers in
            # jax>=0.9, so a trace-side save/restore would leak tracers)
            for i, v in zip(mutable_idx, mutable_vals):
                slots[i].set(v)
            for i, v in zip(readonly_idx, readonly_vals):
                slots[i].set(v)
            wrapped_args = {}  # arg position -> wrapped Tensor

            def wrap_arg(t):
                w = Tensor._wrap(arg_vals[spec["arg_order"][id(t)]],
                                 stop_gradient=t.stop_gradient)
                wrapped_args[spec["arg_order"][id(t)]] = w
                return w

            t_args, t_kwargs = _map_tensors(spec["arg_proto"], wrap_arg)
            guard_vals = []
            with _random.key_source_guard(_random.TracedKeySource(key)):
                if burned is not None:
                    # value-specialized trace: replay the recorded
                    # concretizations (Python takes the burned branches)
                    # and surface the traced predicates as guard outputs
                    with _sot.replaying(burned) as rep:
                        out = fn(*t_args, **t_kwargs)
                    guard_vals = rep.guards
                    if rep.consumed != len(burned):
                        # the trace concretized fewer values than the
                        # record pass — an unguarded burn would commit
                        # wrong-branch results silently; graph-break
                        raise _sot.SotUnsupported(
                            f"trace consumed {rep.consumed} of "
                            f"{len(burned)} recorded values")
                else:
                    out = fn(*t_args, **t_kwargs)
            out_vals = _map_tensors(out, lambda t: t._value)
            new_mutable = [slots[i].get() for i in mutable_idx]
            # grads left on state tensors leak tracers; surface them
            grad_outs = []
            grad_targets = []
            for i, s in enumerate(slots):
                if isinstance(s, _TensorSlot):
                    t = s.ref()
                    if t is not None and t._grad is not None and \
                            _is_tracer(t._grad._value):
                        grad_outs.append(t._grad._value)
                        grad_targets.append(i)
            spec["grad_targets"] = grad_targets
            # grads on argument tensors (input saliency etc.) also surface
            arg_grad_outs = []
            arg_grad_pos = []
            for pos, w in wrapped_args.items():
                if w._grad is not None and _is_tracer(w._grad._value):
                    arg_grad_outs.append(w._grad._value)
                    arg_grad_pos.append(pos)
            spec["arg_grad_pos"] = arg_grad_pos
            return (out_vals, new_mutable, grad_outs, arg_grad_outs,
                    guard_vals)

        # donation lets XLA update param/opt-state buffers in place in HBM;
        # CPU PJRT doesn't support it (warning spam), so gate on backend.
        # Guarded (SOT) programs never donate: a guard miss discards the
        # run and re-executes, which needs the input buffers intact.
        donate = (0,) if self._donate_state and not sot and \
            jax.default_backend() != "cpu" else ()
        # the program is named after the user's function: the trace's
        # `XLA Modules` line reads jit_<name>, not jit_functional
        functional.__name__ = functional.__qualname__ = self.__name__
        jitted = jax.jit(functional, donate_argnums=donate)
        self._stats["signatures"] += 1
        _M_JIT_TRACES.inc(fn=self.__name__)
        _M_JIT_COMPILE_S.observe(discover.seconds, fn=self.__name__,
                                 stage="discover")
        return {"slots": slots, "mutable_idx": mutable_idx,
                "readonly_idx": readonly_idx, "jitted": jitted,
                "spec": spec, "fresh": True,
                "discover_s": discover.seconds,
                "burned": tuple(burned) if burned is not None else None}

    # errors that mean "this function cannot trace as one graph" (value-
    # dependent branching / dynamic shapes) — graph-break material, unlike
    # genuine user errors (bad shapes raise Type/ValueError and propagate)
    _GRAPH_BREAK_ERRORS = (jax.errors.ConcretizationTypeError,
                           jax.errors.TracerArrayConversionError,
                           jax.errors.TracerIntegerConversionError,
                           jax.errors.NonConcreteBooleanIndexError)

    @property
    def _graph_break_errors(self):
        from .dy2static import GraphBreak
        return self._GRAPH_BREAK_ERRORS + (GraphBreak,)

    def __call__(self, *args, **kwargs):
        key = self._arg_key(args, kwargs)
        if key in self._broken_keys:
            self._stats["eager_calls"] += 1
            return self._fn(*args, **kwargs)
        entry = self._cache.get(key)
        if isinstance(entry, dict) and entry.get("sot"):
            return self._sot_dispatch(key, entry, args, kwargs)
        try:
            return self._compiled_call(args, kwargs)
        except self._graph_break_errors as e:
            if self._full_graph:
                raise
            # Before giving up on compilation, try SOT value
            # specialization: burn the concretized values (bool/int/float/
            # item on traced tensors) into a guarded program (jit/sot.py —
            # the reference's jit/sot/translate.py seat).  Only if THAT
            # also fails (dynamic shapes, .numpy() on tracers, diverging
            # replay) does this signature fall back to eager.
            try:
                return self._sot_capture(key, args, kwargs)
            except self._graph_break_errors + (
                    _sot.SotUnsupported, _sot.GuardMiss) as e2:
                # GuardMiss on the capture call itself = the function's
                # burned values depend on Python state it mutates
                # (record/trace divergence) — unguardable, go eager
                self._graph_break(key, e, e2)
                return self._fn(*args, **kwargs)

    def _graph_break(self, key, first_err, sot_err):
        """Per-signature fallback to eager, with the break reason kept for
        `paddle.jit.status()` (the reference SOT's break-reason log)."""
        import warnings
        reason = (f"{type(first_err).__name__} -> SOT: "
                  f"{type(sot_err).__name__}: {sot_err}")
        _M_GRAPH_BREAKS.inc(fn=self.__name__)
        self._stats["graph_breaks"].append(
            {"signature": repr(key[1])[:120], "reason": reason[:300]})
        self._stats["eager_calls"] += 1
        warnings.warn(
            f"to_static({self.__name__}): could not be captured "
            f"({reason}); falling back to eager execution for this "
            "signature (see paddle.jit.status())", stacklevel=3)
        self._broken_keys.add(key)

    def _sot_capture(self, key, args, kwargs):
        """First value-specialized build for this signature."""
        entry = {"sot": True, "specs": {}, "last": None}
        prog = self._build(args, kwargs, sot=True)
        prog["sig"] = key[1]
        if prog["burned"] is not None and len(prog["burned"]) == 0:
            # nothing was concretized: the break came from something the
            # hooks cannot guard (dynamic shapes, host reads) — replaying
            # would just re-raise at run time; decline SOT
            raise _sot.SotUnsupported(
                "no concretized values to guard on")
        self._cache[key] = entry
        entry["specs"][prog["burned"]] = prog
        entry["last"] = prog["burned"]
        self._stats["sot_specializations"] += 1
        return self._run_prog(prog, args, kwargs)

    def _sot_dispatch(self, key, entry, args, kwargs):
        """Guard-checked dispatch over this signature's specializations:
        run the last-hit program; on a guard miss use the trustworthy
        guard prefix to pick (or record + compile) the right one."""
        burned = entry["last"]
        tried = set()
        while True:
            prog = entry["specs"][burned]
            try:
                out = self._run_prog(prog, args, kwargs)
                entry["last"] = burned
                _M_SOT_GUARD.inc(kind="hit")
                return out
            except _sot.GuardMiss as miss:
                self._stats["guard_misses"] += 1
                _M_SOT_GUARD.inc(kind="miss")
                tried.add(burned)
                nxt = _sot.match_prefix(
                    [b for b in entry["specs"] if b not in tried],
                    miss.observed, miss.diverged_at)
                if nxt is not None:
                    burned = nxt
                    continue
                if len(entry["specs"]) >= _sot.MAX_SPECIALIZATIONS:
                    self._graph_break(
                        key, miss, _sot.SotUnsupported(
                            f"guard thrash: {len(entry['specs'])} "
                            "specializations for one signature"))
                    return self._fn(*args, **kwargs)
                prog = self._build(args, kwargs, sot=True)
                prog["sig"] = key[1]
                entry["specs"][prog["burned"]] = prog
                entry["last"] = prog["burned"]
                self._stats["sot_specializations"] += 1
                try:
                    return self._run_prog(prog, args, kwargs)
                except (_sot.GuardMiss, _sot.SotUnsupported) as e:
                    # a fresh specialization must match its own recording;
                    # a miss here means the burns depend on state the
                    # function itself mutates — unguardable
                    self._graph_break(key, miss, e)
                    return self._fn(*args, **kwargs)

    @property
    def _eager_fallback(self):
        """True when any signature has graph-broken (test/debug hook)."""
        return bool(self._broken_keys)

    def _compiled_call(self, args, kwargs):
        key = self._arg_key(args, kwargs)
        prog = self._cache.get(key)
        if prog is None:
            prog = self._build(args, kwargs)
            prog["sig"] = key[1]
            self._cache[key] = prog
        return self._run_prog(prog, args, kwargs)

    def _run_prog(self, prog, args, kwargs):
        if prog.get("fresh", False):
            return self._execute(prog, args, kwargs,
                                 _FirstCall(self.__name__))
        # every later call: state gather, dispatch, state commit
        with _span("to_static:call"):
            return self._execute(prog, args, kwargs, None)

    def _execute(self, prog, args, kwargs, first_call):
        slots = prog["slots"]
        spec = prog["spec"]
        # build arg value list + proto mapping (order by traversal)
        arg_order: Dict[int, int] = {}
        arg_vals: List[Any] = []

        def collect(t):
            arg_order[id(t)] = len(arg_vals)
            arg_vals.append(t._value)
            return t

        _map_tensors((args, kwargs), collect)
        spec["arg_proto"] = (args, kwargs)
        spec["arg_order"] = arg_order
        mutable_vals = [slots[i].get() for i in prog["mutable_idx"]]
        readonly_vals = [slots[i].get() for i in prog["readonly_idx"]]
        # save for rollback: tracing mutates the real objects' storage
        saved = [(s, s.get()) for s in slots]
        saved_grads = [(s, s.ref()._grad) for s in slots
                       if isinstance(s, _TensorSlot) and s.ref() is not None]
        # cleared only after a successful observe, so a first call that
        # raises (GuardMiss, trace fallback) still gets its compile-stage
        # sample on the retry
        try:
            if first_call is None:
                outs = prog["jitted"](mutable_vals, readonly_vals,
                                      _random.next_key(), arg_vals)
            else:
                with first_call:
                    outs = jax.block_until_ready(prog["jitted"](
                        mutable_vals, readonly_vals, _random.next_key(),
                        arg_vals))
            (out_vals, new_mutable, grad_outs, arg_grad_outs,
             guard_vals) = outs
        finally:
            for s, v in saved:
                s.set(v)
            for s, g in saved_grads:
                t = s.ref()
                if t is not None:
                    t._grad = g
        if first_call is not None:
            prog.pop("fresh", None)
            for stage, sec in first_call.seconds.items():
                _M_JIT_COMPILE_S.observe(sec, fn=self.__name__, stage=stage)
            # recompile blame (ISSUE 6): one event per built program,
            # seconds = the four capture stages together
            _compile_tracker.record_compile(
                self.__name__, _blame_signature(prog.get("sig")),
                prog["discover_s"] + sum(first_call.seconds.values()))
        if prog.get("burned"):
            # guard check BEFORE any state commit: a miss discards this
            # run (inputs were not donated) and re-dispatches
            _sot.check_guards(prog["burned"], guard_vals)
        for i, v in zip(prog["mutable_idx"], new_mutable):
            slots[i].set(v)
        for slot_i, g in zip(spec.get("grad_targets", []), grad_outs):
            t = slots[slot_i].ref()
            if t is not None:
                t._grad = Tensor._wrap(g)
        # route arg-tensor grads back to the caller's tensors
        if spec.get("arg_grad_pos"):
            pos_to_tensor = {}
            _map_tensors((args, kwargs), lambda t: pos_to_tensor.setdefault(
                arg_order[id(t)], t))
            for pos, g in zip(spec["arg_grad_pos"], arg_grad_outs):
                t = pos_to_tensor.get(pos)
                if t is not None:
                    t._grad = Tensor._wrap(g)
        # don't pin the caller's argument pytree in the cache
        spec.pop("arg_proto", None)
        spec.pop("arg_order", None)
        return jax.tree_util.tree_map(
            lambda v: Tensor._wrap(v) if isinstance(v, jax.Array) else v,
            out_vals)

    @property
    def code(self):
        import inspect
        return inspect.getsource(self._fn)

    def concrete_program_specify_input_spec(self, *a, **k):
        raise NotImplementedError


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, **kwargs):
    """paddle.jit.to_static equivalent: whole-graph XLA capture.

    full_graph=False (the reference's modern default) allows GRAPH BREAKS:
    a function whose control flow can't be captured — after the dy2static
    AST pass has converted what it can — runs eagerly with a warning
    instead of raising.  full_graph=True restores the hard error."""
    def deco(fn):
        if hasattr(fn, "forward") and not callable(fn):  # pragma: no cover
            raise TypeError("pass a function or Layer")
        from ..nn import Layer
        if isinstance(fn, Layer):
            layer = fn
            orig_forward = layer.forward
            sf = StaticFunction(orig_forward, input_spec, build_strategy,
                                backend, full_graph)
            layer.forward = sf
            return layer
        return StaticFunction(fn, input_spec, build_strategy, backend,
                              full_graph)
    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass
