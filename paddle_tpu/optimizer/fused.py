"""Fused whole-pytree optimizer update: the training-step fast path.

The per-leaf path in `optimizer.py` dispatches one jitted XLA program per
parameter per step (plus two more per parameter for global-norm clipping
and another per parameter for AMP unscaling) — for a GPT-sized pytree
that is hundreds of tiny executables and, with a GradScaler, a forced
device→host `bool(found_inf)` round trip every step.  The reference
solves this with multi-tensor CUDA kernels (`fused_adam_kernel.h`,
`multi_tensor_adam`); the TPU-native analogue is ONE donated jitted
program over the entire flattened pytree that performs, inside a single
executable:

1. AMP unscale (``grad * 1/scale`` per leaf, dtype-preserving),
2. the on-device ``found_inf`` reduction (one OR over per-leaf
   ``any(~isfinite)`` flags, never synced to the host here),
3. gradient clipping — ClipGradByGlobalNorm's fused squared-norm
   reduction + scale (composing with the fleet cross-mesh
   ``_global_norm_reduce_fn`` hook, traced into the program),
   ClipGradByNorm / ClipGradByValue elementwise,
4. the optimizer update for every parameter, including master-weight
   promotion, with ``lax.cond(found_inf)`` skipping the whole update
   (params/masters/states pass through untouched) on an overflow step,
5. the GradScaler's dynamic scale/good/bad bookkeeping, kept as device
   scalars so `GradScaler.step` never blocks the dispatch queue — the
   flag is read back only at the flag-spaced loss sync
   (`GradScaler._sync_fused_state`).

Programs are cached per ``(tree structure + dtypes, per-leaf static
config, clip config, scaler config, donation)`` on the OPTIMIZER
INSTANCE (update rules are per-instance closures over hyperparameters).
Param/master/state buffers are donated so XLA updates them in place in
HBM, exactly like the per-leaf path — and like it, donation is held off
only while an eager SOT record (`jit/sot.py`) holds rollback references
(`to_static`'s traced state discovery executes nothing).

Numerics: the fused program replays the per-leaf computation with the
same primitives in the same order (left-fold squared-norm accumulation,
f32 scalar lr/step inputs), so fp32 results are BIT-IDENTICAL to the
per-leaf path (pinned by tests/test_optimizer.py's parity suite).

Fallbacks (counted on the ``optimizer.fused`` counter, kind=fallback):
L1 decay, custom ClipGradBase subclasses, optimizers without a
registered elementwise rule (LBFGS), a global-norm reduce hook that
cannot trace (host-side cross-mesh reductions), ZeRO trees whose leaves
sit on incompatible device placements, and — scaler path only — aux
hooks (the legacy path gates them on the update actually applying).
Per-leaf ``need_clip`` / ``optimize_attr`` learning rates and
group-level overrides are regular enough to stay fused (static masks /
a traced per-leaf LR vector).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags as _flags
from ..framework.tensor import Tensor
from ..observability import compile_tracker as _compile_tracker
from ..observability import metrics as _metrics

__all__ = ["enabled", "try_step", "scaler_step", "zero3_shard_update"]

# hit = cached program reused; miss = new (tree, config) program traced;
# fallback = irregular step served by the per-leaf path
_M_FUSED = _metrics.counter(
    "optimizer.fused",
    "fused train-update outcomes per step (kind=hit|miss|fallback)")
# the optimizer layer's program dispatches ride the same instrument the
# eager op dispatcher uses, so one metrics delta covers a whole step
_M_DISPATCH = _metrics.counter(
    "dispatch.ops", "eager dispatches per op name")
_K_HIT = (("kind", "hit"),)
_K_MISS = (("kind", "miss"),)
_K_FALLBACK = (("kind", "fallback"),)
_K_FUSED_STEP = (("op", "optimizer.fused_step"),)


def enabled() -> bool:
    try:
        return bool(_flags.get_flag("fused_optimizer"))
    except ValueError:  # pragma: no cover - flag always registered
        return False


def zero3_shard_update(p_shards, g_shards, m_shards, v_shards, step, *,
                       learning_rate, beta1, beta2, eps):
    """Fused Adam over 1/N-resident ZeRO-3 shard lists.

    The one-dispatch fused update applied to SHARDED residents: every
    leaf here is one dp rank's flat parameter/moment shard, and the
    whole list updates inside the caller's program (the fused ZeRO-3
    step traces this after its in-program reduce-scatter, so with
    donation the flat shard buffers update in place in HBM — no
    per-leaf dispatch, no full-parameter moment state anywhere).
    Elementwise only, so the math is length-invariant: the same global
    element sees bit-identical updates at any sharding world size,
    which is what the elastic reshard-on-resume drill pins.  Primitive
    order matches `hybrid_step._adam_math` (bit parity with the ZeRO-1/2
    hybrid path's per-shard update)."""
    new_p, new_m, new_v = [], [], []
    for p, g, m, v in zip(p_shards, g_shards, m_shards, v_shards):
        m2 = beta1 * m + (1 - beta1) * g
        v2 = beta2 * v + (1 - beta2) * jnp.square(g)
        mh = m2 / (1 - beta1 ** step)
        vh = v2 / (1 - beta2 ** step)
        new_p.append(p - learning_rate * mh / (jnp.sqrt(vh) + eps))
        new_m.append(m2)
        new_v.append(v2)
    return new_p, new_m, new_v


def _rule_of(opt):
    """The per-leaf update rule `(w, g, states, lr, wd, step) ->
    (new_w, new_states)` — per-instance closure (Adam family, Momentum)
    or class staticmethod (SGD); None for optimizers without one."""
    r = getattr(opt, "_rule", None)
    if callable(r):
        return r
    r = getattr(opt, "_update_rule", None)
    if isinstance(r, staticmethod):  # Momentum stores an instance staticmethod
        return r.__func__
    return r if callable(r) else None


def _effective_wd(opt, p, wd):
    """Replicates the per-leaf `_apply_one` overrides: AdamW's
    apply_decay_param_fun and Lamb's exclude_from_weight_decay_fn."""
    fn = getattr(opt, "_apply_decay_param_fun", None)
    if fn is not None and not fn(p.name):
        return 0.0
    ex = getattr(opt, "_exclude_fn", None)
    if ex is not None and ex(p):
        return 0.0
    return wd


def _clip_config(opt) -> Tuple[Optional[tuple], Optional[Any], bool]:
    """(static clip key, traced reduce hook, fusible).  Exact-type checks:
    user subclasses of the clip classes fall back to the per-leaf path."""
    clip = opt._grad_clip
    if clip is None:
        return None, None, True
    from ..nn.clip import (ClipGradByGlobalNorm, ClipGradByNorm,
                           ClipGradByValue)
    t = type(clip)
    if t is ClipGradByGlobalNorm:
        hook = clip._global_norm_reduce_fn
        # the hook OBJECT rides in the cache key (functions hash by
        # identity): keeps a strong ref, so a recycled id() can never
        # alias a new hook onto a program traced with the old one
        return (("global", clip.clip_norm, hook), hook, True)
    if t is ClipGradByNorm:
        return ("norm", clip.clip_norm), None, True
    if t is ClipGradByValue:
        return ("value", clip.min, clip.max), None, True
    return None, None, False


def _scaler_config(scaler) -> Optional[tuple]:
    if scaler is None:
        return None
    return ("scaler", float(scaler._incr_ratio), float(scaler._decr_ratio),
            int(scaler._incr_every), int(scaler._decr_every),
            bool(scaler._dynamic))


# the (shape, dtype) cache-key atom every fast-path program cache shares
from ..nn.clip import _aval_key  # noqa: E402


# cache sentinel: this (tree, config) cannot run as one program (e.g.
# leaves committed to incompatible device placements under ZeRO, or a
# host-side _global_norm_reduce_fn hook that cannot trace) — remembered
# so the step doesn't re-raise every iteration
_UNFUSIBLE = object()

# errors that mean "this plan cannot run fused" but are raised BEFORE
# execution (buffers intact, safe to fall back): jit argument/placement
# validation (ValueError) and trace-time concretization of a host-side
# hook (the same family ops/registry treats as trace failures)
_PLAN_ERRORS = (ValueError,
                jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerIntegerConversionError,
                jax.errors.UnexpectedTracerError,
                jax.errors.NonConcreteBooleanIndexError)


def _build_program(rule, statics, clip_cfg, reduce_fn, scaler_cfg, donate):
    """Trace-time factory.  `statics` is a tuple of per-leaf
    (use_master, wd, need_clip); everything per-leaf that the rules
    branch on in Python (wd truthiness) is baked in here."""

    def update_tree(params, grads, masters, states, lrs, step):
        if clip_cfg is not None:
            kind = clip_cfg[0]
            if kind == "global":
                # left-fold accumulation in leaf order — the exact shape
                # of ClipGradByGlobalNorm's eager loop, for bit parity
                sq = None
                for (_, _, nc), g in zip(statics, grads):
                    if not nc:
                        continue
                    s = jnp.sum(jnp.square(g.astype(jnp.float32)))
                    sq = s if sq is None else sq + s
                if sq is not None:
                    if reduce_fn is not None:
                        sq = reduce_fn(sq)
                    gnorm = jnp.sqrt(sq)
                    cscale = clip_cfg[1] / jnp.maximum(gnorm, clip_cfg[1])
                    grads = [(g.astype(jnp.float32) * cscale).astype(g.dtype)
                             if nc else g
                             for (_, _, nc), g in zip(statics, grads)]
            elif kind == "norm":
                cn = clip_cfg[1]

                def clip_one(g):
                    norm = jnp.sqrt(jnp.sum(jnp.square(g)))
                    s = jnp.where(norm > cn, cn / jnp.maximum(norm, 1e-12),
                                  1.0)
                    return g * s
                grads = [clip_one(g) if nc else g
                         for (_, _, nc), g in zip(statics, grads)]
            else:  # value
                lo, hi = clip_cfg[1], clip_cfg[2]
                grads = [jnp.clip(g, lo, hi) if nc else g
                         for (_, _, nc), g in zip(statics, grads)]
            # the per-leaf path rounds clipped grads at its program
            # boundary; fence them here so XLA cannot fma-fuse the clip
            # multiply into the update (bit parity with per-leaf)
            grads = list(jax.lax.optimization_barrier(tuple(grads)))
        new_p, new_m, new_s = [], [], []
        for i, ((use_master, wd, _), p, g, m, st) in enumerate(
                zip(statics, params, grads, masters, states)):
            work = m if use_master else p
            g = g.astype(work.dtype)
            new_w, new_st = rule(work, g, st, lrs[i], wd, step)
            if use_master:
                new_p.append(new_w.astype(p.dtype))
                new_m.append(new_w)
            else:
                new_p.append(new_w)
                new_m.append(None)
            new_s.append(list(new_st))
        return new_p, new_m, new_s

    if scaler_cfg is None:
        def program(params, grads, masters, states, lrs, step):
            return update_tree(params, grads, masters, states, lrs, step)
    else:
        _, incr_ratio, decr_ratio, incr_every, decr_every, dynamic = \
            scaler_cfg

        def program(params, grads, masters, states, lrs, gstep,
                    scale, good, bad, nskip):
            inv = 1.0 / scale
            grads = [g * inv.astype(g.dtype) for g in grads]
            found = jnp.zeros((), jnp.bool_)
            for g in grads:
                found = found | jnp.any(~jnp.isfinite(g))
            # per-leaf rounds unscaled grads at the unscale-program
            # boundary (found is computed inside it — before the fence)
            grads = list(jax.lax.optimization_barrier(tuple(grads)))
            # the legacy path only advances _global_step when the update
            # APPLIES (a skipped step must not advance Adam's bias
            # correction) — so the applied-step count is found-dependent
            # and stays on device with everything else
            new_p, new_m, new_s = jax.lax.cond(
                found,
                lambda: (list(params), list(masters),
                         [list(st) for st in states]),
                lambda: update_tree(params, grads, masters, states, lrs,
                                    (gstep + 1).astype(jnp.float32)))
            new_gstep = jnp.where(found, gstep, gstep + 1)
            # GradScaler.update() replayed on device
            if dynamic:
                bad1 = bad + 1
                good1 = good + 1
                dec = bad1 >= decr_every
                inc = good1 >= incr_every
                scale2 = jnp.where(
                    found,
                    jnp.where(dec, jnp.maximum(scale * decr_ratio, 1.0),
                              scale),
                    jnp.where(inc, scale * incr_ratio, scale))
                good2 = jnp.where(found, 0, jnp.where(inc, 0, good1))
                bad2 = jnp.where(found, jnp.where(dec, 0, bad1), 0)
            else:
                scale2, good2, bad2 = scale, good, bad
            nskip2 = nskip + found.astype(nskip.dtype)
            # the legacy path writes UNSCALED (not clipped) grads back to
            # p.grad; return them so post-step grad introspection matches
            return (new_p, new_m, new_s, grads, new_gstep,
                    (found, scale2, good2, bad2, nskip2))

    return jax.jit(program,
                   donate_argnums=(0, 2, 3) if donate else ())


def _plan(opt, work, scaler, clip_static):
    """Resolve (or build) the fused program for this step's pytree.
    `clip_static` is (clip_key, reduce_fn) to embed in the program, or
    (None, None) when clipping is handled outside (or absent).  Returns
    None when the step is irregular — caller falls back."""
    rule = _rule_of(opt)
    if rule is None:
        return None
    clip_key, reduce_fn = clip_static
    scaler_cfg = _scaler_config(scaler)
    from .optimizer import _donation_safe
    # CPU PJRT doesn't implement donation (same gate as to_static's
    # whole-step programs, jit/api.py) — observed to corrupt the heap
    # under the persistent compile cache; donation is a TPU/HBM feature
    donate = _donation_safe() and jax.default_backend() != "cpu"
    state_names = list(opt._state_names)

    leaves = []   # (p, grad_value, lr, use_master, wd, need_clip)
    for p, g, lr, wd, l1 in work:
        if l1:
            return None  # L1Decay's sign-term stays on the per-leaf path
        gv = g._value if isinstance(g, Tensor) else g
        use_master = opt._multi_precision and p._value.dtype in (
            jnp.float16, jnp.bfloat16)
        wd_eff = _effective_wd(opt, p, wd)
        lr_eff = lr * p.optimize_attr.get("learning_rate", 1.0)
        need_clip = bool(getattr(p, "need_clip", True))
        leaves.append((p, gv, lr_eff, use_master, wd_eff, need_clip))

    statics = tuple((um, wd, nc) for _, _, _, um, wd, nc in leaves)
    # gather state/master arrays now: their actual dtypes (possibly loaded
    # from a checkpoint) are part of the program signature
    masters = [opt._create_master_weight(p) if um else None
               for p, _, _, um, _, _ in leaves]
    states = [[opt._get_state(n, p) for n in state_names]
              for p, _, _, _, _, _ in leaves]
    key = (statics, clip_key, scaler_cfg, donate, tuple(state_names),
           tuple(_aval_key(p._value) for p, *_ in leaves),
           tuple(_aval_key(gv) for _, gv, *_ in leaves),
           tuple(_aval_key(m) if m is not None else None for m in masters),
           tuple(tuple(_aval_key(s) for s in st) for st in states))
    try:
        hash(key)
    except TypeError:
        return None
    cache: Dict[Any, Any] = opt.__dict__.setdefault("_fused_programs", {})
    prog = cache.get(key)
    if prog is _UNFUSIBLE:
        return None
    if prog is None:
        _M_FUSED.inc_key(_K_MISS)
        # recompile blame (ISSUE 6): the first call of a fresh fused
        # program is where the trace+XLA compile lands; the signature
        # names what re-triggers it (a new leaf aval, clip/scaler config)
        blame_sig = (("leaves", len(leaves)),
                     ("clip", repr(clip_key)[:120]),
                     ("scaler", scaler_cfg is not None),
                     ("donate", donate),
                     ("params", tuple(repr(_aval_key(p._value))
                                      for p, *_ in leaves)))
        prog = cache[key] = _compile_tracker.wrap_first_call(
            _build_program(rule, statics, clip_key, reduce_fn,
                           scaler_cfg, donate),
            "optimizer.fused_step", blame_sig)
    elif _metrics._ENABLED:
        _M_FUSED.inc_key(_K_HIT)
    return prog, key, leaves, masters, states, state_names


def _execute(opt, plan, scaler, grads_override=None):
    prog, _key, leaves, masters, states, state_names = plan
    params = [p._value for p, *_ in leaves]
    grads = grads_override if grads_override is not None \
        else [gv for _, gv, *_ in leaves]
    lr_list = [lr for _, _, lr, _, _, _ in leaves]
    if all(isinstance(lr, float) for lr in lr_list):
        # one H2D put (np rounds f64->f32 exactly like per-leaf asarray)
        lrs = jnp.asarray(np.asarray(lr_list, np.float32))
    else:  # traced LR (to_static capture): stack the tracers
        lrs = jnp.stack([jnp.asarray(lr, jnp.float32) for lr in lr_list])
    if _metrics._ENABLED:
        _M_DISPATCH.inc_key(_K_FUSED_STEP)
    if scaler is None:
        step = jnp.asarray(opt._global_step, jnp.float32)
        new_p, new_m, new_s = prog(params, grads, masters, states, lrs, step)
    else:
        # the caller did NOT pre-increment _global_step: whether this
        # step applies is found_inf-dependent, so the program returns the
        # new applied-step count as a device scalar
        gstep = jnp.asarray(opt._global_step, jnp.int32)
        scale, good, bad, nskip = scaler._fused_state()
        new_p, new_m, new_s, out_grads, new_gstep, sc_out = prog(
            params, grads, masters, states, lrs, gstep,
            scale, good, bad, nskip)
        opt._global_step = new_gstep
        scaler._fused_commit(*sc_out)
        for (p, *_), g in zip(leaves, out_grads):
            if p.grad is not None:  # legacy parity: grads end up unscaled
                p.grad._value = g
    for i, (p, _, _, use_master, _, _) in enumerate(leaves):
        p._value = new_p[i]
        if use_master:
            opt._accumulators["master_weight"][id(p)] = new_m[i]
        for n, s in zip(state_names, new_s[i]):
            opt._accumulators[n][id(p)] = s
    _poison_donated_inputs(params, masters, states, new_p, new_m, new_s)


def _poison_donated_inputs(params, masters, states, new_p, new_m, new_s):
    """jaxsan (FLAGS_enable_jaxsan, default off): the fused program
    donates params/masters/states on TPU — on CPU donation is ignored,
    so a stale reference to a pre-step buffer reads plausible bytes in
    every CPU test and garbage in production.  Poisoning the superseded
    input leaves right after the rebind turns that latent use-after-
    donate into an immediate loud jax deleted-array error.  Leaves the
    program passed through by identity are kept alive."""
    from ..testing import jaxsan as _jaxsan
    if not _jaxsan.enabled():
        return
    old = list(params) + [m for m in masters if m is not None]
    for st in states:
        old.extend(st)
    keep = list(new_p) + list(new_m) + [s for st in new_s for s in st]
    _jaxsan.poison_donated(old, site="optimizer.fused_step", keep=keep)


def try_step(opt, work) -> bool:
    """Fused path for a plain `Optimizer.step` (no scaler).  `work` is
    the collected [param, grad, lr, wd, l1] list; the caller has already
    incremented `_global_step`.  False → run the per-leaf path."""
    if not enabled() or not work:
        return False
    if _rule_of(opt) is None or any(item[4] for item in work):
        # no elementwise rule (LBFGS) / L1 decay: cheap Python checks
        # BEFORE the pre-clip below, so a permanently-unfusible config
        # doesn't pay a wasted clip dispatch (and a double clip) per step
        _M_FUSED.inc_key(_K_FALLBACK)
        return False
    clip_key, reduce_fn, clip_ok = _clip_config(opt)
    if not clip_ok:
        _M_FUSED.inc_key(_K_FALLBACK)
        return False
    external = clip_key is not None and clip_key[0] in ("norm", "value")
    # plan first — its key depends on avals only, which clipping
    # preserves — so an _UNFUSIBLE tree falls back without paying the
    # pre-clip dispatch every step
    plan = _plan(opt, work, None,
                 (None, None) if external else (clip_key, reduce_fn))
    if plan is None:
        _M_FUSED.inc_key(_K_FALLBACK)
        return False
    grads_override = None
    if external:
        # per-leaf clips round at their own program boundary so the
        # per-leaf path's bits are reproducible (in-program, XLA may
        # contract the clip multiply into the update as an fma); the
        # clip object's one cached per-tree program + the clip-free
        # update program is still 2 dispatches
        pairs = opt._grad_clip([(p, g) for p, g, *_ in work])
        for item, (_, g) in zip(work, pairs):
            item[1] = g
        grads_override = [g._value if isinstance(g, Tensor) else g
                          for _, g, *_ in work]
    try:
        _execute(opt, plan, None, grads_override)
    except _PLAN_ERRORS:
        # pre-execution failure (placement validation, untraceable clip
        # hook) — buffers intact: remember and fall back
        opt._fused_programs[plan[1]] = _UNFUSIBLE
        _M_FUSED.inc_key(_K_FALLBACK)
        return False
    return True


def scaler_step(scaler, opt) -> bool:
    """Whole `GradScaler.step` as one device program: unscale, found_inf,
    clip, update-or-skip, dynamic scale bookkeeping — found_inf stays on
    device (read back at `scaler._sync_fused_state`).  False → caller
    runs the legacy host-sync path (which may still fuse the update).
    Clipping always runs in-program here (it must see UNSCALED grads,
    and the unscale/found reduction never leaves the program)."""
    if not enabled():
        return False
    if opt._aux_hooks:
        # the legacy path runs aux hooks only when the update actually
        # APPLIES (optimizer.step is skipped on found_inf), which the
        # fused path cannot decide without a host sync — fall back so
        # hook semantics stay identical
        return False
    clip_key, reduce_fn, clip_ok = _clip_config(opt)
    if not clip_ok:
        _M_FUSED.inc_key(_K_FALLBACK)
        return False
    work, _ = opt._collect_work()
    if not work:
        return False
    if sum(1 for p in opt._parameter_list
           if p.grad is not None) != len(work):
        # a param holds a grad but is excluded from the update (frozen
        # via stop_gradient): the legacy path still unscales it and
        # feeds it into found_inf — fall back to keep those semantics
        _M_FUSED.inc_key(_K_FALLBACK)
        return False
    g0 = work[0][1]
    if isinstance(getattr(g0, "_value", g0), jax.core.Tracer) or \
            isinstance(work[0][0]._value, jax.core.Tracer):
        # inside a to_static trace: committing tracers into the scaler's
        # device state would leak them past the trace.  Decline — the
        # legacy path's bool(found_inf) concretization graph-breaks the
        # capture exactly as before, and the eager re-run fuses normally.
        return False
    plan = _plan(opt, work, scaler, (clip_key, reduce_fn))
    if plan is None:
        _M_FUSED.inc_key(_K_FALLBACK)
        return False
    try:
        _execute(opt, plan, scaler)
    except _PLAN_ERRORS:
        # pre-execution failure (placement validation, untraceable clip
        # hook): the legacy host-sync scaler path serves this tree
        opt._fused_programs[plan[1]] = _UNFUSIBLE
        _M_FUSED.inc_key(_K_FALLBACK)
        return False
    return True
