"""Optimizer base + SGD/Momentum/Adam/AdamW/Adagrad/RMSProp/Adadelta/Adamax/Lamb.

Parity: `python/paddle/optimizer/optimizer.py` (+ adamw.py etc.).  TPU-native
detail: each optimizer's update rule is one jitted pure function applied
per-parameter (XLA fuses the elementwise chain; donated buffers update
in place in HBM — the analogue of the reference's fused multi-tensor
optimizer kernels).  Master weights (multi_precision) keep an fp32 shadow for
bf16/fp16 params like `optimizer.py` master-weight path.
"""

from __future__ import annotations

import functools
from collections import defaultdict
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter, Tensor
from ..nn.clip import ClipGradBase
from ..observability import metrics as _metrics
from .lr import LRScheduler

# per-leaf jitted-program dispatches ride the same instrument as the
# eager op dispatcher, so one metrics delta covers a whole train step
# (the fused path counts ONE optimizer.fused_step instead — fused.py)
_M_DISPATCH = _metrics.counter("dispatch.ops", "eager dispatches per op name")
_K_LEAF_UPDATE = (("op", "optimizer.leaf_update"),)

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adagrad",
           "RMSProp", "Adadelta", "Adamax", "Lamb"]

import weakref

# live-optimizer registry consumed by jit capture (paddle_tpu/jit/api.py):
# optimizer accumulators/step counters become captured-program state.
_optimizer_registry: "weakref.WeakSet" = weakref.WeakSet()


def _live_optimizers():
    return list(_optimizer_registry)


def _donation_safe() -> bool:
    """Donation must be off while an eager SOT record (jit/sot.py) holds
    pre-step buffer references for rollback.  `to_static`'s traced state
    discovery executes nothing, so nothing it stages is donated."""
    from ..jit import sot as _sot
    return not _sot.recording_active()


def _instance_update(opt, rule, value, grad, master, states, lr, wd, step):
    """Shared jitted-apply path for optimizers with per-instance rules."""
    donate = _donation_safe()
    cache = getattr(opt, "_rule_jits", None)
    if cache is None:
        cache = opt._rule_jits = {}
    jitted = cache.get(donate)
    if jitted is None:
        def apply(value, grad, master, states, lr, wd, step):
            work = master if master is not None else value
            grad = grad.astype(work.dtype)
            new_work, new_states = rule(work, grad, states, lr, wd, step)
            if master is not None:
                return new_work.astype(value.dtype), new_work, new_states
            return new_work, None, new_states
        jitted = cache[donate] = jax.jit(
            apply, static_argnames=("wd",),
            donate_argnums=(0, 2, 3) if donate else ())
    if _metrics._ENABLED:
        _M_DISPATCH.inc_key(_K_LEAF_UPDATE)
    return jitted(value, grad, master, states,
                  jnp.asarray(lr, jnp.float32), wd,
                  jnp.asarray(step, jnp.float32))


class Optimizer:
    _update_rule: Callable = None  # set by subclasses
    _state_names: List[str] = []

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip: Optional[ClipGradBase] = None, name=None,
                 multi_precision: bool = False):
        if parameters is None:
            raise ValueError(
                "paddle_tpu is dygraph-first: pass `parameters=` explicitly")
        self._lr = learning_rate
        self._param_groups = self._build_groups(parameters)
        self._weight_decay = self._wd_value(weight_decay)
        self._l1 = self._l1_value(weight_decay)
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators: Dict[str, Dict[int, jax.Array]] = defaultdict(dict)
        self._global_step = 0
        self._aux_hooks: List[Callable] = []
        self._lr_override = None  # traced LR installed during jit capture
        _optimizer_registry.add(self)

    @staticmethod
    def _wd_value(weight_decay):
        """Returns the L2 coefficient; L1Decay is handled separately in
        _apply_one (sign-based grad term), never silently folded into L2."""
        if weight_decay is None:
            return 0.0
        from ..regularizer import L1Decay
        if isinstance(weight_decay, L1Decay):
            return 0.0
        if hasattr(weight_decay, "_coeff"):  # regularizer.L2Decay
            return float(weight_decay._coeff)
        return float(weight_decay)

    @staticmethod
    def _l1_value(weight_decay):
        from ..regularizer import L1Decay
        if isinstance(weight_decay, L1Decay):
            return float(weight_decay._coeff)
        return 0.0

    def _build_groups(self, parameters):
        parameters = list(parameters)
        if parameters and isinstance(parameters[0], dict):
            groups = []
            for g in parameters:
                g = dict(g)
                g["params"] = list(g["params"])
                groups.append(g)
            return groups
        return [{"params": parameters}]

    # ------------------------------------------------------------ lr
    def get_lr(self) -> float:
        if self._lr_override is not None:
            return self._lr_override
        if isinstance(self._lr, LRScheduler):
            return self._lr()
        return float(self._lr)

    def set_lr(self, value: float):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("set_lr not allowed with an LRScheduler")
        self._lr = float(value)

    def _create_master_weight(self, p: Parameter):
        key = id(p)
        mw = self._accumulators["master_weight"]
        if key not in mw:
            mw[key] = p._value.astype(jnp.float32)
        return mw[key]

    def _get_state(self, name: str, p: Parameter, like=None):
        key = id(p)
        store = self._accumulators[name]
        if key not in store:
            proto = like if like is not None else p._value
            store[key] = jnp.zeros(proto.shape, jnp.float32
                                   if self._multi_precision else proto.dtype)
        return store[key]

    def _set_state(self, name: str, p: Parameter, value):
        self._accumulators[name][id(p)] = value

    # ------------------------------------------------------------ step
    def _collect_work(self):
        """Collect across ALL groups first so ClipGradByGlobalNorm sees
        the true global norm (paddle clips the whole parameter list at
        once).  Returns (work, all_pg): work items are mutable
        [param, grad, lr, wd, l1] lists."""
        work = []
        all_pg = []
        for group in self._param_groups:
            lr = group.get("learning_rate", 1.0) * self.get_lr() \
                if "learning_rate" in group else self.get_lr()
            gwd = group.get("weight_decay", None)
            wd = self._wd_value(gwd) if "weight_decay" in group \
                else self._weight_decay
            l1 = self._l1_value(gwd) if "weight_decay" in group else self._l1
            for p in group["params"]:
                if p.grad is None or p.stop_gradient:
                    continue
                work.append([p, p.grad, lr, wd, l1])
                all_pg.append((p, p.grad))
        return work, all_pg

    @jax.named_scope("optimizer_step")
    def step(self):
        from . import fused as _fused
        self._global_step += 1
        work, all_pg = self._collect_work()
        if not _fused.try_step(self, work):
            self._apply_per_leaf(work, all_pg)
        for hook in self._aux_hooks:
            hook(self)

    def _apply_per_leaf(self, work, all_pg):
        """The legacy one-program-per-parameter path (FLAGS_fused_optimizer
        off, or an irregular step the fused plan declined)."""
        if self._grad_clip is not None:
            clipped = self._grad_clip(all_pg)
            for item, (_, g) in zip(work, clipped):
                item[1] = g
        for p, g, lr, wd, l1 in work:
            if g is None:
                continue
            self._apply_one(p, g._value if isinstance(g, Tensor) else g,
                            lr * p.optimize_attr.get("learning_rate", 1.0),
                            wd, l1)

    def _apply_one(self, p: Parameter, grad, lr: float, wd: float,
                   l1: float = 0.0):
        if l1:
            grad = grad + l1 * jnp.sign(p._value.astype(grad.dtype))
        use_master = self._multi_precision and p._value.dtype in (
            jnp.float16, jnp.bfloat16)
        master = self._create_master_weight(p) if use_master else None
        states = [self._get_state(n, p) for n in self._state_names]
        new_val, new_master, new_states = self._update(
            p._value, grad, master, states, lr, wd, self._global_step)
        p._value = new_val
        if use_master:
            self._accumulators["master_weight"][id(p)] = new_master
        for n, s in zip(self._state_names, new_states):
            self._set_state(n, p, s)

    def _update(self, value, grad, master, states, lr, wd, step):
        """Dispatch into the jitted rule; scalars ride as traced args so one
        executable serves every step and LR schedule value.  Donation updates
        param/state buffers in place in HBM except during an eager SOT
        record (it holds references for rollback)."""
        rule = type(self)._jitted_rule(donate=_donation_safe())
        if _metrics._ENABLED:
            _M_DISPATCH.inc_key(_K_LEAF_UPDATE)
        lr = jnp.asarray(lr, jnp.float32)
        step = jnp.asarray(step, jnp.float32)
        return rule(value, grad, master, states, lr, wd, step)

    @classmethod
    @functools.cache
    def _jitted_rule(cls, donate: bool = False):
        def apply(value, grad, master, states, lr, wd, step):
            work = master if master is not None else value
            grad = grad.astype(work.dtype)
            new_work, new_states = cls._update_rule(work, grad, states, lr,
                                                    wd, step)
            if master is not None:
                return new_work.astype(value.dtype), new_work, new_states
            return new_work, None, new_states
        return jax.jit(apply, static_argnames=("wd",),
                       donate_argnums=(0, 2, 3) if donate else ())

    # ------------------------------------------------------------ misc
    def clear_grad(self, set_to_zero: bool = True):
        for group in self._param_groups:
            for p in group["params"]:
                p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..static.program import default_main_program, in_static_build
        if in_static_build():
            # building a paddle.static Program: record the update for
            # Executor.run instead of mutating params with build-time zeros
            default_main_program().record_minimize(self, loss)
            return None, None
        if loss._grad_node is not None or not loss.stop_gradient:
            loss.backward()
        self.step()
        return None, None

    @property
    def _parameter_list(self):
        out = []
        for g in self._param_groups:
            out.extend(g["params"])
        return out

    def state_dict(self):
        # keys follow the reference format: "<param_name>_<accumulator>"
        # (`python/paddle/optimizer/optimizer.py` keys accumulators by the
        # parameter's name) so checkpoints survive parameter reordering
        import warnings
        gs = self._global_step
        if not isinstance(gs, int):
            # fused scaler steps keep the applied-step count on device
            # (it is found_inf-dependent); checkpointing materializes it
            try:
                gs = int(gs)
            except TypeError:  # tracer during capture: keep as-is
                pass
        out = {"LR_Scheduler": self._lr.state_dict()
               if isinstance(self._lr, LRScheduler) else {},
               "global_step": gs}
        for name, store in self._accumulators.items():
            for p in self._parameter_list:
                if id(p) in store:
                    key = f"{p.name}_{name}"
                    if key in out:
                        warnings.warn(
                            f"optimizer.state_dict: duplicate parameter "
                            f"name {p.name!r}; state for one of them is "
                            f"overwritten — give parameters unique names")
                    out[key] = Tensor._wrap(store[id(p)])
        return out

    def _known_state_names(self):
        names = set(self._state_names) | set(self._accumulators)
        names.add("master_weight")
        return names

    def remap_state_keys(self, network, sd, to_structured: bool):
        """Translate accumulator keys between this process's auto-generated
        parameter names ("param_37_moment1") and the network's stable
        structured names ("fc.0.weight@moment1"), so a .pdopt saved by one
        process restores into a freshly built model (the reference keys by
        parameter name, which its framework keeps stable across processes;
        our names are a process-global counter, so checkpoints store the
        structured form)."""
        state = network.state_dict()
        by_pname = {p.name: k for k, p in state.items()}
        accs = self._known_state_names()
        out = {}
        for key, v in sd.items():
            if key in ("LR_Scheduler", "global_step"):
                out[key] = v
                continue
            mapped = None
            if to_structured:
                for acc in accs:
                    if key.endswith("_" + acc):
                        sname = by_pname.get(key[:-len(acc) - 1])
                        if sname is not None:
                            mapped = f"{sname}@{acc}"
                        break
            elif "@" in key:
                sname, acc = key.rsplit("@", 1)
                p = state.get(sname)
                if p is not None:
                    mapped = f"{p.name}_{acc}"
            out[mapped or key] = v
        return out

    def set_state_dict(self, state):
        import warnings
        import numpy as np
        if isinstance(self._lr, LRScheduler) and state.get("LR_Scheduler"):
            self._lr.set_state_dict(state["LR_Scheduler"])
        self._global_step = int(state.get("global_step", 0))
        params = self._parameter_list
        by_name = {p.name: p for p in params}
        accs = self._known_state_names()
        for key, v in state.items():
            if key in ("LR_Scheduler", "global_step"):
                continue
            val = v._value if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
            # name-keyed format: "<param_name>_<accumulator>"; exact match on
            # both halves so a param name that prefixes another can't steal
            # its state, and unknown accumulators aren't silently created
            matched = False
            for acc in accs:
                if key.endswith("_" + acc):
                    p = by_name.get(key[:-len(acc) - 1])
                    if p is not None:
                        if tuple(val.shape) != tuple(p.shape):
                            warnings.warn(
                                f"optimizer.set_state_dict: {key!r} shape "
                                f"{tuple(val.shape)} does not match param "
                                f"{p.name} shape {tuple(p.shape)}; skipping")
                            matched = True
                            break
                        self._accumulators.setdefault(acc, {})[id(p)] = val
                        matched = True
                        break
            if matched:
                continue
            # legacy positional format: "<accumulator>_<index>"
            name, _, idx = key.rpartition("_")
            try:
                p = params[int(idx)]
            except (ValueError, IndexError):
                warnings.warn(
                    f"optimizer.set_state_dict: unmatched key {key!r} "
                    f"(no parameter/accumulator for it); skipping")
                continue
            self._accumulators.setdefault(name, {})[id(p)] = val


class SGD(Optimizer):
    _state_names: List[str] = []

    @staticmethod
    def _update_rule(w, g, states, lr, wd, step):
        if wd:
            g = g + wd * w
        return w - lr * g, []


class Momentum(Optimizer):
    _state_names = ["velocity"]

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov
        # per-instance rule (momentum is a python constant baked into jit)
        mu = float(momentum)
        nesterov = bool(use_nesterov)

        def rule(w, g, states, lr, wd, step):
            (v,) = states
            if wd:
                g = g + wd * w
            v2 = mu * v + g
            if nesterov:
                return w - lr * (g + mu * v2), [v2]
            return w - lr * v2, [v2]
        self._update_rule = staticmethod(rule)
        self.__rule_jit = None

    def _update(self, value, grad, master, states, lr, wd, step):
        return _instance_update(self, self._update_rule.__func__, value, grad,
                                master, states, lr, wd, step)


class _AdamBase(Optimizer):
    _state_names = ["moment1", "moment2"]

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None, decoupled: bool = False,
                 apply_decay_param_fun=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = float(beta1 if not isinstance(beta1, Tensor) else beta1.item())
        self._beta2 = float(beta2 if not isinstance(beta2, Tensor) else beta2.item())
        self._epsilon = float(epsilon)
        self._decoupled = decoupled
        self._apply_decay_param_fun = apply_decay_param_fun
        b1, b2, eps, dec = self._beta1, self._beta2, self._epsilon, decoupled

        def rule(w, g, states, lr, wd, step):
            m, v = states
            if wd and not dec:
                g = g + wd * w
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * jnp.square(g)
            mhat = m2 / (1 - b1 ** step)
            vhat = v2 / (1 - b2 ** step)
            upd = mhat / (jnp.sqrt(vhat) + eps)
            if wd and dec:
                upd = upd + wd * w
            return w - lr * upd, [m2, v2]
        self._rule = rule
        self._rule_jit = None

    def _apply_one(self, p, grad, lr, wd, l1=0.0):
        if self._apply_decay_param_fun is not None and \
                not self._apply_decay_param_fun(p.name):
            wd = 0.0
        super()._apply_one(p, grad, lr, wd, l1)

    def _update(self, value, grad, master, states, lr, wd, step):
        return _instance_update(self, self._rule, value, grad, master, states,
                                lr, wd, step)


class Adam(_AdamBase):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         name, decoupled=False)


class AdamW(_AdamBase):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         name, decoupled=True,
                         apply_decay_param_fun=apply_decay_param_fun)


class Adagrad(Optimizer):
    _state_names = ["moment"]

    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        eps = float(epsilon)

        def rule(w, g, states, lr, wd, step):
            (acc,) = states
            if wd:
                g = g + wd * w
            acc2 = acc + jnp.square(g)
            return w - lr * g / (jnp.sqrt(acc2) + eps), [acc2]
        self._rule = rule
        self._rule_jit = None

    _update = _AdamBase._update


class RMSProp(Optimizer):
    _state_names = ["mean_square", "mean_grad", "momentum_acc"]

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        rho_, eps, mu, cent = float(rho), float(epsilon), float(momentum), centered

        def rule(w, g, states, lr, wd, step):
            ms, mg, mom = states
            if wd:
                g = g + wd * w
            ms2 = rho_ * ms + (1 - rho_) * jnp.square(g)
            if cent:
                mg2 = rho_ * mg + (1 - rho_) * g
                denom = jnp.sqrt(ms2 - jnp.square(mg2) + eps)
            else:
                mg2 = mg
                denom = jnp.sqrt(ms2 + eps)
            mom2 = mu * mom + lr * g / denom
            return w - mom2, [ms2, mg2, mom2]
        self._rule = rule
        self._rule_jit = None

    _update = _AdamBase._update


class Adadelta(Optimizer):
    _state_names = ["avg_squared_grad", "avg_squared_update"]

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        rho_, eps = float(rho), float(epsilon)

        def rule(w, g, states, lr, wd, step):
            ag, au = states
            if wd:
                g = g + wd * w
            ag2 = rho_ * ag + (1 - rho_) * jnp.square(g)
            upd = jnp.sqrt(au + eps) / jnp.sqrt(ag2 + eps) * g
            au2 = rho_ * au + (1 - rho_) * jnp.square(upd)
            return w - lr * upd, [ag2, au2]
        self._rule = rule
        self._rule_jit = None

    _update = _AdamBase._update


class Adamax(Optimizer):
    _state_names = ["moment", "inf_norm"]

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        b1, b2, eps = float(beta1), float(beta2), float(epsilon)

        def rule(w, g, states, lr, wd, step):
            m, u = states
            if wd:
                g = g + wd * w
            m2 = b1 * m + (1 - b1) * g
            u2 = jnp.maximum(b2 * u, jnp.abs(g))
            return w - lr / (1 - b1 ** step) * m2 / (u2 + eps), [m2, u2]
        self._rule = rule
        self._rule_jit = None

    _update = _AdamBase._update


class Lamb(Optimizer):
    _state_names = ["moment1", "moment2"]

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, name, multi_precision)
        b1, b2, eps = float(beta1), float(beta2), float(epsilon)
        self._exclude_fn = exclude_from_weight_decay_fn

        def rule(w, g, states, lr, wd, step):
            m, v = states
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * jnp.square(g)
            mhat = m2 / (1 - b1 ** step)
            vhat = v2 / (1 - b2 ** step)
            r = mhat / (jnp.sqrt(vhat) + eps)
            if wd:
                r = r + wd * w
            w_norm = jnp.linalg.norm(w)
            r_norm = jnp.linalg.norm(r)
            trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
            return w - lr * trust * r, [m2, v2]
        self._rule = rule
        self._rule_jit = None

    def _apply_one(self, p, grad, lr, wd, l1=0.0):
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        super()._apply_one(p, grad, lr, wd, l1)

    _update = _AdamBase._update
