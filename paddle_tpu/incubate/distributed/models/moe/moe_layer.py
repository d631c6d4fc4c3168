"""MoE layer with expert parallelism.

Parity: `python/paddle/incubate/distributed/models/moe/moe_layer.py:263`
(MoELayer), `:99/:149` (MoEScatter/MoEGather — replaced by dense einsum
dispatch), `utils.py` (prepare_forward — replaced by the gate's fixed
capacity).

TPU-native: the reference scatters tokens with index ops and moves them
between ranks with an explicit NCCL all-to-all (`global_scatter/gather`).
Here dispatch/combine are einsums over a fixed-capacity buffer
(T,E,C)x(T,M)->(E,C,M); experts run as one batched einsum over stacked
weights (E,M,H)/(E,H,M) so the MXU sees large matmuls; when the stacked
expert dim is sharded over an `ep` mesh axis, GSPMD lowers the dispatch
einsum to the same all-to-all the reference codes by hand — and it rides
ICI inside a jit program instead of going through host NCCL calls.

Fused dispatch (ISSUE 18, default on): the dense dispatch/combine
einsums contract against (T, E, C) one-hot tensors — ``T*E*C*M`` FLOPs
for what is a gather of ``T*k`` rows.  With ``FLAGS_moe_fused_dispatch``
the layer takes the gate's index-form routing (`forward_indices`) and
runs the one-pass Pallas dispatch/combine kernels of
`ops/pallas_moe.py` instead; the dense einsum path stays as the oracle
and the fallback when pallas is unavailable.  The flag is snapshotted
at layer construction (R004: no flag reads inside traced fns).
:func:`audit_dispatch` lowers the active data plane into the X-ray
kernel-coverage ledger — the MoE analogue of the serving warmup audit.
"""

from __future__ import annotations

import math
from typing import Optional

import paddle_tpu as paddle
from paddle_tpu.nn.layer.layers import Layer
import paddle_tpu.nn.functional as F
from paddle_tpu import flags as _flags
from paddle_tpu.ops import pallas_kernels as _pk

from .gate import BaseGate, GShardGate, NaiveGate, SwitchGate

__all__ = ["ExpertMLP", "MoELayer", "audit_dispatch"]


class ExpertMLP(Layer):
    """E parallel two-layer MLPs with stacked weights.

    Weights are (E, d_model, d_hidden) / (E, d_hidden, d_model) so the whole
    expert computation is two einsums; shard dim 0 over the `ep` mesh axis
    for expert parallelism.
    """

    def __init__(self, num_expert: int, d_model: int, d_hidden: int,
                 activation: str = "gelu"):
        super().__init__()
        self.num_expert = num_expert
        self.d_model = d_model
        self.d_hidden = d_hidden
        scale1 = 1.0 / math.sqrt(d_model)
        scale2 = 1.0 / math.sqrt(d_hidden)
        self.w1 = self.create_parameter(
            [num_expert, d_model, d_hidden],
            default_initializer=paddle.nn.initializer.Uniform(-scale1, scale1))
        self.b1 = self.create_parameter(
            [num_expert, 1, d_hidden],
            default_initializer=paddle.nn.initializer.Constant(0.0))
        self.w2 = self.create_parameter(
            [num_expert, d_hidden, d_model],
            default_initializer=paddle.nn.initializer.Uniform(-scale2, scale2))
        self.b2 = self.create_parameter(
            [num_expert, 1, d_model],
            default_initializer=paddle.nn.initializer.Constant(0.0))
        self.act = getattr(F, activation)

    def forward(self, x):
        """x: (E, C, d_model) -> (E, C, d_model), batched over experts."""
        h = paddle.einsum("ecm,emh->ech", x, self.w1) + self.b1
        h = self.act(h)
        return paddle.einsum("ech,ehm->ecm", h, self.w2) + self.b2


class MoELayer(Layer):
    """Mixture-of-experts layer: gate -> dispatch -> experts -> combine.

    Parity: `moe_layer.py:263`.  `gate` may be a BaseGate instance or one of
    the strings "naive"/"switch"/"gshard"; `experts` may be an ExpertMLP
    (recommended, shardable) or a list of per-token Layers applied via
    stacking is NOT supported — build an ExpertMLP instead (the reference's
    per-expert Layer list maps to stacked weights on TPU).

    After each forward the gate's aux loss is available as `self.l_aux`
    (add it to the training loss, as the reference's MoELayer callers do).
    """

    def __init__(self, d_model: int, experts: Optional[ExpertMLP] = None,
                 gate: "BaseGate | str" = "gshard", num_expert: int = None,
                 d_hidden: int = None, top_k: int = 2,
                 capacity_factor: Optional[float] = None, moe_group=None,
                 mp_group=None, **gate_kwargs):
        super().__init__()
        if experts is None:
            assert num_expert and d_hidden, \
                "give experts= or (num_expert=, d_hidden=)"
            experts = ExpertMLP(num_expert, d_model, d_hidden)
        self.experts = experts
        E = experts.num_expert
        if isinstance(gate, str):
            cf = 1.25 if capacity_factor is None else capacity_factor
            if gate == "naive":
                gate = NaiveGate(d_model, E, top_k=top_k,
                                 capacity_factor=cf, **gate_kwargs)
            elif gate == "switch":
                gate = SwitchGate(d_model, E, capacity_factor=cf,
                                  **gate_kwargs)
            elif gate == "gshard":
                if top_k != 2:
                    raise ValueError("gshard gate routes top-2; use "
                                     "gate='naive' for other top_k")
                if "capacity" not in gate_kwargs and \
                        capacity_factor is not None:
                    # translate tokens/(E*k) factor to GShard's tokens/E tuple
                    gate_kwargs["capacity"] = (2 * capacity_factor,
                                               2 * capacity_factor)
                gate = GShardGate(d_model, E, **gate_kwargs)
            else:
                raise ValueError(f"unknown gate {gate!r}")
        self.gate = gate
        self.l_aux = None
        # snapshot (R004): the fused data plane is chosen at construction,
        # never inside a traced forward
        self._fused = (bool(_flags.get_flag("moe_fused_dispatch"))
                       and hasattr(self.gate, "forward_indices"))

    def forward(self, x):
        """x: (..., d_model); routing flattens all leading dims to tokens."""
        orig_shape = x.shape
        d_model = orig_shape[-1]
        xt = paddle.reshape(x, [-1, d_model])                  # (T, M)
        if self._fused:
            out = self._forward_fused(xt)
        else:
            combine, dispatch, aux = self.gate(xt)             # (T,E,C) x2
            self.l_aux = aux
            expert_in = paddle.einsum("tec,tm->ecm", dispatch, xt)
            expert_out = self.experts(expert_in)               # (E, C, M)
            out = paddle.einsum("tec,ecm->tm", combine, expert_out)
        return paddle.reshape(out, orig_shape)

    def _forward_fused(self, xt):
        """One-pass routing: the gate's index-form decision drives the
        Pallas dispatch/combine kernels — no (T, E, C) tensors."""
        eid, slot, keep, w, cap, aux = self.gate.forward_indices(xt)
        self.l_aux = aux
        E = self.gate.tot_expert
        flat, inv = _pk.moe_routing_indices(eid, slot, keep, E, cap)
        rows = _pk.moe_dispatch(xt, inv)                       # (E*C, M)
        expert_in = paddle.reshape(rows, [E, cap, xt.shape[1]])
        expert_out = self.experts(expert_in)                   # (E, C, M)
        return _pk.moe_combine(
            paddle.reshape(expert_out, [E * cap, xt.shape[1]]), w, flat)


def audit_dispatch(layer: MoELayer, num_tokens: int = 64):
    """Register + audit the layer's dispatch/combine program in the
    X-ray kernel-coverage ledger (`xray.kernel_coverage`), the MoE
    analogue of the serving warmup audit: lower a jit of the ACTIVE
    data plane — fused kernels or dense einsums, per the layer's
    snapshot — over abstract (num_tokens, d_model) routing shapes,
    capturing trace-time kernel claims.  Returns the audit row's
    program key."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability import xray as _xray
    from paddle_tpu.ops import pallas_moe as _pm
    from .gate import capacity as _capacity

    gate = layer.gate
    E = gate.tot_expert
    k = gate.top_k
    M = layer.experts.d_model
    T = int(num_tokens)
    cap = _capacity(T, E, k, getattr(gate, "capacity_factor", 1.25),
                    getattr(gate, "min_capacity", 4))
    fused = layer._fused

    if fused:
        def prog(x, inv, w, flat):
            rows = _pm.moe_dispatch(x, inv)
            return _pm.moe_combine(rows, w, flat)
        shapes = (jax.ShapeDtypeStruct((T, M), jnp.float32),
                  jax.ShapeDtypeStruct((E * cap,), jnp.int32),
                  jax.ShapeDtypeStruct((T, k), jnp.float32),
                  jax.ShapeDtypeStruct((T, k), jnp.int32))
    else:
        def prog(x, dispatch, combine):
            expert_in = jnp.einsum("tec,tm->ecm", dispatch, x)
            return jnp.einsum("tec,ecm->tm", combine, expert_in)
        shapes = (jax.ShapeDtypeStruct((T, M), jnp.float32),
                  jax.ShapeDtypeStruct((T, E, cap), jnp.float32),
                  jax.ShapeDtypeStruct((T, E, cap), jnp.float32))

    entry = _xray.register(
        "moe.dispatch", (("T", T), ("E", E), ("C", cap), ("M", M),
                         ("k", k), ("fused", fused)))
    with _xray.capture_kernel_claims() as claims:
        lowered = jax.jit(prog).lower(*shapes)
    _xray.attach_lowered(entry, lowered, claims)
    return entry.key
