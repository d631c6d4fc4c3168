"""exaone_moe_ref.py — the plain reference of the `exaone_moe` model
(K-EXAONE-236B-A23B,
https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json;
the layer is EXAONE 4.0's, arXiv:2507.11407, HF `modeling_exaone4.py`):
grouped-query attention over a window of `sliding_window` positions in the
layers `layer_types` names "sliding_attention" and over the whole context
in the others, post-norm blocks with q and k normed a head, a dense SwiGLU
or a sigmoid-routed mixture of experts with a shared expert, and the
multi-token-prediction module.

Straight `jax.numpy` in float32 with matmul precision "highest": no
kernel, no cache, no ring, no batching (one sequence `[T]`).  No code of
`paddle_tpu`; the expert layer is `glm_moe_dsa_ref.moe_parts` (the same
DeepSeek-V3 routing, given the same expert share).  It is what
`serve-kexaone-mixed`'s `correct` and the CPU tests are judged against.

With `h` `[T, H]` (`h_0 = E[t]`), each layer, every RMSNorm with eps
`rms_norm_eps`:

* `q = RMSNorm_q(h W_q)` `[T, nh, hd]`, `k = RMSNorm_k(h W_k)`, `v = h W_v`
  `[T, nkv, hd]`, each norm over one head.  A window layer rotates `q`
  and `k` (RoPE, half-rotation pairing, theta `rope_theta`, all `hd` dims,
  absolute positions) and query `t` sees key `s` iff `t - W < s <= t`; a
  full layer has no RoPE and `s <= t`.  Query head `j` reads kv head
  `j // (nh / nkv)`; softmax of `q k / sqrt(hd)`; `a = concat W_o`.
* `h <- h + RMSNorm_pa(a)`; `h <- h + RMSNorm_pf(F(h))`, `F` a SwiGLU or
  the held experts' part plus the shared expert's
  (`glm_moe_dsa_ref.moe_parts`: sigmoid scores, top-k of score + bias,
  weights normalised over the picked times `routed_scaling_factor`).
* `logits = RMSNorm(h_L) W_head` (untied).
* The module at position i: `u_i = W_eh [RMSNorm_e(E[t_{i+1}]) ;
  RMSNorm_h(h_i)]` (`h_i` after the final norm), one block of the MoE kind
  with full attention over `u_0..u_i`, `RMSNorm_s`, the model's head.

Attention is taken a block of `Q_BLOCK` queries at a time, a dense
feed-forward `FF_ROWS` rows at a time.  A window
layer's block is scored against the `Q_BLOCK + W` keys that precede and
include it (keys before position 0 are padding, masked): every key outside
that band fails the window rule for every query of the block, so the
masked form is the same.  `no_window=True` lets the window layers attend
the whole context (RoPE kept): the `no_window` control.  With
`operand_dtype` every matmul operand is rounded to that type first: the
reading in the precision below the configuration's.  Weights may arrive in
bfloat16: each is cast to float32 where it is used, a layer at a time.
Departures from the published description: none.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.glm_moe_dsa_ref import (_Math, _f32, _rms,
                                                 moe_parts, swiglu_mlp)

Q_BLOCK = 64             # queries attended at a time
FF_ROWS = 2048           # rows through a dense feed-forward at a time
SLIDING = "sliding_attention"


def rope_neox(x, pos, theta):
    """Rotate-half RoPE of `x` `[T, heads, d]` at positions `pos` `[T]`."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def dims_of(cfgd: dict) -> tuple:
    """The sizes the reference needs, hashable, from a configuration under
    the published keys (`num_layers` is the stage's depth)."""
    L = cfgd["num_layers"]
    c = {"num_heads": cfgd["num_attention_heads"],
         "num_kv_heads": cfgd["num_key_value_heads"],
         "head_dim": cfgd["head_dim"],
         "rms_eps": cfgd["rms_norm_eps"],
         "rope_theta": float(cfgd["rope_parameters"]["rope_theta"]),
         "window": cfgd["sliding_window"],
         "top_k": cfgd["num_experts_per_tok"],
         "routed_scaling_factor": cfgd["routed_scaling_factor"],
         "expert_offset": int(cfgd.get("expert_offset", 0)),
         "windowed": tuple(t == SLIDING for t in cfgd["layer_types"][:L])}
    return tuple(sorted(c.items()))


def attention(m, x, p, c, pos, window, rope):
    """Causal attention over all of `x` `[T, H]`, under the window rule
    where `window` > 0, RoPE where `rope`."""
    T = x.shape[0]
    nh, nkv, hd, eps = c["num_heads"], c["num_kv_heads"], c["head_dim"], \
        c["rms_eps"]
    q = _rms(m.mm(x, p["w_q"]).reshape(T, nh, hd), p["q_norm"], eps)
    k = _rms(m.mm(x, p["w_k"]).reshape(T, nkv, hd), p["k_norm"], eps)
    v = m.mm(x, p["w_v"]).reshape(T, nkv, hd)
    if rope:
        q = rope_neox(q, pos, c["rope_theta"])
        k = rope_neox(k, pos, c["rope_theta"])
    pad = -T % Q_BLOCK
    n = (T + pad) // Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n, Q_BLOCK, nkv, nh // nkv, hd)
    qpos = jnp.pad(pos, (0, pad)).reshape(n, Q_BLOCK)
    if window:
        # keys at positions -W..-1 are padding: masked as negative
        kk = jnp.pad(k, ((window, pad), (0, 0), (0, 0)))
        vv = jnp.pad(v, ((window, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(pos, (window, pad), constant_values=-1)

    def block(args):
        i, qq, qp = args
        if window:
            band = Q_BLOCK + window
            ks = jax.lax.dynamic_slice_in_dim(kk, i * Q_BLOCK, band)
            vs = jax.lax.dynamic_slice_in_dim(vv, i * Q_BLOCK, band)
            kp = jax.lax.dynamic_slice_in_dim(kpos, i * Q_BLOCK, band)
        else:
            ks, vs, kp = k, v, pos
        s = m.ein("qhgd,khd->hgqk", qq, ks) / math.sqrt(hd)
        seen = (kp[None, :] <= qp[:, None]) & (kp[None, :] >= 0)
        if window:
            seen = seen & (kp[None, :] > qp[:, None] - window)
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return m.ein("hgqk,khd->qhgd", a, vs).reshape(Q_BLOCK, nh * hd)

    o = jax.lax.map(block, (jnp.arange(n), qb, qpos)).reshape(
        T + pad, nh * hd)[:T]
    return m.mm(o, p["w_o"])


@functools.partial(jax.jit, static_argnames=("dims", "window", "rope",
                                             "operand_dtype"))
def layer(h, p, pos, dims, window, rope, operand_dtype=None):
    """One block over `h` `[T, H]`: attention under the window rule where
    `window` > 0, RoPE where `rope`."""
    c = dict(dims)
    m = _Math(operand_dtype)
    eps = c["rms_eps"]
    h = h + _rms(attention(m, h, p, c, pos, window, rope), p["ln_pa"], eps)
    if "w_gate" in p:
        routed, shared = moe_parts(m, h, p, c)
        y = routed + shared
    else:
        # a block of rows at a time: [T, 18432] float32 whole is 2.4 GB at
        # a prompt of 32k
        T = h.shape[0]
        pad = -T % FF_ROWS if T > FF_ROWS else 0
        rows = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, min(T, FF_ROWS),
                                                        h.shape[1])
        y = jax.lax.map(lambda x: swiglu_mlp(m, x, p["m_gate"], p["m_up"],
                                             p["m_down"]), rows)
        y = y.reshape(-1, h.shape[1])[:T]
    return h + _rms(y, p["ln_pf"], eps)


def _windows(dims, no_window):
    """(window, rope) of each layer."""
    c = dict(dims)
    return [(0 if no_window or not w else c["window"], w)
            for w in c["windowed"]]


def hidden(params, ids, dims, no_window=False, operand_dtype=None):
    """The model's last hidden states AFTER the final norm, `[T, H]`
    float32, of the one sequence `ids` `[T]`."""
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    h = _f32(jnp.take(params["embed"], ids, axis=0))
    for p, (w, rope) in zip(params["blocks"], _windows(dims, no_window)):
        h = layer(h, p, pos, dims, w, rope, operand_dtype)
    return _rms(h, params["norm"], dict(dims)["rms_eps"])


@functools.partial(jax.jit, static_argnames=("operand_dtype",))
def _head(h, w, operand_dtype=None):
    return _Math(operand_dtype).mm(h, w)


def logits_of(params, h, operand_dtype=None):
    """`W_head h` for hidden states that already passed their norm."""
    return _head(h, params["head"], operand_dtype)


def forward(params, ids, dims, positions=None, no_window=False,
            operand_dtype=None):
    """Logits `[len(positions), V]` (all positions if None) of one
    sequence: the logits at position i predict token i + 1."""
    h = hidden(params, ids, dims, no_window, operand_dtype)
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return logits_of(params, h, operand_dtype)


@functools.partial(jax.jit, static_argnames=("eps", "operand_dtype"))
def _module_input(h, emb, p, eps, operand_dtype=None):
    cat = jnp.concatenate([_rms(_f32(emb), p["enorm"], eps),
                           _rms(h, p["hnorm"], eps)], -1)
    return _Math(operand_dtype).mm(cat, p["w_eh"])


def module_logits(params, h, ids, dims, positions=None, operand_dtype=None):
    """The module's logits at `positions` (all of `0..T-2` if None) of the
    sequence `ids` `[T]` whose model hidden states are `h` `[T, H]`
    (`hidden`): at position i from `h_i` and `ids[i + 1]`, predicting
    token i + 2."""
    ids = jnp.asarray(ids, jnp.int32)
    T = ids.shape[0]
    p = params["mtp"]
    eps = dict(dims)["rms_eps"]
    u = _module_input(h[:T - 1], jnp.take(params["embed"], ids[1:], axis=0),
                      p, eps, operand_dtype)
    z = layer(u, p["block"], jnp.arange(T - 1, dtype=jnp.int32), dims, 0,
              False, operand_dtype)
    z = _rms(z, p["snorm"], eps)
    if positions is not None:
        z = z[jnp.asarray(positions)]
    return logits_of(params, z, operand_dtype)


_ATTN = {"w_q": "self_attn.q_proj", "w_k": "self_attn.k_proj",
         "w_v": "self_attn.v_proj", "w_o": "self_attn.o_proj",
         "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
         "ln_pa": "post_attention_layernorm",
         "ln_pf": "post_feedforward_layernorm"}
_DENSE = {"m_gate": "mlp.gate_proj", "m_up": "mlp.up_proj",
          "m_down": "mlp.down_proj"}
_MOE = {"w_gate": "mlp.gate.weight",
        "gate_bias": "mlp.gate.e_score_correction_bias",
        "e_gate": "mlp.experts.gate_proj", "e_up": "mlp.experts.up_proj",
        "e_down": "mlp.experts.down_proj",
        "s_gate": "mlp.shared_experts.gate_proj",
        "s_up": "mlp.shared_experts.up_proj",
        "s_down": "mlp.shared_experts.down_proj"}


def _block(sd, pre):
    names = dict(_ATTN)
    names.update(_MOE if pre + _MOE["w_gate"] in sd else _DENSE)
    return {k: sd[pre + v] for k, v in names.items()}


def from_state_dict(sd: dict, num_layers: int) -> dict:
    """`models/exaone_moe.py`'s state dict (values are arrays; a matrix is
    `[in, out]`, the experts are stacked `[held, in, out]`) as this file's
    parameter dict.  No copy is made."""
    out = {"embed": sd["embed_tokens"], "norm": sd["norm"],
           "head": sd["lm_head"],
           "blocks": [_block(sd, f"layers.{i}.") for i in range(num_layers)]}
    if "mtp.eh_proj" in sd:
        out["mtp"] = {"enorm": sd["mtp.enorm"], "hnorm": sd["mtp.hnorm"],
                      "w_eh": sd["mtp.eh_proj"],
                      "snorm": sd["mtp.shared_head_norm"],
                      "block": _block(sd, "mtp.block.")}
    return out
