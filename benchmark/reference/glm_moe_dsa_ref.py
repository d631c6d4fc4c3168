"""glm_moe_dsa_ref.py — the plain reference of the `glm_moe_dsa` block
(GLM-5, https://huggingface.co/zai-org/GLM-5/blob/main/config.json):
multi-head latent attention (MLA) restricted, per query, to the keys a
learned indexer picks (DeepSeek sparse attention, DSA), and a
DeepSeek-V3 style mixture of experts (sigmoid scores, a selection bias,
top-k, one shared expert, no token dropped).

Straight `jax.numpy` in float32 with matmul precision "highest": no
kernel, no cache, no batching (one sequence `[T]`), the NON-absorbed
multi-head form of MLA (every head's `k_nope` and `v` are expanded from
the latent), selection by a dense `[T, T]` index score and mask, computed
a block of queries at a time so that 30k tokens fit.  No code of
`paddle_tpu`.  It is what `serve-glm5-docqa`'s `correct` and the CPU
tests are judged against.

With `h` `[T, H]`, block `l`:  `x = h + Attn(RMSNorm(h))`,
`h' = x + FFN(RMSNorm(x))`; FFN is a dense SwiGLU for
`l < first_k_dense_replace`, the MoE after; last `RMSNorm`, then an untied
head.

* MLA.  `c_q = RMSNorm(x W_qa)`; `q = c_q W_qb` -> heads of
  `[q_nope | q_rope]`.  `[c_kv | k_rope] = x W_kva`; `c_kv = RMSNorm(c_kv)`;
  RoPE (interleaved pairs) on `q_rope` and on `k_rope`, which all heads
  share.  `[k_nope | v]` per head `= c_kv W_kvb`.  Score
  `(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)`, softmax over
  the selected set `S_t`, `o = sum p v`, `out = concat(o) W_o`.
* Indexer.  `q^I = c_q W^I_q` -> `Hi` heads of `Di`; `k^I =
  LayerNorm(x W^I_k)` (one a token); RoPE on the first `rope` values of
  each; `w = x W^I_w * Hi^-1/2 * Di^-1/2`.  `I[t, s] = sum_j w[t, j] *
  relu(q^I[t, j] . k^I[s])`; `S_t` = the `index_topk` largest over
  `s <= t` (equal scores: the earlier token first, as `lax.top_k` orders
  them), all of them while `t < index_topk`.
* MoE, in float32: `s = sigmoid(x W_g)`; the `top_k` largest of `s + b`;
  `g = s[picked] / sum s[picked] * routed_scaling_factor`;
  `y = sum_picked g_e E_e(x) + E_shared(x)`, `E(x) = (silu(x W_gate) *
  x W_up) W_down`.

The chip's share.  `expert_offset` and `n_experts_held` say which of the
router's experts this share holds: the router keeps its full width and
normalises `g` over all picked experts, held or not; what the absent
experts would have added is left out, and that partial result goes on.
`n_experts_held` = the router's width gives the uncut model.  The
vocabulary is whatever `embed` / `head` hold (a slice is a smaller
vocabulary).

Departures from the published model (the same two the configuration file
lists): (a) the multi-token-prediction module is not run: next-token
logits do not pass through it; (b) the released inference kernels rotate
`q^I`, `k^I` by a Hadamard matrix and quantize them to FP8; the rotation
leaves every dot product as it was and the configuration states bfloat16,
so neither is done.  Assumed (the config does not say): the indexer's
query reads `c_q`, its key is LayerNorm-ed (eps 1e-6, with a bias), and
`w` is scaled as above — the DSA family's convention.

Weights may arrive in bfloat16 (a served model): each is cast to float32
where it is used, which is exact, a layer (and an expert) at a time, so
the reference never holds a second whole copy of the model.  With
`operand_dtype` every matmul operand is rounded to that type first: the
reading in the precision below the configuration's (`PERF.md` section 4).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6            # the indexer key's LayerNorm (assumed)
Q_BLOCK = 128            # queries scored, selected and attended at a time

COPIED = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim",
          "index_topk", "routed_scaling_factor")   # under the config's names


def _f32(x):
    return x.astype(jnp.float32)


class _Math:
    """The matmul of one reading: float32 "highest" for the reference;
    operands rounded to `operand_dtype` first for the reading in the
    precision below the configuration's."""

    def __init__(self, operand_dtype=None):
        self.dt = operand_dtype

    def r(self, x):
        return _f32(x) if self.dt is None else _f32(_f32(x).astype(self.dt))

    def mm(self, x, w):
        return jnp.matmul(self.r(x), self.r(w),
                          precision=jax.lax.Precision.HIGHEST)

    def ein(self, spec, a, b):
        return jnp.einsum(spec, self.r(a), self.r(b),
                          precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(g)


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * _f32(g) + _f32(b)


def rope_interleaved(x, pos, theta):
    """Rotate the adjacent pairs `(x[2i], x[2i+1])` of the last axis by
    `pos * theta^(-2i/d)`.  `x` is `[T, ..., d]`, `pos` `[T]`."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv            # [T, d/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def swiglu_mlp(m, x, wg, wu, wd):
    return m.mm(jax.nn.silu(m.mm(x, wg)) * m.mm(x, wu), wd)


def route(m, x, p, c):
    """`[T, E]` combine weights over the router's full width: `g` on the
    picked experts, 0 elsewhere.  Float32 whatever the reading."""
    s = jax.nn.sigmoid(jnp.matmul(_f32(x), _f32(p["w_gate"]),
                                  precision=jax.lax.Precision.HIGHEST))
    _, picked = jax.lax.top_k(s + _f32(p["gate_bias"]), c["top_k"])
    sp = jnp.take_along_axis(s, picked, axis=-1)
    g = sp / sp.sum(-1, keepdims=True) * c["routed_scaling_factor"]
    T = x.shape[0]
    return jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(g)


def moe_parts(m, x, p, c):
    """(routed, shared): what the held experts give the tokens routed to
    them, and the shared expert's part (the same on every share)."""
    g = route(m, x, p, c)                                   # [T, E]
    held = p["e_gate"].shape[0]
    g_held = jax.lax.dynamic_slice_in_dim(g, c["expert_offset"], held, 1)

    def one(carry, e):
        wg, wu, wd, ge = e
        return carry + ge[:, None] * swiglu_mlp(m, x, wg, wu, wd), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (p["e_gate"], p["e_up"], p["e_down"], g_held.T))
    shared = swiglu_mlp(m, x, p["s_gate"], p["s_up"], p["s_down"])
    return routed, shared


def attention(m, x, p, c, pos, want):
    """MLA over the indexer's selection, for all of `x` `[T, H]` (already
    normed).  Returns (`[T, H]` output, selected `[len(want), k]` int32
    with -1 where fewer than k keys exist)."""
    T = x.shape[0]
    nh, dn, dr, dv = (c["num_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"], c["v_head_dim"])
    hi, di, topk = c["index_n_heads"], c["index_head_dim"], c["index_topk"]
    theta, eps = c["rope_theta"], c["rms_eps"]
    c_q = _rms(m.mm(x, p["w_qa"]), p["q_norm"], eps)
    q = m.mm(c_q, p["w_qb"]).reshape(T, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_interleaved(q[..., dn:], pos, theta)
    kva = m.mm(x, p["w_kva"])
    c_kv = _rms(kva[:, :c["kv_lora_rank"]], p["kv_norm"], eps)
    k_rope = rope_interleaved(kva[:, c["kv_lora_rank"]:], pos, theta)
    kvb = m.mm(c_kv, p["w_kvb"]).reshape(T, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    # the indexer
    q_i = m.mm(c_q, p["wi_q"]).reshape(T, hi, di)
    q_i = jnp.concatenate([rope_interleaved(q_i[..., :dr], pos, theta),
                           q_i[..., dr:]], -1)
    k_i = _ln(m.mm(x, p["wi_k"]), p["ki_norm_g"], p["ki_norm_b"])
    k_i = jnp.concatenate([rope_interleaved(k_i[:, :dr], pos, theta),
                           k_i[:, dr:]], -1)
    w_i = m.mm(x, p["wi_w"]) * (hi ** -0.5 * di ** -0.5)
    scale = 1.0 / math.sqrt(dn + dr)
    k_sel = min(topk, T)
    pad = -T % Q_BLOCK
    n_blocks = (T + pad) // Q_BLOCK

    def padq(a):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (n_blocks, Q_BLOCK) + a.shape[1:])

    kpos = jnp.arange(T)

    def block(args):
        qn, qr, qi, wi, qpos = args
        causal = kpos[None, :] <= qpos[:, None]              # [Q, T]
        idx_s = jnp.einsum(
            "qh,qhk->qk", wi,
            jax.nn.relu(m.ein("qhd,kd->qhk", qi, k_i)))
        idx_s = jnp.where(causal, idx_s, -jnp.inf)
        top_v, top_i = jax.lax.top_k(idx_s, k_sel)    # ties: lower s first
        keep = causal & jnp.zeros_like(causal).at[
            jnp.arange(Q_BLOCK)[:, None], top_i].set(True)
        s = (m.ein("qhd,khd->hqk", qn, k_nope)
             + m.ein("qhd,kd->hqk", qr, k_rope)) * scale
        a = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        o = m.ein("hqk,khd->qhd", a, v).reshape(Q_BLOCK, nh * dv)
        return o, jnp.where(top_v > -jnp.inf, top_i, -1).astype(jnp.int32)

    o, sel = jax.lax.map(block, (padq(q_nope), padq(q_rope), padq(q_i),
                                 padq(w_i), padq(pos)))
    o = o.reshape(T + pad, nh * dv)[:T]
    sel = sel.reshape(T + pad, k_sel)[:T]
    return m.mm(o, p["w_o"]), sel[want]


@functools.partial(jax.jit, static_argnames=("dims", "operand_dtype"))
def layer(h, p, pos, want, dims, operand_dtype=None):
    """One block over `h` `[T, H]`.  Returns (`h'`, selected, and the
    parts `x`, `routed`, `shared` the shares-add-up test sums; `routed`
    is the whole dense MLP, and `shared` zero, in a dense layer)."""
    c = dict(dims)
    m = _Math(operand_dtype)
    a, sel = attention(m, _rms(h, p["ln1"], c["rms_eps"]), p, c, pos, want)
    x = h + a
    y = _rms(x, p["ln2"], c["rms_eps"])
    if "w_gate" in p:
        routed, shared = moe_parts(m, y, p, c)
    else:
        routed = swiglu_mlp(m, y, p["m_gate"], p["m_up"], p["m_down"])
        shared = jnp.zeros_like(routed)
    return x + routed + shared, sel, (x, routed, shared)


@functools.partial(jax.jit, static_argnames=("rms_eps", "operand_dtype"))
def head(h, norm, w, rms_eps, operand_dtype=None):
    return _Math(operand_dtype).mm(_rms(h, norm, rms_eps), w)


def dims_of(cfg: dict) -> tuple:
    """The sizes `layer` needs, hashable, from a configuration's keys."""
    c = {"num_heads": cfg["num_attention_heads"],
         "top_k": cfg["num_experts_per_tok"],
         "rms_eps": cfg["rms_norm_eps"],
         "rope_theta": float(cfg["rope_parameters"]["rope_theta"]),
         "expert_offset": int(cfg.get("expert_offset", 0))}
    c.update({k: cfg[k] for k in COPIED})
    return tuple(sorted(c.items()))


def forward(params, ids, dims, positions=None, operand_dtype=None):
    """Logits `[len(positions), V]` (all positions if None) of the one
    sequence `ids` `[T]`, and the selected sets at those positions, one
    `[len(positions), k]` int32 array a layer."""
    ids = jnp.asarray(ids, jnp.int32)
    T = ids.shape[0]
    want = jnp.arange(T) if positions is None else jnp.asarray(positions)
    pos = jnp.arange(T)
    h = _f32(params["embed"][ids])
    selected = []
    for p in params["blocks"]:
        h, sel, _ = layer(h, p, pos, want, dims, operand_dtype)
        selected.append(sel)
    rms_eps = dict(dims)["rms_eps"]
    return head(h[want], params["norm"], params["head"], rms_eps,
                operand_dtype), selected


_ATTN = {"w_qa": "self_attn.q_a_proj.weight",
         "q_norm": "self_attn.q_a_layernorm.weight",
         "w_qb": "self_attn.q_b_proj.weight",
         "w_kva": "self_attn.kv_a_proj.weight",
         "kv_norm": "self_attn.kv_a_layernorm.weight",
         "w_kvb": "self_attn.kv_b_proj.weight",
         "w_o": "self_attn.o_proj.weight",
         "wi_q": "self_attn.indexer.wq_b.weight",
         "wi_k": "self_attn.indexer.wk.weight",
         "ki_norm_g": "self_attn.indexer.k_norm.weight",
         "ki_norm_b": "self_attn.indexer.k_norm.bias",
         "wi_w": "self_attn.indexer.weights_proj.weight",
         "ln1": "input_layernorm.weight",
         "ln2": "post_attention_layernorm.weight"}
_DENSE = {"m_gate": "mlp.gate_proj.weight", "m_up": "mlp.up_proj.weight",
          "m_down": "mlp.down_proj.weight"}
_MOE = {"w_gate": "mlp.gate.weight",
        "gate_bias": "mlp.gate.e_score_correction_bias",
        "e_gate": "mlp.experts.gate_proj", "e_up": "mlp.experts.up_proj",
        "e_down": "mlp.experts.down_proj",
        "s_gate": "mlp.shared_experts.gate_proj.weight",
        "s_up": "mlp.shared_experts.up_proj.weight",
        "s_down": "mlp.shared_experts.down_proj.weight"}


def from_state_dict(sd: dict, num_layers: int) -> dict:
    """`models/glm_moe_dsa.py`'s state dict (values are arrays; a linear
    weight is `[in, out]`, the held experts are stacked `[held, in, out]`)
    as this file's parameter dict.  No copy is made."""
    blocks = []
    for i in range(num_layers):
        pre = f"model.layers.{i}."
        names = dict(_ATTN)
        names.update(_MOE if pre + _MOE["w_gate"] in sd else _DENSE)
        blocks.append({k: sd[pre + v] for k, v in names.items()})
    return {"embed": sd["model.embed_tokens.weight"],
            "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"],
            "blocks": blocks}
