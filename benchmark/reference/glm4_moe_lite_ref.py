"""glm4_moe_lite_ref.py — the plain reference of GLM-4.7-Flash
(`glm4_moe_lite`, https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json):
multi-head latent attention (MLA) in which a query attends EVERY earlier
token, a DeepSeek-V3 style mixture of experts, one multi-token-prediction
(MTP) module, and greedy generation in which that module drafts and the
model verifies.

Straight `jax.numpy` in float32 with matmul precision "highest": no
kernel, no cache, no batching (one sequence `[T]`), the NON-absorbed
multi-head form of MLA (every head's `k_nope` and `v` expanded from the
latent), a block of queries at a time so that 17k tokens fit.  No code of
`paddle_tpu`; the norm, RoPE, SwiGLU, router and expert sum are the plain
functions of `glm_moe_dsa_ref.py` (the same family's reference, equally
plain).  It is what `serve-glm47f-agent`'s `correct` and the CPU tests
are judged against.

With `h` `[T, H]`, block `l`: `x = h + MLA(RMSNorm(h))`, `h' = x +
FFN(RMSNorm(x))`, eps 1e-5; FFN is a dense SwiGLU for `l <
first_k_dense_replace`, the MoE after; last `RMSNorm`, then an untied head.

* MLA.  `c_q = RMSNorm(x W_qa)`; `q = c_q W_qb` -> heads of `[q_nope |
  q_rope]`; `[c_kv | k_r] = x W_kva`, `c_kv = RMSNorm(c_kv)`; RoPE
  (interleaved pairs, theta 1e6, all `rope` values) on `q_rope` and `k_r`,
  which all heads share; `[k_nope | v]` per head `= c_kv W_kvb`; score
  `(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)`, causal softmax,
  `o = sum p v`, `out = concat(o) W_o`.
* MoE, in float32: `s = sigmoid(x W_r)`; the `top_k` largest of `s + b`
  (`n_group` = `topk_group` = 1: no group limit); `g = s[picked] / sum
  s[picked] * routed_scaling_factor`; `y = sum_picked g_e E_e(x) +
  E_shared(x)`.
* MTP module (depth 1).  For position i, with `h_i` the model's last
  hidden state AFTER its final RMSNorm and `t_{i+1}` the next token:
  `u_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]` (embedding half
  first), `z_i = Block(u_i)` (one MoE block, its own weights, attending
  `u_0..u_i`, RoPE at position i), `logits^MTP_i = W_head RMSNorm_s(z_i)`,
  which predicts `t_{i+2}`.  `Emb` and `W_head` are the model's.
* Draft and verify, greedy (`generate`): the stream ends in `t_n` with
  draft `d_{n+1}`; one forward over `.. t_n d_{n+1}` gives `logits_n`,
  `logits_{n+1}`; emit `t_{n+1} = argmax logits_n`, and if `d_{n+1} =
  t_{n+1}` also `t_{n+2} = argmax logits_{n+1}`; the next draft is the
  module's argmax at the last emitted position but one.  Here every
  forward is a full forward of the true stream, so the loop is the
  definition, not an optimisation.

Assumed (the config does not say; the configuration file lists the same):
the module's form above (DeepSeek-V3, arXiv:2412.19437 section 2.2, which
the GLM-4.5+ family follows), interleaved-pair RoPE, softmax scale
`1/sqrt(256)`.  Departures from the published model: none.

Weights may arrive in bfloat16: each is cast to float32 where it is used.
With `operand_dtype` every matmul operand is rounded to that type first:
the reading in the precision below the configuration's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.glm_moe_dsa_ref import (_Math, _f32, _rms,
                                                 moe_parts,
                                                 rope_interleaved, swiglu_mlp)

Q_BLOCK = 128            # queries attended at a time

COPIED = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "routed_scaling_factor")


def attention(m, x, p, c, pos):
    """Causal MLA over all of `x` `[T, H]` (already normed): `[T, H]`."""
    T = x.shape[0]
    nh, dn, dr, dv = (c["num_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"], c["v_head_dim"])
    theta, eps = c["rope_theta"], c["rms_eps"]
    c_q = _rms(m.mm(x, p["w_qa"]), p["q_norm"], eps)
    q = m.mm(c_q, p["w_qb"]).reshape(T, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_interleaved(q[..., dn:], pos, theta)
    kva = m.mm(x, p["w_kva"])
    c_kv = _rms(kva[:, :c["kv_lora_rank"]], p["kv_norm"], eps)
    k_rope = rope_interleaved(kva[:, c["kv_lora_rank"]:], pos, theta)
    kvb = m.mm(c_kv, p["w_kvb"]).reshape(T, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)
    pad = -T % Q_BLOCK
    n_blocks = (T + pad) // Q_BLOCK

    def padq(a):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (n_blocks, Q_BLOCK) + a.shape[1:])

    kpos = jnp.arange(T)

    def block(args):
        qn, qr, qpos = args
        causal = kpos[None, :] <= qpos[:, None]              # [Q, T]
        s = (m.ein("qhd,khd->hqk", qn, k_nope)
             + m.ein("qhd,kd->hqk", qr, k_rope)) * scale
        a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return m.ein("hqk,khd->qhd", a, v).reshape(Q_BLOCK, nh * dv)

    o = jax.lax.map(block, (padq(q_nope), padq(q_rope), padq(pos)))
    return m.mm(o.reshape(T + pad, nh * dv)[:T], p["w_o"])


@functools.partial(jax.jit, static_argnames=("dims", "operand_dtype"))
def layer(h, p, pos, dims, operand_dtype=None):
    """One block over `h` `[T, H]`."""
    c = dict(dims)
    m = _Math(operand_dtype)
    x = h + attention(m, _rms(h, p["ln1"], c["rms_eps"]), p, c, pos)
    y = _rms(x, p["ln2"], c["rms_eps"])
    if "w_gate" in p:
        routed, shared = moe_parts(m, y, p, c)
        return x + routed + shared
    return x + swiglu_mlp(m, y, p["m_gate"], p["m_up"], p["m_down"])


@functools.partial(jax.jit, static_argnames=("rms_eps", "operand_dtype"))
def _module_input(h, emb, p, rms_eps, operand_dtype=None):
    cat = jnp.concatenate([_rms(_f32(emb), p["enorm"], rms_eps),
                           _rms(h, p["hnorm"], rms_eps)], -1)
    return _Math(operand_dtype).mm(cat, p["w_eh"])


def dims_of(cfg: dict) -> tuple:
    """The sizes `layer` needs, hashable, from a configuration's keys."""
    c = {"num_heads": cfg["num_attention_heads"],
         "top_k": cfg["num_experts_per_tok"],
         "rms_eps": cfg["rms_norm_eps"],
         "rope_theta": float(cfg["rope_parameters"]["rope_theta"]),
         "expert_offset": int(cfg.get("expert_offset", 0))}
    c.update({k: cfg[k] for k in COPIED})
    return tuple(sorted(c.items()))


def hidden(params, ids, dims, operand_dtype=None):
    """The model's last hidden states AFTER the final norm, `[T, H]`
    float32, of the one sequence `ids` `[T]`."""
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0])
    h = _f32(params["embed"][ids])
    for p in params["blocks"]:
        h = layer(h, p, pos, dims, operand_dtype)
    return _rms(h, params["norm"], dict(dims)["rms_eps"])


@functools.partial(jax.jit, static_argnames=("operand_dtype",))
def _head(h, w, operand_dtype=None):
    return _Math(operand_dtype).mm(h, w)


def logits_of(params, h, operand_dtype=None):
    """`W_head h` for hidden states that already passed their norm."""
    return _head(h, params["head"], operand_dtype)


def forward(params, ids, dims, positions=None, operand_dtype=None):
    """Logits `[len(positions), V]` (all positions if None) of `ids`."""
    h = hidden(params, ids, dims, operand_dtype)
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return logits_of(params, h, operand_dtype)


def module_logits(params, h, ids, dims, positions=None, operand_dtype=None,
                  stale=False):
    """The MTP module's logits at `positions` (all of `0..T-2` if None)
    of the sequence `ids` `[T]` whose model hidden states are `h` `[T, H]`
    (`hidden`): at position i from `h_i` and `ids[i + 1]`, predicting
    token i + 2.  `stale`: the module is fed `h_{i-1}` in `h_i`'s place
    (a control: a drafter whose carried state is one step old)."""
    ids = jnp.asarray(ids, jnp.int32)
    T = ids.shape[0]
    p = params["mtp"]
    eps = dict(dims)["rms_eps"]
    h_in = h[:T - 1]
    if stale:
        h_in = jnp.concatenate([h_in[:1], h_in[:-1]], 0)
    u = _module_input(h_in, params["embed"][ids[1:]], p, eps, operand_dtype)
    z = layer(u, p["block"], jnp.arange(T - 1), dims, operand_dtype)
    z = _rms(z, p["snorm"], eps)
    if positions is not None:
        z = z[jnp.asarray(positions)]
    return logits_of(params, z, operand_dtype)


def generate(params, prompt, max_new_tokens, dims, eos_token_id=None,
             draft=True):
    """Greedy generation, the module drafting and the model verifying
    (`draft=False`: one token a forward, no module).  Returns a dict:
    `tokens` (the output), and for each verify forward in order `drafts`
    (the token judged), `accepted` (was it the model's own choice) and
    `emitted` (1 or 2 tokens; a budget's end or an EOS can cut the second
    off).  Every forward is a full forward of the true stream."""
    import numpy as np
    stream = [int(t) for t in prompt]
    out, drafts, accepted, emitted = [], [], [], []

    def done():
        return len(out) >= max_new_tokens or (
            eos_token_id is not None and out and out[-1] == eos_token_id)

    def emit(tok):
        out.append(int(tok))
        stream.append(int(tok))

    h = hidden(params, stream, dims)
    emit(np.argmax(np.asarray(logits_of(params, h[-1:]))[0]))
    while not done():
        if not draft:
            h = hidden(params, stream, dims)
            emit(np.argmax(np.asarray(logits_of(params, h[-1:]))[0]))
            continue
        h = hidden(params, stream, dims)
        n = len(stream) - 1                     # the last token's position
        d = int(np.argmax(np.asarray(module_logits(
            params, h, stream, dims, positions=[n - 1]))[0]))
        lg = np.asarray(forward(params, stream + [d], dims,
                                positions=[n, n + 1]))
        t1 = int(np.argmax(lg[0]))
        drafts.append(d)
        accepted.append(d == t1)
        emit(t1)
        count = 1
        if d == t1 and not done():
            emit(np.argmax(lg[1]))
            count = 2
        emitted.append(count)
    return {"tokens": out, "drafts": drafts, "accepted": accepted,
            "emitted": emitted}


_ATTN = {"w_qa": "self_attn.q_a_proj.weight",
         "q_norm": "self_attn.q_a_layernorm.weight",
         "w_qb": "self_attn.q_b_proj.weight",
         "w_kva": "self_attn.kv_a_proj.weight",
         "kv_norm": "self_attn.kv_a_layernorm.weight",
         "w_kvb": "self_attn.kv_b_proj.weight",
         "w_o": "self_attn.o_proj.weight",
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


def _block(sd, pre):
    names = dict(_ATTN)
    names.update(_MOE if pre + _MOE["w_gate"] in sd else _DENSE)
    return {k: sd[pre + v] for k, v in names.items()}


def from_state_dict(sd: dict, num_layers: int) -> dict:
    """`models/glm4_moe_lite.py`'s state dict (values are arrays; a linear
    weight is `[in, out]`, the experts are stacked `[held, in, out]`) as
    this file's parameter dict.  No copy is made."""
    out = {"embed": sd["model.embed_tokens.weight"],
           "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"],
           "blocks": [_block(sd, f"model.layers.{i}.")
                      for i in range(num_layers)]}
    if "mtp.eh_proj.weight" in sd:
        out["mtp"] = {"enorm": sd["mtp.enorm.weight"],
                      "hnorm": sd["mtp.hnorm.weight"],
                      "w_eh": sd["mtp.eh_proj.weight"],
                      "snorm": sd["mtp.shared_head_norm.weight"],
                      "block": _block(sd, "mtp.block.")}
    return out
