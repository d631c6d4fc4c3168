"""sdar_moe_ref.py — the plain reference of the `sdar_moe` block
(SDAR-30B-A3B-Chat,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json):
Qwen3-MoE's decoder block — grouped-query attention with a per-head
RMSNorm of q and k before RoPE, and a mixture of experts routed by a
softmax top-k gate — under a mask that is causal over blocks of `L`
positions and full inside one, and the sampler that generates by
diffusion over such blocks.

Straight `jax.numpy` in float32 with matmul precision "highest": no
kernel, no paged cache, no batching (one sequence `[T]`).  No code of
`paddle_tpu`.  It is what `serve-sdar-chat`'s `correct` and the CPU tests
are judged against.

With `h` `[T, H]`, each layer:

* `x = RMSNorm(h; w_in)`; `q = x W_q` as `[T, nh, hd]`, `k = x W_k`,
  `v = x W_v` as `[T, nkv, hd]`; no biases.
* `q = RMSNorm(q; w_qn)`, `k = RMSNorm(k; w_kn)` over the `hd` of each
  head (one weight vector for q, one for k), then RoPE over all `hd`
  dims, half-rotation ("neox") pairing, at absolute positions.
* Query head `j` attends kv head `j // (nh / nkv)`; scores
  `q . k / sqrt(hd)`; key `s` is visible to query `t` iff
  `s < (t // L + 1) * L`; softmax in float32; `h = h + concat(heads) W_o`.
* `y = RMSNorm(h; w_post)`; `p = softmax(y W_r)` over the experts in
  float32; the `top_k` largest are picked (equal scores: the lower expert
  first, as `lax.top_k` orders them); `g = p[picked] / sum p[picked]`;
  `h = h + sum_e g_e W_down,e (silu(W_gate,e y) * W_up,e y)`.
* After the last layer: RMSNorm, `logits = h W_head` (untied).  The
  logits at a position are that position's own token distribution (no
  shift).

Generation of one sequence, greedy (`generate`): the first
`p0 = P // L * L` prompt tokens are context; the first block is the
prompt's last `P - p0` tokens followed by `[MASK]` up to `L`, every later
block `L` x `[MASK]`.  While the block holds a mask: the full forward
over everything so far and the block; `x0 = argmax logits`,
`c = softmax(logits)[x0]` at each masked position; the `L /
denoising_steps` masked positions of highest `c` (ties: the lowest
position) are revealed as their `x0`.  The finished block joins the
sequence.  Tokens beyond `max_new_tokens` are dropped.

`generate` re-runs the whole forward for every denoising step, which is
the definition.  `forward(..., return_kv=True)` and `block_logits` give
the same logits for many block states of one finished sequence at once:
under the block mask nothing before a block depends on it, so each
layer's K and V of the finished sequence, up to the block's start, are
what the re-run would compute again (`tests/test_sdar_moe.py` holds the
two equal).  That is what lets `correct` replay hundreds of denoising
forwards of a served request on the chip.

The chip's share (`expert_offset`, the experts held): as in
`glm_moe_dsa_ref.py` — the router keeps its width and normalises `g` over
all picked experts; what the absent experts would have added is left out.
All experts held is the uncut model.

Assumed (the config does not say; the configuration file lists them with
their reasons): block length 4, 4 denoising steps, the static
low-confidence schedule above, `[MASK]` = 151669, the per-head q/k norm,
no logit shift, prefill under the block mask, a separate commit forward.

Weights may arrive in bfloat16: each is cast to float32 where it is used,
a layer (and an expert) at a time.  With `operand_dtype` every matmul
operand is rounded to that type first: the reading in the precision
below the configuration's (`PERF.md` section 4).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256            # queries attended at a time in `forward`


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


def rope_neox(x, pos, theta):
    """Rotate the pairs `(x[i], x[i + d/2])` of the last axis by
    `pos * theta^(-2i/d)`.  `x` is `[..., T, heads, d]`, `pos` `[..., T]`."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None, None] * inv     # [.., T, 1, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def swiglu_mlp(m, x, wg, wu, wd):
    return m.mm(jax.nn.silu(m.mm(x, wg)) * m.mm(x, wu), wd)


def route(x, p, c):
    """`[T, E]` combine weights over the router's full width: `g` on the
    picked experts, 0 elsewhere.  Float32 whatever the reading."""
    s = jax.nn.softmax(jnp.matmul(_f32(x), _f32(p["w_router"]),
                                  precision=jax.lax.Precision.HIGHEST), -1)
    sp, picked = jax.lax.top_k(s, c["top_k"])
    g = sp / sp.sum(-1, keepdims=True)
    T = x.shape[0]
    return jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(g)


def moe(m, x, p, c):
    """What the held experts give the tokens routed to them, `[T, H]`."""
    g = route(x, p, c)                                      # [T, E]
    held = p["e_gate"].shape[0]
    g_held = jax.lax.dynamic_slice_in_dim(g, c["expert_offset"], held, 1)

    def one(carry, e):
        wg, wu, wd, ge = e
        return carry + ge[:, None] * swiglu_mlp(m, x, wg, wu, wd), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (p["e_gate"], p["e_up"], p["e_down"], g_held.T))
    return routed


def _qkv(m, x, p, c, pos):
    """Normed, rotated q `[.., T, nh, hd]`, k and v `[.., T, nkv, hd]` of
    `x` `[.., T, H]` (already normed) at positions `pos` `[.., T]`."""
    nh, nkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    lead = x.shape[:-1]
    q = m.mm(x, p["w_q"]).reshape(lead + (nh, hd))
    k = m.mm(x, p["w_k"]).reshape(lead + (nkv, hd))
    v = m.mm(x, p["w_v"]).reshape(lead + (nkv, hd))
    q = rope_neox(_rms(q, p["q_norm"], c["rms_eps"]), pos, c["rope_theta"])
    k = rope_neox(_rms(k, p["k_norm"], c["rms_eps"]), pos, c["rope_theta"])
    return q, k, v


def attention(m, x, p, c, pos):
    """Grouped-query attention under the block mask for all of `x`
    `[T, H]` (already normed).  Returns (`[T, H]` output, k, v)."""
    T = x.shape[0]
    nh, nkv, hd, L = (c["num_heads"], c["num_kv_heads"], c["head_dim"],
                      c["block_length"])
    q, k, v = _qkv(m, x, p, c, pos)
    scale = 1.0 / math.sqrt(hd)
    pad = -T % Q_BLOCK
    n_blocks = (T + pad) // Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, Q_BLOCK, nkv, nh // nkv, hd)
    qpos = jnp.pad(pos, (0, pad)).reshape(n_blocks, Q_BLOCK)

    def block(args):
        qq, qp = args
        seen = pos[None, :] < ((qp // L + 1) * L)[:, None]    # [Q, T]
        s = m.ein("qhgd,khd->hgqk", qq, k) * scale
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return m.ein("hgqk,khd->qhgd", a, v).reshape(Q_BLOCK, nh * hd)

    o = jax.lax.map(block, (qb, qpos)).reshape(T + pad, nh * hd)[:T]
    return m.mm(o, p["w_o"]), k, v


@functools.partial(jax.jit, static_argnames=("dims", "operand_dtype"))
def layer(h, p, pos, dims, operand_dtype=None):
    """One block over `h` `[T, H]`.  Returns (`h'`, (k, v) of this layer,
    and the parts `x`, `routed` the shares-add-up test sums)."""
    c = dict(dims)
    m = _Math(operand_dtype)
    a, k, v = attention(m, _rms(h, p["ln1"], c["rms_eps"]), p, c, pos)
    x = h + a
    routed = moe(m, _rms(x, p["ln2"], c["rms_eps"]), p, c)
    return x + routed, (k, v), (x, routed)


@functools.partial(jax.jit, static_argnames=("rms_eps", "operand_dtype"))
def head(h, norm, w, rms_eps, operand_dtype=None):
    return _Math(operand_dtype).mm(_rms(h, norm, rms_eps), w)


def dims_of(cfg: dict, block_length: int) -> tuple:
    """The sizes `layer` needs, hashable, from a configuration's keys."""
    c = {"num_heads": cfg["num_attention_heads"],
         "num_kv_heads": cfg["num_key_value_heads"],
         "head_dim": cfg["head_dim"],
         "top_k": cfg["num_experts_per_tok"],
         "rms_eps": cfg["rms_norm_eps"],
         "rope_theta": float(cfg["rope_theta"]),
         "block_length": int(block_length),
         "expert_offset": int(cfg.get("expert_offset", 0))}
    return tuple(sorted(c.items()))


def forward(params, ids, dims, positions=None, operand_dtype=None,
            return_kv=False):
    """Logits `[len(positions), V]` (all positions if None) of the one
    sequence `ids` `[T]` under the block mask; with `return_kv` also each
    layer's (k `[T, nkv, hd]`, v) for `block_logits`."""
    ids = jnp.asarray(ids, jnp.int32)
    T = ids.shape[0]
    want = jnp.arange(T) if positions is None else jnp.asarray(positions)
    pos = jnp.arange(T)
    h = _f32(params["embed"][ids])
    kv = []
    for p in params["blocks"]:
        h, one, _ = layer(h, p, pos, dims, operand_dtype)
        kv.append(one)
    rms_eps = dict(dims)["rms_eps"]
    logits = head(h[want], params["norm"], params["head"], rms_eps,
                  operand_dtype)
    return (logits, kv) if return_kv else logits


@functools.partial(jax.jit, static_argnames=("dims", "operand_dtype"))
def _block_layer(h, p, pos, starts, kv, dims, operand_dtype=None):
    """One block over `S` block states `h` `[S, L, H]` at positions `pos`
    `[S, L]`: each attends the finished sequence's keys `kv` before its
    own start and its own `L` rows in full."""
    c = dict(dims)
    m = _Math(operand_dtype)
    nh, nkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    S, L, H = h.shape
    k_seq, v_seq = kv
    T = k_seq.shape[0]
    q, k, v = _qkv(m, _rms(h, p["ln1"], c["rms_eps"]), p, c, pos)
    q = q.reshape(S, L, nkv, nh // nkv, hd)
    scale = 1.0 / math.sqrt(hd)
    before = jnp.arange(T)[None, :] < starts[:, None]             # [S, T]
    s_seq = m.ein("slhgd,khd->shglk", q, k_seq) * scale
    s_seq = jnp.where(before[:, None, None, None], s_seq, -jnp.inf)
    s_own = m.ein("slhgd,sjhd->shglj", q, k) * scale
    a = jax.nn.softmax(jnp.concatenate([s_seq, s_own], -1), -1)
    o = (m.ein("shglk,khd->slhgd", a[..., :T], v_seq)
         + m.ein("shglj,sjhd->slhgd", a[..., T:], v))
    x = h + m.mm(o.reshape(S, L, nh * hd), p["w_o"])
    y = _rms(x, p["ln2"], c["rms_eps"]).reshape(S * L, H)
    return x + moe(m, y, p, c).reshape(S, L, H)


def block_logits(params, states, starts, kv, dims, operand_dtype=None):
    """Logits `[S, L, V]` of `S` block states (`states` `[S, L]` token
    ids, `[MASK]` where not yet revealed) of ONE finished sequence, state
    `i` at positions `starts[i] .. starts[i] + L - 1`, given each layer's
    (k, v) of the finished sequence (`forward(..., return_kv=True)`, in
    the same reading).  What the full forward over `sequence[:starts[i]]
    + states[i]` gives at the block's positions."""
    states = jnp.asarray(states, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    L = states.shape[1]
    pos = starts[:, None] + jnp.arange(L)
    h = _f32(params["embed"][states])
    for p, one in zip(params["blocks"], kv):
        h = _block_layer(h, p, pos, starts, one, dims, operand_dtype)
    rms_eps = dict(dims)["rms_eps"]
    return head(h, params["norm"], params["head"], rms_eps, operand_dtype)


def confidence(logits):
    """(`x0`, `log c`) at every position of `logits` `[.., V]` float32:
    the greedy token and the log of its softmax probability."""
    logits = _f32(logits)
    return (jnp.argmax(logits, -1).astype(jnp.int32),
            logits.max(-1) - jax.nn.logsumexp(logits, -1))


def reveal(logits, masked, n: int):
    """One denoising step over a block's `logits` `[L, V]`: of the
    `masked` `[L]` positions the `n` of highest confidence (ties: the
    lowest position).  Returns (`x0` `[L]`, which `[L]` bool)."""
    x0, logc = confidence(logits)
    score = jnp.where(masked, logc, -jnp.inf)
    _, top = jax.lax.top_k(score, min(n, score.shape[0]))
    which = jnp.zeros_like(masked).at[top].set(True) & masked
    return x0, which


def generate(params, prompt, max_new_tokens: int, dims, mask_id: int,
             denoising_steps: int, operand_dtype=None, pad_to: int = 32):
    """The sampler, greedy, one sequence: re-runs the full forward over
    everything so far for every denoising step.  Returns (the
    `max_new_tokens` new tokens, for each the denoising step that
    revealed it).  The sequence is padded behind the block to a multiple
    of `pad_to` (later blocks are invisible to earlier ones), so that the
    forward compiles for a few lengths only."""
    import numpy as np
    L = dict(dims)["block_length"]
    per_step = L // denoising_steps
    seq = [int(t) for t in prompt]
    P = len(seq)
    p0 = P // L * L
    seq, tail = seq[:p0], seq[p0:]
    out, steps = [], []
    while len(out) < max_new_tokens:
        block = np.asarray(tail + [mask_id] * (L - len(tail)), np.int32)
        masked = np.arange(L) >= len(tail)
        step_of = np.full(L, -1)
        step = 0
        while masked.any():
            ids = np.asarray(seq + block.tolist(), np.int32)
            ids = np.pad(ids, (0, -len(ids) % pad_to))
            lg = forward(params, ids, dims,
                         positions=len(seq) + np.arange(L),
                         operand_dtype=operand_dtype)
            x0, which = (np.asarray(a) for a in reveal(
                lg, jnp.asarray(masked), per_step))
            block = np.where(which, x0, block)
            step_of[which] = step
            masked &= ~which
            step += 1
        new = block[len(tail):].tolist()
        out += new
        steps += step_of[len(tail):].tolist()
        seq += block.tolist()
        tail = []
    return out[:max_new_tokens], steps[:max_new_tokens]


_NAMES = {"w_q": "self_attn.q_proj.weight", "w_k": "self_attn.k_proj.weight",
          "w_v": "self_attn.v_proj.weight", "w_o": "self_attn.o_proj.weight",
          "q_norm": "self_attn.q_norm.weight",
          "k_norm": "self_attn.k_norm.weight",
          "ln1": "input_layernorm.weight",
          "ln2": "post_attention_layernorm.weight",
          "w_router": "mlp.gate.weight",
          "e_gate": "mlp.experts.gate_proj", "e_up": "mlp.experts.up_proj",
          "e_down": "mlp.experts.down_proj"}


def from_state_dict(sd: dict, num_layers: int) -> dict:
    """`models/sdar_moe.py`'s state dict (values are arrays; a linear
    weight is `[in, out]`, the held experts are stacked `[held, in, out]`)
    as this file's parameter dict.  No copy is made."""
    blocks = [{k: sd[f"model.layers.{i}.{v}"] for k, v in _NAMES.items()}
              for i in range(num_layers)]
    return {"embed": sd["model.embed_tokens.weight"],
            "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"],
            "blocks": blocks}
