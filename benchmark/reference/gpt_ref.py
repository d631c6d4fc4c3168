"""gpt_ref.py — the plain reference of the GPT-3 block shape (Brown et al.
2020, arXiv:2005.14165, section 2.1: the GPT-2 architecture, pre-norm,
learned positions, GELU, dense causal attention).

Straight `jax.numpy` in float32 with matmul precision "highest": no
kernels, no cache, no batching tricks, no code of `paddle_tpu`.  It is
what every cell's `correct` is judged against.

Parameters are a plain dict:

    wte [V, H]   wpe [P, H]   lnf_g [H]   lnf_b [H]
    blocks: a list, one dict a layer, of
      ln1_g ln1_b [H]   wqkv [H, 3H]  bqkv [3H]   wproj [H, H]  bproj [H]
      ln2_g ln2_b [H]   wfc1 [H, F]   bfc1 [F]    wfc2 [F, H]   bfc2 [H]

A linear layer is `x @ w + b`.  The 3H columns of `wqkv` are ordered
(q|k|v, head, head_dim) — the layout `models/gpt.py` reshapes to
`[b, s, 3, nh, hd]`.  The output head is tied to `wte`, unless the dict
holds `head` [H, V] (the hybrid step's GPT keeps an untied head, and lays
`wqkv` out (head, q|k|v, head_dim): `from_hybrid` reorders it).  Departures from
the paper: none in the mathematics; GELU is the tanh approximation GPT-2
and GPT-3 used; LayerNorm's epsilon is 1e-5.

Weights may arrive in bfloat16 (a served model): each is cast to float32
where it is used, which is exact, and a layer at a time, so the reference
never holds a second whole copy of the model.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-5
_HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * _f32(g) + _f32(b)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=_HI)


@functools.partial(jax.jit, static_argnames=("num_heads",))
def block(x, p, num_heads: int):
    """One pre-norm block over `x` [B, S, H], causal over S."""
    B, S, H = x.shape
    hd = H // num_heads
    h = _ln(x, p["ln1_g"], p["ln1_b"])
    qkv = (_mm(h, p["wqkv"]) + _f32(p["bqkv"])).reshape(B, S, 3,
                                                         num_heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqnd,bknd->bnqk", q, k, precision=_HI) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnqk,bknd->bqnd", a, v, precision=_HI).reshape(B, S, H)
    x = x + _mm(o, p["wproj"]) + _f32(p["bproj"])
    h = _ln(x, p["ln2_g"], p["ln2_b"])
    h = _gelu(_mm(h, p["wfc1"]) + _f32(p["bfc1"]))
    return x + _mm(h, p["wfc2"]) + _f32(p["bfc2"])


@jax.jit
def _embed(wte, wpe, ids):
    S = ids.shape[1]
    if S > wpe.shape[0]:
        raise ValueError(f"{S} tokens > the {wpe.shape[0]} learned positions")
    return _f32(wte)[ids] + _f32(wpe)[:S][None]


@functools.partial(jax.jit, static_argnames=("tied",))
def _head(x, g, b, w, positions, tied: bool):
    """Logits [B, len(positions), V] at the given positions only; `w` is
    `wte` [V, H] when tied, else `head` [H, V]."""
    h = _ln(x[:, positions], g, b)
    return jnp.einsum("bsh,vh->bsv" if tied else "bsh,hv->bsv", h, _f32(w),
                      precision=_HI)


def forward(params: dict, ids, num_heads: int, positions=None):
    """Float32 logits of the full forward pass over `ids` [B, S], at every
    position or at `positions` (a 1-D index array) only."""
    ids = jnp.asarray(ids, jnp.int32)
    x = _embed(params["wte"], params["wpe"], ids)
    for p in params["blocks"]:
        x = block(x, p, num_heads=num_heads)
    if positions is None:
        positions = jnp.arange(ids.shape[1])
    tied = "head" not in params
    return _head(x, params["lnf_g"], params["lnf_b"],
                 params["wte"] if tied else params["head"],
                 jnp.asarray(positions, jnp.int32), tied=tied)


@jax.jit
def _xent(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (lse - hit).mean()


def loss(params: dict, ids, labels, num_heads: int):
    """Mean next-token cross-entropy of `labels` [B, S] under the model."""
    return _xent(forward(params, ids, num_heads),
                 jnp.asarray(labels, jnp.int32))


def from_state_dict(sd: dict, num_layers: int) -> dict:
    """The parameter dict above from `GPTForCausalLM.state_dict()`-style
    names (`gpt.blocks.<i>.attn.qkv.weight` ...) mapped to arrays."""
    def blk(i):
        b = f"gpt.blocks.{i}."
        return {"ln1_g": sd[b + "ln1.weight"], "ln1_b": sd[b + "ln1.bias"],
                "wqkv": sd[b + "attn.qkv.weight"],
                "bqkv": sd[b + "attn.qkv.bias"],
                "wproj": sd[b + "attn.proj.weight"],
                "bproj": sd[b + "attn.proj.bias"],
                "ln2_g": sd[b + "ln2.weight"], "ln2_b": sd[b + "ln2.bias"],
                "wfc1": sd[b + "mlp.fc1.weight"],
                "bfc1": sd[b + "mlp.fc1.bias"],
                "wfc2": sd[b + "mlp.fc2.weight"],
                "bfc2": sd[b + "mlp.fc2.bias"]}
    return {"wte": sd["gpt.wte.weight"], "wpe": sd["gpt.wpe.weight"],
            "lnf_g": sd["gpt.ln_f.weight"], "lnf_b": sd["gpt.ln_f.bias"],
            "blocks": [blk(i) for i in range(num_layers)]}


def from_hybrid(params: dict, num_heads: int) -> dict:
    """The parameter dict above from `hybrid_step.init_gpt_params`'s
    serial layout: block leaves stacked `[L, ...]`, `wqkv`'s columns
    ordered (head, q|k|v, head_dim), and an untied `head` [H, V]."""
    import numpy as np
    b = params["blocks"]
    L, H = b["wqkv"].shape[0], b["wqkv"].shape[1]
    hd = H // num_heads
    # column index of (which, head, d) in the (head, which, d) layout
    cols = (np.arange(num_heads)[None, :, None] * 3 * hd
            + np.arange(3)[:, None, None] * hd
            + np.arange(hd)[None, None, :]).reshape(-1)
    blocks = []
    for i in range(L):
        p = {k: v[i] for k, v in b.items()}
        p["wqkv"], p["bqkv"] = p["wqkv"][:, cols], p["bqkv"][cols]
        blocks.append(p)
    return {"wte": params["wte"], "wpe": params["wpe"],
            "lnf_g": params["lnf_g"], "lnf_b": params["lnf_b"],
            "head": params["head"], "blocks": blocks}
