"""K-EXAONE's model (`exaone_moe`: window attention over a per-slot ring in
three layers of four, full attention over paged kv-head pools in the
fourth, a sigmoid-routed expert layer with a shared expert, a self-drafting
MTP module) against its plain reference, at a tiny size on the CPU, in
float32 (matmul precision "highest": conftest sets none, and the reference
asks for it).

Tolerances compare LOGITS, whose spread here is near 1.  Model and
reference compute the same float32 mathematics in another order (a ring
and a chunk scored apart or in bands, a paged kernel, grouped matmuls
against a dense sum over experts), so they agree to a few float32
roundings: `TOL` = 2e-5 (measured under 3e-6).  Tokens are compared
exactly: the engine's greedy stream is the reference's argmax at each
position of the finished sequence, and each judged draft the reference
module's argmax at the position before.

The window is 8 positions, a block 8, a chunk 16 (buckets 8 and 16), so
prompts cross the window and chunks many times.  `agreeing` weights (the
attention and feed-forward outputs zeroed, the module's projection
`[I | 0]`) make every draft the model's own choice, so both positions of a
verify are kept; with `random` weights nearly every draft is rejected and
the ring keeps a stale key behind every step.
"""

import contextlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.incubate.distributed.models.moe import HeldExpertsLayer
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.exaone_moe import (ExaoneMoeBlock,
                                          ExaoneMoeForCausalLM,
                                          exaone_moe_tiny)
from paddle_tpu.ops import window_attention as wa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.reference import exaone_moe_ref as ref  # noqa: E402
from benchmark.reference import glm_moe_dsa_ref  # noqa: E402

TOL = 2e-5
BS, W = 8, 8
PAD = 96          # reference sequences are padded to this (one compile)


def ref_dims(cfg, **kw):
    return ref.dims_of({
        "num_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.rms_eps,
        "rope_parameters": {"rope_theta": cfg.rope_base},
        "sliding_window": cfg.sliding_window,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "layer_types": list(cfg.layer_types), **kw})


_BUILT = {}


def build(seed=3):
    """A seeded tiny model (norms and selection biases away from their
    trivial values) and its config (cached)."""
    if seed not in _BUILT:
        paddle.seed(seed)
        cfg = exaone_moe_tiny()
        model = ExaoneMoeForCausalLM(cfg)
        model.eval()
        rng = np.random.RandomState(seed)
        for name, p in model.named_parameters():
            if "e_score_correction_bias" in name:
                p._value = jnp.asarray(rng.uniform(-.2, .2, p.shape),
                                       jnp.float32)
            elif "norm" in name:
                p._value = jnp.asarray(rng.uniform(.5, 1.5, p.shape),
                                       jnp.float32)
        _BUILT[seed] = (model, cfg)
    return _BUILT[seed]


@contextlib.contextmanager
def weights(model, kind):
    """The model's weights of `kind` (module docstring) while inside, its
    own after; yields the reference's parameters of them.  The engine
    reads the live weights at every launch, so one engine serves both."""
    saved = {n: p._value for n, p in model.named_parameters()}
    if kind == "agreeing":
        H = model.cfg.hidden_size
        for name, p in model.named_parameters():
            if name.endswith(("o_proj", "down_proj")):
                p._value = jnp.zeros_like(p._value)
        model.mtp.eh_proj._value = jnp.concatenate(
            [jnp.eye(H), jnp.zeros((H, H))], 0)
        model.mtp.enorm._value = jnp.ones((H,))
        model.mtp.shared_head_norm._value = model.norm._value
    try:
        yield ref.from_state_dict(
            {k: v._value for k, v in model.state_dict().items()},
            model.cfg.num_layers)
    finally:
        for n, p in model.named_parameters():
            p._value = saved[n]


def ids_of(n, seed=0, vocab=256):
    return np.random.RandomState(seed).randint(1, vocab, n).astype(np.int32)


def err(got, want):
    return float(jnp.abs(jnp.asarray(got) - jnp.asarray(want)).max())


def ref_rows(params, dims, seq):
    """(logits `[T, V]`, module logits `[T - 1, V]`) of the reference over
    `seq`, padded behind to `PAD` (causal: nothing before moves)."""
    T = len(seq)
    ids = np.zeros((PAD,), np.int32)
    ids[:T] = seq
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(params, ids, dims)
        lg = np.asarray(ref.logits_of(params, h))[:T]
        ml = np.asarray(ref.module_logits(params, h, ids, dims))[:T - 1]
    return lg, ml


# ------------------------------------------------------------ the forward
def test_forward_and_module_are_the_reference():
    model, cfg = build()
    dims = ref_dims(cfg)
    ids = ids_of(PAD, seed=1)
    with jax.default_matmul_precision("highest"), \
            weights(model, "random") as params:
        lg, ml = jax.jit(lambda i: tuple(
            t._value for t in model.forward_mtp(Tensor._wrap(i))))(
            jnp.asarray(ids[None]))
        want, wmod = ref_rows(params, dims, ids)
        # the window is what the reference's window is: without it the
        # logits move by the spread itself
        nw = np.asarray(ref.forward(params, ids, dims, no_window=True))
    assert err(lg[0], want) < TOL
    assert err(ml[0], wmod) < TOL
    assert err(nw, want) > 100 * TOL


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of the router's 16 experts, two a share: their partial
    results, the shared expert counted once, are the uncut reference
    layer's."""
    paddle.seed(5)
    cfg = exaone_moe_tiny(num_experts=16)
    block = ExaoneMoeBlock(cfg, 1)
    whole = block.mlp
    whole.gate.e_score_correction_bias._value = jnp.asarray(
        np.random.RandomState(5).uniform(-.2, .2, 16), jnp.float32)
    x = jnp.asarray(np.random.RandomState(6).normal(size=(12, 64)),
                    jnp.float32)
    shares = []
    for j in range(8):
        share = HeldExpertsLayer(64, cfg.moe_intermediate_size, whole.gate,
                                 n_experts_held=2, expert_offset=2 * j,
                                 shared=whole.shared_experts)
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(share.experts, name)._value = getattr(
                whole.experts, name)._value[2 * j:2 * j + 2]
        shares.append(share)
    with jax.default_matmul_precision("highest"):
        parts, shared = jax.jit(lambda x: (
            [sh(Tensor._wrap(x))._value for sh in shares],
            whole.shared_experts(Tensor._wrap(x))._value))(x)
    sd = {k: v._value for k, v in whole.state_dict().items()}
    p = {"w_gate": sd["gate.weight"],
         "gate_bias": sd["gate.e_score_correction_bias"],
         "e_gate": sd["experts.gate_proj"], "e_up": sd["experts.up_proj"],
         "e_down": sd["experts.down_proj"],
         "s_gate": sd["shared_experts.gate_proj"],
         "s_up": sd["shared_experts.up_proj"],
         "s_down": sd["shared_experts.down_proj"]}
    c = {"top_k": 2, "routed_scaling_factor": 2.5, "expert_offset": 0}
    routed, ref_shared = glm_moe_dsa_ref.moe_parts(
        glm_moe_dsa_ref._Math(), x, p, c)
    assert err(sum(parts) - 7 * shared, routed + ref_shared) < TOL


# ------------------------------------------------------- the window's rule
_attend = jax.jit(wa.window_attend, static_argnames=("window", "scale"))
_write = jax.jit(wa.ring_write)


def dense_window(q, k, v, pos, window):
    """The dense masked form over a whole history: q `[b, s, nh, hd]` at
    positions `pos` `[b, s]`, k, v `[b, T, nkv, hd]` at positions 0..T-1."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    qg = q.reshape(b, s, nkv, nh // nkv, hd)
    sc = jnp.einsum("bshgd,bkhd->bhgsk", qg, k) / np.sqrt(hd)
    t, key = pos[:, :, None], jnp.arange(k.shape[1])[None, None]
    seen = (key <= t) & (key > t - window)
    p = jax.nn.softmax(jnp.where(seen[:, None, None], sc, -jnp.inf), -1)
    return jnp.einsum("bhgsk,bkhd->bshgd", p, v).reshape(b, s, nh, hd)


def ring_of(hist, lens):
    """Rings `[b, nkv, W, hd]` after each sequence wrote its first `lens`
    positions of `hist` `[b, T, nkv, hd]`, one chunk of 3 at a time."""
    b, T, nkv, hd = hist.shape
    ring = jnp.asarray(np.random.RandomState(9).normal(
        size=(b, nkv, W, hd)), jnp.float32)        # an earlier occupant's
    for off in range(0, T, 3):
        n = jnp.clip(lens - off, 0, 3)
        ring = _write(ring, hist[:, off:off + 3],
                      jnp.full((b,), off, jnp.int32), n)
    return ring


# decode (s 1), a verify (s 2), chunks in one band (s 5) and in tiles (16)
FORMS = [(1, [0, 3, 8, 13]), (2, [1, 7, 8, 20]), (5, [0, 9, 10, 30]),
         (16, [0, 5, 8, 17])]


@pytest.mark.parametrize("s, lens", FORMS)
def test_window_attention_is_the_dense_masked_form(s, lens):
    b = len(lens)
    rng = np.random.RandomState(s)
    lens = jnp.asarray(lens, jnp.int32)
    T = int(lens.max()) + s
    k = jnp.asarray(rng.normal(size=(b, T, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, T, 2, 16)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, s, 4, 16)), jnp.float32)
    idx = lens[:, None] + jnp.arange(s)[None]
    rows = lambda a: jnp.take_along_axis(            # noqa: E731
        a, idx[:, :, None, None], axis=1)
    got = _attend(q, rows(k), rows(v), ring_of(k, lens), ring_of(v, lens),
                  lens, window=W, scale=0.25)
    want = dense_window(q, k, v, idx, W)
    with jax.default_matmul_precision("highest"):
        assert err(got, want) < 1e-5


@pytest.mark.parametrize("s", [1, 2, 16])
def test_the_window_edge_is_exact(s):
    """With every score equal, a query's output is the mean of the values
    it sees: the value of key `t - W + 1` is among them, `t - W`'s not."""
    n = 21
    T = n + s
    v = np.zeros((1, T, 2, 16), np.float32)
    t = n + s - 1                          # the last query's position
    v[0, t - W + 1, :, 0] = 1.0            # seen
    v[0, t - W, :, 1] = 1.0                # not seen
    v = jnp.asarray(v)
    k = jnp.zeros_like(v)
    lens = jnp.asarray([n], jnp.int32)
    o = _attend(jnp.zeros((1, s, 4, 16)), k[:, n:], v[:, n:],
                ring_of(k, lens), ring_of(v, lens), lens, window=W,
                scale=1.0)[0, -1]
    assert np.allclose(o[:, 0], 1.0 / W) and np.allclose(o[:, 1], 0.0)


def test_padded_rows_never_reach_the_ring():
    ring = jnp.zeros((2, 2, W, 16))
    new = jnp.ones((2, 16, 2, 16)) * jnp.arange(1, 17)[None, :, None, None]
    lens = jnp.asarray([3, 30], jnp.int32)
    out = wa.ring_write(ring, new, lens, jnp.asarray([5, 12], jnp.int32))
    held = np.asarray(wa.ring_positions(lens + jnp.asarray([5, 12]), W))
    for b, (start, real) in enumerate([(3, 5), (30, 12)]):
        for j in range(W):
            p = held[b, j]
            want = p - start + 1 if start <= p < start + real else 0
            assert float(out[b, 0, j, 0]) == want
    # an idle slot of a tick writes nothing
    idle = wa.ring_write(ring, new[:, :1], lens, jnp.ones((2,), jnp.int32),
                         jnp.asarray([False, True]))
    assert float(jnp.abs(idle[0]).max()) == 0.0


# --------------------------------------------------------------- the engine
_ENGINES = {}


def engine():
    if not _ENGINES:
        model, cfg = build()
        _ENGINES[0] = (ServingEngine(
            model, max_batch=2, max_context=PAD, block_size=BS,
            prefill_chunk=16, pad_buckets=[8, 16]), model, ref_dims(cfg))
    return _ENGINES[0]


# prompts over the window and over chunks, a bucket's padded rows (5, 20,
# 37: chunks of 16 + 5 padded to 8), 5 requests over 2 slots (each slot
# taken over by another request)
PROMPTS = [(37, 11), (5, 9), (20, 12), (16, 7), (50, 10)]


@pytest.mark.parametrize("kind", ["random", "agreeing"])
def test_chunks_then_drafted_decode_are_the_reference(kind):
    eng, model, dims = engine()
    prompts = [ids_of(n, seed=40 + i).tolist()
               for i, (n, _) in enumerate(PROMPTS)]
    reqs = [Request(p, max_new_tokens=m, keep_first_logits=True)
            for p, (_, m) in zip(prompts, PROMPTS)]
    with weights(model, kind) as params:
        for r in reqs:
            eng.add_request(r)
        eng.run()
    drafted = accepted = 0
    for p, r in zip(prompts, reqs):
        assert r.done and len(r.output_ids) == r.max_new_tokens
        seq = p + list(r.output_ids)
        lg, ml = ref_rows(params, dims, seq)
        L = len(p)
        # every emitted token is the reference's argmax where it was chosen
        assert list(r.output_ids) == lg[L - 1:len(seq) - 1].argmax(
            -1).tolist()
        # the prompt's last row as the chunks left it
        assert err(r.first_logits, lg[L - 1]) < TOL
        # each judged draft is the module's choice at the position before
        n = L
        for d, a, c in r.draft_log:
            assert d == int(ml[n - 1].argmax())
            assert a == (d == r.output_ids[n - L + 1])
            drafted, accepted, n = drafted + 1, accepted + a, n + c
    if kind == "agreeing":
        assert accepted == drafted > 0
    else:
        assert accepted < drafted
    counts = eng.stats()["cache"]
    assert counts["ring_writes"] >= sum(len(p) for p in prompts)


def test_ring_bytes_do_not_grow_with_the_context():
    model, cfg = build()
    got = []
    for ctx in (64, 256):
        eng = ServingEngine(model, max_batch=3, max_context=ctx,
                            block_size=BS, prefill_chunk=16,
                            pad_buckets=[8, 16])
        got.append(eng.stats()["cache"]["window_ring_bytes"])
        windowed = [i for i in range(cfg.num_layers) if cfg.window_of(i)]
        for i, layer in enumerate(eng.pools):
            # a window layer's (K, V) pools are a stand-in of the pad
            # block; a full layer's and the module's hold every block
            blocks = layer[0].shape[1]
            assert blocks == (1 if i in windowed else eng.num_blocks + 1)
    assert got[0] == got[1] == 4 * 3 * 2 * (W * 2 * 16 * 4)


@pytest.mark.parametrize("kw, why", [
    ({"prefix_cache": True}, "no snapshot of the ring"),
    ({"spec_decode": True}, "drafts for itself"),
    ({"tp_degree": 2}, "tensor-parallel"),
    ({"quant": "int8"}, "weight-quantized")])
def test_unsupported_mechanisms_raise_with_their_reason(kw, why):
    model, _ = build()
    with pytest.raises(ValueError, match=why):
        ServingEngine(model, max_batch=2, max_context=32, block_size=BS,
                      **kw)
