"""GLM-5's block (`glm_moe_dsa`: MLA + learned sparse attention + a
dropless 256-way MoE of which a chip holds a share) against its plain
reference, at a tiny size on the CPU, in float32.

Tolerances: model and reference compute the same float32 mathematics in
another order (absorbed vs expanded heads, sorted grouped matmuls vs a
scan over experts, tiled vs whole softmax), so logits of magnitude ~2
agree to a few float32 roundings: 2e-5.  Selected sets must be EQUAL:
both sides order equal scores by position (`lax.top_k`), and the tiny
indexer (2 heads) produces exact-zero ties often, so the tie rule is
exercised.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaForCausalLM,
                                           glm_moe_dsa_tiny)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.reference import glm_moe_dsa_ref as ref  # noqa: E402

TOL = 2e-5


def ref_dims(cfg):
    return ref.dims_of({
        "num_attention_heads": cfg.num_heads,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "rms_norm_eps": cfg.rms_eps,
        "rope_parameters": {"rope_theta": cfg.rope_base},
        "expert_offset": cfg.expert_offset,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk,
        "routed_scaling_factor": cfg.routed_scaling_factor})


def build(seed=3, **kw):
    """A seeded tiny model with norms and selection biases away from
    their trivial initial values, and its reference parameters."""
    paddle.seed(seed)
    cfg = glm_moe_dsa_tiny(**kw)
    model = GlmMoeDsaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if "e_score_correction_bias" in name:
            p._value = jnp.asarray(rng.uniform(-.2, .2, p.shape), jnp.float32)
        elif "norm" in name:
            p._value = jnp.asarray(rng.uniform(.5, 1.5, p.shape), jnp.float32)
    sd = {k: v._value for k, v in model.state_dict().items()}
    return model, cfg, ref.from_state_dict(sd, cfg.num_layers)


def ids_of(n, vocab=256, seed=0):
    return np.random.RandomState(seed).randint(1, vocab, n).astype(np.int32)


def wrap(ids):
    return Tensor._wrap(jnp.asarray(ids, jnp.int32))


def same_sets(got, want):
    return all(set(g[g >= 0].tolist()) == set(w[w >= 0].tolist())
               for g, w in zip(np.asarray(got), np.asarray(want)))


def test_forward_is_the_reference():
    model, cfg, params = build(n_experts_held=2, expert_offset=4)
    ids = ids_of(40)
    want, _ = ref.forward(params, ids, ref_dims(cfg))
    with paddle.no_grad():
        got = model(wrap(ids[None]))._value[0]
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("n_prompt,n_total", [
    (5, 8),      # below index_topk (8): every cached token is attended
    (25, 40),    # several times above it: the selection bites
])
def test_prefill_then_decode_through_the_paged_latent_cache(n_prompt,
                                                            n_total):
    """Absorbed-form attention through the latent and index-key pools =
    the reference's expanded-head full forward, logits and selections."""
    model, cfg, params = build(n_experts_held=2, expert_offset=4)
    ids = ids_of(n_total)
    want, want_sel = ref.forward(params, ids, ref_dims(cfg))
    caches = model.init_caches(1, block_size=8, max_context=64)
    outs, sels = [], []

    def step(chunk, caches):
        logits, caches, sel = model.forward_selecting(wrap(chunk[None]),
                                                      caches)
        outs.append(logits._value[0])
        sels.append([a[0] for a in sel])
        return caches

    with paddle.no_grad():
        caches = step(ids[:n_prompt], caches)
        for t in range(n_prompt, n_total):
            caches = step(ids[t:t + 1], caches)
    got = jnp.concatenate(outs, 0)
    assert float(jnp.abs(got - want).max()) < TOL
    for li in range(cfg.num_layers):
        got_sel = np.concatenate([np.asarray(s[li]) for s in sels], 0)
        assert same_sets(got_sel, want_sel[li]), f"layer {li}"
    # every decode step and the one chunk counted their expert rows
    rows = np.asarray(caches[1].moe_rows)
    assert rows[0, 0].sum() > 0 and rows[1, 0].sum() > 0
    assert (rows[:, 1] <= rows[:, 0]).all()
    assert np.asarray(caches[0].moe_rows).sum() == 0     # the dense layer


def test_chunked_prefill_is_monolithic_prefill():
    model, cfg, params = build()
    ids = ids_of(37)
    with paddle.no_grad():
        whole, _ = model.forward_with_cache(
            wrap(ids[None]), model.init_caches(1, block_size=8,
                                               max_context=64))
        caches = model.init_caches(1, block_size=8, max_context=64)
        parts = []
        for a in range(0, 37, 10):        # chunks at unaligned offsets
            lg, caches = model.forward_with_cache(wrap(ids[None, a:a + 10]),
                                                  caches)
            parts.append(lg._value[0])
    assert float(jnp.abs(jnp.concatenate(parts, 0)
                         - whole._value[0]).max()) < TOL


def test_absorbed_attention_is_expanded_head_attention():
    """One attention layer: the cached (absorbed) form = the uncached
    (expanded multi-head) form on the same input."""
    model, cfg, _ = build()
    attn = model.model.layers[1].self_attn
    x = Tensor._wrap(jnp.asarray(
        np.random.RandomState(1).randn(2, 21, cfg.hidden_size), jnp.float32))
    with paddle.no_grad():
        plain = attn(x)._value
        cache = model.init_caches(2, block_size=8, max_context=32)[1]
        absorbed, _, _ = attn(x, cache)
    assert float(jnp.abs(absorbed._value - plain).max()) < TOL


def _serve(model, prompts, n_new, **kw):
    eng = ServingEngine(model, max_batch=2, max_context=96, block_size=8,
                        steps_per_tick=4, **kw)
    reqs = [Request(list(map(int, p)), max_new_tokens=n_new)
            for p in prompts]
    for r in reqs:
        eng.add_request(r)
    eng.run()
    return eng, [list(r.output_ids) for r in reqs]


def _ref_greedy_gap(params, cfg, prompt, out):
    """How far each served token's logit is below the reference's best at
    its position (0 = the reference's own greedy token)."""
    seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    L = len(prompt)
    lg, _ = ref.forward(params, seq, ref_dims(cfg),
                        positions=np.arange(L - 1, L - 1 + len(out)))
    lg = np.asarray(lg)
    return float((lg.max(-1) - lg[np.arange(len(out)), out]).max())


def test_engine_serves_it_chunked_with_the_4_step_tick_and_prefix_cache():
    """Through `add_request` / `run`: chunked prefill, the 4-step tick,
    and a prefix hit that equals a cold prompt."""
    model, cfg, params = build(n_experts_held=2, expert_offset=4)
    doc = ids_of(40, seed=5)
    q1, q2 = ids_of(7, seed=6), ids_of(9, seed=7)
    p1, p2 = np.concatenate([doc, q1]), np.concatenate([doc, q2])
    eng, (o1, o2, o1_again) = _serve(model, [p1, p2, p1], 9,
                                     prefill_chunk=16, prefix_cache=True)
    st = eng.stats()
    assert st["prefill_chunks"] > 0
    assert st["prefix_cache"]["hits"] >= 1
    assert o1 == o1_again                  # a prefix hit = a cold prompt
    for p, o in ((p1, o1), (p2, o2)):
        assert _ref_greedy_gap(params, cfg, p, np.asarray(o)) < TOL
    # the expert-row counts came to the host with a tick's tokens
    rows = st["cache_state"]["moe_rows"]
    assert len(rows) == cfg.num_layers and rows[1][0, 0].sum() > 0
    assert 0 < st["cache_state"]["steps"] <= st["steps"]
    # cold engine without the prefix cache or chunking: the same tokens
    _, (c1,) = _serve(model, [p1], 9, prefill_chunk=0, prefix_cache=False)
    assert c1 == o1


@pytest.mark.parametrize("mech,kw", [
    ("tp_degree", {"tp_degree": 2}),
    ("spec_decode", {"spec_decode": True, "spec_draft": "ngram"}),
    ("quant", {"quant": "int8"}),
])
def test_unsupported_mechanisms_raise_at_construction(mech, kw):
    model, _, _ = build()
    with pytest.raises(ValueError, match=mech):
        ServingEngine(model, max_batch=2, max_context=32, block_size=8, **kw)


def test_a_draft_model_raises_at_construction():
    model, _, _ = build()
    with pytest.raises(ValueError, match="draft_model"):
        ServingEngine(model, max_batch=2, max_context=32, block_size=8,
                      draft_model=model)


def test_gpt_and_llama_describe_their_pools_through_the_same_call():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    for m in (GPTForCausalLM(gpt3_tiny()), LlamaForCausalLM(llama_tiny())):
        spec = m.cache_spec()
        nh, hd = m.cfg.num_heads, m.cfg.hidden_size // m.cfg.num_heads
        assert [r.name for r in spec.rows] == ["k", "v"]
        assert spec.rows[0].shape(10, 16) == (nh, 11, 16, hd)
        assert not spec.unsupported
    glm, cfg, _ = build()
    spec = glm.cache_spec()
    rows = spec.rows
    assert rows[0].shape(10, 16) == (11, 16, cfg.kv_lora_rank
                                     + cfg.qk_rope_head_dim)
    assert rows[1].shape(10, 16) == (11, 16, cfg.index_head_dim)
    assert not rows[2].paged
    # one attention path: the one view class serves every program
    assert spec.chunk_view is spec.view is spec.verify_kernel_view
    # GLM-5's latent row of 576 values is kept in 640 lanes
    wide = GlmMoeDsaForCausalLM(glm_moe_dsa_tiny(
        kv_lora_rank=512, qk_rope_head_dim=64, qk_nope_head_dim=8,
        num_layers=1)).cache_spec().rows[0]
    assert wide.shape(10, 64) == (11, 64, 640)


def test_idle_slots_of_a_decode_step_reach_no_expert():
    """A batch of 4 with one request running: every decode step counts
    exactly `top_k` rows a MoE layer over all 8 experts (held whole
    here), the idle slots none; and the tokens are the batch-of-1
    engine's."""
    model, cfg, _ = build()                       # holds all 8 experts
    prompt = ids_of(11, seed=9)
    outs = {}
    for batch in (1, 4):
        eng = ServingEngine(model, max_batch=batch, max_context=96,
                            block_size=8, steps_per_tick=4)
        r = Request(list(map(int, prompt)), max_new_tokens=9)
        eng.add_request(r)
        eng.run()
        outs[batch] = list(r.output_ids)
        state = eng.stats()["cache_state"]
        decode = state["moe_rows"][1:, 0, 0]      # MoE layers, rows
        assert (decode.sum(-1) == state["steps"]
                * cfg.num_experts_per_tok).all(), (batch, decode)
    assert outs[1] == outs[4]


def test_inactive_tokens_get_the_shared_part_alone():
    from paddle_tpu.incubate.distributed.models.moe import (
        HeldExpertsLayer, SigmoidTopKGate)
    from paddle_tpu.nn import Linear
    paddle.seed(1)
    layer = HeldExpertsLayer(16, 8, SigmoidTopKGate(16, 8, 2),
                             n_experts_held=4, expert_offset=2,
                             shared=Linear(16, 16, bias_attr=False))
    x = Tensor._wrap(jnp.asarray(
        np.random.RandomState(0).randn(6, 16), jnp.float32))
    active = jnp.asarray([True, False, True, True, False, True])
    with paddle.no_grad():
        y_all, rows_all = layer.forward_counted(x)
        y, rows = layer.forward_counted(x, active)
        y_live, rows_live = layer.forward_counted(
            Tensor._wrap(x._value[active]))
        shared = layer.shared_experts(x)._value
    on = np.asarray(active)
    assert float(jnp.abs(y._value[on] - y_all._value[on]).max()) < 1e-6
    assert float(jnp.abs(y._value[~on] - shared[~on]).max()) == 0.0
    assert (np.asarray(rows) == np.asarray(rows_live)).all()
    assert int(rows.sum()) < int(rows_all.sum())


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each: the routed parts of all shares,
    plus the attention residual and the shared expert counted once, are
    the uncut reference's whole layer — and each share's part is what
    the model's expert layer computes."""
    model, cfg, params = build()                  # holds all 8 experts
    dims = dict(ref_dims(cfg))
    h = jnp.asarray(np.random.RandomState(2).randn(33, cfg.hidden_size),
                    jnp.float32)
    pos, want = jnp.arange(33), jnp.arange(33)
    p = params["blocks"][1]
    whole, _, (x, routed_all, shared) = ref.layer(
        h, p, pos, want, tuple(sorted(dims.items())))
    total = jnp.zeros_like(routed_all)
    layer = model.model.layers[1]
    y_in = layer.post_attention_layernorm(Tensor._wrap(x))
    for off in range(0, 8, 2):
        share = dict(p, **{k: p[k][off:off + 2]
                           for k in ("e_gate", "e_up", "e_down")})
        d = tuple(sorted(dict(dims, expert_offset=off).items()))
        out, _, (x_s, routed, shared_s) = ref.layer(h, share, pos, want, d)
        assert float(jnp.abs(x_s - x).max()) == 0.0
        assert float(jnp.abs(out - (x + routed + shared)).max()) < TOL
        total = total + routed
        # the model's layer, told the same share
        e = layer.mlp.experts
        y, rows = e.__class__.mix(
            _Share(e, off, 2), y_in._value,
            *layer.mlp.gate.route(y_in._value), cfg.n_routed_experts, off)
        assert float(jnp.abs(y - routed).max()) < TOL
        assert int(rows.sum()) > 0
    assert float(jnp.abs(total - routed_all).max()) < TOL
    assert float(jnp.abs(x + total + shared - whole).max()) < TOL


class _Share:
    """The stacked expert weights cut to `[off, off + n)`."""

    def __init__(self, experts, off, n):
        for name in ("gate_proj", "up_proj", "down_proj"):
            w = getattr(experts, name)
            setattr(self, name, Tensor._wrap(w._value[off:off + n]))


def test_the_bias_changes_which_experts_are_picked_not_their_weights():
    from paddle_tpu.incubate.distributed.models.moe import SigmoidTopKGate
    paddle.seed(0)
    gate = SigmoidTopKGate(16, 8, top_k=2, routed_scaling_factor=2.5)
    x = jnp.asarray(np.random.RandomState(0).randn(64, 16), jnp.float32)
    s = jax.nn.sigmoid(x @ gate.weight._value)
    picked0, g0 = gate.route(x)
    assert (np.asarray(picked0) == np.asarray(jax.lax.top_k(s, 2)[1])).all()
    # a large bias on expert 5 makes every token pick it ...
    gate.e_score_correction_bias._value = jnp.zeros((8,)).at[5].set(10.0)
    picked, g = gate.route(x)
    assert (np.asarray(picked) == 5).any(axis=1).all()
    assert (np.asarray(picked) != np.asarray(picked0)).any()
    # ... but the weights are the UN-biased scores of the picked experts,
    # normalised over the picked and scaled
    sp = np.take_along_axis(np.asarray(s), np.asarray(picked), axis=1)
    np.testing.assert_allclose(np.asarray(g),
                               sp / sp.sum(1, keepdims=True) * 2.5,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g).sum(1), 2.5, rtol=1e-6)


def test_grouped_matmul_reads_only_the_held_groups():
    from paddle_tpu.ops import pallas_moe as pm
    rng = np.random.RandomState(0)
    sizes = jnp.asarray([3, 0, 5, 1, 4, 0, 2, 5], jnp.int32)
    lhs = jnp.asarray(rng.randn(20, 64), jnp.float32)
    rhs = jnp.asarray(rng.randn(2, 64, 32), jnp.float32)
    for off in (0, 4, 6):
        got = pm.grouped_matmul(lhs, rhs, sizes, off)
        want = pm.grouped_matmul_reference(lhs, rhs, sizes, off)
        assert float(jnp.abs(got - want).max()) < 1e-4
        ends = np.cumsum(np.asarray(sizes))
        lo = ends[off - 1] if off else 0
        assert float(jnp.abs(got[:lo]).max(initial=0)) == 0.0
        assert float(jnp.abs(got[ends[off + 1]:]).max(initial=0)) == 0.0


@pytest.mark.parametrize("W,lanes", [(6, 6), (128, 128), (192, 256),
                                     (576, 640)])
def test_latent_pool_writes_gathers_and_copies_through_the_block_table(
        W, lanes):
    from paddle_tpu.ops import sparse_mla as sm
    from paddle_tpu.ops.pallas_paged import paged_copy_block
    assert sm.padded_width(W) == lanes
    rng = np.random.RandomState(0)
    B, s, bs, nb = 2, 13, 4, 5
    tables = (1 + jnp.arange(B * nb, dtype=jnp.int32)).reshape(B, nb)
    start = jnp.asarray([3, 6], jnp.int32)      # unaligned offsets
    rows = jnp.asarray(rng.randn(B, s, W), jnp.float32)
    pool = sm.write_rows(jnp.ones((B * nb + 1, bs, lanes)), tables, start,
                         rows)
    lin = np.asarray(jnp.take(pool, tables, axis=0).reshape(
        B, nb * bs, lanes))
    for b in range(B):
        a = int(start[b])
        np.testing.assert_array_equal(lin[b, a:a + s, :W],
                                      np.asarray(rows[b]))
        assert (lin[b, a:a + s, W:] == 0).all()          # the pad lanes
        assert (lin[b, :a] == 1).all() and (lin[b, a + s:] == 1).all()
    idx = jnp.asarray(rng.randint(0, nb * bs, (B, 3, 5)), jnp.int32)
    got = sm.gather_rows(pool, tables, idx, W)
    want = np.take_along_axis(lin[:, None], np.asarray(idx)[..., None],
                              2)[..., :W]
    np.testing.assert_array_equal(np.asarray(got), want)
    copied = paged_copy_block(pool, 2, 7, block_axis=0)
    np.testing.assert_array_equal(np.asarray(copied[7]), np.asarray(pool[2]))
    kv = jnp.asarray(rng.randn(3, 9, bs, 4), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(paged_copy_block(kv, 2, 7)[:, 7]), np.asarray(kv[:, 2]))


def test_index_score_kernel_reads_the_keys_through_the_table():
    """`dsa_index_scores` (interpreted here) = the XLA gather form, on a
    shuffled table, a short context, and an empty slot."""
    from paddle_tpu.ops import pallas_dsa, sparse_mla as sm
    rng = np.random.RandomState(0)
    B, H, D, bs, nb = 3, 4, 128, 16, 32
    pool = jnp.asarray(rng.randn(B * nb + 1, bs, D), jnp.float32)
    tables = jnp.asarray(rng.permutation(B * nb).reshape(B, nb) + 1,
                         jnp.int32).at[2].set(0)
    pos = jnp.asarray([[300], [17], [0]], jnp.int32)
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, 1, H), jnp.float32)
    assert pallas_dsa.supported(q, pool, tables)
    assert not pallas_dsa.supported(q[..., :8], pool[..., :8], tables)
    got = np.asarray(pallas_dsa.index_scores_decode(q, w, pool, tables, pos))
    want = np.asarray(sm.index_scores_xla(q, w, pool, tables, pos))
    seen = np.isfinite(want)
    assert (np.isfinite(got) == seen).all()
    assert seen.sum() == 301 + 18 + 1
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-5, atol=1e-5)
