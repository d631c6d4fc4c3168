"""SDAR's block (`sdar_moe`: grouped-query attention over kv-head pools,
a softmax top-k gate over held experts, generation by diffusion over
blocks) against its plain reference, at a tiny size on the CPU, in
float32.

Tolerances: model and reference compute the same float32 mathematics in
another order (query heads folded into rows vs einsum over groups, sorted
grouped matmuls vs a scan over experts, tiled vs whole softmax), so
logits of magnitude ~0.5 agree to a few float32 roundings: 5e-6 (seen:
2e-7 to 6e-7).  The same model in bfloat16 misses the reference by 1e-3
and more, so bfloat16 in float32's place fails every such comparison
(`test_bfloat16_in_float32s_place_fails`).  Served tokens and the order
in which they were revealed must be EQUAL to the reference sampler's:
both sides take the greedy token and rank confidences in float32, ties to
the lowest position.
"""

import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.kv_cache import PagedChunkKernelView, PagedChunkView
from paddle_tpu.models.sdar_moe import SdarMoeForCausalLM, sdar_moe_tiny

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.reference import sdar_moe_ref as ref  # noqa: E402

TOL = 5e-6


def ref_dims(cfg, **kw):
    return ref.dims_of(dict({
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_base,
        "expert_offset": cfg.expert_offset}, **kw), cfg.block_length)


def build(seed=3, **kw):
    """A seeded tiny model with its norms away from one, and its
    reference parameters."""
    paddle.seed(seed)
    cfg = sdar_moe_tiny(**kw)
    model = SdarMoeForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p._value = jnp.asarray(rng.uniform(.5, 1.5, p.shape),
                                   p._value.dtype)
    sd = {k: v._value for k, v in model.state_dict().items()}
    return model, cfg, ref.from_state_dict(sd, cfg.num_layers)


def ids_of(n, vocab=255, seed=0):
    return np.random.RandomState(seed).randint(0, vocab, n).astype(np.int32)


def wrap(ids):
    return Tensor._wrap(jnp.asarray(ids, jnp.int32))


def engine(model, **kw):
    args = dict(max_batch=3, max_context=64, block_size=8, prefill_chunk=8,
                pad_buckets=[8, 16], prefix_cache=True)
    args.update(kw)
    return ServingEngine(model, **args)


def serve(eng, prompts, n_new):
    reqs = [Request(p, max_new_tokens=n) for p, n in zip(prompts, n_new)]
    for r in reqs:
        eng.add_request(r)
    eng.run()
    return reqs


def sampled(params, cfg, prompt, n):
    return ref.generate(params, prompt, n, ref_dims(cfg), cfg.mask_token_id,
                        cfg.denoising_steps)


# ---------------------------------------------------------------- the model

def test_forward_is_the_reference_under_the_block_mask():
    model, cfg, params = build()
    ids = ids_of(37)
    want = ref.forward(params, ids, ref_dims(cfg))
    with paddle.no_grad():
        got = model(wrap(ids[None]))._value[0]
    assert float(jnp.abs(got - want).max()) < TOL
    # the mask is the block's: the causal one gives other logits ...
    causal = ref.forward(params, ids, ref_dims(cfg)[:0] + tuple(
        (k, 1 if k == "block_length" else v) for k, v in ref_dims(cfg)))
    assert float(jnp.abs(causal - want).max()) > 100 * TOL
    # ... and a position sees its whole block and nothing behind it
    other = ids.copy()
    other[12:] = (other[12:] + 1) % 255
    moved = ref.forward(params, other, ref_dims(cfg))
    assert float(jnp.abs(moved[:12] - want[:12]).max()) == 0.0
    assert float(jnp.abs(moved[8:12] - want[8:12]).max()) == 0.0
    other = ids.copy()
    other[11] = (other[11] + 1) % 255
    assert float(jnp.abs(ref.forward(params, other, ref_dims(cfg))[8]
                         - want[8]).max()) > 100 * TOL


def test_bfloat16_in_float32s_place_fails():
    model, cfg, params = build()
    ids = ids_of(24)
    want = ref.forward(params, ids, ref_dims(cfg))
    low = ref.forward(params, ids, ref_dims(cfg),
                      operand_dtype=jnp.bfloat16)
    assert float(jnp.abs(low - want).max()) > 100 * TOL


@pytest.mark.parametrize("view", [PagedChunkView, PagedChunkKernelView])
def test_chunks_and_blocks_through_the_kv_head_pools(view):
    """A prefill in two chunks, then blocks of 4 (written, overwritten and
    committed as the tick does) through pools of the 2 kv heads = the
    reference's full forward."""
    model, cfg, params = build()
    ids = ids_of(28)
    want = ref.forward(params, ids, ref_dims(cfg))
    spec = model.cache_spec()
    assert [r.name for r in spec.rows] == ["k", "v", "moe_rows"]
    assert spec.rows[0].shape(10, 8) == (cfg.num_kv_heads, 11, 8,
                                         cfg.head_dim)
    pools = spec.init_pools(8, 8, jnp.float32)
    tables = jnp.arange(1, 9, dtype=jnp.int32)[None]
    got = []

    def run(chunk, start, pools, in_tick=False):
        views = [view.from_parts(*layer, tables, jnp.asarray(
            [start], jnp.int32), 8) for layer in pools]
        for v in views:
            v.in_tick = in_tick       # as `ServingEngine._forward` says it
        with paddle.no_grad():
            logits, new = model.forward_with_cache(wrap(chunk[None]), views)
        return logits._value[0], [c.pools for c in new]

    for a, b in ((0, 12), (12, 20)):
        lg, pools = run(ids[a:b], a, pools)
        got.append(lg)
    noise = np.full(4, cfg.mask_token_id, np.int32)
    for a in (20, 24):
        _, pools = run(noise, a, pools, True)    # a denoising forward
        lg, pools = run(ids[a:a + 4], a, pools, True)   # the commit
        got.append(lg)
    assert float(jnp.abs(jnp.concatenate(got) - want).max()) < TOL
    # the chunks' and the blocks' expert rows were counted apart, by what
    # the caller said and not by the rows: a chunk of 4 is a chunk
    _, pools = run(ids[:4], 0, pools)
    rows = np.asarray(pools[0][2])
    assert rows[1, 0].sum() == 24 * cfg.num_experts_per_tok
    assert rows[0, 0].sum() == 16 * cfg.num_experts_per_tok


def test_block_states_from_kept_keys_are_the_full_forward_rerun():
    """`block_logits` over the finished sequence's K and V = the full
    forward over everything before the block and the block's state."""
    model, cfg, params = build()
    dims = ref_dims(cfg)
    seq = ids_of(24)
    _, kv = ref.forward(params, seq, dims, return_kv=True)
    M = cfg.mask_token_id
    states = np.asarray([[seq[8], M, M, M], [seq[8], M, seq[10], M],
                         [M, M, M, M], [seq[20], seq[21], M, seq[23]]])
    starts = np.asarray([8, 8, 16, 20])
    got = ref.block_logits(params, states, starts, kv, dims)
    for st, s0, lg in zip(states, starts, got):
        want = ref.forward(params, np.concatenate([seq[:s0], st]), dims,
                           positions=s0 + np.arange(4))
        assert float(jnp.abs(lg - want).max()) < TOL


# ------------------------------------------------------------- the engine

@pytest.mark.parametrize("n_prompt,n_new", [
    (16, 8),     # 0 mod 4: every block is 4 x [MASK]; ends on a block
    (13, 6),     # 1 mod 4: the first block reveals 3; ends mid-block
    (14, 7),     # 2 mod 4
    (15, 9),     # 3 mod 4: the first tick hands over one token
    (3, 5),      # shorter than a block: nothing to prefill
    (12, 1),     # one token of the first block is all it asked for
])
def test_engine_serves_the_reference_samplers_tokens(n_prompt, n_new):
    model, cfg, params = build()
    prompt = ids_of(n_prompt, seed=n_prompt).tolist()
    eng = engine(model)
    (r,) = serve(eng, [prompt], [n_new])
    want, steps = sampled(params, cfg, prompt, n_new)
    assert r.output_ids == want
    assert r.reveal_steps == steps
    assert r.done and r.outcome == "finished"
    st = eng.stats()
    n_ticks = -(-(n_prompt % 4 + n_new) // 4)
    assert st["ticks"] == n_ticks
    assert st["steps"] == n_ticks * (cfg.denoising_steps + 1)
    assert st["free_blocks"] == eng.num_blocks       # nothing leaked


def test_a_batch_is_served_as_each_alone_and_counts_its_expert_rows():
    model, cfg, params = build()
    prompts = [ids_of(n, seed=n).tolist() for n in (12, 21, 6, 17, 9)]
    n_new = [9, 5, 12, 7, 10]
    eng = engine(model)
    reqs = serve(eng, prompts, n_new)
    for r, p, n in zip(reqs, prompts, n_new):
        assert (r.output_ids, r.reveal_steps) == sampled(params, cfg, p, n)
    # every forward of every tick routed its running slots' 4 positions
    # to top_k experts, idle slots to none; chunks are counted apart
    state = eng.stats()["cache_state"]
    blocks = sum(-(-(len(p) % 4 + n) // 4) for p, n in zip(prompts, n_new))
    per_layer = blocks * (cfg.denoising_steps + 1) * 4 \
        * cfg.num_experts_per_tok
    assert (state["moe_rows"][:, 0, 0].sum(-1) == per_layer).all()
    # (the rows a chunk is padded with, here to 8, are not told apart)
    padded = sum(-(-(len(p) // 4 * 4) // 8) * 8 for p in prompts)
    assert (state["moe_rows"][:, 1, 0].sum(-1)
            == padded * cfg.num_experts_per_tok).all()


def test_a_prefix_hit_serves_the_same_tokens():
    """K and V under the block mask depend on nothing behind their block,
    and the cache's blocks of 8 tokens are whole blocks of 4: a hit is as
    valid as under the causal mask."""
    model, cfg, params = build()
    doc = ids_of(24, seed=7).tolist()
    a, b = doc + ids_of(5, seed=8).tolist(), doc + ids_of(6, seed=9).tolist()
    eng = engine(model)
    (ra,) = serve(eng, [a], [6])
    chunks = eng.stats()["prefill_chunks"]
    (rb,) = serve(eng, [b], [7])
    pc = eng.stats()["prefix_cache"]
    assert pc["hits"] == 1 and pc["blocks_shared"] == 3
    # b's 24 cached tokens were not prefilled again: one chunk of 4
    assert eng.stats()["prefill_chunks"] == chunks + 1
    assert (ra.output_ids, ra.reveal_steps) == sampled(params, cfg, a, 6)
    assert (rb.output_ids, rb.reveal_steps) == sampled(params, cfg, b, 7)


def test_a_fully_cached_aligned_prompt_needs_no_copy_on_write():
    """The causal engines' copy-on-write case — a block-aligned prompt
    whose every block is cached — recomputes nothing here and copies
    nothing: no logits are needed of a prompt, the generation opens a
    block of its own, and the tokens are the reference's."""
    model, cfg, params = build()
    prompt = ids_of(16, seed=5).tolist()
    eng = engine(model)
    (r1,) = serve(eng, [prompt], [6])
    chunks = eng.stats()["prefill_chunks"]
    (r2,) = serve(eng, [prompt], [6])
    assert eng.stats()["prefill_chunks"] == chunks     # all of it a hit
    assert eng._cow_fn is None
    assert r1.output_ids == r2.output_ids == sampled(params, cfg, prompt,
                                                     6)[0]
    assert r2._prefix_blocks == 2


def test_an_eviction_mid_generation_leaves_the_others_tokens_unchanged():
    model, cfg, params = build()
    prompts = [ids_of(n, seed=n).tolist() for n in (13, 10, 15)]
    eng = engine(model)
    reqs = [Request(p, max_new_tokens=12) for p in prompts]
    for r in reqs:
        eng.add_request(r)
    while len(reqs[1].output_ids) < 3:
        eng.step()
    reqs[1].cancel()              # between two of its blocks
    eng.run()
    assert reqs[1].outcome == "cancelled" and not reqs[1].done
    # what it was handed before is the reference's, block for block
    n = len(reqs[1].output_ids)
    assert reqs[1].output_ids == sampled(params, cfg, prompts[1], 12)[0][:n]
    for r, p in ((reqs[0], prompts[0]), (reqs[2], prompts[2])):
        assert (r.output_ids, r.reveal_steps) == sampled(params, cfg, p, 12)
    assert eng.stats()["free_blocks"] == eng.num_blocks
    # its slot and blocks serve the next request from the block's start
    (r,) = serve(eng, [prompts[1]], [12])
    assert r.output_ids == sampled(params, cfg, prompts[1], 12)[0]


@pytest.mark.parametrize("mech,kw", [
    ("tp_degree", {"tp_degree": 2}),
    ("spec_decode", {"spec_decode": True, "spec_draft": "ngram"}),
    ("quant", {"quant": "int8"}),
    ("draft_model", {"draft_model": "itself"}),
])
def test_unsupported_mechanisms_raise_at_construction(mech, kw):
    model, _, _ = build()
    if kw.get("draft_model"):
        kw = {"draft_model": model}
    with pytest.raises(ValueError, match=mech):
        ServingEngine(model, max_batch=2, max_context=32, block_size=8, **kw)


def test_what_the_engine_reads_from_the_model_and_what_it_refuses():
    model, cfg, _ = build()
    gen = model.cache_spec().generation
    assert (gen.block_length, gen.denoising_steps, gen.mask_token_id) == (
        cfg.block_length, cfg.denoising_steps, cfg.mask_token_id)
    with pytest.raises(ValueError, match="block_length"):
        ServingEngine(model, max_batch=2, max_context=32, block_size=6)
    eng = engine(model, prefill_chunk=0)
    assert eng.chunk == 16                    # the longest bucket
    grid = [fn().decl.name for fn in eng._grid()]
    assert grid == ["serving.block_tick"] + ["serving.prefill_cont"] * 2
    with pytest.raises(ValueError, match="greedy"):
        eng.add_request(Request([1, 2, 3], max_new_tokens=4,
                                do_sample=True))
    with pytest.raises(ValueError, match="MASK"):
        eng.add_request(Request([1, cfg.mask_token_id, 3],
                                max_new_tokens=4))
    assert not eng.waiting


def test_the_tick_span_counts_its_forwards_and_the_emit_its_tokens(
        tmp_path):
    """`serve:tick_dispatch` says `steps` = the forwards of the launch
    and `block_len`; `serve:emit` the tokens handed over; the counters
    the forwards and what each revealed."""
    from paddle_tpu.observability import metrics
    model, cfg, _ = build()
    eng = engine(model)
    eng.warmup()
    fwd0 = metrics.get("serving.block.forwards").value()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serve(eng, [ids_of(13).tolist(), ids_of(8, seed=1).tolist()],
              [6, 8])
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path) + "/plugins/profile/*/*.xplane.pb")[0]
    events = [(e.name, dict(e.stats))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for ln in plane.lines for e in ln.events
              if e.name in ("serve:tick_dispatch", "serve:emit")]
    ticks = [a for n, a in events if n == "serve:tick_dispatch"]
    assert ticks and all(int(a["steps"]) == 5 and int(a["block_len"]) == 4
                         for a in ticks)
    assert sum(int(a["tokens"]) for n, a in events
               if n == "serve:emit") == 14
    forwards = sum(int(a["steps"]) * int(a["active"]) for a in ticks)
    assert forwards / 14 == pytest.approx(1.25 * 16 / 14)
    assert metrics.get("serving.block.forwards").value() - fwd0 \
        == 5 * len(ticks)


# ----------------------------------------------------------- the expert layer

def test_the_softmax_gate_is_the_references_routing():
    from paddle_tpu.incubate.distributed.models.moe import SoftmaxTopKGate
    paddle.seed(0)
    gate = SoftmaxTopKGate(16, 8, top_k=3)
    x = jnp.asarray(np.random.RandomState(0).randn(11, 16), jnp.float32)
    picked, g = gate.route(x)
    p = jax.nn.softmax(x @ gate.weight._value, -1)
    top = np.argsort(-np.asarray(p), -1)[:, :3]
    assert (np.asarray(picked) == top).all()
    assert np.allclose(np.asarray(g).sum(-1), 1.0, atol=1e-6)
    want = np.take_along_axis(np.asarray(p), top, -1)
    assert np.allclose(np.asarray(g), want / want.sum(-1, keepdims=True),
                       atol=1e-6)
    raw = SoftmaxTopKGate(16, 8, top_k=3, norm_topk_prob=False)
    raw.weight._value = gate.weight._value
    assert np.allclose(np.asarray(raw.route(x)[1]), want, atol=1e-6)
    # the reference's combine weights are these, on the picked experts
    dense = ref.route(x, {"w_router": gate.weight._value}, {"top_k": 3})
    assert np.allclose(np.take_along_axis(np.asarray(dense), top, -1),
                       np.asarray(g), atol=1e-6)
    assert (np.asarray(dense) > 0).sum(-1).max() == 3


class _Share:
    """The stacked expert weights cut to `[off, off + n)`."""

    def __init__(self, experts, off, n):
        for name in ("gate_proj", "up_proj", "down_proj"):
            w = getattr(experts, name)
            setattr(self, name, Tensor._wrap(w._value[off:off + n]))


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts each of the tiny layer's 16: the
    routed parts of all shares and the attention residual are the uncut
    reference's whole layer — and each share's part is what the model's
    expert layer computes under the softmax gate."""
    model, cfg, params = build()                  # holds all 16 experts
    dims = dict(ref_dims(cfg))
    h = jnp.asarray(np.random.RandomState(2).randn(33, cfg.hidden_size),
                    jnp.float32)
    pos = jnp.arange(33)
    p = params["blocks"][1]
    whole, _, (x, routed_all) = ref.layer(h, p, pos,
                                          tuple(sorted(dims.items())))
    total = jnp.zeros_like(routed_all)
    layer = model.model.layers[1]
    y_in = layer.post_attention_layernorm(Tensor._wrap(x))
    for off in range(0, 16, 2):
        share = dict(p, **{k: p[k][off:off + 2]
                           for k in ("e_gate", "e_up", "e_down")})
        d = tuple(sorted(dict(dims, expert_offset=off).items()))
        out, _, (x_s, routed) = ref.layer(h, share, pos, d)
        assert float(jnp.abs(x_s - x).max()) == 0.0
        assert float(jnp.abs(out - (x + routed)).max()) < TOL
        total = total + routed
        e = layer.mlp.experts
        y, rows = e.__class__.mix(
            _Share(e, off, 2), y_in._value,
            *layer.mlp.gate.route(y_in._value), cfg.num_experts, off)
        assert float(jnp.abs(y - routed).max()) < TOL
    assert float(jnp.abs(total - routed_all).max()) < TOL
    assert float(jnp.abs(x + total - whole).max()) < TOL
    # a model told its share computes that share
    held, _, hp = build(n_experts_held=2, expert_offset=6)
    ids = ids_of(12)
    with paddle.no_grad():
        got = held(wrap(ids[None]))._value[0]
    cfg6 = held.cfg
    assert float(jnp.abs(got - ref.forward(hp, ids, ref_dims(cfg6))).max()) \
        < TOL
