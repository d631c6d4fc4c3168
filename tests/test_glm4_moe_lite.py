"""GLM-4.7-Flash's block (`glm4_moe_lite`: MLA over every cached row, a
held MoE, a multi-token-prediction module that drafts for the model)
against its plain reference, at a tiny size on the CPU, in float32.

Tolerances compare LOGITS.  Model and reference compute the same float32
mathematics in another order (absorbed vs expanded heads, sorted grouped
matmuls vs a scan over experts, an online softmax over groups of blocks
vs a whole one), so logits of magnitude ~2 agree to a few float32
roundings: `TOL` = 2e-5 (measured 1.5e-6).  The same model with bfloat16
parameters misses it by three orders (`test_tolerance_is_tight`), so a
bfloat16 computation where float32 is stated cannot pass.  The kernel is
compared with its jnp twin at `KTOL` = 2e-6 of outputs of magnitude ~1:
both accumulate in float32 and differ only in the order of the softmax's
sums (measured 2.4e-7); bfloat16 probabilities would miss it by 1e-3.

The accept path is proven on a model whose drafts are right BY
CONSTRUCTION (`agreeing`): with every projection that writes into the
residual stream zeroed, a position's last hidden state is the norm of its
token's embedding, so the next token is a function of the current token;
with `eh_proj = [I | 0]` the module's output at position i is that same
function of token i + 1: its draft of token i + 2 is the model's choice.
`partly` perturbs the module's last norm so that only some drafts agree.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags as _flags
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.glm4_moe_lite import (Glm4MoeLiteForCausalLM,
                                             glm4_moe_lite_tiny)
from paddle_tpu.ops import pallas_latent

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.reference import glm4_moe_lite_ref as ref  # noqa: E402

TOL = 2e-5
KTOL = 2e-6
BS = 8


def ref_dims(cfg):
    return ref.dims_of({
        "num_attention_heads": cfg.num_heads,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "rms_norm_eps": cfg.rms_eps,
        "rope_parameters": {"rope_theta": cfg.rope_base},
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "routed_scaling_factor": cfg.routed_scaling_factor})


def build(kind="random", seed=3, **kw):
    """A seeded tiny model (norms and selection biases away from their
    trivial values) and its reference parameters.  `kind`: "random",
    "agreeing" or "partly" (module docstring)."""
    paddle.seed(seed)
    cfg = glm4_moe_lite_tiny(**kw)
    model = Glm4MoeLiteForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    for name, p in model.named_parameters():
        if "e_score_correction_bias" in name:
            p._value = jnp.asarray(rng.uniform(-.2, .2, p.shape), jnp.float32)
        elif "norm" in name:
            p._value = jnp.asarray(rng.uniform(.5, 1.5, p.shape), jnp.float32)
    if kind != "random":
        H = cfg.hidden_size
        for name, p in model.named_parameters():
            if name.endswith(("o_proj.weight", "down_proj.weight",
                              "experts.down_proj")):
                p._value = jnp.zeros_like(p._value)
        model.mtp.eh_proj.weight._value = jnp.concatenate(
            [jnp.eye(H), jnp.zeros((H, H))], 0)
        model.mtp.enorm.weight._value = jnp.ones((H,))
        g = model.model.norm.weight._value
        if kind == "partly":
            g = g * jnp.asarray(rng.uniform(.2, 1.8, g.shape), jnp.float32)
        model.mtp.shared_head_norm.weight._value = g
    sd = {k: v._value for k, v in model.state_dict().items()}
    return model, cfg, ref.from_state_dict(sd, cfg.num_layers)


def ids_of(n, vocab=256, seed=0):
    return np.random.RandomState(seed).randint(1, vocab, n).astype(np.int32)


def wrap(ids):
    return Tensor._wrap(jnp.asarray(ids, jnp.int32))


def err(got, want):
    return float(jnp.abs(jnp.asarray(got) - jnp.asarray(want)).max())


def test_forward_and_module_are_the_reference():
    model, cfg, params = build()
    ids = ids_of(40)
    dims = ref_dims(cfg)
    h = ref.hidden(params, ids, dims)
    with paddle.no_grad():
        got, mgot = model.forward_mtp(wrap(ids[None]))
    assert err(got._value[0], ref.logits_of(params, h)) < TOL
    assert err(mgot._value[0], ref.module_logits(params, h, ids, dims)) < TOL


def test_tolerance_is_tight():
    """The same weights in bfloat16 miss `TOL` by orders of magnitude."""
    model, cfg, params = build()
    ids = ids_of(40)
    want = ref.forward(params, ids, ref_dims(cfg))
    for p in model.parameters():
        p._value = p._value.astype(jnp.bfloat16)
    with paddle.no_grad():
        got = model(wrap(ids[None]))._value[0]
    assert err(got.astype(jnp.float32), want) > 100 * TOL


def test_chunks_then_decode_through_the_paged_latent_cache():
    """Prefill in chunks at unaligned offsets, then decode steps and a
    two-position verify, model AND module through their paged pools
    (the module's rows one slot on) = the reference's full forward."""
    model, cfg, params = build()
    ids = ids_of(40)
    dims = ref_dims(cfg)
    h = ref.hidden(params, ids, dims)
    want, mwant = ref.logits_of(params, h), \
        ref.module_logits(params, h, ids, dims)
    n = cfg.num_layers
    caches = model.init_caches(1, block_size=BS, max_context=64)
    outs, mouts = [], []

    def feed(a, b, caches):
        hh, new = model.forward_hidden(wrap(ids[None, a:b]), caches)
        outs.append(model.head(hh)._value[0])
        # the module's view stands where the model's stood before
        z, new = model.draft_hidden(hh, jnp.asarray(ids[None, a + 1:b + 1]),
                                    list(new[:n]) + [caches[n]])
        mouts.append(model.head(z)._value[0])
        return new

    with paddle.no_grad():
        for a, b in ((0, 10), (10, 23), (23, 25)):
            caches = feed(a, b, caches)
        for t in range(25, 35):
            caches = feed(t, t + 1, caches)
        caches = feed(35, 37, caches)        # a verify's two positions
        caches = feed(37, 39, caches)
    assert err(jnp.concatenate(outs, 0), want[:39]) < TOL
    assert err(jnp.concatenate(mouts, 0), mwant[:39]) < TOL
    rows = np.asarray(caches[1].moe_rows)
    assert rows[0, 0].sum() > 0 and rows[1, 0].sum() > 0
    assert np.asarray(caches[0].moe_rows).sum() == 0     # the dense layer
    assert np.asarray(caches[n].moe_rows)[:, 0].sum() > 0   # the module's


@pytest.mark.parametrize("s,first", [(1, 0), (2, 0), (1, 1), (2, 1)])
def test_kernel_is_its_twin(s, first):
    """Ragged lengths (a partial block, whole blocks, a sequence whose
    only rows are the queries'), an idle slot, and a pool whose pad
    lanes are zero, as the engine's are."""
    rng = np.random.RandomState(s * 2 + first)
    B, nh, width, lanes, dc, nb = 5, 4, 20, 128, 16, 9
    pool = rng.randn(B * nb + 1, BS, lanes).astype(np.float32)
    pool[..., width:] = 0
    tables = (1 + np.arange(B * nb)).reshape(B, nb).astype(np.int32)
    tables[1] = 0
    lens = np.array([13, 0, 72, first + s, 40], np.int32)
    q = rng.randn(B, s, nh, width).astype(np.float32)
    args = [jnp.asarray(a) for a in (q, pool, tables, lens)]
    kw = dict(scale=0.25, d_latent=dc, first=first)
    got = pallas_latent.paged_latent_attention(*args, **kw)
    want = pallas_latent.paged_latent_attention_reference(*args, **kw)
    assert got.shape == (B, s, nh, dc) and got.dtype == jnp.float32
    assert err(got, want) < KTOL
    assert float(jnp.abs(got[1]).max()) == 0.0           # the idle slot
    assert err(pallas_latent.latent_chunk_attention(*args, **kw),
               want) < KTOL


def test_chunk_form_tiles_its_queries():
    rng = np.random.RandomState(7)
    B, nh, width, lanes, dc, nb, s = 2, 4, 20, 128, 16, 12, 70
    pool = rng.randn(B * nb + 1, BS, lanes).astype(np.float32)
    pool[..., width:] = 0
    tables = (1 + np.arange(B * nb)).reshape(B, nb).astype(np.int32)
    args = [jnp.asarray(a) for a in (
        rng.randn(B, s, nh, width).astype(np.float32), pool, tables,
        np.array([s + 11, s], np.int32))]
    kw = dict(scale=0.25, d_latent=dc, first=1)
    assert err(pallas_latent.latent_chunk_attention(*args, **kw),
               pallas_latent.paged_latent_attention_reference(*args, **kw)
               ) < KTOL


# --------------------------------------------------------------- the engine
_ENGINES = {}


def engine(kind, draft, **kw):
    """One engine a (weights, drafter) pair, shared by the cases."""
    key = (kind, draft, tuple(sorted(kw.items())))
    if key not in _ENGINES:
        model, cfg, params = build(kind, mtp_draft=draft)
        opts = dict(max_batch=3, max_context=96, block_size=BS,
                    prefill_chunk=16, pad_buckets=[8, 16],
                    prefix_cache=True)
        opts.update(kw)
        _ENGINES[key] = (ServingEngine(model, **opts), cfg, params)
    return _ENGINES[key]


def serve(eng, prompts, budgets, eos=None, sync=False):
    reqs = [Request(p, max_new_tokens=n, eos_token_id=eos)
            for p, n in zip(prompts, budgets)]
    for r in reqs:
        eng.add_request(r)
    if sync:
        while eng.step():
            pass
    else:
        eng.run()
    assert all(r.done for r in reqs)
    return reqs


SHARED = ids_of(2 * BS + 3, seed=5).tolist()      # two whole blocks + 3
CASES = {
    # prompts, budgets, synchronous loop (`step()`: no tick chained)
    "two_tokens_a_forward": ([ids_of(5, seed=1).tolist()], [9], True),
    "budget_ends_on_the_first_token": (
        [ids_of(5, seed=1).tolist()], [8], True),
    "budget_of_one_and_two": (
        [ids_of(7, seed=2).tolist(), ids_of(9, seed=3).tolist()], [1, 2],
        False),
    "kcap_mixed_budgets_in_one_batch": (
        [ids_of(5, seed=1).tolist(), ids_of(11, seed=4).tolist(),
         ids_of(6, seed=6).tolist()], [4, 11, 7], True),
    "chained_ticks": (
        [ids_of(5, seed=1).tolist(), ids_of(11, seed=4).tolist()], [9, 12],
        False),
    "a_chunked_prompt_interleaved": (
        [ids_of(6, seed=8).tolist(), ids_of(45, seed=9).tolist(),
         ids_of(4, seed=10).tolist(), ids_of(37, seed=11).tolist()],
        [14, 6, 12, 9], False),
    "prefix_hit_and_copy_on_write": (
        [SHARED + [7, 8, 9], SHARED + [9, 8, 7, 6], SHARED[:2 * BS],
         SHARED + [7, 8, 9]], [6, 7, 5, 8], True),
}
RUNS = [(c, k) for k in ("agreeing", "partly") for c in sorted(CASES)] + [
    ("a_chunked_prompt_interleaved", "random"),
    ("prefix_hit_and_copy_on_write", "random")]


@pytest.mark.parametrize("case,kind", RUNS)
def test_self_drafted_streams_are_lossless(case, kind):
    """Each stream equals the drafter-off engine's AND the reference's
    loop.  Run synchronously the forwards are the reference's too: the
    drafts, the accept flags and the accepted / drafted counts (a chained
    tick is launched on an upper bound of its slots' budgets, so it may
    take a forward more: there the log is held to the stream)."""
    prompts, budgets, sync = CASES[case]
    eng, cfg, params = engine(kind, True)
    plain, _, _ = engine(kind, False)
    before = dict(eng.stats()["spec"])
    got = serve(eng, prompts, budgets, sync=sync)
    want = serve(plain, prompts, budgets, sync=sync)
    dims = ref_dims(cfg)
    drafted = accepted = 0
    for g, w, p, n in zip(got, want, prompts, budgets):
        assert g.output_ids == w.output_ids
        r = ref.generate(params, p, n, dims)
        assert g.output_ids == r["tokens"]
        # exact accounting: tokens = the prefill's + forwards + second ones
        assert len(g.output_ids) == 1 + sum(c for _, _, c in g.draft_log)
        at = 1
        for d, a, c in g.draft_log:
            # an accepted draft IS the token emitted at its position
            assert a == (d == g.output_ids[at]) and c in (1, 1 + a)
            at += c
        if sync:
            assert [d for d, _, _ in g.draft_log] == r["drafts"]
            assert [a for _, a, _ in g.draft_log] == r["accepted"]
            assert [c for _, _, c in g.draft_log] == r["emitted"]
        drafted += len(r["drafts"])
        accepted += sum(r["accepted"])
    after = eng.stats()["spec"]
    assert after["draft"] == "mtp"
    if sync:
        assert after["drafted"] - before["drafted"] == drafted
        assert after["accepted"] - before["accepted"] == accepted
    else:
        assert after["drafted"] - before["drafted"] >= drafted
    if kind == "agreeing" and max(budgets) > 2:
        assert accepted == drafted > 0
    state = eng.cache_state()
    assert state["mtp"][-1].tolist() == [after["drafted"],
                                         after["accepted"]]
    assert state["mtp"][:-1].sum() == 0


def test_partial_agreement_accepts_some():
    eng, cfg, params = engine("partly", True)
    reqs = serve(eng, [ids_of(9, seed=s).tolist() for s in (1, 2, 3)],
                 [24, 24, 24])
    flags = [a for r in reqs for _, a, _ in r.draft_log]
    assert 0 < sum(flags) < len(flags)


@pytest.mark.parametrize("at", [1, 2, 3, 4])
def test_eos_in_either_position(at):
    """The EOS is the `at`-th token of a stream that the agreeing model
    serves two a forward: it lands on a forward's first token (the second
    is dropped) and on its second."""
    eng, cfg, params = engine("agreeing", True)
    plain, _, _ = engine("agreeing", False)
    prompt = ids_of(5, seed=1).tolist()
    free = serve(plain, [prompt], [10])[0].output_ids
    eos = free[at]
    if eos in free[:at]:
        pytest.skip("the stream repeats this token earlier")
    got = serve(eng, [prompt], [10], eos)[0]
    assert got.output_ids == free[:at + 1]
    assert got.output_ids == ref.generate(
        params, prompt, 10, ref_dims(cfg), eos_token_id=eos)["tokens"]


def test_chained_ticks_keep_the_state_on_the_device():
    """With the loop's overlap on, ticks chain (the next tick's lengths,
    last tokens and drafts come from the device); off, none does; the
    streams are the same."""
    eng, cfg, params = engine("partly", True)
    prompts = [ids_of(7, seed=s).tolist() for s in (21, 22)]
    chained = serve(eng, prompts, [20, 20])
    old = _flags.get_flag("serving_overlap")
    _flags.set_flags({"serving_overlap": False})
    try:
        plain = serve(eng, prompts, [20, 20])
    finally:
        _flags.set_flags({"serving_overlap": old})
    assert [r.output_ids for r in chained] == [r.output_ids for r in plain]
    # (the logs may differ in their tails: a chained tick's cap is an
    # upper bound's, `test_self_drafted_streams_are_lossless`)
    assert all(r.draft_log[:8] == q.draft_log[:8]
               for r, q in zip(chained, plain))


def test_sampled_requests_are_refused_with_the_reason():
    eng, _, _ = engine("random", True)
    with pytest.raises(ValueError, match="served greedy"):
        eng.add_request(Request([1, 2, 3], max_new_tokens=4, do_sample=True))


def test_unsupported_mechanisms_say_why():
    model, _, _ = build()
    for kw in ({"tp_degree": 2}, {"quant": "int8"}, {"spec_decode": True}):
        with pytest.raises(ValueError, match="glm4_moe_lite"):
            ServingEngine(model, max_batch=2, max_context=32, block_size=BS,
                          **kw)


def test_module_rows_behind_a_shared_prefix():
    """Two requests share a registered prefix of two blocks and differ
    from the first token after it.  Through the engine's public probe,
    as an admission does it (the last shared block copied, its last token
    recomputed): the model's and the MODULE's logits from that token on
    are the reference's, so the module's row in each request's first
    private slot was made of that request's own token."""
    eng, cfg, params = engine("random", True)
    dims = ref_dims(cfg)
    serve(eng, [SHARED + [1]], [1])                   # registers the prefix
    for tail in ([7, 8, 9, 10, 11], [9, 8, 7, 6, 5]):
        ids = np.asarray(SHARED[:2 * BS] + tail, np.int32)
        chain = eng.prefix.lookup(ids.tolist()).blocks
        assert len(chain) == 2
        fresh = eng.take_blocks(2)
        eng.copy_block(chain[1], fresh[0])
        table = np.zeros((1, eng.nb_per_seq), np.int32)
        table[0, :3] = [chain[0]] + fresh
        start = 2 * BS - 1
        n = len(ids) - 1 - start
        chunk = np.zeros((1, 8), np.int32)
        nxt = np.zeros((1, 8), np.int32)
        chunk[0, :n], nxt[0, :n] = ids[start:-1], ids[start + 1:]
        out = eng.probe(chunk, table, [start], chunk=True, next_ids=nxt)
        eng.give_blocks(fresh)
        h = ref.hidden(params, ids, dims)
        at = np.arange(start, start + n)
        assert err(out["logits"][:n], ref.logits_of(params, h[at])) < TOL
        assert err(out["draft_logits"][:n], ref.module_logits(
            params, h, ids, dims, positions=at)) < TOL
