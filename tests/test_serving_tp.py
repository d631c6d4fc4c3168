"""Tensor-parallel serving decode (`inference/tp.py` + ServingEngine
tp_degree — ISSUE 9 tentpole).

Runs on the conftest's 8-virtual-device CPU mesh, the same simulated
world `test_eager_comm.py` uses: the shard_map programs here have the
identical jaxpr/HLO a real tp-degree pod slice runs, minus the
transport.  The acceptance contract is BIT-parity: the TP layout never
splits a contraction dimension (column-parallel weights + all-gather
re-replication), so degree 2 and 4 must reproduce degree 1's token
streams exactly — greedy and seeded-sampled alike.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import flag_guard
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import compile_tracker


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt3_tiny())
    m.eval()
    return m


def _prompts():
    rng = np.random.RandomState(0)
    return rng.randint(1, 1000, (12,)), rng.randint(1, 1000, (30,))


def _serve(model, tp, prefix=False):
    p1, p2 = _prompts()
    eng = ServingEngine(model, max_batch=3, max_context=128,
                        block_size=16, steps_per_tick=2, tp_degree=tp,
                        prefix_cache=prefix)
    reqs = [eng.add_request(Request(p1, max_new_tokens=8)),
            eng.add_request(Request(p2, max_new_tokens=6, do_sample=True,
                                    temperature=0.9, top_k=40, seed=77))]
    eng.run()
    return eng, [list(r.output_ids) for r in reqs]


@pytest.mark.slow   # 20.8s measured (PR 14 re-budget): compiles three
                    # TP program sets; bit-parity stays HARD-gated in
                    # the serving_tp bench rung and the @slow TP2
                    # composition pins
def test_tp_degree_2_and_4_bit_identical_to_degree_1(model):
    """THE acceptance test: the same mixed greedy+sampled workload at
    simulated TP degree 2 and 4 reproduces degree 1's streams token for
    token (greedy bit-identical; the sampled stream is drawn from the
    same replicated logits + request seed, so it is identical too)."""
    eng1, s1 = _serve(model, 1)
    eng2, s2 = _serve(model, 2)
    eng4, s4 = _serve(model, 4)
    assert s2 == s1
    assert s4 == s1
    assert eng1.stats()["tp_degree"] == 1
    assert eng2.stats()["tp_degree"] == 2
    assert eng4.stats()["tp_degree"] == 4
    # scheduler invariants hold identically across degrees
    for eng in (eng2, eng4):
        assert eng.stats()["free_blocks"] == eng.num_blocks
        assert eng.stats()["reserved"] == 0


def test_tp_weights_and_pools_are_sharded(model):
    """The memory story: each rank holds 1/tp of every sharded matrix
    and of every KV pool (head axis)."""
    eng = ServingEngine(model, max_batch=2, max_context=64,
                        block_size=16, tp_degree=2)
    qkv = eng._tp_params["blocks"][0]["qkv_w"]
    assert "tp" in str(qkv.sharding.spec)
    # per-device shard bytes = half the global array
    shard = qkv.addressable_shards[0].data
    assert shard.size * 2 == qkv.size
    kp, _ = eng.pools[0]
    pshard = kp.addressable_shards[0].data
    assert pshard.shape[0] * 2 == kp.shape[0]      # heads split
    assert pshard.shape[1:] == kp.shape[1:]
    # replicated scheduler inputs: ln params stay whole everywhere
    ln = eng._tp_params["blocks"][0]["ln1_w"]
    assert ln.addressable_shards[0].data.shape == ln.shape


@pytest.mark.slow   # 8.8s measured (PR 14 re-budget): TP warmup grid;
                    # the degree-1 zero-compile pins stay fast
def test_tp_warmup_grid_zero_postwarmup_compiles(model):
    """TP programs enumerate into the PR 7 warmup grid: after warmup()
    a TP engine serves traffic — including a prefix-cache hit and the
    CoW path — with ZERO compile-tracker events."""
    with flag_guard(serving_pad_buckets="16,32,64"):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16, steps_per_tick=1, tp_degree=2,
                            prefix_cache=True)
        info = eng.warmup()
        # tick k=1, host-sampling decode, 3 prefill + 3 suffix-prefill
        # buckets, the CoW copy
        assert info["programs"] == 9
        before = compile_tracker.total_compiles()
        rng = np.random.RandomState(5)
        sysp = list(rng.randint(1, 1000, (32,)))
        a = eng.add_request(Request(sysp + [7, 8], max_new_tokens=4))
        eng.run()
        b = eng.add_request(Request(sysp + [9], max_new_tokens=4))
        eng.run()
        c = eng.add_request(Request(sysp, max_new_tokens=4))  # CoW
        eng.run()
        assert compile_tracker.total_compiles() == before
        st = eng.stats()
        assert st["prefix_cache"]["hits"] == 2
        assert all(len(r.output_ids) == 4 for r in (a, b, c))


@pytest.mark.slow   # 7.0s measured (PR 14 re-budget): TP x prefix
                    # composition; covered by the @slow serving_tp
                    # schema gate (prefix_hit_speedup + parity)
def test_tp_prefix_hit_stream_matches_degree_1_miss(model):
    """Compose: a TP-degree-2 engine WITH prefix reuse serves the same
    tokens as a degree-1 engine WITHOUT it."""
    rng = np.random.RandomState(9)
    sysp = list(rng.randint(1, 1000, (32,)))
    prompt = sysp + [3, 1, 4]

    def serve(tp, prefix, warm_first):
        eng = ServingEngine(model, max_batch=2, max_context=128,
                            block_size=16, tp_degree=tp,
                            prefix_cache=prefix)
        if warm_first:   # make the second admission a genuine hit
            w = eng.add_request(Request(sysp + [9, 9], max_new_tokens=3))
            eng.run()
            assert w.done
        r = eng.add_request(Request(prompt, max_new_tokens=6))
        eng.run()
        if prefix:
            assert eng.stats()["prefix_cache"]["hits"] >= 1
            assert r._prefix_blocks == 2
        return list(r.output_ids)

    baseline = serve(1, False, False)
    assert serve(2, True, True) == baseline


def test_tp_validation_errors(model):
    with pytest.raises(ValueError, match="devices"):
        ServingEngine(model, max_batch=2, max_context=64, block_size=16,
                      tp_degree=16)
    with pytest.raises(ValueError, match="divide"):
        # gpt3_tiny has 4 heads: degree 3 cannot shard them
        ServingEngine(model, max_batch=2, max_context=64, block_size=16,
                      tp_degree=3)

    class NotAGPT:
        cfg = model.cfg

    with pytest.raises(ValueError, match="GPT-family"):
        from paddle_tpu.inference.tp import build_plan
        build_plan(NotAGPT(), 2)


@pytest.mark.slow  # 7s measured: constructs a second (tp) engine; plan-shape and flag-validation tests stay fast
def test_tp_flag_routes_engine_construction(model):
    with flag_guard(serving_tp_degree=2):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16)
    assert eng.tp == 2 and eng._tp_mesh is not None
    p1, _ = _prompts()
    r = eng.add_request(Request(p1, max_new_tokens=4))
    eng.run()
    assert r.done and len(r.output_ids) == 4


# ------------------------------------------------- derived specs (ISSUE 30)
# A program declares what each argument IS; the engine derives the
# shard_map specs and donate_argnums from that.  This table is the
# reference: the tuples as they were once counted by hand, a program.
# S = the plan's parameter spec tree, K = the pool spec (heads over
# 'tp'), R = replicated.  (in_specs, out_specs, donate_argnums)
def _spec_table(S):
    from jax.sharding import PartitionSpec as P
    K, R = P("tp"), P()
    plain = {
        "tick": ((S, K) + (R,) * 9, (R, K, R), (1,)),
        "decode": ((S, K) + (R,) * 3, (R, R, K), (1,)),
        "prefill": ((S, K, R, R, R), (R, K), (1,)),
        "prefill_cont": ((S, K) + (R,) * 4, (R, K), (1,)),
        "cow": ((K, R, R), K, (0,)),
    }
    ngram = dict(plain, spec_tick=(
        (S, K) + (R,) * 10, (R,) * 5 + (K,), (1,)))
    model = {
        "tick": plain["tick"], "decode": plain["decode"],
        "spec_tick": ((S, R, K, R) + (R,) * 9, (R,) * 5 + (K, R), (2, 3)),
        "prefill": ((S, R, K, R, R, R, R), (R, K, R), (2, 3)),
        "prefill_cont": ((S, R, K) + (R,) * 5, (R, K, R), (2, 3)),
        "cow": ((K, R, R, R), (K, R), (0, 1)),
    }
    return {"plain": plain, "spec_ngram": ngram, "spec_model": model}


@pytest.mark.parametrize("mode", ["plain", "spec_ngram", "spec_model"])
def test_declared_programs_derive_the_hand_counted_specs(
        model, mode, monkeypatch):
    """Every program of a TP-2 engine's grid: the in/out specs its
    shard_map gets and the donate_argnums its jit gets on a chip equal
    the table above, and its degree-1 twin donates the same positions."""
    import jax
    kw = {"plain": {},
          "spec_ngram": dict(spec_decode=True, spec_draft="ngram", spec_k=2),
          "spec_model": dict(spec_decode=True, spec_k=2, draft_model=model),
          }[mode]
    seen = {}
    real_shard_map, real_program = jax.shard_map, ServingEngine._program

    def shard_map(fn, **kw):
        seen["specs"] = (kw["in_specs"], kw["out_specs"])
        return real_shard_map(fn, **kw)

    def program(self, name, fn, donate, *blame):
        seen["donate"] = donate
        return real_program(self, name, fn, donate, *blame)

    monkeypatch.setattr(jax, "shard_map", shard_map)
    monkeypatch.setattr(ServingEngine, "_program", program)
    for tp in (2, 1):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16, steps_per_tick=2, tp_degree=tp,
                            pad_buckets="16", prefix_cache=True, **kw)
        table = _spec_table(eng._tp_specs)[mode]
        grid = eng._grid()
        assert len(grid) == {"plain": 6, "spec_ngram": 7,
                             "spec_model": 7}[mode]
        for build in grid:
            seen.clear()
            fn = build()
            in_specs, out_specs, donate = table[fn.decl.grid["program"]]
            assert seen["donate"] == fn.decl.donated == donate
            assert len(fn.decl.args) == len(in_specs)
            if tp > 1:
                assert seen["specs"] == (in_specs, out_specs)
            else:
                assert "specs" not in seen
