"""The fused qkv projection of the GPT block (`GPTAttention.forward`).

q, k and v are column windows of the projection's `[b, s, 3H]` result, not
a reshape to `[b, s, 3, nh, hd]` unbound on its new axis: XLA:TPU folds
that reshape into the dot, as a windowed convolution whose kernel wants
the weight re-laid-out, and every launch then copied every layer's weight
(PERF.md §6, PR 35; `tests/test_tpu_aot_compile.py` holds the compiled
programs to it).  The two forms name the same values, element for
element, and the plain reference (`benchmark/reference/gpt_ref.py`) keeps
the reshape: the engine serves its tokens.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import (GPTAttention, GPTConfig, GPTForCausalLM,
                                   gpt3_tiny)
from paddle_tpu.models.kv_cache import StaticKVCache


class _Recorder(StaticKVCache):
    """A cache that keeps what the block hands it and attends to
    nothing."""

    def __init__(self):
        self.seen = None

    def update_and_attend(self, q, k, v):
        self.seen = (q, k, v)
        return self, q


@pytest.mark.parametrize("nh, hd", [(16, 128), (16, 64), (1, 128)])
@pytest.mark.parametrize("b, s", [(3, 1), (1, 8)])
def test_qkv_windows_equal_reshape_and_unbind(nh, hd, b, s):
    paddle.seed(nh * hd + s)
    attn = GPTAttention(GPTConfig(hidden_size=nh * hd, num_heads=nh,
                                  num_layers=1, vocab_size=32))
    attn.eval()
    x = paddle.to_tensor(np.random.RandomState(s).standard_normal(
        (b, s, nh * hd)).astype(np.float32))
    cache = _Recorder()
    with paddle.no_grad():
        attn(x, kv_cache=cache)
        fused = np.asarray(attn.qkv(x)._value)
    want = fused.reshape(b, s, 3, nh, hd)
    for i, got in enumerate(cache.seen):
        assert got.shape == (b, s, nh, hd)
        np.testing.assert_array_equal(np.asarray(got), want[:, :, i])


def test_engine_serves_the_tokens_of_the_reshape_form():
    """A tiny engine (chunked prefill, 4-step ticks, paged cache) serves,
    token for token, what greedy decoding by the plain reference gives,
    whose block reshapes to `[B, S, 3, nh, hd]` as the model used to."""
    from benchmark.reference import gpt_ref
    paddle.seed(0)
    cfg = gpt3_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    params = gpt_ref.from_state_dict(
        {k: v._value for k, v in model.state_dict().items()}, cfg.num_layers)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 1000, (n,)) for n in (12, 30, 7)]
    eng = ServingEngine(model, max_batch=3, max_context=128, block_size=16,
                        steps_per_tick=4, prefill_chunk=16)
    reqs = [eng.add_request(Request(p, max_new_tokens=9)) for p in prompts]
    eng.run()
    for req, prompt in zip(reqs, prompts):
        ids = [int(t) for t in prompt]
        for _ in range(req.max_new_tokens):
            last = gpt_ref.forward(params, np.asarray(ids, np.int32)[None],
                                   cfg.num_heads,
                                   positions=np.array([len(ids) - 1]))
            ids.append(int(np.argmax(np.asarray(last)[0, 0])))
        np.testing.assert_array_equal(req.output_ids, ids[len(prompt):])
