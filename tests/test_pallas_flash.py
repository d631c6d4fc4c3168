"""Pallas flash-attention kernels, run in interpreter mode on CPU.

Parity target: `phi/kernels/gpu/flash_attn_kernel.cu` (+ flash_attn_grad);
the reference tests compare against a plain softmax attention computed in
fp32 (`test/legacy_test/test_flash_attention.py` pattern).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_flash import (flash_attention,
                                         flash_attention_fwd, supported)


def ref_attn(q, k, v, causal, kv_mask=None):
    hd = q.shape[-1]
    if k.shape[2] != q.shape[2]:  # GQA: repeat kv heads
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(hd)
    Sq, Sk = q.shape[1], k.shape[1]
    if causal:
        # end-aligned: query i attends keys <= i + (Sk - Sq)
        mask = (jnp.arange(Sq)[:, None] + (Sk - Sq)
                >= jnp.arange(Sk)[None, :])
        s = jnp.where(mask, s, -jnp.inf)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] != 0, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows -> zeros
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _qkv(B, S, nh, hd, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, nh, hd).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _qkv(2, 128, 2, 64)
    out = flash_attention(q, k, v, causal, True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref_attn(q, k, v, causal)),
                               rtol=2e-5, atol=2e-5)


def test_forward_multiblock_causal():
    # S=256 with block 128 exercises the online-softmax accumulation and
    # the causal block-skip predicate
    q, k, v = _qkv(1, 256, 2, 64, seed=1)
    out = flash_attention(q, k, v, True, True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref_attn(q, k, v, True)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_reference(causal):
    q, k, v = _qkv(1, 256, 2, 64, seed=2)
    f = lambda q, k, v: jnp.sum(jnp.square(
        flash_attention(q, k, v, causal, True)))
    g = lambda q, k, v: jnp.sum(jnp.square(ref_attn(q, k, v, causal)))
    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_lse_is_logsumexp():
    q, k, v = _qkv(1, 128, 1, 64, seed=3)
    _, lse = flash_attention_fwd(q, k, v, False, True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64)
    want = jax.scipy.special.logsumexp(s, axis=-1)  # [B, nh, S]
    np.testing.assert_allclose(np.asarray(lse[..., 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_supported_gate():
    assert supported((2, 1024, 12, 64))
    assert supported((2, 128, 2, 128))
    assert not supported((2, 100, 2, 64))    # seq not block-divisible
    assert not supported((2, 128, 2, 80))    # head_dim not MXU-friendly
    assert not supported((2, 128, 64))       # wrong rank


def test_padding_mask_matches_reference():
    q, k, v = _qkv(2, 128, 2, 64, seed=5)
    rng = np.random.RandomState(5)
    kv_mask = jnp.asarray((rng.rand(2, 128) > 0.3).astype(np.int32))
    out = flash_attention(q, k, v, False, True, kv_mask, None,
                          (2, 128), 0.0)
    want = ref_attn(q, k, v, False, kv_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # grads through the masked kernel
    f = lambda q, k, v: jnp.sum(jnp.square(flash_attention(
        q, k, v, False, True, kv_mask, None, (2, 128), 0.0)))
    g = lambda q, k, v: jnp.sum(jnp.square(ref_attn(q, k, v, False,
                                                    kv_mask)))
    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_fully_masked_batch_row_is_zero():
    """A batch row whose keys are ALL padded must produce zeros (and not
    poison the online softmax with exp(-inf - -inf) = 1 garbage)."""
    q, k, v = _qkv(2, 128, 2, 64, seed=6)
    kv_mask = jnp.asarray(np.stack([np.ones(128), np.zeros(128)])
                          .astype(np.int32))
    out = flash_attention(q, k, v, False, True, kv_mask, None,
                          (2, 128), 0.0)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out[1]), 0.0, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(ref_attn(q, k, v, False)[0]),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_matches_repeated_reference(causal):
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 128, 4, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 128, 2, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 128, 2, 64).astype(np.float32))
    out = flash_attention(q, k, v, causal, True)
    want = ref_attn(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    f = lambda q, k, v: jnp.sum(jnp.square(
        flash_attention(q, k, v, causal, True)))
    g = lambda q, k, v: jnp.sum(jnp.square(ref_attn(q, k, v, causal)))
    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_cross_attention_end_aligned_causal():
    """Sq != Sk (cached decode chunk): query i sees keys <= i + Sk - Sq."""
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(1, 64, 2, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 256, 2, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 256, 2, 64).astype(np.float32))
    out = flash_attention(q, k, v, True, True)
    want = ref_attn(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # 7s measured (PR 18 re-budget): compiles the dropout kernel twice; the forward/backward/GQA parity pins stay fast
def test_dropout_deterministic_and_consistent():
    """In-kernel dropout: same seed reproduces; backward regenerates the
    forward's keep mask (autodiff grad == numerical grad of the SAME
    seeded function).  The interpret-mode TPU PRNG ignores seed VALUES
    (every block draws the same bits) but keeps fwd/bwd consistent —
    value sensitivity is exercised on real TPU hardware."""
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
    seed = jnp.int32(42)
    args = (False, True, None, seed, None, 0.2)
    out1 = flash_attention(q, k, v, *args)
    out2 = flash_attention(q, k, v, *args)
    assert bool(jnp.all(out1 == out2))
    out0 = flash_attention(q, k, v, False, True)
    assert not bool(jnp.all(out1 == out0))  # dropout actually applied
    f = lambda q: jnp.sum(jnp.square(flash_attention(q, k, v, *args)))
    g1 = jax.grad(f)(q)
    assert bool(jnp.isfinite(g1).all())
    eps = 2e-2
    idx = (0, 3, 1, 5)
    num = (f(q.at[idx].add(eps)) - f(q.at[idx].add(-eps))) / (2 * eps)
    np.testing.assert_allclose(float(g1[idx]), float(num),
                               rtol=0.1, atol=1e-3)


def test_supported_gqa_gate():
    assert supported((2, 128, 4, 64), (2, 128, 2, 64))
    assert supported((2, 64, 4, 64), (2, 256, 4, 64))   # cross lengths
    assert not supported((2, 128, 4, 64), (2, 128, 3, 64))  # nh % nkv
    assert not supported((2, 128, 4, 64), (2, 100, 4, 64))  # Sk not tiled
    assert not supported((2, 128, 4, 64), (2, 128, 4, 128))  # hd mismatch


def test_eager_dispatch_and_tape(monkeypatch):
    """The dispatched op differentiates through the kernel's custom VJP."""
    import paddle_tpu as paddle
    from paddle_tpu.ops import pallas_kernels as pk
    # force the kernel path on CPU (it runs interpreted there)
    monkeypatch.setattr(pk, "flash_attention_available",
                        lambda *a, **kw: True)
    q, k, v = _qkv(1, 128, 2, 64, seed=4)
    tq = paddle.Tensor._wrap(q, stop_gradient=False)
    tk = paddle.Tensor._wrap(k, stop_gradient=False)
    tv = paddle.Tensor._wrap(v, stop_gradient=False)
    out = pk.flash_attention(tq, tk, tv, causal=True)
    out.sum().backward()
    assert tq.grad is not None and tk.grad is not None
    ref = lambda q, k, v: jnp.sum(ref_attn(q, k, v, True))
    want = jax.grad(ref, argnums=(0,))(q, k, v)[0]
    np.testing.assert_allclose(np.asarray(tq.grad._value),
                               np.asarray(want), rtol=2e-4, atol=2e-4)


def test_sdpa_routes_padding_mask_to_kernel(monkeypatch):
    """A BERT-style [B, 1, 1, S] boolean keep-mask must reach the Pallas
    kernel as its kv_mask (not force the XLA fallback), and match XLA."""
    import paddle_tpu as paddle
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "flash_attention_available",
                        lambda *a, **kw: True)
    q, k, v = _qkv(2, 128, 2, 64, seed=10)
    rng = np.random.RandomState(10)
    keep = (rng.rand(2, 128) > 0.25)
    mask4 = paddle.Tensor._wrap(jnp.asarray(keep)[:, None, None, :])
    tq, tk, tv = (paddle.Tensor._wrap(x) for x in (q, k, v))
    calls = []
    orig = pk.flash_attention
    monkeypatch.setattr(
        pk, "flash_attention",
        lambda *a, **kw: calls.append(kw) or orig(*a, **kw))
    out = F.scaled_dot_product_attention(tq, tk, tv, attn_mask=mask4,
                                         training=False)
    assert calls and calls[0]["kv_mask"] is not None
    want = ref_attn(q, k, v, False, jnp.asarray(keep.astype(np.int32)))
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
