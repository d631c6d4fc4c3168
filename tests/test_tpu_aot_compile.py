"""AOT Mosaic gate: every default-on Pallas kernel must COMPILE for a
TPU v5e at the widths the repo ships, checked without a chip.

libtpu can compile for a v5e with no chip attached:
`topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")`
returns four `TPU v5 lite` devices that initialise no backend, and
`jax.jit(f).lower(*ShapeDtypeStructs sharded onto one of them).compile()`
runs XLA:TPU and Mosaic.  CPU CI runs these kernels interpreted, which
accepts programs Mosaic refuses (a bf16 shape cast, a 1024-row query tile
that does not fit VMEM, a one-row DMA that is not tile-aligned) — this
file is what catches those before a chip run does.

Shapes are `chip_smoke.py` Phase 3's: the GPT-3 124M widths (12 heads of
64) and the `gpt3_1p3b` head shape (16 heads of 128), bf16 and float32,
plus the chunk-prefill buckets 1024 and 2048 (the top of the pad ladder
at max_context 1024 / 2048).  Whether the compiled kernels are RIGHT is
the chip's to say (`chip_smoke.py` Phase 3).
"""

import concurrent.futures
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_common, pallas_flash, pallas_moe
from paddle_tpu.ops import pallas_paged as pp

BF16, F32 = jnp.bfloat16, jnp.float32
WIDTHS = ((12, 64), (16, 128))        # (heads, head_dim): 124M, 1.3B


@pytest.fixture(scope="module")
def v5e():
    """One device of a chipless v5e:2x2 topology.

    Not on a host that HAS chips: building the topology there loads
    libtpu into this process and takes its multi-process lock
    (`/tmp/libtpu_lockfile`) — measured on the v5e in PR 21: while the
    parent held a topology, a child's `jax.devices()` failed with
    "Unable to initialize backend 'tpu': ABORTED".  There the chip's own
    compiler is the check (`chip_smoke.py`)."""
    from jax.experimental import topologies

    from paddle_tpu.core.device import local_tpu_chips
    if local_tpu_chips():
        reason = ("this host has TPU chips: a chipless topology would "
                  "take libtpu's process lock; run chip_smoke.py here")
        print(f"SKIP test_tpu_aot_compile: {reason}")
        pytest.skip(reason)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - libtpu absent/unusable here
        reason = f"cannot build a v5e topology without a chip: {e!r}"
        print(f"SKIP test_tpu_aot_compile: {reason}")
        pytest.skip(reason)
    dev = topo.devices[0]
    assert dev.platform == "tpu" and "v5 lite" in dev.device_kind.lower()
    return dev


def _compile(dev, fn, *shapes):
    sh = SingleDeviceSharding(dev)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _flash_loss(causal, rate):
    def loss(q, k, v, mask=None, seed=None):
        out = pallas_flash.flash_attention(
            q, k, v, causal, False, mask, seed, None, rate)
        return jnp.sum(out.astype(F32))
    return jax.grad(loss, argnums=(0, 1, 2))


def _cases():
    """name -> (function, argument shapes): every default-on kernel at
    Phase 3's shapes.  bf16 pools are what a deployment serves (the
    engine takes the pool dtype from the parameters); the chunk cases
    are the engine's shape (B=1) at the top pad buckets, where the whole
    chunk as one query tile does not fit VMEM."""
    i32 = jnp.int32
    cases = {}
    for dt in (BF16, F32):
        n = jnp.dtype(dt).name
        for nh, hd, S in ((12, 64, 1024), (16, 128, 2048)):
            qkv = ((2, S, nh, hd), dt)
            cases[f"flash fwd+bwd causal nh{nh} hd{hd} S{S} {n}"] = (
                _flash_loss(True, 0.0), (qkv, qkv, qkv))
        for nh, hd in WIDTHS:
            B, bs, nb = 8, 64, 16
            pool = ((nh, B * nb + 1, bs, hd), dt)
            tl = (((B, nb), i32), ((B,), i32))
            cases[f"paged_attention nh{nh} hd{hd} {n}"] = (
                functools.partial(pp.paged_attention, interpret=False),
                (((B, nh, hd), dt), pool, pool) + tl)
            cases[f"paged_verify_attention k4 nh{nh} hd{hd} {n}"] = (
                functools.partial(pp.paged_verify_attention,
                                  interpret=False),
                (((B, 4, nh, hd), dt), pool, pool) + tl)
        for T, M, E, k in ((1024, 768, 8, 2), (1024, 2048, 64, 8)):
            C = int(T * k / E * 1.25)
            cases[f"moe_dispatch T{T} M{M} E{E} {n}"] = (
                functools.partial(pallas_moe.moe_dispatch, interpret=False),
                (((T, M), dt), ((E * C,), i32)))
            cases[f"moe_combine T{T} M{M} E{E} k{k} {n}"] = (
                functools.partial(pallas_moe.moe_combine, interpret=False),
                (((E * C, M), dt), ((T, k), F32), ((T, k), i32)))
    for s, nh, hd, dt in ((1024, 12, 64, BF16), (2048, 12, 64, BF16),
                          (1024, 16, 128, BF16), (2048, 16, 128, BF16),
                          (1024, 12, 64, F32), (2048, 16, 128, F32)):
        pool = ((nh, 129, 64, hd), dt)
        assert pp._chunk_q_tile(s, nh, hd) < s
        cases[f"paged_chunk_attention s{s} nh{nh} hd{hd} "
              f"{jnp.dtype(dt).name}"] = (
            functools.partial(pp.paged_chunk_attention, interpret=False),
            (((1, s, nh, hd), dt), pool, pool,
             ((1, s // 64), i32), ((1,), i32)))
    B, S, nh, hd = 2, 1024, 12, 64
    kv = ((B, S, nh // 4, hd), BF16)            # GQA: 3 kv heads for 12
    cases["flash fwd+bwd kv_mask dropout gqa bf16"] = (
        _flash_loss(False, 0.1),
        (((B, S, nh, hd), BF16), kv, kv, ((B, S), i32), ((), i32)))
    return cases


CASES = _cases()


@pytest.fixture(scope="module")
def compiled(v5e):
    """Every case compiled once, side by side: XLA:TPU and Mosaic release
    the GIL, so the file costs the slowest few compiles, not their sum
    (tier-1 has little room)."""
    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
        return {name: pool.submit(_compile, v5e, fn, *shapes)
                for name, (fn, shapes) in CASES.items()}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(compiled, name):
    compiled[name].result()       # re-raises the MosaicError, if any


def test_graft_entry_compiles_for_v5e(v5e, monkeypatch):
    """`__graft_entry__.entry()` — the forward the driver compile-checks
    on one chip — compiled here for `TPU v5 lite`, as the chip would
    build it: kernels through Mosaic, not the interpreter."""
    import __graft_entry__ as ge
    monkeypatch.setattr(pallas_common, "interpret_default", lambda: False)
    fwd, (vals, ids) = ge.entry()
    sh = SingleDeviceSharding(v5e)
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                          sharding=sh)
    lowered = jax.jit(fwd).lower([spec(v) for v in vals], spec(ids))
    assert "tpu_custom_call" in lowered.as_text()     # flash, S=512
    lowered.compile()


# ------------------------------------------------ names (ISSUE 26 §3)
# The per-layer metrics find a kernel's events in the chip's trace by the
# custom call's instruction name, and a program's launches by its module
# name: both are read off the v5e programs here, so a refactor that drops
# a name fails on the CPU and not in a chip run.

_FLASH = "flash fwd+bwd causal nh16 hd128 S2048 bfloat16"
KERNEL_CASES = {
    "flash_fwd": _FLASH, "flash_bwd_dq": _FLASH, "flash_bwd_dkv": _FLASH,
    "paged_decode": "paged_attention nh16 hd128 bfloat16",
    "paged_chunk_prefill": "paged_chunk_attention s1024 nh16 hd128 bfloat16",
    "paged_spec_verify": "paged_verify_attention k4 nh16 hd128 bfloat16",
}


def _custom_call_names(hlo_text: str) -> list:
    return [ln.split(" = ")[0].split()[-1]
            for ln in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


@pytest.mark.parametrize("kernel", sorted(KERNEL_CASES))
def test_custom_call_is_named_for_its_kernel(compiled, kernel):
    names = _custom_call_names(
        compiled[KERNEL_CASES[kernel]].result().as_text())
    assert names and any(kernel in n for n in names), names
    # ...and one kernel's name does not match another's pattern
    others = [k for k in KERNEL_CASES if k != kernel and kernel in k]
    assert not others


@pytest.fixture(scope="module")
def serve_modules(v5e):
    """The serving programs of a one-layer engine with the 1.3B head
    shape, lowered for the v5e with Mosaic kernels: `{compile-tracker
    name: lowered text}`."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    saved = pallas_common.interpret_default
    pallas_common.interpret_default = lambda: False
    try:
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=256, hidden_size=128, num_layers=1, num_heads=1,
            max_seq_len=256, intermediate_size=256))
        model.eval()
        model.bfloat16()
        eng = ServingEngine(model, max_batch=8, max_context=256,
                            block_size=64, steps_per_tick=4,
                            prefill_chunk=128)
        sh = SingleDeviceSharding(v5e)
        spec = lambda tree: jax.tree_util.tree_map(    # noqa: E731
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=sh), tree)
        B, nb = eng.B, eng.nb_per_seq
        i32 = lambda *s: np.zeros(s, np.int32)         # noqa: E731
        sched = (i32(B, nb), i32(B), i32(B))
        samp = (np.zeros((B,), np.bool_), np.ones((B,), np.float32), i32(B),
                np.ones((B,), np.float32), np.zeros((B,), np.uint32), i32(B))
        out = {}
        with eng._params_for_call() as params:
            for fn, args in (
                    (eng._tick_program(4),
                     (params, eng.pools) + sched + samp),
                    (eng._decode_program(), (params, eng.pools) + sched),
                    (eng._prefill_cont_program(128),
                     (params, eng.pools, i32(1, nb), i32(1, 128),
                      np.int32(1), np.int32(0))),
                    (eng._prefill_program(128),
                     (params, eng.pools, i32(1, nb), i32(1, 128),
                      np.int32(1))),
                    (eng._cow_program(),
                     (eng.pools, np.int32(0), np.int32(0)))):
                out[fn._compile_name] = fn.__wrapped__.lower(
                    *spec(args)).as_text()
        return out
    finally:
        pallas_common.interpret_default = saved


@pytest.mark.parametrize("name, kernel", [
    ("serving.tick", "paged_decode"), ("serving.decode", "paged_decode"),
    ("serving.prefill_cont", "paged_chunk_prefill"),
    ("serving.prefill", None), ("serving.cow", None)])
def test_serve_program_is_named_for_its_compile_tracker_entry(
        serve_modules, name, kernel):
    text = serve_modules[name]
    assert f"module @jit_{name.replace('.', '_')} " in text, text[:200]
    if kernel is not None:
        assert "tpu_custom_call" in text and kernel in text
