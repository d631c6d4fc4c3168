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
import threading

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import (pallas_common, pallas_dsa, pallas_flash,
                            pallas_latent,
                            pallas_moe, sparse_mla)
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


def _mosaic(fn):
    """`fn` traced with every kernel it reaches compiled by Mosaic (the
    wrappers ask `interpret_default()`, which says "interpret" on this
    sandbox's CPU backend)."""
    @functools.wraps(fn)
    def traced(*args):
        # one trace at a time: `compiled` lowers its cases on threads, and
        # two swaps that interleave would leave the lambda behind
        with _MOSAIC_LOCK:
            saved = pallas_common.interpret_default
            pallas_common.interpret_default = lambda: False
            try:
                return fn(*args)
            finally:
                pallas_common.interpret_default = saved
    return traced


_MOSAIC_LOCK = threading.Lock()


_CELL_STEP = "paged_decode_step cell B16 nb24 nh16 hd128 bfloat16"
_DSA_DECODE = "sparse_latent_attention decode B16 ctx32k bf16"
_DSA_CHUNK = "sparse_latent_attention chunk s512 ctx32k bf16"


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
        if dt is BF16:
            # the train cell's heads (16 x 64) and the widest head
            # `supported` admits, at the cell's sequence
            for nh, hd in ((16, 64), (8, 256)):
                qkv = ((1, 2048, nh, hd), dt)
                cases[f"flash fwd+bwd causal nh{nh} hd{hd} S2048 {n}"] = (
                    _flash_loss(True, 0.0), (qkv, qkv, qkv))
        for nh, hd in WIDTHS:
            B, bs, nb = 8, 64, 16
            pool = ((nh, B * nb + 1, bs, hd), dt)
            tl = (((B, nb), i32), ((B,), i32))
            cases[f"paged_attention nh{nh} hd{hd} {n}"] = (
                functools.partial(pp.paged_attention, interpret=False),
                (((B, nh, hd), dt), pool, pool) + tl)
            cases[f"paged_decode_step nh{nh} hd{hd} {n}"] = (
                functools.partial(pp.paged_decode_step, interpret=False),
                (((B, nh, hd), dt),) * 3 + (pool, pool) + tl)
            cases[f"paged_verify_attention k4 nh{nh} hd{hd} {n}"] = (
                functools.partial(pp.paged_verify_attention,
                                  interpret=False),
                (((B, 4, nh, hd), dt), pool, pool) + tl)
        if dt is BF16:
            # the 1.3B serve cell's own decode step: batch 16 over a table
            # 24 wide, 384 blocks of 64 + the pad block, the row stored
            B, nb = 16, 24
            pool = ((16, B * nb + 1, 64, 128), dt)
            cases[_CELL_STEP] = (
                functools.partial(pp.paged_decode_step, interpret=False),
                (((B, 16, 128), dt),) * 3 + (pool, pool)
                + (((B, nb), i32), ((B,), i32)))
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
    # GLM-5's expert layer at its published widths: a decode step's rows
    # (16 slots x 8 choices) and a 512-token chunk's, over 16 held of 256
    # (257 groups: the rows of idle slots sort into one that no chip holds)
    for rows in (128, 4096):
        for k, n in ((6144, 2048), (2048, 6144)):
            cases[f"moe_grouped_matmul m{rows} k{k} n{n} bf16"] = (
                functools.partial(pallas_moe.grouped_matmul,
                                  group_offset=80, interpret=False),
                (((rows, k), BF16), ((16, k, n), BF16), ((257,), i32)))
    # ... and its sparse selection inside paged attention: a decode step
    # over 16 contexts of 32k through both pools (the indexer's scores by
    # the `dsa_index_scores` kernel, the rest plain XLA), and the kernel
    nb = 512
    cases["dsa_index_scores B16 ctx32k bf16"] = (
        functools.partial(pallas_dsa.index_scores_decode, interpret=False),
        (((16, 1, 32, 128), BF16), ((16, 1, 32), BF16),
         ((6145, 64, 128), BF16), ((16, nb), i32), ((16, 1), i32)))
    cases[_DSA_DECODE] = (
        _mosaic(functools.partial(sparse_mla.sparse_latent_attention,
                                  topk=2048, scale=1 / 16, d_latent=512)),
        (((16, 1, 64, 576), BF16), ((16, 1, 32, 128), BF16),
         ((16, 1, 32), BF16), ((6145, 64, 640), BF16),
         ((6145, 64, 128), BF16), ((16, nb), i32), ((16, 1), i32)))
    # ... and a 512-token chunk of one sequence at the same widths: every
    # tile of 32 queries x 64 heads walks the sequence's blocks under the
    # selection's mask (`paged_latent_chunk`; the scores in plain XLA)
    cases[_DSA_CHUNK] = (
        _mosaic(functools.partial(sparse_mla.sparse_latent_attention,
                                  topk=2048, scale=1 / 16, d_latent=512)),
        (((1, 512, 64, 576), BF16), ((1, 512, 32, 128), BF16),
         ((1, 512, 32), BF16), ((6145, 64, 640), BF16),
         ((6145, 64, 128), BF16), ((1, nb), i32), ((1, 512), i32)))
    # SDAR-30B-A3B's block tick and chunk at its published head shapes:
    # 32 query heads over kv-head pools of 4 x 128 (nothing repeats K or
    # V), the mask full inside blocks of 4; a tick's block of 4 positions
    # for 32 slots over a table 64 wide, and a 512-token chunk
    pool = ((4, 2561, 64, 128), BF16)
    for B, s in ((32, 4), (1, 512)):
        cases[f"paged_chunk_attention gqa8 L4 B{B} s{s} bf16"] = (
            functools.partial(pp.paged_chunk_attention, interpret=False,
                              mask_block=4),
            (((B, s, 32, 128), BF16), pool, pool,
             ((B, 64), i32), ((B,), i32)))
    cases["paged_write_chunk kv4 L4 B32 s4 bf16"] = (
        functools.partial(pp.paged_write_chunk, align=4),
        (pool, pool, ((32, 64), i32), ((32,), i32),
         ((32, 4, 4, 128), BF16), ((32, 4, 4, 128), BF16)))
    # ... and its expert layer: all 128 experts held, a tick's rows (32
    # slots x 4 positions x 8 choices) and a 512-token chunk's
    for rows in (1024, 4096):
        for k, n in ((2048, 768), (768, 2048)):
            cases[f"moe_grouped_matmul e128 m{rows} k{k} n{n} bf16"] = (
                functools.partial(pallas_moe.grouped_matmul,
                                  group_offset=0, interpret=False),
                (((rows, k), BF16), ((128, k, n), BF16), ((129,), i32)))
    # GLM-4.7-Flash's dense latent attention at its published shape: 20
    # heads over 640-lane rows, batch 24 over a table 288 wide (a context
    # of 18,432), a decode step's one query a slot and a self-drafted
    # verify's two (the module's cache attends from slot 1); a 512-token
    # chunk in the XLA form; and its expert layer, all 64 experts held:
    # a verify's rows (24 slots x 2 positions x 4 choices) and a chunk's
    pool = ((4097, 64, 640), BF16)
    for s_q in (1, 2):
        cases[f"paged_latent_attention B24 s{s_q} nh20 ctx18k bf16"] = (
            functools.partial(pallas_latent.paged_latent_attention,
                              scale=1 / 16, d_latent=512, first=s_q - 1,
                              interpret=False),
            (((24, s_q, 20, 576), BF16), pool, ((24, 288), i32),
             ((24,), i32)))
    cases["latent_chunk_attention s512 nh20 ctx18k bf16"] = (
        functools.partial(pallas_latent.latent_chunk_attention,
                          scale=1 / 16, d_latent=512),
        (((1, 512, 20, 576), BF16), pool, ((1, 288), i32), ((1,), i32)))
    for rows in (192, 2048):
        for k, n in ((2048, 1536), (1536, 2048)):
            cases[f"moe_grouped_matmul e64 m{rows} k{k} n{n} bf16"] = (
                functools.partial(pallas_moe.grouped_matmul,
                                  group_offset=0, interpret=False),
                (((rows, k), BF16), ((64, k, n), BF16), ((65,), i32)))
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


@pytest.mark.parametrize("case, fetches_rows", [(_DSA_DECODE, True),
                                                (_DSA_CHUNK, False)])
def test_only_a_decode_step_gathers_single_latent_rows(compiled, case,
                                                       fetches_rows):
    """A gathered row costs the v5e 27 ns whatever its bytes (PERF.md
    section 6, PR 38): a decode step's 2,048 a query are worth it, a
    chunk's 512 x 2,048 a layer are not, so the chunk program copies the
    sequence's blocks whole and holds no gather of one 640-lane row."""
    rows = [ln for ln in compiled[case].result().as_text().splitlines()
            if " gather(" in ln and "slice_sizes={1,640}" in ln]
    assert bool(rows) == fetches_rows, rows


# ------------------------------------------------ names (ISSUE 26 §3)
# The per-layer metrics find a kernel's events in the chip's trace by the
# custom call's instruction name, and a program's launches by its module
# name: both are read off the v5e programs here, so a refactor that drops
# a name fails on the CPU and not in a chip run.

_FLASH = "flash fwd+bwd causal nh16 hd128 S2048 bfloat16"
_FLASH64 = "flash fwd+bwd causal nh16 hd64 S2048 bfloat16"   # the train cell
KERNEL_CASES = {
    "flash_fwd": _FLASH, "flash_bwd_dq": _FLASH, "flash_bwd_dkv": _FLASH,
    "paged_decode": "paged_attention nh16 hd128 bfloat16",
    "paged_chunk_prefill": "paged_chunk_attention s1024 nh16 hd128 bfloat16",
    "paged_spec_verify": "paged_verify_attention k4 nh16 hd128 bfloat16",
    "dsa_index_scores": "dsa_index_scores B16 ctx32k bf16",
    "paged_latent_attention":
        "paged_latent_attention B24 s2 nh20 ctx18k bf16",
    "paged_latent_chunk": _DSA_CHUNK,
}


def _custom_call_names(hlo_text: str) -> list:
    return [ln.split(" = ")[0].split()[-1]
            for ln in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


@pytest.mark.parametrize("kernel,case", sorted(
    list(KERNEL_CASES.items())
    + [(k, _FLASH64) for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")]
    # the writing variant at the cell's shape (the kernel copies the
    # blocks itself) and at heads of 64 (it may not: BlockSpecs)
    + [("paged_decode", _CELL_STEP),
       ("paged_decode", "paged_decode_step nh12 hd64 bfloat16")]))
def test_custom_call_is_named_for_its_kernel(compiled, kernel, case):
    names = _custom_call_names(compiled[case].result().as_text())
    assert names and any(kernel in n for n in names), names
    # ...and one kernel's name does not match another's pattern
    others = [k for k in KERNEL_CASES if k != kernel and kernel in k]
    assert not others


def _lower_engine_programs(v5e, chunk, max_batch=8, **cfg):
    """The programs of a bf16 `ServingEngine` over a small GPT, lowered
    for the v5e with Mosaic kernels: `(pool shape, {label: (compile-tracker
    name, Lowered)})`.  `_program` drops donation on this sandbox's CPU
    backend, so each program's own Python body is jitted again with the
    donation the chip gets."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    sh = SingleDeviceSharding(v5e)
    spec = lambda tree: jax.tree_util.tree_map(        # noqa: E731
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sh),
        tree)
    saved = pallas_common.interpret_default
    pallas_common.interpret_default = lambda: False
    try:
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            **{"vocab_size": 256, "intermediate_size": 256, **cfg}))
        model.eval()
        model.bfloat16()
        eng = ServingEngine(model, max_batch=max_batch,
                            max_context=cfg["max_seq_len"], block_size=64,
                            steps_per_tick=4, prefill_chunk=chunk)
        out = {}
        with eng._params_for_call() as params:
            # each program's arguments and donated positions are its own
            # declaration's: nothing here restates a signature
            for label, fn in (
                    ("tick k1", eng._tick_program(1)),
                    ("tick k4", eng._tick_program(4)),
                    ("decode", eng._decode_program()),
                    ("prefill_cont", eng._prefill_cont_program(chunk)),
                    ("prefill", eng._prefill_program(chunk)),
                    ("cow", eng._cow_program())):
                body = fn.__wrapped__.__wrapped__
                out[label] = (fn._compile_name, jax.jit(
                    body, donate_argnums=fn.decl.donated).lower(
                        *spec(eng._inert_args(fn.decl, params))))
        return eng.pools[0][0].shape, out
    finally:
        pallas_common.interpret_default = saved


@pytest.fixture(scope="module")
def serve_modules(v5e):
    """The serving programs of a one-layer engine with the 1.3B head
    shape, lowered for the v5e with Mosaic kernels: `{compile-tracker
    name: lowered text}`."""
    _, lowered = _lower_engine_programs(
        v5e, 128, hidden_size=128, num_layers=1, num_heads=1,
        max_seq_len=256)
    return {name: low.as_text() for name, low in lowered.values()}


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


# ------------------------------------- pools stay put (ISSUE 27)
# XLA:TPU gives a scatter's operand the layout that makes the scattered
# dims major; the paged kernels pin the default one.  While the pool
# writes were scatters, every serving program re-laid-out every pool on
# each side of each write: 2 copies of every pool a launch and one a scan
# step, 70% of the 1.3B serve cell's device time.  The engine's own
# programs on a two-layer model at the cell's pool geometry (batch 16,
# context 1536, 384 blocks of 64 + the pad block, bf16, pools donated) are
# compiled here and their HLO read: no instruction that copies, scatters
# or transposes a whole pool, and less temp memory than one pool.

POOL_PROGRAMS = ("tick k1", "tick k4", "prefill_cont", "prefill", "cow")


@pytest.fixture(scope="module")
def pool_programs(v5e):
    """`{(heads, head_dim, program): (compiled, pool shape)}`, the chunk
    program's start traced."""
    lowered = {}
    for nh, hd in WIDTHS:
        shape, progs = _lower_engine_programs(
            v5e, 256, max_batch=16, hidden_size=nh * hd, num_layers=2,
            num_heads=nh, max_seq_len=1536)
        assert shape == (nh, 385, 64, hd)
        lowered.update({(nh, hd, label): (progs[label][1], shape)
                        for label in POOL_PROGRAMS})
    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
        futs = {key: (ex.submit(low.compile), shape)
                for key, (low, shape) in lowered.items()}
    return {key: (f.result(), shape) for key, (f, shape) in futs.items()}


def _relayouts(hlo_text: str, shapes) -> list:
    """(computation, instruction line) of every `copy`, `scatter` or
    `transpose` whose result has one of `shapes` (bf16)."""
    import re
    dims = "|".join(",".join(str(d) for d in shape) for shape in shapes)
    op = re.compile(rf"= bf16\[(?:{dims})\]\S* (copy|scatter|transpose)\(")
    out, comp = [], None
    for ln in hlo_text.splitlines():
        if ln.endswith("{") and not ln.startswith(" "):
            comp = "ENTRY" if ln.startswith("ENTRY") else ln.split()[0]
        elif op.search(ln):
            out.append((comp, ln.strip()[:160]))
    return out


@pytest.mark.parametrize("program", POOL_PROGRAMS)
@pytest.mark.parametrize("nh, hd", WIDTHS)
def test_serving_program_leaves_the_pools_in_place(pool_programs, nh, hd,
                                                   program):
    compiled, shape = pool_programs[nh, hd, program]
    text = compiled.as_text()
    if program not in ("cow", "prefill"):
        assert "tpu_custom_call" in text         # the kernel reads the pool
    hits = _relayouts(text, [shape])
    assert not [h for h in hits if " copy(" not in h[1]], hits
    dims = ",".join(str(d) for d in shape)
    row_major = f"bf16[{dims}]{{3,2,1,0:" in text.splitlines()[0]
    if row_major:
        # 16 heads of 128: the chip hands the program its pools in the
        # kernels' layout, and nothing moves them
        assert not hits, hits
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 2 * nh * 385 * 64 * hd, temp
    else:
        # heads of 64: the v5e's own layout for such an array is
        # {1,3,2,0} (64 lanes would waste half a tile), so a program that
        # calls a kernel copies each pool on the way in (twice where the
        # chunk write's loop comes first and wants a third layout) and
        # once on the way out (PERF.md §7) - never inside a loop, and none
        # where no kernel runs
        assert all(comp == "ENTRY" for comp, _ in hits), hits
        assert len(hits) <= 3 * 4, hits           # 2 layers x (k, v)
        if "tpu_custom_call" not in text:
            assert not hits, hits


@pytest.mark.parametrize("program", ["tick k1", "tick k4"])
@pytest.mark.parametrize("nh, hd", WIDTHS)
def test_tick_holds_one_paged_decode_a_layer(pool_programs, nh, hd, program):
    """The serve job holds every kernel claim of `serving.tick*` to
    `paged_decode`: the two-layer tick has two Mosaic custom calls, both
    of that name - the row's store is inside them, not a kernel beside."""
    compiled, _ = pool_programs[nh, hd, program]
    names = _custom_call_names(compiled.as_text())
    assert len(names) == 2 and all("paged_decode" in n for n in names), names


# ------------------------------------- weights stay put (ISSUE 35)
# XLA:TPU folds a reshape of a fused projection's `[b, s, 3H]` result to
# `[b, s, 3, nh, hd]` into the dot, as a `bf01_01oi->b01f` convolution
# with a 3 x nh window whose kernel operand wants the weight in the other
# layout: every launch of every serving program then copies each layer's
# `[H, 3H]` weight first (25 MB a layer at the 1.3B serve cell's widths:
# 1.7 ms a launch, a tenth of the cell's device time, PERF.md §6, PR 35).
# The engine's programs on a two-layer model at the cell's real widths
# are compiled here and their HLO read: no `copy` or `transpose` whose
# result has the shape of a weight, either way round.

WEIGHT_PROGRAMS = ("tick k1", "tick k4", "prefill_cont", "prefill")


@pytest.fixture(scope="module")
def cell_programs(v5e):
    """`{program: compiled}` at `serve-1p3b-chat`'s widths: hidden 2048,
    16 heads of 128, FFN 8192, batch 16, context 1536, chunk 256; the
    vocabulary is 512 so that no activation has the embedding's shape."""
    _, progs = _lower_engine_programs(
        v5e, 256, max_batch=16, hidden_size=2048, num_layers=2,
        num_heads=16, max_seq_len=1536, intermediate_size=8192,
        vocab_size=512)
    with concurrent.futures.ThreadPoolExecutor(max_workers=4) as ex:
        futs = {label: ex.submit(progs[label][1].compile)
                for label in WEIGHT_PROGRAMS}
    return {label: f.result() for label, f in futs.items()}


@pytest.mark.parametrize("program", WEIGHT_PROGRAMS)
def test_serving_program_leaves_the_weights_in_place(cell_programs,
                                                     program):
    compiled = cell_programs[program]
    # a weight is a 2-D bf16 argument of the program
    weights = {tuple(a.shape)
               for a in jax.tree_util.tree_leaves(compiled.args_info)
               if len(a.shape) == 2 and a.dtype == BF16}
    assert {(2048, 6144), (2048, 8192), (8192, 2048)} <= weights, weights
    text = compiled.as_text()
    hits = _relayouts(text, weights | {w[::-1] for w in weights})
    assert not hits, hits
    # ...and the fused projection is a matmul over the parameter as it
    # lies, not a windowed convolution
    assert "dim_labels=bf01_01oi->b01f" not in text


def test_copy_metric_reads_the_opcode_copy_and_no_other(pool_programs):
    """`copy_time_pct.serve` (a data-only metric: a pattern for the
    accepted `xplane:matching_time_pct`) finds a re-layout by the
    instruction's text, which is what the chip's trace names an op by:
    held here to XLA:TPU's own output (the 12 x 64 tick copies its pools
    at the program's edges, above) and to the recorded train trace, where
    it must read what the breakdown's `copy` class reads."""
    import json
    import os
    import re

    from benchmark.reducers import xplane
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "copy_time_pct.serve.json")) as f:
        lm = json.load(f)
    rx = re.compile(lm["args"]["pattern"])
    compiled, shape = pool_programs[12, 64, "tick k4"]
    text = compiled.as_text()
    assert _relayouts(text, [shape])
    kinds = set()
    for ln in text.splitlines():
        ln = ln.strip()
        if ln.startswith("%") and " = " in ln:
            kind = xplane.op_kind(ln)
            kinds.add(kind)
            assert bool(rx.search(ln)) == (kind == "copy"), ln
    assert {"copy", "copy-start", "copy-done", "fusion"} <= kinds, kinds
    trace = xplane.load(os.path.join(
        root, "tests", "benchmark", "data", "train_350m_3steps.xplane.pb.gz"))
    got = xplane.matching_time_pct(trace, {}, lm["args"])
    want = 100.0 * dict(xplane.top_ops(trace, limit=100))["copy"] \
        / xplane.busy_s(trace)
    assert 0 < got < 100 and abs(got - want) < 1e-6, (got, want)
