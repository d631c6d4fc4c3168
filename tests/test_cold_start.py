"""Cold-start subsystem (ISSUE 7): persistent compilation cache
(`core/compile_cache.py`), the serving pad-bucket ladder, and
`ServingEngine.warmup()`.

The acceptance story: a warm restart reads executables from
FLAGS_compilation_cache_dir instead of recompiling, and a warmed
serving engine triggers ZERO compile-tracker events once traffic runs —
every program the engine can dispatch was enumerated from the ONE
pad-bucket ladder and compiled up front.
"""

import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import compile_cache
from paddle_tpu.flags import flag_guard
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import compile_tracker
from paddle_tpu.observability import metrics as obs_metrics


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt3_tiny())
    m.eval()
    return m


# ------------------------------------------------------ persistent cache

def test_flag_applies_and_detaches_cache_dir(tmp_path):
    """FLAGS_compilation_cache_dir drives jax_compilation_cache_dir via
    the on_change hook, and restoring the flag detaches it again."""
    d = str(tmp_path / "cache")
    assert not compile_cache.is_enabled()
    with flag_guard(compilation_cache_dir=d):
        assert compile_cache.is_enabled()
        applied = compile_cache.active_dir()
        assert applied == os.path.abspath(d) and os.path.isdir(applied)
        assert jax.config.jax_compilation_cache_dir == applied
    assert not compile_cache.is_enabled()
    assert jax.config.jax_compilation_cache_dir is None


def test_enable_flag_gates_the_dir(tmp_path):
    """FLAGS_enable_compilation_cache=0 keeps the dir flag inert."""
    with flag_guard(enable_compilation_cache=False,
                    compilation_cache_dir=str(tmp_path / "c2")):
        assert not compile_cache.is_enabled()
        assert jax.config.jax_compilation_cache_dir is None
    assert not compile_cache.is_enabled()


def test_cache_hits_misses_counters_report_and_prometheus(tmp_path):
    """A fresh dir takes misses, a cleared in-process cache then HITS
    the persistent entries; both are visible as registry counters, in
    the Prometheus rendering (compile_cache_{hits,misses}_total), and in
    compile_report()['persistent_cache'] with a hit ratio."""
    from paddle_tpu.observability.export import render_prometheus
    hits = obs_metrics.get("compile.cache_hits_total")
    misses = obs_metrics.get("compile.cache_misses_total")
    h0, m0 = hits.total(), misses.total()
    with flag_guard(compilation_cache_dir=str(tmp_path / "c3")):
        x = paddle.to_tensor(np.ones((37, 41), np.float32))
        np.asarray((x @ x.T).sum()._value)
        assert misses.total() > m0          # fresh dir: compiles missed
        rep = compile_cache.cache_report()
        assert rep["enabled"] and rep["entries"] > 0 and rep["bytes"] > 0
        jax.clear_caches()                  # drop in-process executables
        np.asarray((x @ x.T).sum()._value)
        assert hits.total() > h0            # ...and reload from disk
        rep = compile_cache.cache_report()
        assert rep["hits"] > 0 and 0.0 < rep["hit_ratio"] <= 1.0
        text = render_prometheus()
        assert "compile_cache_hits_total" in text
        assert "compile_cache_misses_total" in text
    full = compile_tracker.compile_report()
    assert "persistent_cache" in full
    assert set(full["persistent_cache"]) >= {
        "enabled", "dir", "hits", "misses", "hit_ratio", "entries",
        "bytes"}


def test_autotune_kernel_enable_routes_through_compile_cache(tmp_path):
    """ISSUE 7 satellite: incubate.autotune no longer owns a private
    hard-coded cache dir — kernel.enable applies the flag-configured
    dir through core/compile_cache and reports it in get_config()."""
    from paddle_tpu.incubate import autotune
    d = str(tmp_path / "tune")
    with flag_guard(compilation_cache_dir=d):
        autotune.set_config({"kernel": {"enable": True}})
        cfg = autotune.get_config()
        assert cfg["kernel"]["cache_dir"] == os.path.abspath(d)
        assert jax.config.jax_compilation_cache_dir == os.path.abspath(d)
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_dir_rule_env_beats_flag_beats_checkout_default(
        tmp_path, monkeypatch):
    """The one rule of core/compile_cache.py: JAX_COMPILATION_CACHE_DIR,
    as given, even with the flag set; else the flag; else the fixed
    `<checkout>/.jax_cache`.  The report and the hit/miss listeners are
    live whichever chose the directory."""
    env_dir, flag_dir = str(tmp_path / "from_env"), str(tmp_path / "flag")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.resolve_dir() == compile_cache.DEFAULT_DIR
    misses = obs_metrics.get("compile.cache_misses_total")
    try:
        with flag_guard(compilation_cache_dir=flag_dir):
            assert compile_cache.active_dir() == flag_dir
            monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
            assert compile_cache.resolve_dir() == env_dir
            assert compile_cache.configure() == env_dir
            assert jax.config.jax_compilation_cache_dir == env_dir
            m0 = misses.total()
            x = paddle.to_tensor(np.ones((29, 31), np.float32))
            np.asarray((x @ x.T).sum()._value)
            rep = compile_cache.cache_report()
            assert rep["enabled"] and rep["dir"] == env_dir
            assert rep["entries"] > 0 and misses.total() > m0
            assert not os.path.exists(flag_dir) or not os.listdir(flag_dir)
        # the flag that never chose the directory is gone: env stays
        assert compile_cache.active_dir() == env_dir
        monkeypatch.delenv(compile_cache.ENV_VAR)
        # an explicit configure() with neither set: the checkout default,
        # which a later flag re-apply keeps (it is not a flag's to drop)
        monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                            str(tmp_path / "checkout" / ".jax_cache"))
        assert compile_cache.configure() == compile_cache.DEFAULT_DIR
        compile_cache.flags_changed()
        assert compile_cache.active_dir() == compile_cache.DEFAULT_DIR
    finally:
        compile_cache._apply_dir(None)
    assert jax.config.jax_compilation_cache_dir is None


# ---------------------------------------------------------- ladder rules

def test_default_ladder_matches_legacy_pow2(model):
    """With the flag unset the materialized ladder reproduces the legacy
    min(power-of-two, block-table) formula bucket for bucket."""
    eng = ServingEngine(model, max_batch=2, max_context=96, block_size=16)
    assert eng.pad_ladder == (16, 32, 64, 96)
    cap = eng.nb_per_seq * eng.bs
    for L in range(1, 97):
        b = 16
        while b < L:
            b *= 2
        assert eng._pad_bucket(L) == min(b, cap), L


def test_custom_ladder_clamps_sorts_and_validates(model):
    eng = ServingEngine(model, max_batch=2, max_context=96,
                        block_size=16, pad_buckets="64, 16,32,1000")
    assert eng.pad_ladder == (16, 32, 64, 96)      # clamped + sorted
    eng = ServingEngine(model, max_batch=2, max_context=96,
                        block_size=16, pad_buckets=(20, 50))
    assert eng._pad_bucket(18) == 20               # non-pow2 rungs work
    assert eng._pad_bucket(21) == 50
    assert eng._pad_bucket(60) == 64               # beyond ladder: pow2
    with pytest.raises(ValueError, match="positive"):
        ServingEngine(model, max_batch=2, max_context=96,
                      block_size=16, pad_buckets="0,16")


def test_ladder_drives_worst_case_accounting(model):
    """add_request's worst-case block math uses the SAME ladder as
    admission padding: a bucket admitted here can never out-size the
    block table at prefill time."""
    with flag_guard(serving_pad_buckets="16,96"):
        eng = ServingEngine(model, max_batch=2, max_context=96,
                            block_size=16, num_blocks=6)
    # prompt 17 pads to bucket 96 -> 6 blocks now; growth 0 extra; fits
    # exactly.  Under the default ladder it would pad to 32 (2 blocks).
    r = eng.add_request(Request(np.arange(1, 18), max_new_tokens=4))
    eng.run()
    assert r.done and len(r.output_ids) == 4
    assert eng.stats()["free_blocks"] == 6


# -------------------------------------------------------------- warmup

def _drive_mixed_traffic(eng, vocab, lens, budget=7):
    rng = np.random.RandomState(11)
    reqs = []
    for i, L in enumerate(lens):
        kw = {} if i % 2 == 0 else dict(do_sample=True, temperature=0.9,
                                        top_k=30, seed=100 + i)
        reqs.append(eng.add_request(
            Request(rng.randint(1, vocab, (L,)), max_new_tokens=budget,
                    **kw)))
    eng.run()
    return reqs


@pytest.mark.slow  # 18s measured (PR 18 re-budget): warms the full bucket grid; test_ladder_drives_worst_case_accounting keeps the fast ladder pin and test_pallas_paged_kernels warms an engine fast
def test_warmup_grid_zero_compiles_then_one_blamed_outside(model):
    """THE acceptance test (ISSUE 7 satellite): after warmup, mixed
    greedy/sampled traffic across every pad bucket triggers zero
    compile-tracker events; a request OUTSIDE the ladder still works,
    at the price of exactly one compile blamed on the new L_pad."""
    vocab = model.cfg.vocab_size
    with flag_guard(serving_warmup=True, serving_pad_buckets="16,32,64"):
        eng = ServingEngine(model, max_batch=2, max_context=128,
                            block_size=16, steps_per_tick=2)
        info = eng.warmup()
        # 2 tick variants (k=2 + the k=1 tail; greedy and sampled share
        # each) + the host-sampling decode program + 3 prefill buckets
        # + (prefix cache, ISSUE 9) 3 suffix-prefill buckets + the CoW
        # block copy
        assert info["programs"] == 10
        assert [g["L_pad"] for g in info["grid"]
                if g["program"] == "prefill"] == [16, 32, 64]
        assert [g["L_pad"] for g in info["grid"]
                if g["program"] == "prefill_cont"] == [16, 32, 64]
        assert [g["program"] for g in info["grid"]].count("cow") == 1
        assert eng.warmup() is info                   # idempotent
        before = compile_tracker.total_compiles()
        # budgets of 7 = 1 prefill token + 2 full k=2 ticks + k=1 tails,
        # prompts span all three buckets, greedy and sampled mixed
        reqs = _drive_mixed_traffic(eng, vocab, (12, 20, 40, 60))
        assert compile_tracker.total_compiles() == before
        assert all(len(r.output_ids) == 7 for r in reqs)
        st = eng.stats()
        assert st["warmup"]["programs"] == 10
        assert st["warmup"]["warmup_s"] > 0
        assert st["pad_buckets"] == [16, 32, 64]
        # outside the ladder: prompt 70 -> pow2 fallback bucket 128
        rng = np.random.RandomState(12)
        r = eng.add_request(Request(rng.randint(1, vocab, (70,)),
                                    max_new_tokens=4))
        eng.run()
        assert r.done and len(r.output_ids) == 4
        assert compile_tracker.total_compiles() == before + 1
        ev = compile_tracker.compile_report()["recent_events"][-1]
        assert ev["fn"] == "serving.prefill"
        assert "L_pad" in ev["cause"] and "128" in ev["cause"]


@pytest.mark.slow   # 22.6s measured (PR 14 re-budget): serves three
                    # full engines; the AOT path itself stays pinned
                    # fast by the zero-compile grid tests
def test_warmup_fallback_parity_with_unwarmed(model):
    """warmup(aot=False) — the dummy-execution fallback — and the AOT
    path both serve token-for-token what an unwarmed engine serves."""
    vocab = model.cfg.vocab_size

    def serve(warm):
        eng = ServingEngine(model, max_batch=2, max_context=128,
                            block_size=16, steps_per_tick=2,
                            pad_buckets="16,32")
        if warm is not None:
            info = eng.warmup(aot=warm)
            assert info["aot_programs"] == (info["programs"] if warm
                                            else 0)
        reqs = _drive_mixed_traffic(eng, vocab, (12, 24))
        return [list(r.output_ids) for r in reqs]

    baseline = serve(None)
    assert serve(False) == baseline
    assert serve(True) == baseline


@pytest.mark.slow   # 17.9s measured (PR 14 re-budget): compiles the
                    # 11-program spec grid; the plain-grid zero-compile
                    # pin stays fast and the ngram/fp8 @slow twin
                    # covers the spec-grid variant
def test_warmup_grid_spec_quant_zero_compiles(model):
    """ISSUE 10 acceptance: with spec decode AND int8 quant on, the
    warmup grid gains exactly the spec tick (draft/verify programs:
    prefill/cont/cow absorb the draft writes without new programs) and
    mixed post-warmup traffic still triggers ZERO compile-tracker
    events."""
    paddle.seed(0)
    draft = GPTForCausalLM(gpt3_tiny())
    draft.eval()
    vocab = model.cfg.vocab_size
    # ISSUE 14: the pin extends to X-ray sampling — a synced probe is
    # wrapper-level accounting, so it must add ZERO programs/compiles
    with flag_guard(serving_warmup=True, serving_pad_buckets="16,32,64",
                    xray_sample_interval=2):
        eng = ServingEngine(model, max_batch=2, max_context=128,
                            block_size=16, steps_per_tick=2,
                            draft_model=draft, spec_decode=True,
                            spec_k=3, quant="int8")
        info = eng.warmup()
        # the 10-program prefix grid + the one spec tick
        assert info["programs"] == 11
        assert [g["program"] for g in info["grid"]].count("spec_tick") \
            == 1
        assert next(g for g in info["grid"]
                    if g["program"] == "spec_tick")["spec_k"] == 3
        before = compile_tracker.total_compiles()
        reqs = _drive_mixed_traffic(eng, vocab, (12, 20, 40, 60))
        assert compile_tracker.total_compiles() == before
        assert all(len(r.output_ids) == 7 for r in reqs)
        st = eng.stats()
        assert st["speculative"]["ticks"] > 0
        assert st["quant"]["mode"] == "int8"
        assert st["warmup"]["programs"] == 11


@pytest.mark.slow   # compiles a full warmup grid incl. 3 ladder rungs;
                    # tier-1 keeps only the legacy-grid pins fast
def test_warmup_grid_ngram_adaptive_fp8_zero_compiles(model):
    """ISSUE 13 acceptance: with model-free drafting + the adaptive-k
    ladder + fp8 weight-only ALL on, the warmup grid enumerates one
    hostdraft spec program per ladder rung (no draft model anywhere)
    and post-warmup traffic — including adaptive-k transitions under
    a repetitive workload — triggers ZERO compile-tracker events."""
    vocab = model.cfg.vocab_size
    with flag_guard(serving_warmup=True, serving_pad_buckets="16,32,64"):
        eng = ServingEngine(model, max_batch=2, max_context=128,
                            block_size=16, steps_per_tick=2,
                            spec_decode=True, spec_draft="ngram",
                            spec_adaptive=True, spec_k_ladder="2,4,8",
                            quant="fp8")
        info = eng.warmup()
        # the 10-program prefix grid + one spec tick per ladder rung
        assert info["programs"] == 13
        spec_rungs = [g for g in info["grid"]
                      if g["program"] == "spec_tick"]
        assert [g["spec_k"] for g in spec_rungs] == [2, 4, 8]
        assert all(g["draft"] == "ngram" for g in spec_rungs)
        before = compile_tracker.total_compiles()
        reqs = _drive_mixed_traffic(eng, vocab, (12, 20, 40, 60))
        # a repetitive stream ramps k up the ladder under traffic —
        # adaptation must step between WARMED programs only
        rng = np.random.RandomState(13)
        pat = list(rng.randint(1, vocab, (4,)))
        r = eng.add_request(Request(np.array(pat * 12),
                                    max_new_tokens=30))
        eng.run()
        assert compile_tracker.total_compiles() == before
        assert all(len(q.output_ids) == 7 for q in reqs)
        assert r.done and len(r.output_ids) == 30
        st = eng.stats()
        assert st["speculative"]["draft"] == "ngram"
        assert st["speculative"]["k_switches"] >= 1
        assert st["quant"]["mode"] == "fp8"
        assert st["warmup"]["programs"] == 13


@pytest.mark.slow   # compiles a second full warmup grid — tier-1's
                    # ~30s margin keeps only the legacy-grid pins fast
def test_warmup_grid_chunked_zero_compiles(model):
    """ISSUE 11 acceptance: with chunked prefill on, the warmup grid
    swaps the monolithic prefill programs for the suffix-prefill chunk
    programs (one per ladder bucket — chunk offsets are traced), and
    mixed post-warmup traffic spanning every bucket still triggers
    ZERO compile-tracker events."""
    vocab = model.cfg.vocab_size
    with flag_guard(serving_warmup=True, serving_pad_buckets="16,32,64",
                    serving_prefill_chunk=8):
        eng = ServingEngine(model, max_batch=2, max_context=128,
                            block_size=16, steps_per_tick=2)
        info = eng.warmup()
        # 2 tick variants + host-sampling decode + 3 prefill_cont
        # buckets + CoW (prefix cache on) — and NO monolithic prefill:
        # a chunked engine never dispatches it
        assert info["programs"] == 7
        assert [g["L_pad"] for g in info["grid"]
                if g["program"] == "prefill_cont"] == [16, 32, 64]
        assert not any(g["program"] == "prefill" for g in info["grid"])
        before = compile_tracker.total_compiles()
        reqs = _drive_mixed_traffic(eng, vocab, (12, 20, 40, 60))
        assert compile_tracker.total_compiles() == before
        assert all(len(r.output_ids) == 7 for r in reqs)
        assert eng.stats()["prefill_chunks"] > 0


@pytest.mark.slow  # 6s measured: warms both sampling variants; test_warmup_grid_zero_compiles keeps the fast zero-compile pin
def test_warmup_covers_both_sampling_variants(model):
    """The grid always includes the host-sampling decode program AND
    the device-sampling tick: FLAGS_serving_device_sampling is read
    live at every dispatch, so flipping it on a WARMED engine mid-run
    must not route traffic to an un-warmed program."""
    vocab = model.cfg.vocab_size
    with flag_guard(serving_pad_buckets="16,32"):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16, steps_per_tick=1)
        info = eng.warmup()     # taken with device sampling ON
        assert [g["program"] for g in info["grid"]] == \
            ["tick", "decode", "prefill", "prefill",
             "prefill_cont", "prefill_cont", "cow"]
        before = compile_tracker.total_compiles()
        with flag_guard(serving_device_sampling=False):
            # sampled request on the host-sampling path -> decode program
            reqs = _drive_mixed_traffic(eng, vocab, (10, 20), budget=4)
        reqs += _drive_mixed_traffic(eng, vocab, (12,), budget=4)
        assert compile_tracker.total_compiles() == before
        assert all(len(r.output_ids) == 4 for r in reqs)


def _tiny_draft():
    paddle.seed(1)
    d = GPTForCausalLM(gpt3_tiny())
    d.eval()
    return d


# mode -> (engine arguments, the warm-up grid it must list, in order)
_TICKS = [{"program": "tick", "steps_per_tick": 2},
          {"program": "tick", "steps_per_tick": 1},
          {"program": "decode", "steps_per_tick": 1}]
_PREFILL = [{"program": "prefill", "L_pad": 16}]
_CONT = [{"program": "prefill_cont", "L_pad": 16}]
_COW = [{"program": "cow"}]
GRID_MODES = {
    "plain": (dict(prefix_cache=False), _TICKS + _PREFILL),
    "prefix_chunk": (dict(prefix_cache=True, prefill_chunk=8),
                     _TICKS + _CONT + _COW),
    "spec_model": (
        dict(prefix_cache=True, spec_decode=True, spec_k=3),
        _TICKS + [{"program": "spec_tick", "spec_k": 3, "draft": "model"}]
        + _PREFILL + _CONT + _COW),
    "spec_ngram": (
        dict(prefix_cache=True, spec_decode=True, spec_draft="ngram",
             spec_adaptive=True, spec_k_ladder="2,4"),
        _TICKS + [{"program": "spec_tick", "spec_k": k, "draft": "ngram"}
                  for k in (2, 4)] + _PREFILL + _CONT + _COW),
    "tp2": (dict(prefix_cache=True, tp_degree=2),
            _TICKS + _PREFILL + _CONT + _COW),
}


@pytest.mark.parametrize("mode", sorted(GRID_MODES))
def test_warmup_grid_is_the_engines_program_list(model, mode):
    """`warmup()` is a loop over the engine's own program list for its
    mode: the grid names every program the mode can dispatch, in the
    order they were always warmed, each an AOT executable, and traffic
    afterwards (greedy and sampled, a prefix hit where there is a prefix
    cache) compiles nothing."""
    kw, grid = GRID_MODES[mode]
    if mode == "spec_model":
        kw = dict(kw, draft_model=_tiny_draft())
    vocab = model.cfg.vocab_size
    eng = ServingEngine(model, max_batch=2, max_context=64, block_size=8,
                        steps_per_tick=2, pad_buckets="16", **kw)
    info = eng.warmup()
    assert info["grid"] == grid
    assert info["programs"] == info["aot_programs"] == len(grid)
    assert eng.stats()["warmup"]["programs"] == len(grid)
    before = compile_tracker.total_compiles()
    reqs = _drive_mixed_traffic(eng, vocab, (12, 9), budget=5)
    # the same 12-token prompt again: its first block is a prefix hit
    reqs += _drive_mixed_traffic(eng, vocab, (12,), budget=5)
    assert compile_tracker.total_compiles() == before
    assert all(len(r.output_ids) == 5 for r in reqs)
    assert eng.prefix is None or eng.prefix.hits >= 1
