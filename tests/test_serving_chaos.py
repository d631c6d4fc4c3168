"""Crash-only serving (ISSUE 15): failure isolation, poison-request
quarantine, the tick watchdog, graceful drain, and warm restart from an
exported prefix cache — every recovery path driven by deterministic
chaos injection.

The headline contracts pinned here:

* a request whose admission program raises (or whose prefill logits go
  non-finite under the NaN watchdog) strikes out after two attempts and
  is rejected ``reason=poisoned`` — the engine loop survives and the
  block ledger stays balanced;
* a transient dispatch failure under ``FLAGS_serving_dispatch_retries``
  is INVISIBLE: the retried stream is bit-identical to an uninjected
  run;
* per-slot non-finite decode logits evict exactly the implicated slot
  ``outcome=error`` while every other slot's greedy stream stays
  BIT-identical to an uninjected run (blocksan armed and clean);
* a harvest that never materializes trips the tick watchdog
  (``FLAGS_serving_tick_timeout_s``) and fails the tick instead of
  wedging the loop;
* drain closes admission (healthz 503 ``draining``), cancels the
  waiting queue ``outcome=drained``, and exports the prefix cache
  through the atomic-manifest machinery; a fresh engine imports it and
  a cached-prefix prompt's stream bit-matches the warm engine's
  prefix-hit path — while corrupt export versions are skipped with a
  counter, never loaded.
"""

import contextlib
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import flag_guard
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import flight_recorder
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.testing import chaos


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt3_tiny())
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_context", 64)
    kw.setdefault("block_size", 16)
    return ServingEngine(model, **kw)


def _counter(name, **labels):
    snap = obs_metrics.snapshot().get(name)
    if not snap:
        return 0
    for s in snap["series"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    return 0


# ------------------------------------------------------- poison quarantine

def test_poison_quarantine_after_two_dispatch_strikes(model):
    """A request whose prefill program raises is re-queued once, then
    quarantined ``reason=poisoned`` — the loop survives, every block is
    released, and the evidence lands on counters + the flight ring.
    (The injection fires BEFORE the program call, so this test compiles
    nothing.)"""
    eng = _engine(model)
    bad = eng.add_request(Request([5, 6, 7], max_new_tokens=4))
    p0 = _counter("serving.poisoned_requests")
    with chaos.fail_at("serving.prefill.dispatch", on_calls=[1, 2],
                       exc=RuntimeError) as fault:
        eng.run()
    assert fault.fires == 2
    assert bad._strikes == 2
    assert bad.outcome == "poisoned"
    assert bad.trace["outcome"] == "rejected:poisoned"
    assert bad in eng.finished and not bad.output_ids
    assert eng.poisoned_requests == 1 and eng.tick_errors == 2
    assert _counter("serving.poisoned_requests") == p0 + 1
    assert _counter("serving.rejections", reason="poisoned") >= 1
    # nothing leaked: the failed admissions undid every draw
    st = eng.stats()
    assert st["free_blocks"] == eng.num_blocks and st["reserved"] == 0
    events = [e for e in flight_recorder.default_recorder().events()
              if e["kind"] == "poison_quarantine"]
    assert events and events[-1]["rid"] == bad.rid


def test_transient_dispatch_retry_is_invisible(model):
    """One injected transient RuntimeError under
    ``FLAGS_serving_dispatch_retries`` retries in place: the stream is
    BIT-identical to an uninjected run, the request finishes, and the
    retry is counted — no strike, no eviction."""
    ref = _engine(model)
    rr = ref.add_request(Request([5, 6, 7], max_new_tokens=4))
    ref.run()
    eng = _engine(model)
    req = eng.add_request(Request([5, 6, 7], max_new_tokens=4))
    with flag_guard(serving_dispatch_retries=2):
        with chaos.fail_at("serving.prefill.dispatch", on_calls=[1],
                           exc=RuntimeError) as fault:
            eng.run()
    assert fault.fires == 1
    assert req.outcome == "finished"
    assert req.output_ids == rr.output_ids
    assert eng.dispatch_retries == 1 and eng.tick_errors == 0
    assert _counter("serving.dispatch_retries",
                    site="serving.prefill.dispatch") >= 1


@pytest.mark.parametrize("stage", ["serve:tick_stage", "serve:chunk_stage"])
def test_transient_transfer_fault_is_retried_with_its_dispatch(
        model, monkeypatch, stage):
    """A launch's host-to-device transfers run inside the callable
    `_dispatch_call` retries (ISSUE 36 moved them under a span of their
    own, not out of the retry): one that fails transiently is sent again
    with the dispatch — same stream, one retry counted, no strike."""
    eng = _engine(model, prefill_chunk=16)
    rr = eng.add_request(Request([5, 6, 7], max_new_tokens=4))
    eng.run()
    real, fires = ServingEngine._staging, []

    @contextlib.contextmanager
    def staging(self, name, shield=None):
        with real(self, name, shield) as dev:
            def flaky(a):
                if name == stage and not fires:
                    fires.append(name)
                    raise RuntimeError("transfer: transient")
                return dev(a)
            yield flaky

    monkeypatch.setattr(ServingEngine, "_staging", staging)
    req = eng.add_request(Request([5, 6, 7], max_new_tokens=4))
    with flag_guard(serving_dispatch_retries=2):
        eng.run()
    assert fires == [stage]
    assert req.outcome == "finished"
    assert req.output_ids == rr.output_ids
    assert eng.dispatch_retries == 1 and eng.tick_errors == 0
    site = "serving.tick.dispatch" if stage == "serve:tick_stage" \
        else "serving.prefill.dispatch"
    assert _counter("serving.dispatch_retries", site=site) >= 1


@pytest.mark.slow   # two engines compile their grids (~4-8s)
def test_nan_prefill_quarantine_and_batch_isolation(model):
    """NaN-injected prefill logits (flight-recorder watchdog armed)
    strike the poisoned request twice -> quarantined, while a healthy
    request admitted through the SAME engine streams bit-identically to
    an uninjected run.  The NaN is screened BEFORE prefix registration,
    so the shared index never holds a poisoned prompt."""
    ref = _engine(model)
    rr = ref.add_request(Request([5, 6, 7], max_new_tokens=4))
    ref.run()
    eng = _engine(model, prefix_cache=True)
    bad = eng.add_request(Request([9, 9, 9], max_new_tokens=4))
    ok = eng.add_request(Request([5, 6, 7], max_new_tokens=4))
    with flag_guard(enable_nan_watchdog=True):
        with chaos.nan_logits("serving.prefill", rids=[bad.rid]) as f:
            eng.run()
    assert f.fires == 2
    assert bad.outcome == "poisoned" and not bad.output_ids
    assert ok.outcome == "finished"
    assert ok.output_ids == rr.output_ids
    # the poisoned prompt must not be in the prefix index
    assert eng.prefix.lookup(bad.prompt_ids).blocks == []
    st = eng.stats()
    assert st["free_blocks"] == eng.num_blocks and st["reserved"] == 0


@pytest.mark.slow   # two engines + two runs compile (~4-8s)
def test_decode_nan_evicts_only_implicated_slot_bit_parity(model):
    """ACCEPTANCE (ISSUE 15): chaos-injected non-finite logits on slot
    i — the per-slot failure the host-sampling decode path can
    attribute — end that request ``outcome=error`` with its blocks
    fully released (blocksan armed: the verify at every boundary and at
    the drained end stays green), and every OTHER slot's greedy stream
    is BIT-identical to an uninjected run."""
    def serve(inject=None):
        with flag_guard(serving_device_sampling=False,
                        enable_nan_watchdog=True, enable_jaxsan=True):
            eng = ServingEngine(model, max_batch=3, max_context=64,
                                block_size=16, steps_per_tick=1)
            reqs = [eng.add_request(Request([5 + i, 6, 7],
                                            max_new_tokens=6))
                    for i in range(3)]
            if inject is not None:
                with chaos.nan_logits("serving.decode",
                                      rids=[reqs[inject].rid]):
                    eng.run()
            else:
                eng.run()
            return eng, reqs

    _, ref = serve()
    eng, reqs = serve(inject=1)
    assert reqs[1].outcome == "error"
    assert len(reqs[1].output_ids) == 1      # the prefill token only
    for i in (0, 2):
        assert reqs[i].outcome == "finished"
        assert reqs[i].output_ids == ref[i].output_ids
    st = eng.stats()
    assert st["free_blocks"] == eng.num_blocks and st["reserved"] == 0
    assert eng._blocksan is not None and eng._blocksan.verifies > 0
    evs = [e for e in flight_recorder.default_recorder().events()
           if e["kind"] == "slot_error"]
    assert evs and evs[-1]["rid"] == reqs[1].rid


@pytest.mark.slow   # compiles one engine grid (~5s) — the fast twin is
                    # the prefill-stage quarantine test above
def test_tick_dispatch_failure_evicts_batch_ledger_clean(model):
    """A TICK-level dispatch failure (the whole-batch program raised —
    no slot attributable) evicts exactly the slots the tick covered,
    outcome=error, with blocksan armed: the eviction's block releases
    reconcile at the drained end (the R007 error-path audit's runtime
    regression evidence)."""
    with flag_guard(enable_jaxsan=True):
        eng = _engine(model)
        reqs = [eng.add_request(Request([5 + i, 6, 7],
                                        max_new_tokens=6))
                for i in range(2)]
        # admission prefills fire a DIFFERENT site, so the tick
        # site's first call is the first mid-stream decode tick
        with chaos.fail_at("serving.tick.dispatch", on_calls=[1],
                           exc=RuntimeError) as f:
            eng.run()
    assert f.fires == 1
    assert eng.tick_errors == 1
    for r in reqs:
        assert r.outcome == "error"
        assert len(r.output_ids) >= 1     # the prefill token landed
    st = eng.stats()
    assert st["free_blocks"] == eng.num_blocks and st["reserved"] == 0
    assert eng._blocksan is not None and eng._blocksan.verifies > 0


# ------------------------------------------------------------ tick watchdog

@pytest.mark.slow  # 7s measured (PR 18 re-budget): compiles an engine grid around a stalled harvest; the drain/admission + retry pins stay fast
def test_tick_watchdog_fails_hung_harvest(model):
    """A harvest stalled past ``FLAGS_serving_tick_timeout_s`` raises
    TickTimeout inside the loop; the guard absorbs it — implicated
    slots evicted ``outcome=error``, blocks released — and run()
    returns instead of wedging forever."""
    eng = _engine(model)
    req = eng.add_request(Request([5, 6, 7], max_new_tokens=6))
    t0 = _counter("serving.tick_errors")
    with flag_guard(serving_tick_timeout_s=0.3):
        with chaos.delay_at("serving.harvest", 3.0, on_calls=[1]) as f:
            eng.run()
    assert f.fires == 1
    assert req.outcome == "error"
    assert eng.tick_errors == 1
    assert _counter("serving.tick_errors") == t0 + 1
    st = eng.stats()
    assert st["free_blocks"] == eng.num_blocks and st["reserved"] == 0
    # watchdog off (default): the same delay merely slows the harvest
    eng2 = _engine(model)
    r2 = eng2.add_request(Request([5, 6, 7], max_new_tokens=2))
    with chaos.delay_at("serving.harvest", 0.05):
        eng2.run()
    assert r2.outcome == "finished"


# ------------------------------------------------------------------- drain

def test_drain_cancels_waiting_closes_admission_and_healthz(model):
    """drain() with no admitted work: the waiting queue is cancelled
    ``outcome=drained``, admission rejects (reason=draining), and
    health() reports the draining state with in-flight/waiting counts.
    (No request ever admits, so this test compiles nothing.)"""
    eng = _engine(model)
    eng.run()                       # no work: marks ready, zero ticks
    assert eng.health()["ready"] is True
    waiting = [eng.add_request(Request([5, 6, 7], max_new_tokens=4))
               for _ in range(2)]
    eng.request_drain()
    doc = eng.health()
    assert doc == {"ready": False, "reason": "draining", "in_flight": 0,
                   "waiting": 2, "prefilling": 0}
    with pytest.raises(ValueError, match="draining"):
        eng.add_request(Request([1, 2], max_new_tokens=2))
    assert _counter("serving.rejections", reason="draining") >= 1
    info = eng.drain(deadline_s=5.0)
    assert info["cancelled_waiting"] == 2
    assert info["evicted_running"] == 0 and info["export"] is None
    for r in waiting:
        assert r.outcome == "drained" and r in eng.finished
        assert r.trace["outcome"] == "drained"
    assert eng.drain() is info      # idempotent
    st = eng.stats()
    assert st["draining"] is True and st["drain"]["cancelled_waiting"] == 2
    assert st["free_blocks"] == eng.num_blocks


@pytest.mark.slow   # compiles the engine grid and ticks through a
                    # stream mid-drain (~2-6s)
def test_drain_finishes_in_flight_and_verifies_blocksan(model):
    """An ADMITTED request finishes inside the drain deadline (its
    stream completes normally); blocksan is armed, so the drain-end
    verify reconciling the emptied ledger is a hard assertion, not a
    no-op."""
    with flag_guard(enable_jaxsan=True):
        eng = _engine(model)
        req = eng.add_request(Request([5, 6, 7], max_new_tokens=4))
        eng.step()                  # admit + first tick
        assert req.slot is not None and not req.done
        info = eng.drain(deadline_s=30.0)
        assert req.outcome == "finished"
        assert len(req.output_ids) == 4
        assert info["evicted_running"] == 0
        assert eng._blocksan is not None and eng._blocksan.verifies > 0
        assert eng.stats()["free_blocks"] == eng.num_blocks


@pytest.mark.slow   # compiles one engine then drains past the deadline
def test_drain_deadline_evicts_stragglers(model):
    """A request that cannot finish inside the deadline is evicted
    ``outcome=drained`` with its blocks released."""
    eng = _engine(model)
    req = eng.add_request(Request([5, 6, 7], max_new_tokens=30))
    eng.step()
    info = eng.drain(deadline_s=0.0)
    assert req.outcome == "drained" and not req.done
    assert info["evicted_running"] == 1
    assert eng.stats()["free_blocks"] == eng.num_blocks


# ------------------------------------------- export / import warm restart

SYS_PROMPT = list(range(1, 40))


def _serve_one(eng, suffix, n=6):
    r = eng.add_request(Request(SYS_PROMPT + suffix, max_new_tokens=n))
    eng.run()
    return r


@pytest.mark.slow   # two prefix engines compile their grids (~14s)
def test_drain_export_then_import_bit_matches_prefix_hit_path(model):
    """ACCEPTANCE (ISSUE 15): drain -> export -> new engine import: the
    token stream for a cached-prefix prompt BIT-matches the warm
    engine's prefix-hit path, and the import re-pinned the blocks
    through the ordinary accounting (blocksan armed on the importing
    engine, free-block invariant intact)."""
    tmp = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                       f"pfx_export_{os.getpid()}")
    with flag_guard(serving_prefix_export_dir=tmp):
        a = _engine(model, max_context=96, prefix_cache=True)
        _serve_one(a, [77])                  # registers the prefix
        hit = _serve_one(a, [88])            # the warm prefix-HIT path
        assert a.stats()["prefix_cache"]["hits"] == 1
        info = a.drain()
        exp = info["export"]
        assert exp["entries"] == exp["blocks"] == 2
        assert os.path.exists(os.path.join(exp["path"], "COMPLETE"))
        i0 = _counter("serving.prefix_import_blocks")
        with flag_guard(enable_jaxsan=True):
            b = _engine(model, max_context=96, prefix_cache=True)
        imp = b.stats()["prefix_cache"]["import"]
        assert imp == {"step": 1, "blocks": 2, "skipped_corrupt": 0}
        assert _counter("serving.prefix_import_blocks") == i0 + 2
        rb = _serve_one(b, [88])
        assert rb.output_ids == hit.output_ids
        assert b.stats()["prefix_cache"]["hits"] == 1
        assert b.stats()["free_blocks"] == b.num_blocks


def test_corrupt_export_skipped_with_counter_and_fallback(model):
    """Corrupted/truncated export versions are SKIPPED — counter +
    flight event, never loaded — and import falls back to the next
    older valid version.  (Exports are hand-built through the same
    commit helper, so nothing here compiles.)"""
    from paddle_tpu.distributed.checkpoint import manager as ckpt
    tmp = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                       f"pfx_corrupt_{os.getpid()}")
    probe = ServingEngine(model, max_batch=2, max_context=64,
                          block_size=16, prefix_cache=True)
    meta = probe._prefix_fingerprint()
    (nh,), (hd,), bs = probe.cache.rows[0].lead, probe.cache.rows[0].trail, \
        probe.bs
    layers = probe.model.cfg.num_layers
    dtype = np.asarray(probe.pools[0][0]).dtype

    def fabricate(step, n_entries):
        index = {"schema": "paddle_tpu.prefix/v1", "block_size": bs,
                 "meta": meta,
                 "entries": [{"hash": f"{i:02d}" * 16, "parent": None,
                              "block": i + 1}
                             for i in range(n_entries)]}
        arrays = {"block_ids": np.arange(1, n_entries + 1, dtype=np.int64)}
        for li in range(layers):
            arrays[f"k{li}"] = np.full((nh, n_entries, bs, hd), step,
                                       dtype)
            arrays[f"v{li}"] = np.full((nh, n_entries, bs, hd), -step,
                                       dtype)

        def write(d):
            with open(os.path.join(d, "prefix_index.json"), "w") as f:
                json.dump(index, f)
            with open(os.path.join(d, "prefix_blocks.npz"), "wb") as f:
                np.savez(f, **arrays)
            return ["prefix_index.json", "prefix_blocks.npz"]

        return ckpt.commit_single_rank(tmp, step, write)

    fabricate(1, n_entries=1)                   # older, valid
    newest = fabricate(2, n_entries=2)          # newest — then corrupted
    chaos.flip_bytes(os.path.join(newest, "prefix_blocks.npz"), 64, 8)
    s0 = _counter("serving.prefix_import_skipped_corrupt",
                  reason="corrupt")
    with flag_guard(serving_prefix_export_dir=tmp):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16, prefix_cache=True)
    imp = eng.stats()["prefix_cache"]["import"]
    assert imp == {"step": 1, "blocks": 1, "skipped_corrupt": 1}
    assert _counter("serving.prefix_import_skipped_corrupt",
                    reason="corrupt") == s0 + 1
    evs = [e for e in flight_recorder.default_recorder().events()
           if e["kind"] == "prefix_import_skip"]
    assert evs and evs[-1]["step"] == 2
    # the imported block holds version 1's bytes (never version 2's)
    blk = eng.prefix.resident_blocks()[0]
    assert float(np.asarray(eng.pools[0][0])[:, blk].ravel()[0]) == 1.0
    # a fingerprint mismatch is also skipped, with its own reason
    import shutil
    with open(os.path.join(newest, "prefix_index.json")) as f:
        idx = json.load(f)
    shutil.rmtree(newest)
    idx["meta"] = dict(meta, quant="int8")
    m0 = _counter("serving.prefix_import_skipped_corrupt",
                  reason="mismatch")

    def write_mismatch(d):
        with open(os.path.join(d, "prefix_index.json"), "w") as f:
            json.dump(idx, f)
        with open(os.path.join(d, "prefix_blocks.npz"), "wb") as f:
            np.savez(f, block_ids=np.asarray([1], np.int64))
        return ["prefix_index.json", "prefix_blocks.npz"]

    ckpt.commit_single_rank(tmp, 3, write_mismatch)
    with flag_guard(serving_prefix_export_dir=tmp):
        eng2 = ServingEngine(model, max_batch=2, max_context=64,
                             block_size=16, prefix_cache=True)
    assert _counter("serving.prefix_import_skipped_corrupt",
                    reason="mismatch") == m0 + 1
    assert eng2.stats()["prefix_cache"]["import"]["step"] == 1


def test_export_state_import_state_round_trip():
    """PrefixCache.export_state orders entries parent-first and
    import_state rebuilds the index (child counters included) onto
    remapped blocks, skipping orphans when capacity cuts a parent."""
    from paddle_tpu.inference.prefix_cache import PrefixCache
    src = PrefixCache(4)
    refs = []
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]
    src.register(prompt, [7, 8, 9], refs.append)
    assert len(src) == 3 and len(refs) == 3
    state = src.export_state()
    # parent-first: depth increases monotonically
    assert [e["block"] for e in state["entries"]] == [7, 8, 9]
    assert state["entries"][0]["parent"] is None
    dst = PrefixCache(4)
    alloc_ids = iter([101, 102, 103])
    mapping = {}
    n = dst.import_state(state, lambda: next(alloc_ids),
                         lambda old, new: mapping.__setitem__(old, new))
    assert n == 3 and mapping == {7: 101, 8: 102, 9: 103}
    # the chain resolves lookups exactly as the source did
    assert dst.lookup(prompt).blocks == [101, 102, 103]
    assert dst.lookup(prompt[:8]).blocks == [101, 102]
    # capacity cut: only the root fits -> children skipped, no orphans
    dst2 = PrefixCache(4)
    short = iter([201])
    n2 = dst2.import_state(state,
                           lambda: next(short, None),
                           lambda old, new: None)
    assert n2 == 1 and len(dst2) == 1
    assert dst2.lookup(prompt).blocks == [201]
    # block_size mismatch refuses loudly
    with pytest.raises(ValueError, match="block_size"):
        PrefixCache(8).import_state(state, lambda: 1, lambda a, b: None)
