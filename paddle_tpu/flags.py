"""Global flag registry.

TPU-native analogue of the reference's gflags clone
(`paddle/common/flags_native.cc:299` RegisterFlag / `:377` SetFlagsFromEnv /
`:400` ParseCommandLineFlags and the `paddle.set_flags/get_flags` Python API at
`python/paddle/base/framework.py:76,:101`).  One process-global registry; every
flag can be seeded from the environment (``FLAGS_xxx``) at import time and
changed at runtime via :func:`set_flags`.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Optional

__all__ = [
    "define_flag",
    "get_flags",
    "set_flags",
    "flag_guard",
]


@dataclass
class _Flag:
    name: str
    default: Any
    value: Any
    type: type
    help: str
    on_change: Optional[Callable[[Any], None]] = None


_registry: Dict[str, _Flag] = {}
_lock = threading.RLock()
# serializes on_change hook execution (NOT value reads/writes): hooks run
# outside _registry's lock so they may take module locks, but two racing
# set_flags must not interleave the same hook — RLock so a hook may
# itself call set_flags
_hook_lock = threading.RLock()


def _coerce(ftype: type, raw: Any) -> Any:
    if isinstance(raw, str) and ftype is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return ftype(raw)


def define_flag(name: str, default: Any, help: str = "",
                on_change: Optional[Callable[[Any], None]] = None) -> None:
    """Register a flag. Environment variable ``FLAGS_<name>`` overrides the default."""
    with _lock:
        if name in _registry:
            return
        ftype = type(default)
        value = default
        env = os.environ.get("FLAGS_" + name)
        if env is not None:
            value = _coerce(ftype, env)
        _registry[name] = _Flag(name, default, value, ftype, help, on_change)


def get_flags(names: Iterable[str] | str) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    with _lock:
        out = {}
        for n in names:
            if n not in _registry:
                raise ValueError(f"Unknown flag: {n!r}")
            out[n] = _registry[n].value
        return out


def get_flag(name: str) -> Any:
    return get_flags([name])[name]


def set_flags(flags: Dict[str, Any]) -> None:
    hooks = []
    with _lock:
        # validate AND coerce every value before assigning any: a bad
        # name or an uncoercible value must not leave the dict half-
        # applied (assigned values whose hooks then never run)
        coerced = []
        for name, v in flags.items():
            if name not in _registry:
                raise ValueError(f"Unknown flag: {name!r}")
            f = _registry[name]
            coerced.append((f, _coerce(f.type, v)))
        for f, v in coerced:
            f.value = v
            if f.on_change is not None:
                hooks.append((f.on_change, f.name))
    # on_change hooks run OUTSIDE the registry lock (graft-lint R005, the
    # PR 7 AB-BA class): a hook that acquires a module lock, while any
    # other thread holds that module lock and READS a flag, deadlocked
    # when hooks ran under _lock.  (Calling set_flags/flag_guard while
    # holding such a module lock is still an inversion — R005 flags it.)
    # Values are therefore visible to concurrent readers before their
    # hooks finish — hooks must tolerate that (they always had to: reads
    # never waited for hooks' effects on OTHER modules).  _hook_lock
    # serializes hook execution, and each hook receives the flag's value
    # re-read INSIDE that critical section: two racing set_flags run
    # their hooks in some order, and whichever runs last applies the
    # registry's final value — hook-applied state converges instead of
    # ending inverted (assign-A, assign-B, hook-B, hook-A).  Every flag
    # is assigned before any hook runs (a flag_guard restore can't be
    # left half-applied); the first hook failure is re-raised after all
    # hooks ran.
    deferred_exc = None
    with _hook_lock:
        for hook, name in hooks:
            try:
                hook(get_flag(name))
            except BaseException as e:
                if deferred_exc is None:
                    deferred_exc = e
    if deferred_exc is not None:
        raise deferred_exc


class flag_guard:
    """Context manager that temporarily overrides flags."""

    def __init__(self, **flags: Any):
        self._flags = flags
        self._saved: Dict[str, Any] = {}

    def __enter__(self):
        self._saved = get_flags(list(self._flags))
        set_flags(self._flags)
        return self

    def __exit__(self, *exc):
        set_flags(self._saved)
        return False


# ---------------------------------------------------------------------------
# Core flags (mirroring the commonly used subset of paddle/common/flags.cc)
# ---------------------------------------------------------------------------
def _nan_flag_changed(enabled):
    from .ops import registry as _reg
    _reg._on_nan_flag_change(enabled)


define_flag("check_nan_inf", False,
            "Scan every op output for NaN/Inf (debugging).",
            on_change=_nan_flag_changed)
define_flag("check_nan_inf_level", 0, "0: fail on nan/inf; >0: warn only.")
define_flag("check_nan_inf_stride", 1,
            "ops between host syncs of the nan/inf flags (1 = immediate, "
            "precise; larger = on-device accumulation, one sync per window).")
define_flag("use_stride_kernel", False, "Unused on TPU; kept for API parity.")
define_flag("eager_delete_tensor_gb", 0.0, "Kept for API parity; XLA owns memory.")
define_flag("benchmark", False, "Block on every op for accurate per-op timing.")
define_flag("tpu_deterministic", False, "Force deterministic XLA reductions.")
define_flag("log_level", 0, "VLOG-style verbosity for paddle_tpu internals.")

# Comm-watchdog flags (used by distributed/collective.py and watchdog.py).
# Registered here — the single source of truth — so readers never depend on
# watchdog's import having run first.
define_flag("enable_comm_watchdog", True,
            "watch host-side comm tasks for hangs")
define_flag("comm_watchdog_timeout_s", 300.0,
            "seconds before a host comm task is reported as hung")
define_flag("comm_static_check", False,
            "verify shape/dtype across ranks before collectives")
define_flag("tpu_fast_rng", True,
            "use the fast 'rbg' PRNG for framework keys on TPU (an order "
            "of magnitude cheaper dropout masks); 0 = threefry everywhere")


def _metrics_flag_changed(enabled):
    from .observability import metrics as _metrics
    _metrics._sync_enabled(enabled)


define_flag("enable_metrics", True,
            "runtime metrics registry (observability.metrics); 0 makes "
            "every instrument a single-boolean-check no-op",
            on_change=_metrics_flag_changed)


def _nan_watchdog_flag_changed(enabled):
    from .observability import flight_recorder as _fr
    _fr._sync_enabled(enabled)


define_flag("enable_nan_watchdog", False,
            "NaN/Inf watchdog on instrumented train-loop losses "
            "(observability.flight_recorder.check_finite) + automatic "
            "flight-recorder dumps on unhandled train-step exceptions; "
            "off (the default) = a single-boolean-check no-op that never "
            "touches the probed value",
            on_change=_nan_watchdog_flag_changed)
define_flag("nan_watchdog_interval", 1,
            "train steps between watchdog loss checks on async paths "
            "(each check materializes the loss on the host; hapi already "
            "syncs the loss every step, so this gates the hybrid step)")
def _flight_capacity_changed(value):
    from .observability import flight_recorder as _fr
    _fr._sync_capacity(value)


define_flag("flight_recorder_steps", 64,
            "ring capacity of the flight recorder (last-K step records "
            "and events kept for post-mortem dumps); resizes the "
            "default recorder at runtime",
            on_change=_flight_capacity_changed)
define_flag("flight_dump_dir", "",
            "directory automatic flight-recorder dumps are written to "
            "(empty = ./flight_dumps, created on demand — never the "
            "repo/CWD root)")

# Training-step fast path (optimizer/fused.py, hapi/model.py, io).
define_flag("fused_optimizer", True,
            "route Optimizer.step through ONE donated jitted XLA program "
            "over the whole param/grad/state pytree (AMP unscale, on-device "
            "found_inf, global-norm clip and the update fused per "
            "(optimizer, tree structure, clip/scaler config)); 0 restores "
            "the per-parameter program-per-leaf path.  Irregular cases "
            "(L1 decay, custom clip classes) fall back per step either "
            "way — see the optimizer.fused hit/miss/fallback counter")
define_flag("loss_sync_interval", 1,
            "train steps between host materializations of the hapi loss "
            "(fit/train_batch): K>1 leaves the loss on device and reads "
            "it back every K-th step, so step dispatch overlaps the "
            "previous step's compute; the NaN watchdog and the telemetry "
            "loss/synced annotations ride the synced steps only")
define_flag("dataloader_device_prefetch", True,
            "io.DataLoader double-buffers batch fetch + collate + "
            "jax.device_put on a background thread, so H2D transfer of "
            "batch t+1 overlaps step t's compute; 0 fetches batches "
            "inline on the consuming thread")

# Fault tolerance (distributed/checkpoint/manager.py, io.DataLoader).
define_flag("ckpt_io_retries", 3,
            "transient-I/O retry attempts per checkpoint write/commit "
            "step (OSError only); each retry backs off exponentially "
            "from FLAGS_ckpt_io_backoff_s and counts on ckpt.io_retries")
define_flag("ckpt_io_backoff_s", 0.1,
            "base backoff seconds between checkpoint I/O retries "
            "(doubles per attempt)")
define_flag("ckpt_commit_timeout_s", 300.0,
            "seconds the commit coordinator waits for every rank's "
            "manifest to appear in the step_<N>.tmp directory before "
            "failing the save")
define_flag("dataloader_retries", 2,
            "transient-OSError retries of one DataLoader batch fetch "
            "(dataset access + collate) before the error surfaces; "
            "retries count on dataloader.retries")
define_flag("dataloader_retry_backoff_s", 0.05,
            "base backoff seconds between DataLoader fetch retries "
            "(doubles per attempt)")

# Cold start (core/compile_cache.py, inference/serving.py ISSUE 7):
# persistent XLA compilation cache + serving AOT warmup + pad ladders.
def _compile_cache_flag_changed(_value):
    from .core import compile_cache as _cc
    _cc.flags_changed()


define_flag("compilation_cache_dir", "",
            "directory of the persistent XLA compilation cache, "
            "applied once at import and re-applied on change; warm "
            "restarts then skip XLA compilation for every already-seen "
            "program.  JAX_COMPILATION_CACHE_DIR in the environment "
            "takes precedence over this flag; with both empty the "
            "cache is off until a program calls "
            "core.compile_cache.configure(), which then uses "
            "<checkout>/.jax_cache",
            on_change=_compile_cache_flag_changed)
define_flag("enable_compilation_cache", True,
            "master switch for the persistent compilation cache; 0 "
            "keeps every directory choice inert (and detaches an "
            "already-applied dir on change)",
            on_change=_compile_cache_flag_changed)
define_flag("compilation_cache_min_entry_bytes", -1,
            "smallest serialized executable worth persisting "
            "(jax_persistent_cache_min_entry_size_bytes); -1 (the "
            "default) caches everything — restart-to-first-token wants "
            "even the small serving programs warm",
            on_change=_compile_cache_flag_changed)
define_flag("compilation_cache_min_compile_secs", 0.0,
            "smallest compile wall time worth persisting "
            "(jax_persistent_cache_min_compile_time_secs); 0.0 (the "
            "default) caches everything",
            on_change=_compile_cache_flag_changed)
define_flag("serving_warmup", False,
            "ServingEngine.run() calls warmup() before admitting "
            "traffic: precompile the full program grid the engine can "
            "ever dispatch (every pad bucket x tick size x decode "
            "variant), so post-warmup traffic triggers ZERO compiles; "
            "stats()['warmup'] reports warmup_s and program count")
define_flag("serving_pad_buckets", "",
            "comma-separated ascending prompt pad-bucket ladder for the "
            "serving engine (e.g. '64,256,1024'), clamped to the block "
            "table; one source of truth shared by admission padding, "
            "worst-case block accounting and the warmup grid.  Empty "
            "(the default) keeps the power-of-two ladder.  Prompts "
            "beyond the ladder fall back to the power-of-two bucket "
            "(one blamed compile names the new L_pad)")

def _jaxsan_flag_changed(enabled):
    from .testing import jaxsan as _jaxsan
    _jaxsan._sync_enabled(enabled)


define_flag("enable_jaxsan", False,
            "runtime trace-safety sanitizer (testing.jaxsan): checksum "
            "host buffers fed to in-flight compiled programs (verify at "
            "harvest; in-place mutation raises JaxsanError) and poison "
            "donated leaves after donated program calls so use-after-"
            "donate fails loudly even on CPU where donation is a no-op; "
            "off (the default) = a single-boolean-check no-op",
            on_change=_jaxsan_flag_changed)

# Scale-out serving (inference/serving.py, inference/tp.py,
# inference/prefix_cache.py — ISSUE 9).
define_flag("serving_tp_degree", 1,
            "tensor-parallel degree of the serving engine's compiled "
            "programs: weights (attention heads + FFN/vocab columns) and "
            "the paged KV pools are sharded over a 'tp' mesh axis of the "
            "first N local devices, the host scheduler stays rank-0 and "
            "broadcasts admissions/tick inputs.  1 (the default) is the "
            "single-program path; >1 requires a GPT-family model whose "
            "head/FFN/vocab dims divide the degree")
define_flag("serving_prefix_cache", True,
            "refcounted prompt-prefix reuse over the serving block "
            "table: full prompt blocks are registered in a hash-chain "
            "index, an admission whose prefix is resident points its "
            "table at the shared blocks and prefills only the suffix "
            "(copy-on-write when a shared block would be written; index "
            "eviction under pool pressure frees only orphaned blocks); "
            "0 restores prefill-per-request")

# Speculative + quantized serving (inference/speculative.py,
# inference/quant.py — ISSUE 10).
define_flag("serving_spec_decode", False,
            "draft/verify speculative decoding in the serving engine "
            "(requires a draft model at construction: "
            "ServingEngine(model, draft_model=...)): the draft proposes "
            "FLAGS_serving_spec_k tokens per slot inside one compiled "
            "program and the target judges every proposal in a "
            "single chunk verify forward — lossless (greedy streams "
            "bit-identical to the plain engine; seeded sampling follows "
            "the rejection-sampling correction, so the output "
            "distribution is unchanged)")
define_flag("serving_spec_k", 4,
            "draft tokens proposed per slot per speculative tick; a "
            "tick emits 1..k tokens depending on acceptance.  "
            "Eligibility is PER SLOT (a per-slot emit cap rides into "
            "the program as a device input): a short-budget slot emits "
            "at most its remaining budget without demoting the rest of "
            "the batch.  With FLAGS_serving_spec_adaptive this is "
            "superseded by the ladder")
define_flag("serving_spec_draft", "model",
            "speculative proposal source: 'model' runs the draft "
            "model's k-step scan (needs draft_model= at engine "
            "construction); 'ngram' proposes from a per-request "
            "host-side n-gram/suffix table over the prompt + generated "
            "tokens (inference/drafting.py) — no draft model, no draft "
            "KV pools, no draft prefill; proposals ride into the "
            "verify program as device inputs.  Both are lossless "
            "(acceptance corrects any proposal quality)")
define_flag("serving_spec_adaptive", False,
            "adapt the speculative k at tick boundaries from the live "
            "acceptance rate: k steps through "
            "FLAGS_serving_spec_k_ladder (up while acceptance is high, "
            "down when proposals are mostly rejected).  Every ladder "
            "rung's program is enumerated into the warmup grid, so "
            "adaptation NEVER compiles under traffic")
define_flag("serving_spec_k_ladder", "2,4,8",
            "comma-separated speculative-k rungs for "
            "FLAGS_serving_spec_adaptive (each >= 2; one compiled spec "
            "program per rung, all warmed).  Ignored with adaptation "
            "off — FLAGS_serving_spec_k is the single fixed k")
define_flag("serving_quant", "",
            "weight-only quantized serving: 'int8' (per-output-channel "
            "absmax codes) or 'fp8' (e4m3fn, same 1 byte/weight with "
            "relative per-channel precision) snapshots the engine's "
            "matmul weights at construction and dequantizes inside the "
            "compiled programs (~4x less fp32 weight memory on device; "
            "logits change within the mode's documented parity budget). "
            "Composes with FLAGS_serving_tp_degree (quantize-then-shard "
            "is bit-exact) and spec decode.  Empty (the default) serves "
            "full-precision weights")

# Continuous batching: chunked prefill + SLO-aware scheduling + the
# streaming serve endpoint (inference/serving.py, observability/http.py
# — ISSUE 11).
define_flag("serving_prefill_chunk", 0,
            "chunked prefill: absorb an arriving prompt in chunks of at "
            "most this many tokens, interleaved between decode ticks, so "
            "a running stream's inter-token gap is bounded by one chunk "
            "+ one tick regardless of arriving prompt length.  Chunks "
            "run the suffix-prefill (prefill_cont) program per ladder "
            "bucket — streams stay BIT-identical to monolithic prefill "
            "and the warmup grid stays enumerable.  0 (the default) "
            "keeps legacy whole-prompt prefill")
define_flag("serving_prefill_chunks_per_tick", 1,
            "scheduler budget: prefill chunk programs dispatched per "
            "tick boundary (the N of 'one decode tick + up to N "
            "chunks'); higher drains arriving prompts faster at the "
            "price of longer inter-token gaps for running streams")
define_flag("serving_chunk_overlap", True,
            "overlap chunked-prefill work across tick boundaries (the "
            "PR 11 polish the chunks_per_tick auto-tuner didn't take): "
            "with the tick loop double-buffered (serving_overlap) and "
            "an admission mid-chunked-prefill, NON-FINAL chunks also "
            "dispatch behind the chained decode tick instead of waiting "
            "for the next real boundary — device programs serialize in "
            "dispatch order, so the chunk chains on the in-flight "
            "tick's pool handle and streams stay bit-identical.  The "
            "FINAL chunk (host-sync logits screen + slot install) "
            "always lands at a real boundary.  0 keeps all chunk work "
            "at boundaries")
define_flag("zero3_bucket_mb", 16,
            "fused ZeRO-3 gather bucket size in MiB "
            "(fleet/hybrid_step.py make_zero3_train_step): consecutive "
            "flat parameter shards are grouped into buckets of at most "
            "this many MiB and each bucket is ONE in-program all-gather "
            "— small enough that XLA's latency-hiding scheduler can "
            "overlap bucket N+1's gather with bucket N's compute, large "
            "enough to amortize collective launch overhead.  Read at "
            "program-build time (a new value means a new step program); "
            "0 puts every leaf in its own bucket")
define_flag("serving_slo_shed", False,
            "SLO-aware load shedding: at each scheduler boundary, while "
            "the live TTFT/TPOT p99 sketches breach their "
            "FLAGS_serving_{ttft,tpot}_slo_ms targets AND the waiting "
            "queue is deeper than FLAGS_serving_shed_queue_depth, the "
            "newest lowest-priority waiting requests are rejected with "
            "reason=slo_shed (serving.slo_sheds counter) instead of "
            "queueing into certain SLO violations.  Needs "
            "FLAGS_enable_metrics (the sketches are the evidence)")
define_flag("serving_shed_queue_depth", 8,
            "waiting-queue watermark for FLAGS_serving_slo_shed: "
            "shedding only engages while more requests than this are "
            "queued for admission")
define_flag("serving_http_port", 0,
            "TCP port of the streaming serve endpoint (POST /generate, "
            "Server-Sent Events token stream; same daemon also answers "
            "the /metrics//healthz//requests scrapes), started by "
            "ServingEngine.run()/serve_forever(); 0 (the default) = no "
            "server.  Binds 127.0.0.1 — widening exposure is an "
            "explicit operator decision, like FLAGS_metrics_host")

# Crash-only serving: failure isolation, graceful drain and warm
# restart from an exported prefix cache (inference/serving.py,
# inference/prefix_cache.py — ISSUE 15).
define_flag("serving_tick_timeout_s", 0.0,
            "serving tick watchdog: seconds the harvest may block on "
            "the compiled tick's device outputs before the tick is "
            "FAILED (implicated slots evicted outcome=error, "
            "serving.tick_errors counted) instead of wedging "
            "run()/serve_forever() on a hung block_until_ready.  0 "
            "(the default) waits forever — the historical behavior")
define_flag("serving_drain_timeout_s", 30.0,
            "graceful-drain deadline: seconds ServingEngine.drain() "
            "(SIGTERM under serve_forever, or POST /drain) keeps "
            "ticking to finish in-flight requests after admission "
            "closes; stragglers past the deadline are evicted with "
            "outcome=drained (their partial streams end in an SSE "
            "error frame)")
define_flag("serving_prefix_export_dir", "",
            "prefix-cache persistence root: drain() exports the "
            "hash-chain index + every referenced block's KV contents "
            "(draft pools included) as an atomic manifest-checked "
            "version under this directory, and a NEW engine imports "
            "the newest valid version at construction (corrupt or "
            "truncated exports are skipped with "
            "serving.prefix_import_skipped_corrupt, never loaded) — "
            "restart-to-first-token on a hot system prompt is then "
            "warm-cache + warm-compile.  Empty (the default) disables "
            "both directions")
# Paged Pallas kernels for the X-ray suspects (ops/pallas_paged.py,
# ops/pallas_moe.py, models/kv_cache.py — ISSUE 18).  Snapshotted at
# engine/layer construction (graft-lint R004: never read under trace).
define_flag("serving_pallas_prefill", True,
            "run suffix/chunked prefill attention (prefill_cont — both "
            "the prefix-hit suffix write and ladder-bucket chunks) "
            "through the chunked paged-prefill Pallas kernel "
            "(PagedChunkKernelView) instead of the dense linearized-"
            "table gather; the kernel compiles through Mosaic on a TPU "
            "and runs interpreted elsewhere "
            "(ops/pallas_common.interpret_default), greedy streams stay "
            "bit-identical either way")
define_flag("serving_pallas_verify", True,
            "run the spec-decode verify chunk (spec_tick's k candidate "
            "positions) through the paged spec-verify Pallas kernel "
            "(PagedVerifyKernelView) instead of gathering the whole "
            "pool; Mosaic on a TPU, interpreted elsewhere "
            "(ops/pallas_common.interpret_default), accept/reject "
            "decisions stay bit-identical either way")
define_flag("moe_fused_dispatch", True,
            "route MoE token dispatch/combine through the fused "
            "capacity-bucketed one-pass path (ops/pallas_moe.py) "
            "instead of the dense (tokens, experts, capacity) one-hot "
            "einsums; gate outputs and gradients stay bit-close to the "
            "dense reference")
define_flag("serving_dispatch_retries", 0,
            "bounded in-place retries of a serving program dispatch "
            "that raised a transient RuntimeError/XlaRuntimeError "
            "(shared io_retry helper, exponential backoff, counted on "
            "serving.dispatch_retries); exhausted retries surface to "
            "the tick guard (request failures strike toward poison "
            "quarantine, tick failures evict the implicated slots).  "
            "0 (the default) surfaces the first failure")

# Serving decode fast path (inference/serving.py).
define_flag("serving_device_sampling", True,
            "sample temperature/top-k/top-p INSIDE the compiled decode "
            "step (per-slot params + PRNG keys as device inputs), so "
            "sampling requests ride the full k-step tick; 0 restores the "
            "host-side per-row sampler, which demotes every tick with a "
            "sampling request to k=1")
# Scrape surface + request lifecycle tracing (observability/http.py,
# observability/export.py, inference/serving.py).
define_flag("metrics_port", 0,
            "TCP port of the Prometheus scrape endpoint (/metrics, "
            "/healthz, /requests), started by ServingEngine.run() and "
            "Model.fit(); 0 (the default) = no server.  Binds "
            "FLAGS_metrics_host (127.0.0.1 unless overridden)")
define_flag("metrics_host", "127.0.0.1",
            "bind address of the metrics HTTP endpoint; the loopback "
            "default keeps operational data host-local — widening it is "
            "an explicit operator decision")
def _xray_flag_changed(value):
    from .observability import xray as _xray
    _xray._sync_interval(value)


define_flag("xray_sample_interval", 0,
            "engine X-ray device-time sampling (observability/xray.py): "
            "every Nth dispatch of each compiled program runs a SYNCED "
            "timing probe (block_until_ready on the outputs before the "
            "stop clock) feeding the per-program device-seconds/MFU "
            "ledger; a due probe forces a real serving tick-loop "
            "boundary, so the double-buffered overlap path is never "
            "measured through a chained dispatch.  0 (the default) "
            "disables sampling — per-program dispatch counting stays on",
            on_change=_xray_flag_changed)
define_flag("serving_ttft_slo_ms", 0.0,
            "time-to-first-token SLO in milliseconds; a request whose "
            "TTFT exceeds it counts on serving.slo_violations"
            "{metric=ttft}.  0 disables the check")
define_flag("serving_tpot_slo_ms", 0.0,
            "per-output-token latency (TPOT) SLO in milliseconds; each "
            "decoded token whose imputed inter-token gap exceeds it "
            "counts on serving.slo_violations{metric=tpot}.  0 disables "
            "the check")
define_flag("serving_overlap",  True,
            "double-buffer the serving tick loop: dispatch tick t+1's "
            "compiled step (feeding tick t's on-device last-token handle "
            "forward) BEFORE harvesting/detokenizing tick t, overlapping "
            "device compute with host admission/harvest work; 0 keeps "
            "the synchronous dispatch-then-harvest loop")
define_flag("fleet_affinity_tokens", 64,
            "prefix length (tokens) the fleet router hashes for replica "
            "affinity — the blake2b chain hash of the prompt's first "
            "fleet_affinity_tokens tokens (the engine prefix cache's "
            "first-block hash when this matches the engine block_size), "
            "rendezvous-hashed over the ready replicas so shared-prefix "
            "traffic lands on the replica whose KV already holds it")
define_flag("fleet_ttft_budget_ms", 0.0,
            "router-side admission budget: a request whose PREDICTED "
            "time-to-first-token (queue-position model over the "
            "replica's /healthz ttft_evidence) exceeds this on every "
            "ready replica is shed at the router with 429 before any "
            "engine queues it.  0 disables predictive shedding")
define_flag("fleet_poll_interval_s", 0.25,
            "fleet router health-poll cadence: how often each replica's "
            "/healthz readiness + queue depth + TTFT evidence is "
            "refreshed on the router's poller thread")
define_flag("fleet_router_port", 0,
            "fleet router bind port for `flight route` (127.0.0.1 only "
            "— the route accepts work); 0 binds an ephemeral port")
define_flag("serving_chunks_per_tick_auto", False,
            "tune the chunked-prefill chunks-per-tick budget at tick "
            "boundaries from the live tick-level TPOT sketch against "
            "FLAGS_serving_tpot_slo_ms: running p90 over the SLO spends "
            "fewer chunk programs per boundary, under half of it spends "
            "more, always within [1, "
            "FLAGS_serving_prefill_chunks_per_tick].  Only the budget "
            "moves — the program grid and warmup signatures are fixed "
            "at construction.  Off (the default) keeps the static flag "
            "budget; inert without a TPOT SLO")
define_flag("fleet_trace", True,
            "distributed trace propagation (observability/tracing.py): "
            "the fleet router mints a trace id per /generate, forwards "
            "it as the X-Graft-Trace header, and records router-side "
            "queue/plan/proxy spans; replicas thread it into Request so "
            "lifecycle, flight and handoff records share one trace_id "
            "across processes.  0 stops minting/forwarding (explicit "
            "client headers still parse)")
define_flag("fleet_metrics_interval_s", 0.0,
            "fleet metrics federation cadence: every interval the "
            "router polls each replica's /metrics/snapshot (mergeable "
            "counters + DDSketch states + engine telemetry), re-exports "
            "the merged view as fleet_* series on GET /fleet/metrics, "
            "and feeds the SLO burn-rate monitor.  0 (the default) "
            "disables the federation poller; GET /fleet/metrics then "
            "federates once on demand")
define_flag("fleet_slo_burn_cordon", False,
            "auto-cordon a replica whose SLO error-budget burn rate "
            "exceeds fleet_burn_threshold in BOTH the fast and slow "
            "windows (bad events: always-on TTFT-SLO violations + "
            "error/poisoned outcomes from the federated telemetry); "
            "un-cordons when the fast window cools below 1x.  A cordon "
            "is a routing preference, not a verdict — if every replica "
            "is cordoned the degraded plan still routes (PR 16 "
            "contract).  Requires the federation poller "
            "(fleet_metrics_interval_s > 0)")
define_flag("fleet_burn_fast_window_s", 60.0,
            "fast window of the SLO burn-rate monitor: catches an "
            "acute error spike within about a minute")
define_flag("fleet_burn_slow_window_s", 600.0,
            "slow window of the SLO burn-rate monitor: keeps a brief "
            "blip from flapping the cordon — both windows must burn "
            "over threshold to cordon")
define_flag("fleet_burn_threshold", 2.0,
            "burn-rate multiple that trips the cordon: 1.0 spends the "
            "error budget exactly at the sustainable rate, 2.0 spends "
            "it twice as fast")
define_flag("fleet_error_budget", 0.05,
            "SLO error budget as a bad-event fraction (bad = TTFT-SLO "
            "violations + error/poisoned outcomes over total terminal "
            "events): the denominator of the burn rate")

# Unattended elastic training: heartbeat leases, stall watchdog and
# store hardening (distributed/launch/main.py, distributed/store.py,
# distributed/fleet/elastic/loop.py — ISSUE 20).
define_flag("elastic_lease_interval_s", 1.0,
            "heartbeat-lease publish cadence: each launcher bumps its "
            "per-generation lease key (lease/{gen}/{node}) on the TCP "
            "store at this interval from its watch loop, proving the "
            "node is alive to every peer")
define_flag("elastic_lease_timeout_s", 5.0,
            "lease expiry horizon: a peer whose lease value has not "
            "changed for this many seconds of LOCAL observation time "
            "(clock-skew free — the value is opaque, only its motion "
            "matters) is declared dead; any surviving launcher then "
            "bumps restart_generation so the fleet re-settles without "
            "the dead node.  Should comfortably exceed "
            "elastic_lease_interval_s; expiry checks only arm after "
            "one full timeout of generation uptime (join grace)")
define_flag("elastic_stall_timeout_s", 0.0,
            "progress watchdog: a local worker whose step heartbeat "
            "(progress/{gen}/{rank}, published by the trainer's "
            "ProgressReporter) stops advancing for this many seconds "
            "is SIGKILLed by its launcher, converting a wedged "
            "collective or deadlock into the ordinary crash→restart "
            "path.  Arms per rank only after the FIRST heartbeat is "
            "observed (uninstrumented scripts are never stall-killed). "
            "0 (the default) disables the watchdog")
define_flag("store_retries", 3,
            "TCPStore transient-error budget: attempts per request on "
            "ECONNRESET/EPIPE-style socket errors before the error "
            "propagates (semantic timeouts never retry; non-idempotent "
            "ADD only retries when the failure provably preceded the "
            "send).  1 = the historical fail-fast behavior")
define_flag("store_retry_backoff_s", 0.05,
            "base sleep between TCPStore retry attempts (doubles per "
            "attempt: backoff, 2*backoff, ...)")
